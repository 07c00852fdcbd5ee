"""The dense PPO cells' trainer programs, compiled for one v5e chip with no
chip: `pythia-1.4b.ppo-hh`'s trunk-cache fill (the fallback since PR 49) and
the train step resumed from it, its one `generate` program that follows a
chunk's longest prompt (and `gpt2-xl.ppo-sentiments`' `generate`, which is
too narrow to), and both cells' score programs that hand out the trunk state,
traced from where `PPOTrainer` makes them at the cells' widths
(`aot_tpu.ppo_cell_trainer`). `lfm2-8b-a1b.ppo-hh`'s are in
`test_lfm2_compile_tpu.py`.
"""

import pytest

import jax
from jax.extend.core import Literal
from jax.sharding import SingleDeviceSharding

pytest.importorskip("libtpu", reason="AOT compilation for the TPU needs libtpu")

from aot_tpu import (  # noqa: E402, F401  (v5e and pallas_mode are fixtures)
    abstract, BF16, donated_outputs, F32, held_bytes, I32, kernel_names, loop_body_instructions,
    pallas_mode, ppo_cell_params, ppo_cell_trainer, S, traced_generate, traced_score, v5e,
)


# pythia-1.4b.ppo-hh (bench/workloads): chunks of 16 x (896 + 128) tokens,
# batches of 8, 64 rollouts a cycle, 2 blocks trained. Every width is the
# cell's; the depth is cut to 2 frozen blocks under the 2 trained ones (the
# 22 of the cell are one block's program 22 times, and a minute to compile)
PPO_HH = dict(vocab_size=50304, attn_impl="flash", n_layers=4)


@pytest.fixture(scope="module")
def ppo_hh_trainer(tmp_path_factory):
    return ppo_cell_trainer(tmp_path_factory.mktemp("ppo_hh"), "pythia-1.4b", PPO_HH,
                         batch_size=8, num_rollouts=64, chunk_size=16, max_new=128)


def test_dense_ppo_cell_trunk_cache_fill_compiles_under_its_name(v5e, pallas_mode, ppo_hh_trainer):
    """`jit_trunk_cache_fill` over one chunk of the cell, 16 x 1,024 tokens:
    the frozen blocks' flash forwards and no head, the state in bfloat16,
    the forward's own dtype."""
    trainer = ppo_hh_trainer
    one = SingleDeviceSharding(v5e[0])
    train, frozen = ppo_cell_params(trainer)
    fill = trainer._build_trunk_cache_fn()
    traced = fill.trace(*abstract((train, frozen, S((16, 1024), I32)), one))
    assert traced.out_info.shape == (16, 1024, 2048) and traced.out_info.dtype == BF16
    lowered = traced.lower(lowering_platforms=("tpu",))
    assert "module @jit_trunk_cache_fill" in lowered.as_text()[:200]
    compiled = lowered.compile()
    assert kernel_names(compiled) == ["flash_fwd"] * trainer.split


def test_dense_ppo_cell_train_step_resumes_from_the_trunk_cache(v5e, pallas_mode, ppo_hh_trainer):
    """The cell's train step in outline (the trainer's own loss, gradients
    of the top two blocks, AdamW) over a batch of 8 that names its rows of
    the cycle's cache `[64, 1024, 2048]`: the gather, the two trained
    blocks forward and backward and the windowed head lower and compile
    for one v5e chip, and no frozen block runs: two flash forwards, where
    the whole forward of the same step runs one a block."""
    import optax

    from trlx_tpu.data import PPORLBatch

    trainer = ppo_hh_trainer
    one = SingleDeviceSharding(v5e[0])
    train, frozen = ppo_cell_params(trainer)
    loss_fn = trainer.make_loss_fn()
    opt = optax.adamw(6e-6)
    opt_state = jax.eval_shape(opt.init, train)
    b, q, new = 8, 896, 128
    batch = PPORLBatch(
        query_tensors=S((b, q), I32), response_tensors=S((b, new), I32),
        logprobs=S((b, new), F32), values=S((b, new), F32), rewards=S((b, new), F32),
        trunk_rows=S((b,), I32), trunk_cache=S((64, q + new, 2048), BF16))

    def train_step(train, frozen, opt_state, batch):
        grads = jax.grad(lambda p: loss_fn(p, frozen, batch)[0])(train)
        updates, opt_state = opt.update(grads, opt_state, train)
        return optax.apply_updates(train, updates), opt_state

    def flash_forwards(batch):
        compiled = jax.jit(train_step, donate_argnums=(0, 2)).trace(
            *abstract((train, frozen, opt_state, batch), one)).lower(
            lowering_platforms=("tpu",)).compile()
        return sum(name.startswith("flash_fwd") for name in kernel_names(compiled)), compiled

    resumed, compiled = flash_forwards(batch)
    whole, _ = flash_forwards(batch.replace(trunk_rows=None, trunk_cache=None))
    assert (resumed, whole) == (2, trainer.model_cfg.n_layers)
    # the cache is an argument the step reads and hands back to nobody
    assert donated_outputs(compiled) == len(jax.tree_util.tree_leaves((train, opt_state)))


def _loops_and_branches(jaxpr):
    """(the `while` equations, the `cond` equations) of a jaxpr's top level."""
    return ([e for e in jaxpr.eqns if e.primitive.name == "while"],
            [e for e in jaxpr.eqns if e.primitive.name == "cond"])


def test_dense_ppo_cell_generate_follows_the_longest_prompt_in_one_program(v5e, pallas_mode, ppo_hh_trainer,
                                                                           monkeypatch):
    """`generate` for one chunk of the cell, 16 x 896 + 128: ONE prefill
    loop over blocks of 128 columns whose first trip is data (the chunk's
    first live block), then the one 128-step loop, in which every attention
    layer chooses among five suffixes of the cache's 1,024 columns (256 /
    384 / 512 / 768 / 1,024). It compiles for one v5e chip and holds less
    than the one-shot program at the same depth (whose prefill scores
    `[16, 16, 896, 1024]` in float32 a layer), and no step of the decode
    loop copies a whole cache plane as an operation of its own (3.2 GB a
    step at the cell's depth). The planes do change their layout once
    between the two loops, as half of them do after the one-shot prefill;
    the hoisted switch (one loop a width under one conditional) copied them
    in every branch and its program did not load beside the trainer's
    state: PERF.md section 6, PR 46."""
    from trlx_tpu.models.transformer import live_widths
    from trlx_tpu.ops import sampling

    trainer = ppo_hh_trainer
    plan = trainer._rollout_plan(896, trainer.generate_kwargs)
    assert (plan.block, plan.pad, plan.blocks, plan.columns) == (128, 0, 7, 1024)
    assert live_widths(plan.columns) == (256, 384, 512, 768, 1024)
    traced = traced_generate(trainer, v5e[0], 16, 896)
    loops, branches = _loops_and_branches(traced.jaxpr.jaxpr)
    assert len(loops) == 2 and not branches, (len(loops), len(branches))
    prefill, decode = loops
    start = prefill.invars[prefill.params["cond_nconsts"] + prefill.params["body_nconsts"]]
    assert not isinstance(start, Literal), "the prefill loop starts at a constant block"
    # the prefill's body attends over the whole cache; a decode step's layers choose
    assert not _loops_and_branches(prefill.params["body_jaxpr"].jaxpr)[1]
    choices = _loops_and_branches(decode.params["body_jaxpr"].jaxpr)[1]
    assert [len(c.params["branches"]) for c in choices] == [5] * trainer.model_cfg.n_layers
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 2 and text.count(" conditional(") == trainer.model_cfg.n_layers
    plane = 16 * 1024 * 16 * 128
    steps = list(loop_body_instructions(compiled, having=" conditional("))
    # (the loop in hand is the one that writes a token's keys and values into every plane)
    assert sum(op == "dynamic-update-slice" and n == plane for n, op, _ in steps) == 2 * trainer.model_cfg.n_layers
    moved = [line[:140] for n, op, line in steps
             if n >= plane and op in ("copy", "copy-start", "copy-done", "transpose")]
    assert not moved, moved
    monkeypatch.setattr(sampling, "PREFILL_BLOCK", 0)
    one_shot = traced_generate(trainer, v5e[0], 16, 896).lower(lowering_platforms=("tpu",)).compile()
    assert one_shot.as_text().count(" while(") == 1 and one_shot.as_text().count(" conditional(") == 0
    assert held_bytes(compiled) < held_bytes(one_shot) < 16e9, (held_bytes(compiled), held_bytes(one_shot))


def test_gpt2_xl_generate_is_too_narrow_to_follow_and_keeps_its_program(v5e, pallas_mode, tmp_path):
    """`gpt2-xl.ppo-sentiments` generates 128 x 64 + 40: a prompt block under
    two blocks of 128 and a cache of 104 columns with one width to read. Its
    program holds the one-shot prefill, one loop and no conditional."""
    trainer = ppo_cell_trainer(tmp_path, "gpt2-xl", dict(vocab_size=50257, attn_impl="flash", n_layers=4),
                               batch_size=32, num_rollouts=128, chunk_size=128, max_new=40)
    assert trainer._rollout_plan(64, trainer.generate_kwargs) is None
    traced = traced_generate(trainer, v5e[0], 128, 64)
    loops, branches = _loops_and_branches(traced.jaxpr.jaxpr)
    assert len(loops) == 1 and not branches
    text = traced.lower(lowering_platforms=("tpu",)).compile().as_text()
    assert text.count(" while(") == 1 and text.count(" conditional(") == 0


def test_gpt2_xl_score_keeps_its_weight_prefetches_with_the_trunk_state(v5e, pallas_mode, tmp_path):
    """`gpt2-xl.ppo-sentiments` scores one chunk of 128 x 104, whose whole
    residual stream (43 MB) fits a v5e's fast memory. Handed out as a plain
    sixth output the state made the compiler keep that stream there and
    stop prefetching the frozen blocks' MLP weights (sliced copies joined by
    `ConcatBitcast`): 0.676 against 0.566 s a chunk on the chip (PERF.md
    section 6, PR 40). `score` hands it out behind a barrier for that; this
    holds the plan, at the cell's widths and a depth of 4 frozen blocks:
    the six-output program prefetches what the five-output program does."""
    trainer = ppo_cell_trainer(tmp_path, "gpt2-xl", dict(vocab_size=50257, attn_impl="flash", n_layers=6),
                            batch_size=32, num_rollouts=128, chunk_size=128, max_new=40)
    assert trainer._score_hands_out_trunk_state()

    def prefetched_weights(hands_out):
        traced = traced_score(trainer, v5e[0], 128, 104, hands_out)
        return traced.lower(lowering_platforms=("tpu",)).compile().as_text().count("ConcatBitcast")

    five, six = prefetched_weights(False), prefetched_weights(True)
    assert five >= 4 * 6 and six >= five, (five, six)


def _pythia_score(tmp_path, device, hands_out):
    """(what it returns, its text lowered for the TPU) of `pythia-1.4b.ppo-hh`'s
    score program over one chunk of 16 x 1,024, five outputs or six."""
    trainer = ppo_cell_trainer(tmp_path / str(hands_out), "pythia-1.4b", PPO_HH,
                               batch_size=8, num_rollouts=64, chunk_size=16, max_new=128)
    traced = traced_score(trainer, device, 16, 1024, hands_out)
    lowered = traced.lower(lowering_platforms=("tpu",))
    assert "module @jit_score" in lowered.as_text()[:200]
    return traced.out_info, lowered


def test_pythia_score_hands_out_a_chunks_trunk_state_in_the_one_program_named_score(v5e, pallas_mode, tmp_path):
    """`pythia-1.4b.ppo-hh` scores a chunk of 16 x 1,024; since PR 49 the
    rule finds room for the collection's four states beside a generation in
    flight and the cell's `jit_score` has a sixth output, the chunk's state
    (67 MB, bfloat16, the fill's). One program named `score` either way, the
    same five results in front and the same kernel calls in it, and the state
    of a several-chunk collection leaves plainly: no barrier, which is what
    cost this program its weight prefetches on the chip (PERF.md section 6,
    PR 49). Lowered for the TPU, not compiled: the compile is the test below."""
    (five_out, five), (six_out, six) = (_pythia_score(tmp_path, v5e[0], h) for h in (False, True))
    assert len(five_out) == 5 and len(six_out) == 6
    assert (six_out[5].shape, six_out[5].dtype) == ((16, 1024, 2048), BF16)
    assert [(o.shape, o.dtype) for o in six_out[:5]] == [(o.shape, o.dtype) for o in five_out]
    calls = [text.as_text().count("tpu_custom_call") for text in (five, six)]
    assert calls[0] == calls[1] == 8, calls  # six flash forwards, two fused CEs
    assert "optimization_barrier" not in six.as_text()


@pytest.mark.slow  # 4-7 min a program at [16, 1024], whatever the depth (ROADMAP S5): 15 min of tier-1's 24
def test_pythia_score_keeps_its_weight_prefetches_with_the_trunk_state(v5e, pallas_mode, tmp_path):
    """Both programs compiled for a v5e: the six-output program prefetches the
    frozen blocks' weights in slices where the five-output program does
    (`ConcatBitcast` joins, `slice-start` copies: it was gpt2-xl's plan that
    one more output moved, above), at the cell's widths and the file's depth.
    (At this depth the six-output program has MORE of both, 29 | 22 joins;
    the chip's reading, 97 | 97 plainly and 19 behind the barrier, needs all
    24 layers: my AOT and chip runs, PR 49.)"""
    five, six = (_pythia_score(tmp_path, v5e[0], h)[1].compile() for h in (False, True))
    # (the two schedules order the policy's and the reference's calls differently)
    assert sorted(kernel_names(six)) == sorted(kernel_names(five)) == ["flash_fwd"] * 6 + ["fused_ce_fwd"] * 2
    for prefetch in ("ConcatBitcast", "slice-start"):
        n5, n6 = five.as_text().count(prefetch), six.as_text().count(prefetch)
        assert n6 >= n5 > 0, (prefetch, n5, n6)
