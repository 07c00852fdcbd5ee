"""Rollout chunks: a collection's prompts sorted by length by the loader
(PromptPipeline.create_loader(group_window=)), every chunk generated at the
pool's width (PPOTrainer._rollout_generate, TPUTrainer.generate), and the
sampler's own rule for whether its one program then follows the chunk's
longest prompt (ops.sampling.block_plan).

Pinned here:
- the grouped loader hands out, window by window, the prompts the
  ungrouped loader would have, longest first, the same on two loaders of
  one seed, across an epoch's end, and after a save and a restore of the
  stream's place;
- a model without a block plan (right padding, a pool under two blocks,
  beams) runs every chunk through ONE program at the pool's width, and
  `_rollout_generate` adds a counter to `generate` and nothing else;
- `generate` runs a batch at the width it was given: nothing recognises a
  rollout chunk by its shape;
- a model with a plan runs four chunks through one program, counts the
  blocks it runs, and gives the tokens, logprobs and activations of the
  one-shot program whichever block the chunk's longest prompt starts in;
- collections add no `generate` compile after the first, and the counter
  in front of every rollout dispatch adds up;
- GRPO's groups stay together through the sorted loader and the block form.
"""

import math
from statistics import NormalDist

import jax
import numpy as np
import pytest

from trlx_tpu.data.configs import TokenizerConfig
from trlx_tpu.models.transformer import live_widths
from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config
from trlx_tpu.pipeline import LoaderStream
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
from trlx_tpu.tokenizers import get_tokenizer
from trlx_tpu.trainer import ppo_trainer
from trlx_tpu.trainer.ppo_trainer import PPOTrainer

ROWS, CHUNKS = 4, 4
WINDOW = ROWS * CHUNKS
MAX_NEW = 4
# the block tests' `PREFILL_BLOCK`: the pool's width, 100, runs in `generate`'s
# bucket of 128, four blocks of 32 (one block, and so no plan, at the sampler's 128)
BLOCK = 32


def _lognormal_lengths(n, median, sigma, lo, hi):
    """n lengths at the mid-quantiles (bench/benchlib/traffic.py's pools)."""
    nd = NormalDist()
    vals = [median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return [int(v) for v in np.clip(np.rint(vals), lo, hi)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return ["".join(chr(c) for c in rng.integers(97, 123, size=n)) for n in lengths]


# 40 prompts, heavy-tailed, longest 100: two whole windows of 16 and one of 8
LENGTHS = list(np.random.default_rng(5).permutation(_lognormal_lengths(40, 20, 0.9, 2, 100)))
POOL = _prompts(LENGTHS)


@pytest.fixture(scope="module")
def tokenizer():
    return get_tokenizer(TokenizerConfig(tokenizer_path="byte"))


@pytest.fixture(scope="module")
def pipeline(tokenizer):
    return PromptPipeline(POOL, max_prompt_length=100, tokenizer=tokenizer)


def _rows(batch):
    """A collated batch as a list of prompts (token tuples, padding off)."""
    return [tuple(int(t) for t, m in zip(ids, mask) if m)
            for ids, mask in zip(batch["input_ids"], batch["attention_mask"])]


def _take(stream, n):
    return [_rows(next(stream)) for _ in range(n)]


# ---------------------------------------------------------------------------
# (a) the loader
# ---------------------------------------------------------------------------


def test_grouped_loader_keeps_each_windows_prompts_sorted_into_chunks(pipeline):
    plain = LoaderStream(pipeline.create_loader(ROWS, shuffle=True, seed=3))
    grouped = LoaderStream(pipeline.create_loader(ROWS, shuffle=True, seed=3, group_window=WINDOW))
    twin = LoaderStream(pipeline.create_loader(ROWS, shuffle=True, seed=3, group_window=WINDOW))
    # two epochs of 10 chunks: windows of 4, 4 and 2 chunks, twice, so an
    # epoch's end lies inside
    for epoch in range(2):
        for chunks in (4, 4, 2):
            want = [p for chunk in _take(plain, chunks) for p in chunk]
            got = _take(grouped, chunks)
            flat = [p for chunk in got for p in chunk]
            assert sorted(flat) == sorted(want), "a window holds other prompts than it did"
            assert [len(p) for p in flat] == sorted((len(p) for p in flat), reverse=True)
            assert all(len(chunk) == ROWS for chunk in got)
            assert got == _take(twin, chunks), "two loaders of one seed disagree"
    assert grouped.state() == {"epoch": 1, "position": 10}  # the next chunk opens epoch 2
    # the collated batch keeps the pool's width
    assert next(iter(pipeline.create_loader(ROWS, group_window=WINDOW)))["input_ids"].shape == (ROWS, 100)


def test_stream_gives_the_same_chunks_after_a_restore(pipeline):
    stream = LoaderStream(pipeline.create_loader(ROWS, shuffle=True, seed=9, group_window=WINDOW))
    _take(stream, 7)
    state = stream.state()
    assert state == {"epoch": 0, "position": 7}
    want = _take(stream, 8)  # through the epoch's end (10 chunks an epoch)
    fresh = LoaderStream(pipeline.create_loader(ROWS, shuffle=True, seed=9, group_window=WINDOW))
    fresh.restore(state)
    assert _take(fresh, 8) == want
    assert fresh.state() == stream.state() == {"epoch": 1, "position": 5}


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                   model_extra_configs={"dtype": "float32"}),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=104, batch_size=8, total_steps=4, tracker=None, seed=11,
                   tracing=True, checkpoint_dir=str(tmp_path_factory.mktemp("ckpt"))),
        method=dict(num_rollouts=WINDOW, chunk_size=ROWS, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=MAX_NEW, do_sample=True)),
    )
    return PPOTrainer(config, reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
                      devices=jax.devices()[:1])


def _with_pool(trainer, prompts, num_rollouts=WINDOW):
    trainer.config.method.num_rollouts = num_rollouts
    trainer.add_prompt_pipeline(
        PromptPipeline(prompts, max_prompt_length=100, tokenizer=trainer.tokenizer))
    return trainer


def _generate_programs(trainer):
    return {name: rec.compiles for name, rec in trainer._compile_ledger.fns.items()
            if name.startswith("generate[")}


def _left_padded(lengths, width, pad_id, seed=1):
    rng = np.random.default_rng(seed)
    ids = np.full((len(lengths), width), pad_id, np.int32)
    mask = np.zeros((len(lengths), width), np.int32)
    for i, n in enumerate(lengths):
        ids[i, width - n:] = rng.integers(97, 123, size=n)
        mask[i, width - n:] = 1
    return ids, mask


def _spans_of(monkeypatch):
    """The `trlx:` counters the trainer writes while a session is active
    (the build account's `build.event`, one a program built meanwhile, is
    not the trainer's)."""
    spans = []
    monkeypatch.setattr(ppo_trainer.tracing, "active", lambda: True)
    monkeypatch.setattr(ppo_trainer.tracing, "counters",
                        lambda name, /, **values: spans.append((name, values))
                        if name.startswith("ppo.") else None)
    return spans


@pytest.mark.parametrize("reason, new_tokens", [
    ("right_padding", MAX_NEW + 5), ("pool_under_two_blocks", MAX_NEW + 6), ("two_beams", MAX_NEW + 7)])
def test_a_model_without_a_block_plan_runs_every_chunk_at_the_pools_width(
        trainer, monkeypatch, reason, new_tokens):
    from trlx_tpu.ops import sampling

    # (greedy, so a second call gives the same tokens; a token budget a
    # case: neither the side nor the block is in a program's name)
    gen_kwargs = dict(max_new_tokens=new_tokens, do_sample=False)
    if reason == "right_padding":  # no block in front of the prompts is empty
        monkeypatch.setattr(sampling, "PREFILL_BLOCK", BLOCK)
        monkeypatch.setattr(trainer.config.tokenizer, "padding_side", "right")
        monkeypatch.setattr(trainer.tokenizer, "padding_side", "right")
    elif reason == "two_beams":  # not the token-at-a-time loop
        monkeypatch.setattr(sampling, "PREFILL_BLOCK", BLOCK)
        gen_kwargs["num_beams"] = 2
    else:  # the pool's 100 columns run in a bucket of 128: one block of PREFILL_BLOCK
        assert sampling.PREFILL_BLOCK == 128
    _with_pool(trainer, POOL)
    assert trainer._rollout_plan(100, gen_kwargs) is None
    spans = _spans_of(monkeypatch)
    before, longest = dict(_generate_programs(trainer)), []
    for _ in range(CHUNKS):
        batch = next(trainer.prompt_iterator)
        longest.append(int(batch["attention_mask"].sum(axis=1).max()))
        out = jax.device_get(trainer._rollout_generate(batch, gen_kwargs))
        again = jax.device_get(trainer.generate(batch["input_ids"], batch["attention_mask"], gen_kwargs))
        # (`_unbucket_output` trims the bucket's 28 columns of padding on the left only)
        assert out["samples"].shape == (ROWS, (128 if reason == "right_padding" else 100) + new_tokens)
        assert sorted(out) == sorted(again)
        for name in out:
            np.testing.assert_array_equal(out[name], again[name])
    assert len(set(longest)) == CHUNKS and longest[-1] <= 32, longest  # short chunks too
    after = _generate_programs(trainer)
    new = {name: n for name, n in after.items() if name not in before}
    assert list(new.values()) == [1] and all(n.startswith("generate[b8,p128,lm,kw") for n in new), new
    assert {name: after[name] for name in before} == before
    assert [name for name, _ in spans] == ["ppo.prefill"] * CHUNKS
    for _, v in spans:
        assert list(v) == ["calls", "rows", "width", "prompt_tokens", "padded_tokens", "pad_tokens"]
        assert (v["rows"], v["width"], v["padded_tokens"]) == (8, 128, 8 * 128)


@pytest.mark.parametrize("width, bucket", [(100, 128), (40, 64)])
def test_generate_runs_a_batch_at_the_width_it_was_given(trainer, width, bucket):
    """An evaluation batch as wide as the rollout pool, and a narrower one,
    of prompts far shorter than either: nothing narrows them (ROADMAP D17)."""
    _with_pool(trainer, POOL)
    ids, mask = _left_padded([5, 30, 17, 23, 8, 2, 29, 11], width, trainer.tokenizer.pad_token_id)
    gen_kwargs = dict(max_new_tokens=MAX_NEW + 8, do_sample=False)  # a budget no other test's program has
    before = set(_generate_programs(trainer))
    out = jax.device_get(trainer.generate(ids, mask, gen_kwargs))
    assert out["samples"].shape == (8, width + MAX_NEW + 8)
    np.testing.assert_array_equal(out["samples"][:, :width], ids)
    new = set(_generate_programs(trainer)) - before
    assert len(new) == 1 and all(n.startswith(f"generate[b8,p{bucket},lm,kw") for n in new), new


def test_collections_add_no_generate_compile_after_the_first(trainer, monkeypatch):
    _with_pool(trainer, POOL)
    backend = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: backend.append(kw.get("fun_name"))
        if event.endswith("backend_compile_duration") else None)
    logged = []
    monkeypatch.setattr(trainer.tracker, "log", lambda stats, step=None: logged.append(stats))
    for cycle in range(3):
        trainer.store.clear_history()
        trainer.make_experience(WINDOW, cycle)
        assert len(trainer.store) == WINDOW
        if cycle == 0:
            # the one program every chunk runs through, whatever its longest prompt
            programs = _generate_programs(trainer)
            rollouts = [n for n in programs if n.startswith("generate[b8,p128,lm,kw")]
            assert rollouts and all(programs[n] == 1 for n in rollouts), programs
            del backend[:]
        calls, width_sum, padded, pad = (int(x) for x in trainer._prefill_tally)
        # four chunks at the pool's width in its 32-column bucket (no plan: one block of 128)
        assert (calls, width_sum, padded) == (CHUNKS, CHUNKS * 128, CHUNKS * 8 * 128)
        assert logged[-1]["rollout/prefill_width"] == 128
        assert logged[-1]["rollout/prefill_padding_share"] == pad / padded
    assert _generate_programs(trainer) == programs, "a collection compiled a generate program"
    assert not [name for name in backend if "generate" in str(name)], backend


@pytest.mark.parametrize("prompts, num_rollouts", [
    (POOL, ROWS),                      # a collection is one chunk
    (_prompts([40] * 24), WINDOW),     # a pool of one length
])
def test_one_chunk_recipe_and_fixed_pool_keep_the_parents_batches_and_program(
        trainer, prompts, num_rollouts):
    _with_pool(trainer, prompts, num_rollouts)
    parent = LoaderStream(PromptPipeline(prompts, max_prompt_length=100, tokenizer=trainer.tokenizer)
                          .create_loader(ROWS, shuffle=True))
    gen_kwargs = trainer.generate_kwargs
    before = set(_generate_programs(trainer))
    for _ in range(5):
        batch, want = next(trainer.prompt_iterator), next(parent)
        np.testing.assert_array_equal(batch["input_ids"], want["input_ids"])
        np.testing.assert_array_equal(batch["attention_mask"], want["attention_mask"])
        out = trainer._rollout_generate(batch, gen_kwargs)
        assert out["samples"].shape == (ROWS, batch["input_ids"].shape[1] + MAX_NEW)
    new = set(_generate_programs(trainer)) - before
    width = -(-batch["input_ids"].shape[1] // 32) * 32
    assert len(new) <= 1 and all(n.startswith(f"generate[b8,p{width},lm,kw") for n in new), new


def test_prefill_counter_adds_up(trainer, monkeypatch):
    _with_pool(trainer, POOL)
    spans = _spans_of(monkeypatch)
    gen_kwargs = trainer.generate_experience_kwargs or trainer.generate_kwargs
    tokens, longest = 0, []
    for _ in range(CHUNKS):
        batch = next(trainer.prompt_iterator)
        tokens += int(batch["attention_mask"].sum())
        longest.append(int(batch["attention_mask"].sum(axis=1).max()))
        out = trainer._rollout_generate(batch, gen_kwargs)
        assert out["samples"].shape == (ROWS, 100 + MAX_NEW)
    assert [name for name, _ in spans] == ["ppo.prefill"] * CHUNKS
    for _, v in spans:
        assert list(v) == ["calls", "rows", "width", "prompt_tokens", "padded_tokens", "pad_tokens"]
        assert v["calls"] == 1 and v["rows"] == 8  # 4 prompts in `generate`'s row bucket of 8
        assert v["width"] == 128  # the pool's width in its 32-column bucket, whatever the chunk holds
        assert v["padded_tokens"] == v["rows"] * v["width"]
        assert v["pad_tokens"] == v["padded_tokens"] - v["prompt_tokens"]
    assert sum(v["prompt_tokens"] for _, v in spans) == tokens
    # the chunks themselves still come longest first
    assert longest == sorted(longest, reverse=True) and len(set(longest)) == CHUNKS


def test_trainer_resumes_the_stream_where_it_was(trainer):
    _with_pool(trainer, POOL)
    _take(trainer.prompt_iterator, 3)
    state = trainer._extra_resume_state()
    assert state["prompt_stream"] == {"epoch": 0, "position": 3}
    want = _take(trainer.prompt_iterator, 9)
    _with_pool(trainer, POOL)  # a restarted process: a new loader, at the pool's start
    trainer._load_extra_resume_state(state)
    assert _take(trainer.prompt_iterator, 9) == want


@pytest.fixture(scope="module")
def grpo():
    from trlx_tpu.trainer.grpo_trainer import GRPOTrainer

    config = default_grpo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=104, batch_size=8, tracker=None),
        method=dict(num_rollouts=16, chunk_size=4, ppo_epochs=1, group_size=2,
                    gen_kwargs=dict(max_new_tokens=MAX_NEW, do_sample=True)),
    )
    return GRPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples),
                       devices=jax.devices()[:1])


def test_grpo_groups_stay_together_in_sorted_chunks(grpo):
    grpo.add_prompt_pipeline(PromptPipeline(POOL, max_prompt_length=100, tokenizer=grpo.tokenizer))
    # a collection is 8 prompts x 2 completions in 4 chunks of 2 prompts
    window = [p for chunk in _take(grpo.prompt_iterator, 4) for p in chunk]
    assert len(window) == 16 and window[0::2] == window[1::2]
    assert [len(p) for p in window] == sorted((len(p) for p in window), reverse=True)
    assert grpo._rollout_plan(100, grpo.generate_kwargs) is None  # one block of 128


def test_grpo_chunk_goes_through_the_block_form(grpo, monkeypatch):
    from trlx_tpu.ops import sampling

    monkeypatch.setattr(sampling, "PREFILL_BLOCK", BLOCK)
    grpo.add_prompt_pipeline(PromptPipeline(POOL, max_prompt_length=100, tokenizer=grpo.tokenizer))
    spans = _spans_of(monkeypatch)
    gen_kwargs = dict(max_new_tokens=MAX_NEW + 1, do_sample=True)  # a budget a block: see below
    for _ in range(4):
        batch = next(grpo.prompt_iterator)
        lengths = batch["attention_mask"].sum(axis=1)
        out = jax.device_get(grpo._rollout_generate(batch, gen_kwargs))
        # 2 prompts x 2 completions, a prompt's side by side, each from its own draw
        np.testing.assert_array_equal(batch["input_ids"][0::2], batch["input_ids"][1::2])
        np.testing.assert_array_equal(out["samples"][:, :100], batch["input_ids"])
        assert out["samples"].shape == (4, 100 + MAX_NEW + 1)
        _, v = spans[-1]
        assert (v["rows"], v["blocks"]) == (8, 4)
        assert v["blocks_run"] == -(-int(lengths.max()) // BLOCK)  # from the group's longest prompt
        assert v["width"] == v["blocks_run"] * BLOCK and v["prompt_tokens"] == int(lengths.sum())
    assert [v["blocks_run"] for _, v in spans] == sorted((v["blocks_run"] for _, v in spans), reverse=True)
    assert len({v["blocks_run"] for _, v in spans}) > 1


# ---------------------------------------------------------------------------
# a sampler whose one program follows the chunk's longest prompt (BlockPlan)
# ---------------------------------------------------------------------------

@pytest.fixture
def block_form(trainer, monkeypatch):
    from trlx_tpu.ops import sampling

    monkeypatch.setattr(sampling, "PREFILL_BLOCK", BLOCK)
    return _with_pool(trainer, POOL)


def test_block_form_plan_is_the_samplers_own_rule(block_form):
    trainer = block_form
    plan = trainer._rollout_plan(100, trainer.generate_kwargs)
    assert (plan.block, plan.pad, plan.blocks, plan.columns) == (BLOCK, 0, 4, 128 + MAX_NEW)
    # the loader still sorts a collection's prompts: that is what makes a chunk's longest short
    window = [p for chunk in _take(trainer.prompt_iterator, CHUNKS) for p in chunk]
    assert [len(p) for p in window] == sorted((len(p) for p in window), reverse=True)
    # who has no plan: a pipelined trainer's own `generate`
    # (right padding, one block and beams: the test of the pool's width above)
    from trlx_tpu.trainer.pipelined_ppo_trainer import PipelinedPPOTrainer

    assert PipelinedPPOTrainer._rollout_plan(trainer, 100, trainer.generate_kwargs) is None


def test_block_form_runs_four_chunks_through_one_program_and_counts_its_blocks(block_form, monkeypatch):
    trainer = block_form
    spans = _spans_of(monkeypatch)
    # (a token budget of its own: the block is not in a program's name)
    new_tokens = MAX_NEW + 4
    gen_kwargs = dict(trainer.generate_experience_kwargs or trainer.generate_kwargs, max_new_tokens=new_tokens)
    before, tokens, longest = set(_generate_programs(trainer)), 0, []
    trainer._prefill_tally[:] = 0  # as a collection's start does
    for _ in range(CHUNKS):
        batch = next(trainer.prompt_iterator)
        tokens += int(batch["attention_mask"].sum())
        longest.append(int(batch["attention_mask"].sum(axis=1).max()))
        out = trainer._rollout_generate(batch, gen_kwargs)
        assert out["samples"].shape == (ROWS, 100 + new_tokens)
        np.testing.assert_array_equal(np.asarray(out["samples"])[:, :100], batch["input_ids"])
    new = set(_generate_programs(trainer)) - before
    assert len(new) == 1 and all(n.startswith("generate[b8,p128,lm,kw") for n in new), new
    assert len(set(longest)) == CHUNKS, longest  # four chunks, four longest prompts
    assert [name for name, _ in spans] == ["ppo.prefill"] * CHUNKS
    for (_, v), need in zip(spans, longest):
        assert list(v) == ["calls", "rows", "width", "prompt_tokens", "padded_tokens", "pad_tokens",
                           "blocks", "blocks_run", "read_columns", "cache_columns"]
        assert v["rows"] == 8 and v["blocks"] == 4 and v["cache_columns"] == 128 + new_tokens
        assert v["blocks_run"] == -(-need // BLOCK)  # the blocks that hold the longest prompt
        assert v["width"] == v["blocks_run"] * BLOCK
        assert v["padded_tokens"] == v["rows"] * v["blocks_run"] * BLOCK
        assert v["pad_tokens"] == v["padded_tokens"] - v["prompt_tokens"]
        # the narrowest of the cache's suffix widths that holds the prompt and the response
        widths = live_widths(128 + new_tokens)
        assert widths == (40, 56, 72, 104, 136)
        assert v["read_columns"] == next(w for w in widths if w >= need + new_tokens)
    assert sum(v["prompt_tokens"] for _, v in spans) == tokens
    calls, width_sum, padded, pad = (int(x) for x in trainer._prefill_tally)
    assert calls == CHUNKS and width_sum == sum(v["width"] for _, v in spans)
    assert padded == sum(v["padded_tokens"] for _, v in spans) < CHUNKS * 8 * 128


# the chunk's longest prompt by the block of 4 it starts in (the bucket's 128 columns
# hold the pool's 100 behind 28 of `_bucket_prompts`' padding)
LONGEST_BY_FIRST_BLOCK = {0: 100, 1: 90, 2: 50, 3: 30}


@pytest.mark.parametrize("capture", [True, False], ids=["capture", "plain"])
@pytest.mark.parametrize("first_block", sorted(LONGEST_BY_FIRST_BLOCK))
def test_block_form_chunk_matches_the_one_shot_program(block_form, monkeypatch, first_block, capture):
    """Through the trainer's own door, with and without the captured stats:
    the chunk generated by blocks against the same chunk through the
    one-shot program, whichever block its longest prompt starts in. (The two
    programs of a `capture` value are made by the first of its cases to run,
    whichever that is, and read from the trainer's cache by the others.)"""
    from trlx_tpu.ops import sampling

    trainer = block_form
    longest = LONGEST_BY_FIRST_BLOCK[first_block]
    ids, mask = _left_padded([5, longest, 17, 23, 8, 2, 29, 11], 100, trainer.tokenizer.pad_token_id,
                             seed=first_block)
    # greedy, so one more token of budget changes none of the tokens before
    # it (a budget a program: the block is not in a program's name)
    n = MAX_NEW + 2
    plan = trainer._rollout_plan(100, dict(max_new_tokens=n, do_sample=False))
    assert int(plan.first_block(int(sampling.first_live_column(mask)) + 28)) == first_block
    before = set(_generate_programs(trainer))
    blocks = jax.device_get(trainer.generate(ids, mask, dict(max_new_tokens=n, do_sample=False),
                                             capture=capture))
    monkeypatch.setattr(sampling, "PREFILL_BLOCK", 0)
    whole = jax.device_get(trainer.generate(ids, mask, dict(max_new_tokens=n + 1, do_sample=False),
                                            capture=capture))
    # the pair is made by whichever case of this `capture` value runs first
    # and read from the trainer's cache by the rest: none is made twice
    programs = _generate_programs(trainer)
    new = set(programs) - before
    stem = "generate[b8,p128,lm," + ("cap," if capture else "") + "kw"
    assert len(new) <= 2 and all(name.startswith(stem) for name in new), new
    ours = {name: made for name, made in programs.items() if name.startswith(stem)}
    assert len(ours) >= 2 and set(ours.values()) == {1}, ours
    assert ("h_split" in blocks) == ("logprobs" in blocks) == capture and sorted(blocks) == sorted(whole)
    assert blocks["samples"].shape == (8, 100 + n)
    for name in ("samples", "samples_mask"):
        np.testing.assert_array_equal(blocks[name], whole[name][:, :100 + n])
    np.testing.assert_array_equal(blocks["samples"][:, :100], ids)
    if capture:
        assert blocks["h_split"].shape[:2] == (8, 100 + n)
        np.testing.assert_allclose(blocks["logprobs"], whole["logprobs"][:, :n], atol=2e-5)
        np.testing.assert_allclose(blocks["values"], whole["values"][:, :n], atol=2e-5)
        live = blocks["samples_mask"].astype(bool)[:, :-1]
        np.testing.assert_allclose(blocks["h_split"][:, :-1][live], whole["h_split"][:, :100 + n - 1][live],
                                   atol=2e-5)
