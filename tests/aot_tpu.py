"""What the AOT-compile tests share: a described v5e, the lowering for it,
and the readers of a compiled program's text and of its memory account.

The installed libtpu compiles for a described topology
(`jax.experimental.topologies`), so the TPU compiler's verdict on a kernel or
a whole program costs seconds of CPU here instead of minutes of chip. Every
file that imports this skips without libtpu
(`pytest.importorskip("libtpu")` in front of the import)."""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

S = jax.ShapeDtypeStruct
BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8

# pythia-1.4b.rollout-batch (bench/workloads): 1,280 blocks of 32 tokens,
# 16 kv heads of 128, 64 slots x 20 table entries
CELL = dict(n_blocks=1280, nkv=16, blk=32, hd=128, slots=64, n_tbl=20)

# laguna-xs.2.rollout-code (bench/workloads): 7,168 blocks of 32 tokens, 8 kv
# heads of 128, 64 slots x 160 table entries, 48 query heads on full layers
# and 64 on sliding ones (window 512)
CODE_CELL = dict(n_blocks=7168, nkv=8, blk=32, hd=128, slots=64, n_tbl=160, window=512)

# openpangu-ultra-moe-718b.rollout-longctx (bench/workloads): 12,288 blocks of
# 32 tokens, one latent plane of 512 + 64 values a token a layer, 64 slots x
# 288 table entries, 128 query heads of 128 + 64 against values of 128
LONGCTX_CELL = dict(n_blocks=12288, blk=32, width=576, values=512, heads=128, slots=64, n_tbl=288,
                    qk_dim=192, v_dim=128)

# lfm2-8b-a1b.ppo-hh (bench/workloads): d 2048, experts of width 1792, 8 of
# 32 held, 4 a token; a train step's 16 x 1024 tokens (65,536 dispatch rows)
# and a decode step's 64
LFM2 = dict(vocab_size=16384, n_layers=10, moe_local_experts=8, attn_impl="flash")


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices


def compile_for(fn, args, shardings, out_shardings=None):
    """Lower `fn` for the TPU and compile it for the shardings' devices."""
    jitted = jax.jit(fn, in_shardings=shardings, out_shardings=out_shardings)
    return jitted.trace(*args).lower(lowering_platforms=("tpu",)).compile()


def mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def kernel_names(compiled) -> list:
    """The names of the Mosaic custom calls of a compiled program: the
    `name=` of each `pl.pallas_call`, which is what the profiler's trace
    calls the kernel's events (`%flash_fwd.1 = ... custom-call(...)`)."""
    return [m.group(1) for m in re.finditer(
        r'%([A-Za-z_][\w-]*?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())]



@pytest.fixture
def pallas_mode(monkeypatch):
    """The kernel rule looks at the process's devices, which are CPUs here:
    the test answers for it, as `cell_engine` does for the engine."""
    from trlx_tpu.ops import attention

    monkeypatch.setattr(attention, "kernel_mode", lambda: "pallas")


def _instructions(compiled):
    """(elements of the result, opcode, text) of every instruction of a
    compiled program whose result is one array."""
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if m:
            yield int(np.prod([int(d) for d in m.group(1).split(",")])), m.group(2), line.strip()


def arena_rewrites(compiled, *operands) -> list:
    """The `copy` / `transpose` instructions of a compiled program whose
    result has as many elements as one of `operands` (a K/V arena or an
    int8 scale plane, under whatever shape the program views it): each is
    the whole operand moved through HBM once more than the work needs."""
    sizes = {int(np.prod(op.shape)) for op in operands}
    return [text[:120] for n, op, text in _instructions(compiled)
            if op in ("copy", "transpose") and n in sizes]


def instructions_of_at_least(compiled, elements: int) -> list:
    """The instructions of a compiled program whose result has `elements`
    elements or more, other than a buffer's update in place and what only
    names a buffer."""
    names_or_updates = ("parameter", "bitcast", "get-tuple-element", "tuple", "dynamic-update-slice")
    return [text[:160] for n, op, text in _instructions(compiled)
            if n >= elements and op not in names_or_updates]


def loop_body_instructions(compiled, having: str = ""):
    """(elements, opcode, text) of every array-valued instruction that a
    `while` of a compiled program (one whose body's text holds `having`)
    runs as a step of its own: those of its
    body and condition and of the loops and branches nested in them. A
    fusion counts as what its root is (a fusion that is one `copy` moves
    its operand through HBM; a `copy` nested inside a product's fusion is
    how that product reads its operand, and is not listed)."""
    text = compiled.as_text()
    blocks = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M)}
    flow = r"(?:body|condition|branch_computations|true_computation|false_computation|to_apply)=\{?([^}\n]*)"
    whiles = [re.findall(r"%([\w.-]+)", " ".join(re.findall(flow, line)))
              for body in blocks.values() for line in body.splitlines() if " while(" in line]
    todo = [name for names in whiles if any(having in blocks.get(n, "") for n in names) for name in names]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in blocks:
            continue
        seen.add(name)
        todo += [n for line in blocks[name].splitlines() if " fusion(" not in line and " reduce(" not in line
                 for names in re.findall(flow, line) for n in re.findall(r"%([\w.-]+)", names)]
    result = r"= \w+\[([\d,]+)\]\S* ([\w-]+)\("
    for name in sorted(seen):
        for line in blocks[name].splitlines():
            m = re.search(result, line)
            if not m:
                continue
            op, fused = m.group(2), re.search(r"calls=%([\w.-]+)", line)
            if op == "fusion" and fused and fused.group(1) in blocks:
                root = re.search(r"ROOT [^\n]*?" + result, blocks[fused.group(1)])
                op = root.group(2) if root else op
            yield int(np.prod([int(d) for d in m.group(1).split(",")])), op, line.strip()


def donated_outputs(compiled) -> int:
    """How many outputs of the program live in a donated input's buffer."""
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry_computation_layout",
                      compiled.as_text())
    return alias.group(1).count("-alias)") if alias else 0


def abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype, sharding=sharding), tree)



def held_bytes(compiled) -> int:
    """What the program holds on the device while it runs, by the compiler's
    account: arguments, temporaries and outputs, a donated buffer once."""
    memory = compiled.memory_analysis()
    return memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.output_size_in_bytes - memory.alias_size_in_bytes


def serve_cell_engine(v5e, name: str, traffic: str, max_new: int, n_tbl: int):
    """A paged `InferenceEngine` as the cell `<name>.<traffic>` builds it, at
    the configuration file's own cut and no weights: (engine, abstract params)."""
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.models import CausalLMPolicy, config_from_preset
    from trlx_tpu.ops.sampling import GenerationConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = json.load(open(os.path.join(root, "bench", "configs", f"{name}.json")))["bench"]
    cell = json.load(open(os.path.join(root, "bench", "workloads", f"{name}.{traffic}.json")))["engine"]
    extra = dict(bench["program"]["model_extra_configs"])
    cfg = config_from_preset(bench["program"]["model_path"].split(":", 1)[1], extra.pop("vocab_size"), **extra,
                             param_dtype=BF16, dtype=BF16)
    model = CausalLMPolicy(cfg)
    tokens = jnp.zeros((1, 32), I32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"])
    gen_cfg = GenerationConfig(max_new_tokens=max_new, do_sample=True,
                               eos_token_id=cfg.vocab_size + 1, pad_token_id=0)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(InferenceEngine, "_param_devices", lambda self: [v5e[0]])
        engine = InferenceEngine(
            model, cfg, None, gen_cfg, kv_paging=True, num_slots=cell["num_slots"],
            max_prompt_len=cell["max_prompt_len"], max_prefill_batch=cell["max_prefill_batch"],
            prompt_bucket=cell["prompt_bucket"], kv_block_size=cell["kv_block_size"],
            kv_pool_blocks=cell["kv_pool_blocks"], kv_cache_dtype=cell["kv_cache_dtype"])
    assert engine.decode_path == "pallas" and engine._n_tbl == n_tbl
    return engine, params


def compile_engine_program(engine, params, device, prefill=None):
    """The engine's decode step, or with `prefill=(rows, width[, fresh])` that
    prefill program over as many table entries as a slot has, traced over
    shapes and compiled for `device` with the pool donated."""
    one = SingleDeviceSharding(device)
    pool, params = abstract(engine._pool, one), abstract(params, one)
    if prefill is None:
        traced = engine._decode_fn.trace(params, pool)
    else:
        rows, width = prefill[:2]
        shapes = dict(ids=(rows, width), tmask=(rows, width), tables=(rows, engine._n_tbl),
                      slot_ids=(rows,), max_new=(rows,), shared_len=(rows,))
        traced = engine._get_paged_insert(*prefill).trace(
            pool, params, *(S(shape, I32, sharding=one) for shape in shapes.values()))
    return traced.lower(lowering_platforms=("tpu",)).compile()


def ppo_cell_trainer(checkpoint_dir, preset, extra, *, batch_size, num_rollouts, chunk_size, max_new,
                     seq_length=1024):
    """A `PPOTrainer` whose scorer, loss and trunk-cache fill are a PPO
    cell's: built at test size, then handed the cell's model at the cell's
    widths, so `_build_score_fn`, `make_loss_fn` and `_build_trunk_cache_fn`
    trace the programs the cell runs over shapes and no array."""
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.models import CausalLMWithValueHead, config_from_preset
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=seq_length, batch_size=batch_size, tracker=None,
                   checkpoint_dir=str(checkpoint_dir)),
        method=dict(num_rollouts=num_rollouts, chunk_size=chunk_size, ppo_epochs=4,
                    gen_kwargs=dict(max_new_tokens=max_new, top_k=0, top_p=1.0, do_sample=True)),
    )
    trainer = PPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples),
                         devices=jax.devices()[:1])
    cfg = config_from_preset(preset, **extra)
    trainer.model, trainer.model_cfg, trainer.split = CausalLMWithValueHead(cfg), cfg, cfg.n_layers - 2
    # the trainer's mesh is this process's CPU; the programs are placed
    # where the test compiles them
    trainer._trunk_cache_sharding = lambda shape=None: None
    assert trainer._trunk_cache_available()
    return trainer


def ppo_cell_params(trainer, with_ref=False):
    """(trainable, frozen) flat float32 leaves of the cell's model, as
    shapes; with the reference branch's subtree where asked."""
    from flax.traverse_util import flatten_dict

    from trlx_tpu.models.policy import ref_param_subtree, trainable_mask

    probe = jnp.zeros((1, 8), I32)
    params = jax.eval_shape(
        lambda: trainer.model.init(jax.random.PRNGKey(0), probe, jnp.ones_like(probe))["params"])
    flat = flatten_dict(params)
    mask = flatten_dict(trainable_mask(params, trainer.model_cfg, 2))
    train = {k: v for k, v in flat.items() if mask[k]}
    frozen = {k: v for k, v in flat.items() if not mask[k]}
    if not with_ref:
        return train, frozen
    return train, frozen, jax.eval_shape(
        lambda p: ref_param_subtree(p, trainer.model_cfg, trainer.split), params)


def traced_generate(trainer, device, rows, width, **generate_kwargs):
    """The trainer's `generate` program for a batch of that shape, traced
    over shapes (float32 leaves, as the PPO cells hold them) for one
    described chip."""
    one = SingleDeviceSharding(device)
    probe = jnp.zeros((1, 8), I32)
    params = jax.eval_shape(
        lambda: trainer.model.init(jax.random.PRNGKey(0), probe, jnp.ones_like(probe))["params"])
    tokens = S((rows, width), I32, sharding=one)
    fn = trainer.get_generate_fn(rows, width, trainer.generate_kwargs, **generate_kwargs)
    return fn.trace(abstract(params, one), tokens, tokens,
                    S(trainer.rng.shape, trainer.rng.dtype, sharding=one))


def traced_score(trainer, device, rows, width, hands_out):
    """The one program behind `_score_fn`, from where the trainer makes it,
    traced over a chunk of the cell for one described chip."""
    programs, ljit = {}, type(trainer)._ljit
    trainer._ljit = lambda fn, name, **kw: programs.setdefault(name, ljit(trainer, fn, name, **kw))
    trainer._score_hands_out_trunk_state = lambda program=None: hands_out
    trainer._build_score_fn()
    assert trainer._score_with_trunk_state is hands_out and list(programs) == ["score"]
    assert (trainer._score_fn is programs["score"]) is not hands_out
    return programs["score"].trace(*abstract(
        (*ppo_cell_params(trainer, with_ref=True), S((rows, width), I32)), SingleDeviceSharding(device)),
        with_trunk_state=hands_out)
