"""The lowered text of the benchmark cells' programs, one file a program,
for comparing two trees: a refactor that moves Python and no operation
leaves every file the same.

    cd <tree> && python scripts/lowered_text.py --out /root/scratch/text/<tag>
    python scripts/lowered_text.py --compare /root/scratch/text/parent /root/scratch/text/change

A Pallas kernel arrives in the text as a serialized Mosaic module whose
debug locations hold the path and line of every frame that led to the call
(`.../trlx_tpu/models/transformer.py:916`), so two checkouts of one commit
already differ there; `--compare` reads each kernel body without its
locations and holds everything else to the letter.

Needs no chip: every program is traced at its cell's widths and recipe
(depth cut to `--layers`, which changes no shape) and lowered for the TPU
platform with the Pallas kernels on, never compiled or run. The PPO cells'
trainers are built as `bench/jobs/ppo.py` builds them and stopped at the
first call of each jitted program (`_ljit` is where a trainer makes one,
so the score program is reached through `_score_fn` whether or not that is
a door in front of it; where the schedule trains from the trunk cache, the
resumed train step is taken beside the whole-forward step, and the fill
where the schedule runs one: not where the score program hands the state
out);
the serve cells' engines as `tests/test_serve_cells_compile_tpu.py` builds them.
"""

import argparse
import base64
import hashlib
import os
import re
import sys
import types

ROOT = os.getcwd()
sys.path[:0] = [os.path.join(ROOT, "bench"), ROOT]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

PPO_CELLS = ("pythia-1.4b.ppo-hh", "gpt2-xl.ppo-sentiments", "lfm2-8b-a1b.ppo-hh")
# serve cell -> the (rows, width) of the insert programs whose text is taken
SERVE_CELLS = {"pythia-1.4b.rollout-batch": ((1, 256), (8, 512)),
               "laguna-xs.2.rollout-code": ((1, 2048), (2, 4096)),
               "openpangu-ultra-moe-718b.rollout-longctx": ((1, 8192),),
               # a tree from before PR 41 has no such preset: name the other cells with --cells there
               "ling-3.0-flash-vl.rollout-reason": ((1, 1024),),
               "solar-open2-250b.rollout-longctx": ((1, 8192),),  # likewise from before PR 43
               "falcon-h1-34b.rollout-chat": ((1, 1024),),  # and from before PR 48
               "dots3-note-prev.rollout-longdoc": ((1, 8192),),  # and from before PR 51
               "smallthinker-21b-a3b.rollout-transcript": ((1, 8192),),  # and from before PR 55
               "ouro-2.6b.rollout-math": ((1, 256),)}  # and from before PR 57


class Lowered(Exception):
    """Raised in place of running a program whose text has been taken."""


def lower_text(jitted, args, kwargs=None) -> str:
    return jitted.trace(*args, **(kwargs or {})).lower(lowering_platforms=("tpu",)).as_text()


def install_capture(texts: dict):
    """Every `_ljit` program of a trainer: lower at its first call, keep the
    text under the site's name, and stop the caller there."""
    from trlx_tpu.observability import compile_ledger
    from trlx_tpu.ops import attention

    attention.kernel_mode = lambda: "pallas"  # the CPU's devices would say "off"
    plain_jit = compile_ledger.ledgered_jit

    def capturing_jit(fn, name, budget=1, ledger=None, **jit_kwargs):
        jitted = jax.jit(fn, **jit_kwargs)

        def call(*args, **kwargs):
            texts[name] = lower_text(jitted, args, kwargs)
            raise Lowered(name)

        return call

    compile_ledger.ledgered_jit = capturing_jit
    return lambda: setattr(compile_ledger, "ledgered_jit", plain_jit)


def stop_at_program(fn, *args, **kwargs):
    try:
        fn(*args, **kwargs)
    except Lowered:
        return
    raise RuntimeError(f"{fn} returned without reaching a jitted program")


def ppo_programs(workload: str, layers: int) -> dict:
    from benchlib import files
    from trlx_tpu.data import PPORLBatch

    _, cell, config, traffic = files.load_cell(workload)
    config["program"]["model_extra_configs"]["n_layers"] = layers
    texts = {}
    uninstall = install_capture(texts)
    ctx = types.SimpleNamespace(cell=cell, config=config, traffic=traffic, seed=7,
                                rehearse=False, control=False)
    trainer, cfg, pipeline = files.load_module("jobs/ppo.py").build_trainer(ctx)
    batch = next(iter(pipeline.create_loader(cfg.method.chunk_size, shuffle=False)))
    ids, mask = np.asarray(batch["input_ids"]), np.asarray(batch["attention_mask"])
    max_new = int(cfg.method.gen_kwargs["max_new_tokens"])

    stop_at_program(trainer.generate, ids, mask, cfg.method.gen_kwargs)
    # the sampler check's program: 8 rows, the cell's `sampler_tokens`, capture on
    stop_at_program(trainer.generate, ids[:8], mask[:8],
                    {**cfg.method.gen_kwargs, "max_new_tokens": int(cell["check"]["sampler_tokens"])},
                    capture=True)
    trainer._build_score_fn()
    tokens = jnp.zeros((ids.shape[0], ids.shape[1] + max_new), jnp.int32)
    stop_at_program(trainer._score_fn, trainer.train_params, trainer.frozen_params,
                    trainer.ref_params, tokens)
    b = cfg.train.batch_size
    zeros = np.zeros((b, max_new), np.float32)
    minibatch = PPORLBatch(query_tensors=np.ones((b, ids.shape[1]), np.int32),
                           response_tensors=np.ones((b, max_new), np.int32),
                           logprobs=zeros, values=zeros, rewards=zeros)
    stop_at_program(trainer.train_minibatch, [minibatch])
    if trainer._trunk_cache_available():
        # the schedule trains from the trunk cache: one chunk's fill (none
        # where the score program has handed the chunk's state out: a tree
        # from before that fills always), and the step over a batch that
        # names its rows of the cycle's array
        if not getattr(trainer, "_score_with_trunk_state", False):
            trainer._open_trunk_cache()
            trainer._note_trunk_chunk(ids, np.ones((ids.shape[0], max_new), np.int32))
            stop_at_program(trainer._close_trunk_cache)
        trainer._trunk_cache = jax.ShapeDtypeStruct(
            (cfg.method.num_rollouts, ids.shape[1] + max_new, trainer.model_cfg.d_model),
            trainer.model_cfg.dtype)
        whole = texts["train_step"]
        stop_at_program(trainer.train_minibatch,
                        [minibatch.replace(trunk_rows=np.arange(b, dtype=np.int32))])
        texts["train_step.from_trunk_cache"], texts["train_step"] = texts["train_step"], whole
    uninstall()
    return texts


def serve_programs(workload: str, layers: int) -> dict:
    from benchlib import files
    from jax.sharding import SingleDeviceSharding
    from trlx_tpu.inference import InferenceEngine
    from trlx_tpu.models import CausalLMPolicy, config_from_preset
    from trlx_tpu.models.transformer import prefill_fuses
    from trlx_tpu.ops.sampling import GenerationConfig

    _, cell, config, mix = files.load_cell(workload)
    eng = cell["engine"]
    # the configuration as `bench/jobs/serve.py` builds it, its per-layer lists cut with the depth
    extra = {k: v[:layers] if isinstance(v, list) else v
             for k, v in config["program"]["model_extra_configs"].items()}
    extra["n_layers"] = layers
    cfg = config_from_preset(config["program"]["model_path"].split(":")[1], extra.pop("vocab_size"),
                             **extra, param_dtype=jnp.bfloat16, dtype=jnp.bfloat16)
    model = CausalLMPolicy(cfg)
    tokens = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"])
    gen_cfg = GenerationConfig(max_new_tokens=int(mix["output_len"]["max"]), do_sample=True,
                               eos_token_id=cfg.vocab_size + 1, pad_token_id=0)
    tpu = types.SimpleNamespace(platform="tpu")  # the engine picks the kernel by its params' device
    InferenceEngine._param_devices = lambda self: [tpu]
    engine = InferenceEngine(
        model, cfg, None, gen_cfg, kv_paging=True, num_slots=eng["num_slots"],
        max_prompt_len=eng["max_prompt_len"], max_prefill_batch=eng["max_prefill_batch"],
        prompt_bucket=eng["prompt_bucket"], kv_block_size=eng["kv_block_size"],
        kv_pool_blocks=eng["kv_pool_blocks"], kv_cache_dtype=eng["kv_cache_dtype"])
    assert engine.decode_path == "pallas", engine.decode_path
    one = SingleDeviceSharding(jax.devices()[0])
    abstract = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)
    pool, params = abstract(engine._pool), abstract(params)
    texts = {"engine.decode": lower_text(engine._decode_fn, (params, pool))}
    n_tbl = engine._pool["table"].shape[1]
    for rows, width in SERVE_CELLS[workload]:
        shapes = dict(ids=(rows, width), tmask=(rows, width), tables=(rows, n_tbl),
                      slot_ids=(rows,), max_new=(rows,), shared_len=(rows,))
        args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one) for s in shapes.values()]
        fresh = prefill_fuses(cfg, width)  # the program a prompt with no cached prefix takes
        texts[f"engine.paged_insert[b{rows},p{width}{',fresh' if fresh else ''}]"] = lower_text(
            engine._get_paged_insert(rows, width, fresh), (pool, params, *args))
    return texts


KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def without_kernel_locations(text: str) -> str:
    """`text` with every serialized Mosaic module replaced by a digest of
    its operations printed without debug locations."""
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True

    def digest(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return match.group(1) + hashlib.sha256(asm.encode()).hexdigest() + match.group(3)

    return KERNEL_BODY.sub(digest, text)


def compare(a: str, b: str) -> int:
    names = sorted(n for n in set(os.listdir(a)) | set(os.listdir(b)) if n.endswith(".txt"))
    differing = 0
    for name in names:
        pa, pb = os.path.join(a, name), os.path.join(b, name)
        if not (os.path.exists(pa) and os.path.exists(pb)):
            verdict = "only on one side"
        else:
            ta, tb = open(pa).read(), open(pb).read()
            kernels = len(KERNEL_BODY.findall(ta))
            if ta == tb:
                verdict = f"identical ({kernels} kernel calls)"
            elif without_kernel_locations(ta) == without_kernel_locations(tb):
                verdict = f"identical but for the debug locations inside {kernels} kernel bodies"
            else:
                verdict = "DIFFERENT"
        differing += verdict in ("DIFFERENT", "only on one side")
        print(f"{name}: {verdict}")
    return differing


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--compare", nargs=2, metavar=("A", "B"))
    parser.add_argument("--out")
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--cells", nargs="*", default=[*PPO_CELLS, *SERVE_CELLS])
    args = parser.parse_args()
    if args.compare:
        return compare(*args.compare)
    os.makedirs(args.out, exist_ok=True)
    for cell in args.cells:
        texts = (serve_programs if cell in SERVE_CELLS else ppo_programs)(cell, args.layers)
        for name, text in texts.items():
            path = os.path.join(args.out, re.sub(r"[^\w.\-]+", "_", f"{cell}.{name}") + ".txt")
            with open(path, "w") as f:
                f.write(text)
        print(f"{cell}: {sorted(texts)}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
