"""Chip smoke: PPO and the serving engine through their normal doors, at
GPT-2 small's full width, on the TPU this process finds.

    python chip_smoke.py                      # the check: needs a TPU
    python chip_smoke.py --fsdp 2 --tensor 2  # a parameter-sharding layout
    python chip_smoke.py --rehearse-cpu       # tiny CPU walk-through of the
                                              # same code; never a pass

One process (a chip belongs to one process at a time), no network, every
weight random from a seed. In order, failing the run the moment a step
fails:

1. device   — platform, kind, count, jax/jaxlib/libtpu versions; anything
              but a TPU is a failure, not a fallback.
2. trainer  — `trlx_tpu.train` with `default_ppo_config()` (random
              gpt2-small, byte tokenizer, 128 rollouts of 64 + 40 tokens,
              batch 32, 4 PPO epochs, 2 unfrozen layers) widened to the
              real 50,257 vocab with `attn_impl="flash"`; library defaults
              otherwise; two collection cycles. Losses finite at every
              step, trainable params moved, second cycle compiled nothing,
              every device of the mesh holds train state and did work.
3. server   — `trainer.serve(background=True)` with `inference.kv_paging`,
              `/generate` requests of several lengths through
              `remote_generate`, some concurrent so slots are reused;
              `/healthz` names the decode kernel the engine resolved to.
4. kernels  — which path flash, fused CE and paged decode actually took in
              the programs above (on a TPU: the compiled kernel, nothing
              else), and their parity against the XLA references.

Prints compile seconds per program and the device's peak bytes; no rates.
The last stdout line of a passing run is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
"""

import argparse
import functools
import glob
import importlib.metadata
import itertools
import json
import math
import shutil
import sys
import tempfile
import time

N_PROMPT = 64
N_REQUESTS = (5, 16, 17, 33, 64, 100, 7, 48, 31, 64, 12, 90)  # > num_slots: reuse


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileLog:
    """Backend compiles of this process, from jax.monitoring: (seconds,
    function name, wall-clock time) per compile."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.events.append((duration, kwargs.get("fun_name", "?"), time.time()))

    def seconds_by_program(self):
        """Compile seconds grouped under the programs S6 asks about; the
        small helper jits (rng splits, casts, scatters) land in `other`."""
        programs = {"generate": "generate", "score": "score",
                    "train_step": "train", "train_scan": "train",
                    "accum_step": "train", "apply_step": "train",
                    "insert": "prefill", "prefill": "prefill", "decode": "decode"}
        groups = dict.fromkeys([*programs.values(), "other"], 0.0)
        for secs, name, _ in self.events:
            fn = name[4:-1] if name.startswith("jit(") else name
            groups[programs.get(fn, "other")] += secs
        return {k: round(v, 2) for k, v in groups.items()}


def device_phase(rehearse: bool):
    import jax
    import jaxlib

    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: {json.dumps(info)}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu}")
    if rehearse:
        check(dev.platform == "cpu", "--rehearse-cpu is for JAX_PLATFORMS=cpu")
        log("REHEARSAL on the CPU at a tiny size: proves the script's control "
            "flow only, and is never a pass on the chip")
    elif dev.platform != "tpu":
        raise SystemExit(
            f"[chip_smoke] FAIL: platform is {dev.platform!r} "
            f"({dev.device_kind}), not 'tpu' — this check does not run off-chip"
        )
    return info


def build_config(args, workdir: str):
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        # full GPT-2 vocab + the flash kernels
        model=dict(model_extra_configs=dict(vocab_size=50257, attn_impl="flash")),
        train=dict(total_steps=32, checkpoint_dir=f"{workdir}/ckpts",
                   logging_dir=f"{workdir}/logs"),
        parallel=dict(data=args.data, fsdp=args.fsdp, tensor=args.tensor),
        inference=dict(kv_paging=True),
    )
    if args.rehearse_cpu:
        config = config.evolve(
            model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                       model_extra_configs=dict(vocab_size=512, attn_impl="flash")),
            train=dict(seq_length=128, batch_size=8, total_steps=16),
            method=dict(num_rollouts=16, chunk_size=16,
                        gen_kwargs=dict(max_new_tokens=8)),
        )
    return config


def trainer_phase(args, config, compiles: CompileLog):
    import jax
    import numpy as np

    import trlx_tpu
    from trlx_tpu import native

    n_prompt = 16 if args.rehearse_cpu else N_PROMPT
    rng = np.random.default_rng(0)
    prompts = ["".join(chr(c) for c in rng.integers(97, 123, size=n_prompt))
               for _ in range(256)]
    calls = []

    def reward_fn(samples, prompts, outputs, **kwargs):
        calls.append(len(samples))
        if args.fail_reward:
            raise RuntimeError("--fail-reward: injected reward_fn failure")
        return [float(out.count("e") - out.count("z")) for out in outputs]

    t0 = time.time()
    trainer = trlx_tpu.train(reward_fn=reward_fn, prompts=prompts, config=config)
    t_done = time.time()
    log(f"trainer: trlx_tpu.train returned after {t_done - t0:.1f}s, "
        f"{trainer.iter_count} optimizer steps, reward_fn calls {calls}; "
        f"collate path: {native.backend()}")

    steps_per_cycle = (config.method.num_rollouts // config.train.batch_size
                       * config.method.ppo_epochs)
    check(trainer.iter_count == config.train.total_steps, "did not reach total_steps")
    # reward_fn scores each collection chunk (and each evaluation batch)
    rollouts = [n for n in calls if n == config.method.chunk_size]
    check(len(rollouts) >= 2, f"fewer than two collection cycles: {calls}")

    rows = []
    for path in glob.glob(f"{config.train.logging_dir}/*.metrics.jsonl"):
        with open(path) as f:
            rows += [json.loads(line) for line in f if line.strip()]
    rows.sort(key=lambda r: r["_step"])
    losses = [(r["_step"], r["losses/total_loss"]) for r in rows
              if "losses/total_loss" in r]
    check(len(losses) == config.train.total_steps,
          f"{len(losses)} logged losses for {config.train.total_steps} steps")
    bad = [(s, v) for s, v in losses if not math.isfinite(v)]
    check(not bad, f"non-finite losses: {bad[:4]}")
    log(f"trainer: {len(losses)} finite losses over {len(losses) // steps_per_cycle} "
        f"cycles, first {losses[0][1]:.4f} last {losses[-1][1]:.4f}")

    # trainable params moved: the hydra reference branch is a frozen copy
    # of the initial top layers; the train partition is flat, keyed by
    # ("lm", *path) for the same leaves
    from flax.traverse_util import flatten_dict

    ref = flatten_dict(trainer.ref_params)
    shared = [k for k in trainer.train_params if k[1:] in ref]
    moved = [k for k in shared
             if bool(jax.numpy.any(ref[k[1:]] != trainer.train_params[k]))]
    check(len(moved) > 0, f"none of {len(shared)} trainable leaves differs from "
          "the frozen reference copy of its initial value")
    log(f"trainer: {len(moved)} of {len(shared)} trainable leaves moved off "
        "their initial values")

    # The second cycle's window opens when the first cycle's last step is
    # logged and closes when the run's second-to-last step is: collection,
    # scoring and all but one repeat of the train step. (The last step's
    # row is written after the final checkpoint and evaluation.)
    when = {r["_step"]: r["_time"] for r in rows}
    t_open, t_close = when[steps_per_cycle], when[config.train.total_steps - 1]
    in_cycle2 = [(name, round(secs, 2)) for secs, name, at in compiles.events
                 if t_open < at <= t_close]
    check(not in_cycle2, f"second collection cycle compiled: {in_cycle2}")
    n_first = sum(1 for *_, at in compiles.events if at <= t_open)
    late = [name for _, name, at in compiles.events if t_close < at <= t_done]
    log(f"trainer: {n_first} backend compiles up to the end of cycle 1, "
        f"0 in cycle 2, {len(late)} after it "
        f"(final checkpoint + evaluation: {sorted(set(late))})")

    # every device of the mesh holds shards of the train state, and on every
    # device some shard has moved off its initial value: "n chips" is not
    # device 0 n times
    devices = list(trainer.runtime.mesh.devices.flat)
    held = {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves((trainer.train_params, trainer.opt_state)):
        for shard in getattr(leaf, "addressable_shards", ()):
            held[shard.device.id] += shard.data.nbytes
    check(all(v > 0 for v in held.values()),
          f"devices holding no train state: {held}")
    for d in devices:
        def moved_here(key):
            cur = {s.device.id: s for s in trainer.train_params[key].addressable_shards}
            old = {s.device.id: s for s in ref[key[1:]].addressable_shards}
            return (d.id in cur and d.id in old and cur[d.id].index == old[d.id].index
                    and bool(np.any(np.asarray(cur[d.id].data) != np.asarray(old[d.id].data))))
        check(any(moved_here(k) for k in moved),
              f"device {d.id}: no shard of the train state moved there")
    stats = {d.id: (d.memory_stats() or {}) for d in devices}
    peaks = {i: s.get("peak_bytes_in_use") for i, s in stats.items()}
    if not args.rehearse_cpu:
        check(all(p for p in peaks.values()), f"a device reports no peak bytes: {peaks}")
    log(f"trainer: mesh {dict(trainer.runtime.mesh.shape)}; train-state bytes per device {held}; "
        f"peak_bytes_in_use per device {peaks}")
    return trainer


def server_phase(args, trainer):
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from trlx_tpu.inference import remote_generate

    server = trainer.serve(background=True, host="127.0.0.1", port=0)
    try:
        client = remote_generate(server.url, timeout=600.0, retries=0)
        lengths = N_REQUESTS[:5] if args.rehearse_cpu else N_REQUESTS
        cap = trainer.config.inference.max_prompt_len
        prompts = ["".join(chr(97 + (i * 7 + j) % 26) for j in range(min(n, cap)))
                   for i, n in enumerate(lengths)]
        # two alone (cold prefill buckets), the rest at once: more requests
        # than slots, so finished slots are reclaimed and reused
        budget = min(trainer.config.inference.max_new_tokens,
                     trainer.generate_kwargs["max_new_tokens"])
        replies = [client(prompts[0], max_new_tokens=min(8, budget)),
                   client(prompts[1], max_new_tokens=min(16, budget))]
        with ThreadPoolExecutor(len(prompts)) as pool:
            futures = [pool.submit(client, p, max_new_tokens=min(24, budget))
                       for p in prompts[2:]]
            replies += [f.result(timeout=900) for f in futures]
        for rep in replies:
            check(len(rep.get("token_ids") or []) > 0, f"reply without tokens: {rep}")
            check(rep.get("finish_reason") in ("eos", "length", "stop"),
                  f"bad finish_reason: {rep}")
            check(isinstance(rep.get("ttft_s"), float) and rep["ttft_s"] >= 0,
                  f"reply without ttft_s: {rep}")
        with urllib.request.urlopen(server.url + "/healthz", timeout=30) as resp:
            health = json.loads(resp.read())
        # the compiled kernel needs its params on exactly one TPU device; an
        # engine over a multi-chip trainer mesh serves the gather path
        one_chip = trainer.runtime.mesh.devices.size == 1 and not args.rehearse_cpu
        want = "pallas" if one_chip else "xla"
        check(health.get("decode_kernel") == want,
              f"/healthz decode_kernel {health.get('decode_kernel')!r}, want {want!r}")
        kv = health.get("kv", {})
        if one_chip:
            check(kv.get("kv_kernel_dispatches", 0) > 0, f"no kernel dispatches: {kv}")
        check(not kv.get("kv_kernel_fallbacks"), f"kernel fallbacks: {kv}")
        # where the slot pool ended up: it starts as unplaced zeros beside
        # params that live on the trainer's mesh
        arena = server.engine._pool["layers"][0]["k"]
        log(f"server: KV arena {arena.shape} {arena.dtype} on "
            f"{len(arena.devices())} device(s), sharding "
            f"{getattr(arena.sharding, 'spec', arena.sharding)}, one shard "
            f"{arena.addressable_shards[0].data.shape}")
        log(f"server: {len(replies)} /generate replies "
            f"(prompt lengths {[len(p) for p in prompts]}, new tokens "
            f"{[len(r['token_ids']) for r in replies]}); /healthz decode_kernel="
            f"{health['decode_kernel']!r}, kv_kernel_dispatches="
            f"{kv.get('kv_kernel_dispatches')}, fallbacks={kv.get('kv_kernel_fallbacks')}")
    finally:
        server.shutdown()


def flash_and_ce_parity(interpret=False):
    """The Pallas flash forward and fused-CE kernels against their XLA paths,
    at GPT-2 small's heads over 1,024 tokens and its vocabulary (a few rows
    and heads of the same kernels where `interpret` runs them off the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.ops.attention import _flash_fwd_pallas, blockwise_attention
    from trlx_tpu.ops.fused_ce import _logprobs_pallas, _logprobs_xla

    key = jax.random.PRNGKey(0)
    shape = (2, 256, 2, 64) if interpret else (4, 1024, 12, 64)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
               for i in range(3))
    mask = jnp.ones(shape[:2], jnp.int32).at[:, -100:].set(0)
    pallas = jax.jit(lambda q, k, v, m: _flash_fwd_pallas(
        q, k, v, m, True, 128, 128, interpret=interpret))(q, k, v, mask)
    xla = jax.jit(lambda q, k, v, m: blockwise_attention(q, k, v, m))(q, k, v, mask)
    flash_dev = float(np.abs(np.asarray(pallas, np.float32) - np.asarray(xla, np.float32)).max())

    n, vocab = (256, 5000) if interpret else (2048, 50257)
    logits = jax.random.normal(jax.random.fold_in(key, 3), (n, vocab), jnp.bfloat16) * 3
    labels = jax.random.randint(jax.random.fold_in(key, 4), (n,), 0, vocab)
    pallas = jax.jit(lambda l, y: _logprobs_pallas(l, y, interpret=interpret)[0])(logits, labels)
    xla = jax.jit(lambda l, y: _logprobs_xla(l.astype(jnp.float32), y)[0])(logits, labels)
    ce_dev = float(np.abs(np.asarray(pallas) - np.asarray(xla)).max())

    log(f"kernels: flash max|dev| {flash_dev:.2e} (bound 5e-2), "
        f"fused CE max|dev| {ce_dev:.2e} (bound 1e-3)")
    check(flash_dev < 5e-2, f"flash-attention parity {flash_dev} >= 5e-2")
    check(ce_dev < 1e-3, f"fused-CE parity {ce_dev} >= 1e-3")


def flash_backward_parity(interpret=False):
    """The Pallas dq / dk,dv kernels against the XLA scan backward, at one
    PPO minibatch's shape (32 rows of 64 + 40 tokens, 12 heads x 64; two rows
    of two heads where `interpret` runs them off the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.ops import attention

    key = jax.random.PRNGKey(0)
    shape = (2, 104, 2, 64) if interpret else (32, 104, 12, 64)
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
                  for i in range(4))
    mask = jnp.ones(shape[:2], jnp.int32).at[:, -9:].set(0)
    out, lse = jax.jit(lambda q, k, v, m: attention._flash_fwd_pallas_lse(
        q, k, v, m, True, None, None, interpret=interpret))(q, k, v, mask)
    pallas = jax.jit(lambda *a: attention._flash_bwd_pallas(
        *a, True, None, None, interpret=interpret))(q, k, v, mask, out, lse, g)
    xla = jax.jit(lambda *a: attention._flash_bwd_xla(*a, True, None))(
        q, k, v, mask, out, lse, g)
    for name, a, b in zip(("dq", "dk", "dv"), pallas, xla):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = float(np.abs(b).max())
        dev = float(np.abs(a - b).max())
        log(f"kernels: flash backward {name}: max|dev| {dev:.2e} against "
            f"max|grad| {scale:.2e} (bound 2e-2 relative)")
        check(np.isfinite(a).all() and scale > 0, f"flash backward {name} degenerate")
        check(dev <= 2e-2 * scale, f"flash backward {name} parity {dev} vs {scale}")


def check_kernel_paths(args, trainer):
    """Which path the programs above were built with (recorded by the
    dispatch as it emitted each kernel into a traced program)."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.ops import attention, fused_ce
    from trlx_tpu.ops.attention import KERNEL_PATHS

    mesh = trainer.runtime.mesh
    log(f"kernels: paths taken, with the shapes each was emitted for: "
        f"{json.dumps(KERNEL_PATHS)}")
    for kernel in ("flash_fwd", "flash_bwd", "fused_ce"):
        check(KERNEL_PATHS.get(kernel), f"{kernel} was never dispatched")
        check("interpret" not in KERNEL_PATHS[kernel], f"{kernel} ran interpreted")
    if args.rehearse_cpu:
        want = {k: {"xla"} for k in KERNEL_PATHS}
    elif mesh.devices.size == 1:
        want = {k: {"pallas"} for k in KERNEL_PATHS}
    else:
        # Several chips: the kernels run under shard_map wherever the shape
        # divides over the mesh, and every XLA-path shape must be one that
        # does not (the 1-row init trace; a vocab the tensor axis cannot
        # split). The sharded forward keeps the recompute backward.
        want = {"flash_fwd": {"sharded", "xla"}, "flash_bwd": {"xla"},
                "fused_ce": {"sharded", "xla"}}
        S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
        for shape in KERNEL_PATHS["flash_fwd"].get("xla", []):
            check(not attention._sharded_flash_ok(mesh, S(shape), S(shape)),
                  f"flash forward took the XLA path at a shardable shape {shape}")
        for n, v in KERNEL_PATHS["fused_ce"].get("xla", []):
            check(not fused_ce._sharded_ce_ok(mesh, n, v),
                  f"fused CE took the XLA path at a shardable shape {(n, v)}")
        rows = trainer.config.train.batch_size
        check(any(shape[0] >= rows for shape in KERNEL_PATHS["flash_fwd"].get("sharded", [])),
              "no training-sized flash forward ran under shard_map")
        if trainer.model_cfg.vocab_size % dict(mesh.shape)["tensor"]:
            log(f"kernels: vocab {trainer.model_cfg.vocab_size} does not divide over "
                f"tensor={dict(mesh.shape)['tensor']}: fused CE has no kernel for this layout")
        else:
            check("sharded" in KERNEL_PATHS["fused_ce"], "fused CE never ran under shard_map")
    for kernel, paths in want.items():
        check(set(KERNEL_PATHS[kernel]) <= paths,
              f"{kernel} took {sorted(KERNEL_PATHS[kernel])}, this layout allows {sorted(paths)}")


def kernel_phase(args, trainer):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.models import config_from_preset
    from trlx_tpu.ops import quant
    from trlx_tpu.ops.paged_attention import (
        copies_blocks,
        paged_attention_decode,
        paged_attention_reference,
        paged_kv_write,
        writes_in_kernel,
    )

    check_kernel_paths(args, trainer)

    # on-chip bounds: 5e-2 for bf16 flash (the paged kernel's
    # bf16 output shares it: an ulp at |x| ~ 2 is 1.6e-2) and 1e-3 for CE
    paged_tol = 5e-2
    flash_and_ce_parity(interpret=args.rehearse_cpu)
    flash_backward_parity(interpret=args.rehearse_cpu)

    # paged decode vs the gather reference: GPT-2 small's shape (group 1),
    # the one causal preset family with group > 1, and the transcript cell's
    # 4 K/V heads of 128 under groups of 7 (whose blocks the kernel copies
    # itself: `copies_blocks`), there with a band of 100 as well; bf16 and
    # int8 arenas. Ragged rows over a table of three tiles (two of sixteen
    # entries): a single token, block edges, the whole table, a mask with
    # holes, an all-masked row. Table slack past a row's live entries names
    # ids beyond the arena, and every block no live entry names (the zero
    # block too, and under a band the blocks in front of it) is poison in
    # the kernel's copy of the arena: a dead entry that was fetched, or its
    # place in the kernel's scratch that no copy wrote, would show as NaN.
    shapes = {name: (cfg.n_heads, cfg.kv_heads, cfg.head_dim, (None,)) for name, cfg in (
        (name, config_from_preset(name, vocab_size=50257)) for name in ("gpt2-small", "llama-tiny"))}
    shapes["4 x 7 x 128"] = (28, 4, 128, (None, 100))
    rng = np.random.default_rng(1)
    b, blk, n_tbl = 8, 32, 20
    n_blocks = b * n_tbl + 1
    lens = np.array([1, blk - 1, blk, blk + 1, 9 * blk + 3, n_tbl * blk, 0, 150])
    n_live = -(-lens // blk)
    mask = (np.arange(n_tbl * blk)[None, :] < lens[:, None]).astype(np.int32)
    mask[4, [2, blk, 5 * blk + 7]] = 0
    ids = 1 + rng.permutation(n_blocks - 1)
    table = np.full((b, n_tbl), n_blocks + 3, np.int32)
    for r in range(b):
        table[r, :n_live[r]] = ids[r * n_tbl:r * n_tbl + n_live[r]]
    table_ref = jnp.asarray(np.where(table < n_blocks, table, 0))
    for (name, (nh, nkv, hd, windows)), dtype in itertools.product(shapes.items(), ("bf16", "int8")):
        q = jnp.asarray(rng.standard_normal((b, nh, hd)), jnp.bfloat16)
        ka = jnp.asarray(rng.standard_normal((n_blocks, nkv, blk, hd)), jnp.bfloat16).at[0].set(0)
        va = jnp.asarray(rng.standard_normal((n_blocks, nkv, blk, hd)), jnp.bfloat16).at[0].set(0)
        for window in windows:
            front = np.maximum(lens - (window or n_tbl * blk), 0) // blk  # entries wholly in front of the band
            dead = np.setdiff1d(np.arange(n_blocks), np.concatenate([table[r, front[r]:n_live[r]] for r in range(b)]))
            kw, kw_poison = {}, {}
            k_in, v_in = ka, va
            k_poison, v_poison = ka.at[dead].set(jnp.nan), va.at[dead].set(jnp.nan)
            if dtype == "int8":
                k_in, ks = quant.quantize_kv(ka)
                v_in, vs = quant.quantize_kv(va)
                kw = dict(k_scale=ks.reshape(n_blocks, 1, -1),
                          v_scale=vs.reshape(n_blocks, 1, -1))
                k_poison, v_poison = k_in.at[dead].set(127), v_in.at[dead].set(127)
                kw_poison = {key: plane.at[dead].set(jnp.nan) for key, plane in kw.items()}
            out = jax.jit(lambda *a, kw=kw_poison: paged_attention_decode(
                *a, interpret=args.rehearse_cpu, window=window, **kw))(q, k_poison, v_poison, jnp.asarray(table), mask)
            ref = jax.jit(lambda *a, kw=kw: paged_attention_reference(
                *a, window=window, **kw))(q, k_in, v_in, table_ref, mask)
            out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
            what = f"paged {name}/{dtype}" + (f"/window {window}" if window else "")
            check(bool(np.isfinite(out).all()), f"{what}: non-finite output (a dead table entry was read)")
            check(bool((out[lens == 0] == 0.0).all()), f"{what}: all-masked row not 0.0")
            dev = float(np.abs(out - ref)[lens > 0].max())
            copied = copies_blocks(nkv, blk, hd, k_in.dtype)
            log(f"kernels: paged decode {name} (heads {nh}/{nkv} x {hd}, group {nh // nkv}"
                f"{f', window {window}' if window else ''}) {dtype}, blocks {'copied' if copied else 'operands'}, "
                f"{len(dead)} of {n_blocks} blocks poison: max|dev| {dev:.2e} (bound {paged_tol:.0e})")
            check(dev < paged_tol, f"{what} parity {dev} >= {paged_tol}")
            if writes_in_kernel(k_in):
                # a decode step's write from the kernel itself against `paged_kv_write` in front of
                # it: the same products over the same bits, so arenas AND outputs bit for bit
                new_k, new_v = (jnp.asarray(rng.standard_normal((b, 1, nkv, hd)), jnp.bfloat16) for _ in range(2))
                column, live = jnp.asarray(np.maximum(lens - 1, 0), jnp.int32), jnp.asarray(lens > 0)[:, None]

                def xla_write(q, k, v, t, m):
                    new = paged_kv_write({"k": k, "v": v}, new_k, new_v, t, column, live)
                    return paged_attention_decode(
                        q, new["k"], new["v"], t, m, interpret=args.rehearse_cpu, window=window), new["k"], new["v"]

                def kernel_write(q, k, v, t, m):
                    return paged_attention_decode(q, k, v, t, m, interpret=args.rehearse_cpu, window=window,
                                                  new_kv=(new_k[:, 0], new_v[:, 0]), column=column)

                want = jax.jit(xla_write)(q, k_in, v_in, jnp.asarray(table), mask)
                got = jax.jit(kernel_write)(q, k_in, v_in, jnp.asarray(table), mask)
                same = [bool((np.asarray(x).view(np.uint16) == np.asarray(y).view(np.uint16)).all())
                        for x, y in zip(got, want)]
                log(f"kernels: {what}: the kernel's own write against paged_kv_write's, "
                    f"output / K arena / V arena bit for bit: {same}")
                check(all(same), f"{what}: the kernel's write differs from paged_kv_write's {same}")
                check(not bool((got[1] == k_in).all()), f"{what}: the kernel wrote nothing")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--data", type=int, default=-1)
    parser.add_argument("--fsdp", type=int, default=1)
    parser.add_argument("--tensor", type=int, default=1)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny CPU walk-through; never reports ok")
    parser.add_argument("--fail-reward", action="store_true",
                        help="inject a reward_fn failure (the run must fail)")
    args = parser.parse_args()

    info = device_phase(args.rehearse_cpu)
    compiles = CompileLog()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        config = build_config(args, workdir)
        trainer = trainer_phase(args, config, compiles)
        server_phase(args, trainer)
        kernel_phase(args, trainer)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    import jax

    log(f"compile seconds by program: {json.dumps(compiles.seconds_by_program())} "
        f"({len(compiles.events)} backend compiles)")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    log(f"peak_bytes_in_use per device at exit: {peaks}")
    if args.rehearse_cpu:
        log("REHEARSAL finished: not a chip result")
        print(json.dumps({"rehearsal": True, "device": info}))
        return
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    sys.exit(main())
