"""The `ppo` job: one PPOTrainer, whole collection-and-training cycles timed
until the window has passed. `--trace 1` profiles the first timed cycle,
with spans put round the trainer's methods from outside; `--trace 2` times
exactly as `--trace 0` does and then profiles one more whole cycle, in which
the program's own `trlx:` spans say what the host did.

A cycle is the classic path of `learn()` (after bench.py `run_cycle`): clear
the store, `make_experience(num_rollouts)`, then `ppo_epochs` passes of
`train_minibatch` over the store, closed by one host copy of every step's
loss. The recipe (cell file) overrides `default_ppo_config()`; the model is
the configuration's preset; weights come from the seed through `get_arch`,
the trainer's own door for a model. The library's schedule flags stay at
their defaults unless the cell's file sets them.
"""

import math
import time

import numpy as np

from benchlib import tracing, traffic, weights
from benchlib.files import load_module, merge
from benchlib.result import Checks


def build_trainer(ctx):
    import jax.numpy as jnp

    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.models import CausalLMWithValueHead, resolve_transformer_config
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    which = "rehearse" if ctx.rehearse else "program"
    program = ctx.config[which]
    recipe = merge(ctx.cell["recipe"], ctx.cell.get("rehearse_recipe") if ctx.rehearse else None)
    overrides = merge(
        {"model": {"model_path": program["model_path"],
                   "model_extra_configs": program["model_extra_configs"]},
         "train": {"seed": ctx.seed % (2**31), "tracker": None}},
        recipe)
    if ctx.control:
        # the program's own lower-precision path: int8 weights for the
        # frozen trunk in the sampler's view
        overrides = merge(overrides, {"method": {"quantize_frozen_trunk": True}})
    config = default_ppo_config().evolve(**overrides)
    seed = ctx.seed

    class SeededPPOTrainer(PPOTrainer):
        def get_arch(self, config):
            cfg = resolve_transformer_config(config.model, self.tokenizer.vocab_size)
            model = CausalLMWithValueHead(cfg, num_value_layers=0)
            tokens = jnp.zeros((1, 32), jnp.int32)
            shapes = weights.param_shapes(model, tokens, jnp.ones_like(tokens))
            return model, cfg, weights.make_params(shapes, seed, cfg.param_dtype)

    def reward_fn(samples, prompts, outputs, **kwargs):
        # bench.py's cheap host reward: the choreography of a reward model
        # (decode, score on the host, back to the device) without its cost
        return [float(out.count("e") - out.count("z")) for out in outputs]

    trainer = SeededPPOTrainer(config, reward_fn=reward_fn)

    mix = merge(ctx.traffic, ctx.traffic.get("rehearse") if ctx.rehearse else None)
    rng = np.random.default_rng(ctx.seed)
    lens = traffic.lengths(mix["prompt_len"], mix["pool"], rng)
    # the trainer's door for prompts is text: lower-case letters, one byte
    # and one token each under the byte tokenizer
    prompts = ["".join(chr(c) for c in rng.integers(97, 123, size=int(n))) for n in lens]
    pipeline = PromptPipeline(prompts, max_prompt_length=int(mix["prompt_len"]["max"]),
                              tokenizer=trainer.tokenizer)
    trainer.add_prompt_pipeline(pipeline)
    return trainer, config, pipeline


def run_cycle(trainer, config):
    """One whole PPO cycle; returns every optimizer step's loss as floats
    (the host copy is the sync that closes the cycle's timing)."""
    import jax

    from trlx_tpu.pipeline import MiniBatchIterator

    with tracing.span("make_experience"):
        trainer.store.clear_history()
        trainer.make_experience(config.method.num_rollouts, trainer.iter_count)
    losses = []
    with tracing.span("train_epochs"):
        for _ in range(config.method.ppo_epochs):
            loader = trainer.create_train_dataloader()
            for minibatch in MiniBatchIterator(loader, trainer.mb_size, trainer.num_mb):
                stats = trainer.train_minibatch(minibatch)
                trainer.iter_count += 1
                losses.append(stats["losses"]["total_loss"])
            trainer.post_backward_callback()
    with tracing.span("loss_fetch"):
        return [float(x) for x in jax.device_get(losses)]


def compare_outputs(ctx, trainer, config, pipeline, int8_reference=False, parts=("scorer", "sampler")):
    """Readings of the program against the configuration's plain reference,
    on seeded inputs at the cell's own shapes, before anything has trained:

    - the scorer (`_score_fn`: flash attention, fused CE, the frozen
      reference branch) on one chunk of prompt + response tokens: logprobs of
      two rows against the reference's (the policy/reference log ratio at
      init is printed too: two bf16 paths over the same weights, ~0.016);
    - the sampler (`generate`, prefill then decode through the KV cache, in
      the param view rollouts use): the logprob it captured for each token it
      sampled against the reference's full forward over the same tokens.

    `int8_reference` adds the control's readings (the reference computed in
    int8 in the program's place), for setting limits; no run of a cell asks
    for it.
    """
    import jax
    import jax.numpy as jnp

    ref = load_module(f"reference/{ctx.config['reference']}.py")
    sizes = ctx.config["rehearse_sizes"] if ctx.rehearse else ctx.config["sizes"]
    lm = trainer.params["lm"]
    pad = trainer.tokenizer.pad_token_id
    max_new = int(config.method.gen_kwargs["max_new_tokens"])
    n_ref = 2  # rows of the chunk the reference scores
    out = {}

    def rms(name, got, want, valid, control=None):
        # the root mean square over hundreds of tokens is steady from seed to
        # seed; the largest single error is not (PERF.md section 2)
        out[f"{name}_logprob_rms"] = float(np.sqrt(np.mean((got - want)[valid] ** 2)))
        out[f"{name}_tokens"] = int(valid.sum())
        if control is not None:
            out[f"control:{name}_logprob_rms"] = float(np.sqrt(np.mean((control - want)[valid] ** 2)))

    batch = next(iter(pipeline.create_loader(config.method.chunk_size, shuffle=False)))
    prompt_ids, prompt_mask = np.asarray(batch["input_ids"]), np.asarray(batch["attention_mask"])
    if "scorer" in parts:
        rng = np.random.default_rng(ctx.seed + 1)
        response = rng.integers(0, sizes["vocab_size"],
                                size=(len(prompt_ids), max_new)).astype(np.int32)
        response[response == pad] = 0
        all_tokens = np.concatenate([prompt_ids, response], axis=1)
        if trainer._score_fn is None:
            trainer._build_score_fn()
        logprobs, _, log_ratio, _, _ = jax.device_get(trainer._score_fn(
            trainer.train_params, trainer.frozen_params, trainer.ref_params,
            jnp.asarray(all_tokens)))
        mask = (all_tokens != pad).astype(np.int32)
        want = np.asarray(ref.logprobs(lm, all_tokens[:n_ref], mask[:n_ref], sizes))
        control = (np.asarray(ref.logprobs(lm, all_tokens[:n_ref], mask[:n_ref], sizes, int8=True))
                   if int8_reference else None)
        rms("scorer", np.asarray(logprobs)[:n_ref], want, mask[:n_ref, :-1].astype(bool), control)
        out["init_log_ratio_max"] = float(np.abs(log_ratio).max())

    if "sampler" in parts:
        k = int(ctx.cell["check"]["sampler_tokens"])
        rows = n_ref = 8  # one row bucket of `generate`; the reference scores them all
        gen = trainer.generate(prompt_ids[:rows], prompt_mask[:rows],
                               {**config.method.gen_kwargs, "max_new_tokens": k}, capture=True)
        samples, got, rmask = jax.device_get((gen["samples"], gen["logprobs"], gen["response_mask"]))
        smask = np.concatenate([prompt_mask[:rows], np.asarray(rmask)], axis=1).astype(np.int32)
        q = prompt_ids.shape[1]
        window = slice(q - 1, q - 1 + k)
        want = np.asarray(ref.logprobs(lm, np.asarray(samples)[:n_ref], smask[:n_ref], sizes))
        control = (np.asarray(ref.logprobs(lm, np.asarray(samples)[:n_ref], smask[:n_ref], sizes,
                                           int8=True))[:, window] if int8_reference else None)
        rms("sampler", np.asarray(got)[:n_ref], want[:, window],
                np.asarray(rmask)[:n_ref].astype(bool), control)
    return out


def check_outputs(ctx, trainer, config, pipeline, checks: Checks):
    limits = load_module(f"reference/{ctx.config['reference']}.py").LIMITS[ctx.cell["job"]]
    for name, value in compare_outputs(ctx, trainer, config, pipeline).items():
        if name in limits:
            checks.at_most(f"{name} |program - reference|", value, limits[name])
        else:
            ctx.log(f"reading {name}: {value}")


def moved_leaves(trainer):
    """(moved, shared): trainable leaves that differ from the frozen
    reference copy of their initial value (after chip_smoke.py)."""
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict

    ref = flatten_dict(trainer.ref_params)
    shared = [k for k in trainer.train_params if k[1:] in ref]
    moved = [k for k in shared if bool(jnp.any(ref[k[1:]] != trainer.train_params[k]))]
    return len(moved), len(shared)


def run(ctx):
    checks = Checks()
    trainer, config, pipeline = build_trainer(ctx)
    with tracing.span("check_outputs"):
        check_outputs(ctx, trainer, config, pipeline, checks)

    # warm-up: one whole cycle compiles (or reads back) generate, score and
    # the train step at the cell's shapes
    warm = run_cycle(trainer, config)
    ctx.log(f"warm-up cycle done, loss {warm[-1]:.4f}; "
            f"{len(ctx.compiles.events)} backend compiles so far, "
            f"{ctx.compiles.seconds():.1f} s")

    if ctx.trace == 1:
        for attr, name in (("generate", "generate_dispatch"), ("_score_fn", "score_dispatch"),
                           ("reward_fn", "reward_fn"), ("decode", "host_decode"),
                           ("train_minibatch", "train_minibatch_dispatch")):
            tracing.wrap(trainer, attr, name)
    window = tracing.TracedWindow()
    t0 = time.monotonic()
    setup_s = t0 - ctx.t_start
    losses, cycle_s = [], []
    while True:
        traced = ctx.trace == 1 and not cycle_s  # `--trace 1` profiles its first cycle
        if traced:
            window.start()
        c0 = time.monotonic()
        losses += run_cycle(trainer, config)
        cycle_s.append(time.monotonic() - c0)
        if traced:
            window.stop()
        if time.monotonic() - t0 >= ctx.seconds:
            break
    t1 = time.monotonic()
    wall = sum(cycle_s)  # whole cycles only; reading the trace back is outside them
    if ctx.trace == 2:
        # measured first, traced afterwards: one more whole cycle under the
        # profiler (what starting it costs lies inside `start()`, before the
        # cycle). Its losses count for the finiteness check, not for the rate
        window.start()
        c0 = time.monotonic()
        losses += run_cycle(trainer, config)
        traced_s = time.monotonic() - c0
        window.stop()
        timed_s = float(np.median(cycle_s))
        ctx.log(f"the traced cycle took {traced_s:.3f} s, the timed cycles' median "
                f"{timed_s:.3f} s: tracing on cost {100 * (traced_s / timed_s - 1):.2f}%")

    in_window = ctx.compiles.between(t0, t1)
    checks.equal("backend compiles inside the window", len(in_window), 0)
    if in_window:
        ctx.log(f"compiled inside the window: {in_window}")
    bad = [x for x in losses if not math.isfinite(x)]
    checks.equal(f"non-finite losses among {len(losses)} optimizer steps", len(bad), 0)
    moved, shared = moved_leaves(trainer)
    checks.true(f"trainable leaves moved ({moved} of {shared})", moved == shared and shared > 0)

    n = len(cycle_s) * config.method.num_rollouts
    ctx.log(f"{len(cycle_s)} cycles of {config.method.num_rollouts} rollouts, "
            f"cycle seconds {[round(c, 3) for c in cycle_s]}, last loss {losses[-1]:.4f}")
    return {
        "checks": checks, "attempted": n, "failed": len(bad),
        "end_to_end": {"setup_s": setup_s, "rl_samples_per_s": n / wall},
        "measurements": {
            "trace": window.trace, "cycles_traced": 1,
            "series": {"cycle_s": cycle_s},
        },
    }
