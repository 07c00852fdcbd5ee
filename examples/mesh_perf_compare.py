"""Mesh-layout performance comparison (parity with reference
examples/nemo_vs_ds_chat.py, which benchmarks the same chat-PPO workload
under NeMo vs DeepSpeed backends). Here the two "backends" are mesh
layouts of ONE trainer family: run the same PPO workload under several
(data, fsdp, tensor) splits and print samples/s for each.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/mesh_perf_compare.py '{"meshes": [[8,1,1],[2,2,2]]}'
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)) + "/..")

import numpy as np


def run_one(mesh, steps=2):
    import jax

    from trlx_tpu.data import PPORLElement
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.pipeline import MiniBatchIterator
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    data, fsdp, tensor = mesh
    n = data * fsdp * tensor
    batch_size = max(8, 2 * data * fsdp)
    config = default_ppo_config().evolve(
        model=dict(model_path="random:gpt2-tiny"),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=64, batch_size=batch_size, tracker=None),
        method=dict(gen_kwargs=dict(max_new_tokens=8, do_sample=True)),
        parallel=dict(data=data, fsdp=fsdp, tensor=tensor),
    )
    trainer = PPOTrainer(
        config, reward_fn=lambda samples, **kw: [0.0] * len(samples),
        devices=jax.devices()[:n],
    )
    rng = np.random.default_rng(0)
    for _ in range(batch_size * 2):
        L = 8
        trainer.store.push([PPORLElement(
            query_tensor=rng.integers(3, 250, size=L).astype(np.int32),
            response_tensor=rng.integers(3, 250, size=L).astype(np.int32),
            logprobs=rng.normal(size=L).astype(np.float32),
            values=rng.normal(size=L).astype(np.float32),
            rewards=rng.normal(size=L).astype(np.float32),
        )])

    def one_pass():
        loader = trainer.store.create_loader(batch_size, shuffle=True)
        stats = None
        for minibatch in MiniBatchIterator(loader, trainer.mb_size, trainer.num_mb):
            stats = trainer.train_minibatch(minibatch)
        return float(np.asarray(stats["losses"]["total_loss"]))

    one_pass()  # compile
    t0 = time.time()
    for _ in range(steps):
        one_pass()
    dt = (time.time() - t0) / steps
    samples_per_s = len(trainer.store) / dt
    return {"mesh": mesh, "samples_per_s": round(samples_per_s, 2),
            "sec_per_pass": round(dt, 4)}


def main(hparams={}):
    meshes = hparams.get("meshes", [[1, 1, 1]])
    results = [run_one(tuple(m)) for m in meshes]
    for r in results:
        print(json.dumps(r))
    return results


if __name__ == "__main__":
    hparams = {} if len(sys.argv) == 1 else json.loads(sys.argv[1])
    main(hparams)
