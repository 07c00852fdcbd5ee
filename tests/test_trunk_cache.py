"""Frozen-trunk activation cache: the hydra trunk below the split is
entirely frozen, so the state entering block `split` is the same in every
optimizer step of a cycle. The PPO schedule computes it once for each
rollout chunk, keeps it on the device for the cycle, and resumes every
step from it; `_trunk_cache_available` decides from the model, the recipe
and the chip, and no flag does.

Pinned here:
- a whole classic cycle (`make_experience`, then `ppo_epochs` passes of
  `create_train_dataloader` / `train_minibatch`, as bench/jobs/ppo.py
  drives it) gives, under float32, every step's loss and the final
  trainable leaves BITWISE equal to the same cycle run from the whole
  forward: the fill lays a chunk's tokens out as the train batches will,
  so a cached row is column for column what the whole forward computes;
- where a collection is one chunk, or the device says that the
  collection's states fit beside a generation in flight (and where it says
  nothing: the CPU), the score program hands the state out and nothing is
  filled (`_score_hands_out_trunk_state`): bitwise the
  whole forward's where the scorer's layout is the train batches' (the
  benchmark's cells), and to the last bits where the loader pads queries
  wider than the scorer saw them (attention over 14 columns and over 32,
  18 of them masked, add up in another order);
- the cycle's cache is one `jax.Array` that no `device_get` and no
  collator touches: a batch carries `int32[b]` row numbers;
- the arbiter's table; the cache's dtype (the forward's own);
- `SparseMoE`'s dispatch counters survive a cached step; a quarantined
  row leaves the other rows' numbers right; a restored store trains from
  the whole forward.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.default_configs import default_ppo_config
from trlx_tpu.models import CausalLMWithValueHead
from trlx_tpu.models.transformer import position_ids
from trlx_tpu.observability import hbm
from trlx_tpu.ops.ppo import get_advantages_and_returns
from trlx_tpu.pipeline import MiniBatchIterator
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
from trlx_tpu.trainer import ppo_trainer
from trlx_tpu.trainer.base_trainer import merge_params
from trlx_tpu.trainer.ppo_trainer import PPOTrainer, _to_batch_columns

MAX_NEW = 6
SUPPRESS = [i for i in range(259) if not (32 <= i < 127 or i == 258)]
PROMPTS = ["hello world", "jax tpu", "ppo", "fast"] * 2
# the widest prompt fills the loader's query bucket (seq_length 32 - 6 new):
# the scorer's layout is then the train batches', as in the benchmark's cells
SCORER_LAYOUT = dict(prompts=["hello world, jax on a tpu!", "jax tpu", "ppo", "fast"] * 2,
                     max_prompt_length=26)


def _make_trainer(tmp_path, model=None, train=None, prompts=PROMPTS, cls=PPOTrainer,
                  tokenizer="byte", devices=None, max_prompt_length=8, **method):
    method = {
        "num_rollouts": 8, "chunk_size": 8, "ppo_epochs": 2,
        "gen_kwargs": dict(max_new_tokens=MAX_NEW, do_sample=True,
                           suppress_tokens=SUPPRESS),
        **method,
    }
    config = default_ppo_config().evolve(
        # float32 end to end so the tests can assert BITWISE equality
        # (bf16 rounding would mask the exactness claim)
        model={**dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1,
                      model_extra_configs={"dtype": "float32"}), **(model or {})},
        tokenizer=dict(tokenizer_path=tokenizer),
        train={**dict(seq_length=32, batch_size=8, total_steps=4, tracker=None,
                      checkpoint_dir=str(tmp_path), seed=11), **(train or {})},
        method=dict(**method),
    )
    # (one device: the benchmark's PPO cells, and no partitioner between
    # two programs that should add up in the same order)
    trainer = cls(
        config,
        reward_fn=lambda samples, **kw: [float(len(s)) for s in samples],
        devices=None if devices is None else jax.devices()[:devices],
    )
    pipeline = PromptPipeline(prompts, max_prompt_length=max_prompt_length,
                              tokenizer=trainer.tokenizer)
    trainer.add_prompt_pipeline(pipeline)
    return trainer


def _whole_forward(trainer):
    """The same trainer with the arbiter saying no: every step runs the
    whole forward."""
    trainer._trunk_cache_available = lambda: False
    return trainer


def _no_room(trainer, free=0):
    """The same trainer on a device that reports a capacity and `free` bytes
    of it free: the rule reckons (it sizes the trainer's own `generate`
    unless a test answers for it) and finds no room for a collection's
    states beside a generation in flight."""
    trainer._trunk_cache_budget = V5E // 8
    trainer._device_free_bytes = lambda: free
    return trainer


def _cycle(trainer):
    """One whole classic PPO cycle, as bench/jobs/ppo.py `run_cycle` drives
    it; every optimizer step's loss and stats."""
    method = trainer.config.method
    trainer.store.clear_history()
    trainer.make_experience(method.num_rollouts, trainer.iter_count)
    losses, stats = [], None
    for _ in range(method.ppo_epochs):
        loader = trainer.create_train_dataloader()
        for minibatch in MiniBatchIterator(loader, trainer.mb_size, trainer.num_mb):
            stats = trainer.train_minibatch(minibatch)
            trainer.iter_count += 1
            losses.append(stats["losses"]["total_loss"])
        trainer.post_backward_callback()
    return [float(x) for x in jax.device_get(losses)], stats


def _assert_same_leaves(a, b):
    assert sorted(a.train_params) == sorted(b.train_params)
    for k in a.train_params:
        np.testing.assert_array_equal(
            np.asarray(a.train_params[k]), np.asarray(b.train_params[k]), err_msg=str(k))


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    """Shared trainer (classic sampler, f32) with one collected store — the
    loss-level tests all read the same batch."""
    tr = _make_trainer(tmp_path_factory.mktemp("trunk_cache"))
    assert tr._trunk_cache_available()
    tr.make_experience(8)
    return tr


@pytest.fixture(scope="module")
def chunk(trainer):
    """One collated batch from the store: row numbers from the collator,
    the cycle's cache from the trainer (here on one device, for the eager
    loss-level tests)."""
    host = next(iter(trainer.create_train_dataloader()))
    assert host.trunk_cache is None and host.trunk_rows.dtype == np.int32
    assert sorted(host.trunk_rows.tolist()) == list(range(8))
    placed = trainer.batch_to_device(host)
    assert placed.trunk_cache is trainer._trunk_cache
    assert placed.trunk_cache.shape == (
        8, host.query_tensors.shape[1] + host.response_tensors.shape[1],
        trainer.model_cfg.d_model)
    return jax.tree_util.tree_map(jnp.asarray, host).replace(
        trunk_cache=jnp.asarray(np.asarray(trainer._trunk_cache)))


def _eager_trunk(trainer, tokens):
    """The state entering block `split`, recomputed EAGERLY with the exact
    op sequence the full forward runs."""
    params = merge_params(trainer.train_params, trainer.frozen_params)
    amask = (tokens != trainer.tokenizer.pad_token_id).astype(jnp.int32)
    return trainer.model.apply(
        {"params": params}, tokens, amask, position_ids(amask), stop=trainer.split,
        method=CausalLMWithValueHead.forward,
    )[1]


def _filled_trunk(trainer, tokens):
    """The same state as the cycle's fill computes it (one jitted program)."""
    return trainer._build_trunk_cache_fn()(trainer.train_params, trainer.frozen_params, tokens)


def _count_fills(trainer):
    """The token shapes of every `trunk_cache_fill` call from here on."""
    fills, build = [], trainer._build_trunk_cache_fn

    def counting_fill():
        fill = build()
        return lambda *args: (fills.append(args[2].shape), fill(*args))[1]

    trainer._build_trunk_cache_fn = counting_fill
    return fills


def _assert_rows_hold_their_tokens_state(tr):
    """Every element of the store names the state of ITS tokens, laid out
    as the loader will lay them out."""
    pad, q = tr.tokenizer.pad_token_id, tr._train_query_width(8)
    for e in tr.store.history:
        tokens = np.full((1, q + MAX_NEW), pad, np.int32)
        tokens[0, q - len(e.query_tensor):q] = e.query_tensor
        tokens[0, q:q + len(e.response_tensor)] = e.response_tensor
        want = _eager_trunk(tr, jnp.asarray(tokens))[0]
        real = tokens[0] != pad
        np.testing.assert_allclose(
            np.asarray(tr._trunk_cache[e.trunk_row])[real], np.asarray(want)[real],
            rtol=1e-5, atol=1e-6)


def _grads(trainer, loss_fn, batch):
    return jax.grad(
        lambda p: loss_fn(p, trainer.frozen_params, batch)[0]
    )(trainer.train_params)


def _uncached(batch):
    return batch.replace(trunk_rows=None, trunk_cache=None)


# ----------------------------------------------------------------------
# The whole classic cycle
# ----------------------------------------------------------------------


@pytest.mark.parametrize("recipe", [
    dict(chunk_size=8, devices=1),                                  # one chunk a cycle
    dict(chunk_size=8),                                             # rows over 8 devices
    # two chunks on a device with no room beside a generation: one array from both fills
    dict(chunk_size=4, train=dict(batch_size=4), devices=1, fills=True),
    dict(chunk_size=8, train=dict(minibatch_size=4), devices=1),    # the accumulation step
    dict(SCORER_LAYOUT, chunk_size=8, devices=1),
    dict(SCORER_LAYOUT, chunk_size=8),
    dict(SCORER_LAYOUT, chunk_size=8, train=dict(minibatch_size=4), devices=1),
    # several chunks, every one's state handed out: one array from the score program's
    dict(chunk_size=4, train=dict(batch_size=4), devices=1),
    dict(SCORER_LAYOUT, chunk_size=2, train=dict(batch_size=4), devices=1),
], ids=["one_chunk", "one_chunk_8_devices", "two_chunks", "accumulation",
        "one_chunk_scorer_layout", "one_chunk_8_devices_scorer_layout",
        "accumulation_scorer_layout", "two_chunks_handed_out",
        "four_chunks_handed_out_scorer_layout"])
def test_classic_cycle_bitwise_equals_the_whole_forward(tmp_path, monkeypatch, recipe):
    """Two cycles with the cache against two cycles from the whole forward:
    every step's loss and the final trainable leaves bitwise equal; the
    cycle's cache is a device array that nothing copies to the host, and
    the collator builds row numbers, never a [b, T, d] array. Where the rule
    says yes (one chunk a collection, or several with room for their states)
    the rows come from the score program and no fill is ever
    built; on one device in the scorer's layout they are the fill's to the
    bit, otherwise to the last bits."""
    recipe = dict(recipe)
    train, scored = recipe.pop("train", None), not recipe.pop("fills", False)
    cached = _make_trainer(tmp_path / "cached", train=train, **recipe)
    whole = _whole_forward(_make_trainer(tmp_path / "whole", train=train, **recipe))
    assert cached._trunk_cache_available() and not whole._trunk_cache_available()
    if not scored:
        _no_room(cached)
        monkeypatch.setattr(cached, "_generate_held_bytes", lambda: (0, 0))
    assert cached._score_hands_out_trunk_state() is scored
    assert not whole._score_hands_out_trunk_state()
    # the score program's state is the fill's to the bit on one device in
    # the scorer's own layout; partitioned over 8 devices beside the heads
    # and the reference branch, or moved under wider queries, to the last bits
    bitwise = not scored or ("prompts" in recipe and recipe.get("devices") == 1)

    fetched = []
    real_get = jax.device_get

    def spy_get(tree):
        fetched.extend(np.shape(x) for x in jax.tree_util.tree_leaves(tree))
        return real_get(tree)

    for cycle in range(2):
        monkeypatch.setattr(jax, "device_get", spy_get)
        got, _ = _cycle(cached)
        monkeypatch.setattr(jax, "device_get", real_get)
        want, _ = _cycle(whole)
        assert np.all(np.isfinite(got)) and len(got) == 2 * (8 // cached.config.train.batch_size)
        if bitwise:
            assert got == want, (cycle, got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-6, err_msg=str(cycle))

        cache = cached._trunk_cache
        assert isinstance(cache, jax.Array) and cache.dtype == jnp.float32
        assert cache.shape == (8, 32, cached.model_cfg.d_model)
        assert sorted(e.trunk_row for e in cached.store.history) == list(range(8))
        # where the rows came from: the score program's sixth output, or
        # one fill a chunk when the collection has ended
        assert cached._trunk_scored_rows == (8 if scored else 0)
        assert (cached._trunk_cache_fn is None) is scored
        for host in cached.create_train_dataloader():
            leaves = jax.tree_util.tree_leaves(host)
            assert all(isinstance(x, np.ndarray) and x.ndim <= 2 for x in leaves)
            assert host.trunk_rows.shape == (cached.config.train.batch_size,)
    assert fetched and all(len(shape) <= 2 for shape in fetched), fetched
    assert whole._trunk_cache is None and whole._trunk_cache_fn is None
    assert all(e.trunk_row is None for e in whole.store.history)
    if bitwise:
        _assert_same_leaves(cached, whole)
    else:
        # (a key bias has no gradient but rounding, which Adam turns into
        # steps of the learning rate's size: 4 steps either way)
        for k in cached.train_params:
            np.testing.assert_allclose(
                np.asarray(cached.train_params[k]), np.asarray(whole.train_params[k]),
                rtol=1e-4, atol=8 * cached.config.optimizer.kwargs["lr"], err_msg=str(k))


def _score_args(tr, rows=8, width=8 + MAX_NEW):
    return (tr.train_params, tr.frozen_params, tr.ref_params,
            jax.ShapeDtypeStruct((rows, width), jnp.int32))


@pytest.mark.parametrize("room", [False, True], ids=["no_room", "both_handed_out"])
def test_two_chunks_score_with_five_outputs_and_fill_both_at_the_end(tmp_path, room):
    """A recipe that collects in two chunks dispatches the second chunk's
    generation before the first is scored. On a device with no room for the
    collection's states beside it (the rule sizes this trainer's own
    `generate` and stops there) the arbiter says no, the score program is the one
    a trainer without a trunk cache builds, and both chunks are filled when
    the collection has ended; where there is room (here: no capacity known)
    both chunks' states come from the score program and nothing is filled."""
    tr = _make_trainer(tmp_path / "two", chunk_size=4, train=dict(batch_size=4), devices=1)
    plain = _make_trainer(tmp_path / "plain", chunk_size=4, train=dict(batch_size=4), devices=1,
                          ppo_epochs=1)
    if not room:
        _no_room(tr)
    assert tr._trunk_cache_available() and not plain._trunk_cache_available()
    for t in (tr, plain):
        t._build_score_fn()
    assert tr._score_with_trunk_state is room and not plain._score_with_trunk_state
    fills = _count_fills(tr)
    if room:
        six = jax.eval_shape(lambda *args: tr._score_fn(*args, trunk_state=True), *_score_args(tr, rows=4))
        assert len(six) == 6 and six[5].shape == (4, 8 + MAX_NEW, tr.model_cfg.d_model)
    else:
        texts = [t._score_fn.lower(*_score_args(t, rows=4)).as_text() for t in (tr, plain)]
        assert texts[0] == texts[1] and "jit_score" in texts[0][:200]
    assert len(jax.eval_shape(tr._score_fn, *_score_args(tr, rows=4))) == 5
    tr.make_experience(8)
    assert fills == ([] if room else [(4, 32), (4, 32)])
    assert tr._trunk_scored_rows == (8 if room else 0) and (tr._trunk_cache_fn is None) is room
    assert tr._trunk_cache.shape == (8, 32, tr.model_cfg.d_model)
    _assert_rows_hold_their_tokens_state(tr)


def test_the_reckoning_compiles_the_programs_the_collection_runs(tmp_path, caplog):
    """What the rule sizes are the trainer's own `generate` and six-output
    `score` at the collection's shapes, by the compiler's analysis of each;
    the collection then runs those executables and compiles neither again."""
    import logging

    tr = _make_trainer(tmp_path, chunk_size=4, train=dict(batch_size=4), devices=1)
    tr._trunk_cache_budget = V5E // 8
    tr._device_free_bytes = lambda: V5E

    def compiled():
        names = [r.getMessage().split("jit(")[1].split(")")[0] for r in caplog.records
                 if "Finished XLA compilation of jit(" in r.getMessage()]
        caplog.clear()
        return [n for n in names if n in ("generate", "score")]

    with jax.log_compiles(), caplog.at_level(logging.WARNING, logger="jax"):
        tr._build_score_fn()
        assert compiled() == ["generate", "score"] and tr._score_with_trunk_state
        assert min(tr._generate_held_bytes()) > 0 and compiled() == []
        tr.make_experience(8)
        assert compiled() == [] and tr._trunk_scored_rows == 8
    # a program that cannot be lowered here (`scripts/lowered_text.py` stands one in) is not sized
    assert tr._program_held_bytes(lambda *args: None) is None


@pytest.mark.parametrize("chunk_size, room", [(8, False), (4, False), (4, True)],
                         ids=["handing_out", "five_outputs", "two_chunks_handing_out"])
def test_score_fn_is_a_five_output_door_under_both_answers(tmp_path, chunk_size, room):
    """What bench/jobs/ppo.py `compare_outputs`, scripts/lowered_text.py and
    the multi-turn collection unpack. Behind the door stands ONE program
    named `score`; the sixth result goes to the caller that asks for it.
    (One chunk reads no memory; two chunks hand out where there is room.)"""
    tr = _make_trainer(tmp_path, chunk_size=chunk_size, devices=1)
    if not room:
        _no_room(tr)
    names = []
    ljit = tr._ljit
    tr._ljit = lambda fn, name, **kw: (names.append(name), ljit(fn, name, **kw))[1]
    tr._build_score_fn()
    hands_out = chunk_size == 8 or room
    # (the rule, where it reckons, sizes the collection's `generate` too)
    assert [n for n in names if not n.startswith("generate[")] == ["score"]
    assert tr._score_with_trunk_state is hands_out
    tokens = np.full((8, 8 + MAX_NEW), 65, np.int32)
    out = tr._score_fn(tr.train_params, tr.frozen_params, tr.ref_params, jnp.asarray(tokens))
    logprobs, values, log_ratio, mean_kl, mean_kl_per_token = out
    assert logprobs.shape == values.shape == log_ratio.shape == (8, 8 + MAX_NEW - 1)
    if hands_out:
        six = tr._score_fn(tr.train_params, tr.frozen_params, tr.ref_params,
                           jnp.asarray(tokens), trunk_state=True)
        assert len(six) == 6 and six[5].shape == (8, 8 + MAX_NEW, tr.model_cfg.d_model)
        np.testing.assert_array_equal(np.asarray(six[0]), np.asarray(logprobs))
        np.testing.assert_array_equal(
            np.asarray(six[5]), np.asarray(_filled_trunk(tr, jnp.asarray(tokens))))

    # the multi-turn collection scores whole conversations through the door
    # (`_episodes_to_elements`), at a width of its own
    stats = {}
    episodes = [([65, 66, 67], [("policy", [68, 69], [-0.5, -0.25], 1.0),
                                ("env", [70], None, 0.0),
                                ("policy", [71, 72, 73], [-0.1, -0.2, -0.3], 2.0)], 1)] * 2
    elements = tr._episodes_to_elements(episodes, stats)
    assert len(elements) == 2 and np.isfinite(stats["policy/sqrt_kl"])
    assert all(e.trunk_row is None and len(e.response_tensor) == 6 for e in elements)
    assert tr._trunk_cache is None and tr._trunk_chunks is None


def test_the_next_collection_over_an_empty_store_drops_the_cache(tmp_path):
    tr = _make_trainer(tmp_path)
    tr.make_experience(8)
    first = tr._trunk_cache
    # a second collection over the SAME store adds its rows behind the first's
    tr.make_experience(8)
    assert tr._trunk_cache.shape[0] == 16
    assert [e.trunk_row for e in tr.store.history] == list(range(16))
    np.testing.assert_array_equal(np.asarray(tr._trunk_cache[:8]), np.asarray(first))
    tr.store.clear_history()
    tr._open_trunk_cache()
    assert tr._trunk_cache is None and tr._trunk_chunks == []


def test_a_several_chunk_collection_that_adds_to_a_live_cache_fills_its_chunks(tmp_path):
    """The rule counted one collection's states. A second collection over the
    same store finds the first's cache standing, which was not in the
    reckoning: its chunks wait for the collection's end, when nothing is in
    flight, and the fills' rows join the cache behind the scored ones."""
    tr = _make_trainer(tmp_path, chunk_size=4, train=dict(batch_size=4), devices=1)
    fills = _count_fills(tr)
    tr.make_experience(8)
    assert tr._score_with_trunk_state and tr._trunk_scored_rows == 8 and fills == []
    tr.make_experience(8)
    assert fills == [(4, 32), (4, 32)] and tr._trunk_scored_rows == 8
    assert tr._trunk_cache.shape[0] == 16
    assert [e.trunk_row for e in tr.store.history] == list(range(16))
    _assert_rows_hold_their_tokens_state(tr)


def test_one_epoch_fills_nothing_and_trains_from_the_whole_forward(tmp_path):
    """One optimizer pass over a chunk's rows: the fill would cost what it
    saves, so the schedule is today's, program for program."""
    tr = _make_trainer(tmp_path, ppo_epochs=1)
    assert not tr._trunk_cache_available()
    losses, _ = _cycle(tr)
    assert np.all(np.isfinite(losses))
    assert tr._trunk_cache is None and tr._trunk_cache_fn is None
    assert all(e.trunk_row is None for e in tr.store.history)
    host = next(iter(tr.create_train_dataloader()))
    assert host.trunk_rows is None and tr.batch_to_device(host).trunk_cache is None


def test_a_restored_store_trains_from_the_whole_forward(tmp_path):
    """The rows a checkpointed store held went with the process that
    filled the cache: the restored rollouts carry none."""
    tr = _make_trainer(tmp_path)
    tr.make_experience(8)
    state = tr._extra_resume_state()
    assert all(e.trunk_row is not None for e in state["store_history"])
    tr._load_extra_resume_state(state)
    assert tr._trunk_cache is None
    assert all(e.trunk_row is None for e in tr.store.history)
    host = next(iter(tr.create_train_dataloader()))
    assert host.trunk_rows is None
    # rows that outlive their cache some other way are dropped at the door
    orphan = tr.batch_to_device(host.replace(trunk_rows=np.arange(8, dtype=np.int32)))
    assert orphan.trunk_rows is None and orphan.trunk_cache is None


# ----------------------------------------------------------------------
# The arbiter
# ----------------------------------------------------------------------

V5E = 16 * hbm.GiB


class _OwnLoss(PPOTrainer):
    def make_loss_fn(self):
        return super().make_loss_fn()


def _patch(trainer, monkeypatch, *, method=None, model_cfg=None, train=None, budget=0, **attrs):
    if method or train:
        monkeypatch.setattr(trainer, "config", trainer.config.evolve(
            method=dict(method or {}), train=dict(train or {})))
    if model_cfg:
        monkeypatch.setattr(
            trainer, "model_cfg", dataclasses.replace(trainer.model_cfg, **model_cfg))
    monkeypatch.setattr(trainer, "_trunk_cache_budget", budget)
    for name, value in attrs.items():
        monkeypatch.setattr(trainer, name, value)


class _OwnScore(PPOTrainer):
    def _build_score_fn(self):
        return super()._build_score_fn()


CELL = dict(budget=int(ppo_trainer.TRUNK_CACHE_HBM_SHARE * V5E))
# What `pythia-1.4b.ppo-hh` reads on its v5e chip when its scorer is built (my
# chip runs, PR 49): `bytes_limit` 16,909,336,064 less 8,205,274,112 in use (the
# weights, the optimizer, the reference) and 524,288 reserved; and the compiler's
# analysis of the two programs at the cell's shapes, (temporaries, code + results)
FREE = 16_909_336_064 - 8_205_274_112 - 524_288
GENERATE = (6_767_312_896, 9_828_864 + 147_968)    # `generate[b16,p896]` since PR 46 (`bytes_in_use` rose by their sum at its first dispatch)
GENERATE_PR42 = (8_371_238_912, GENERATE[1])       # PR 42's sampler at the same shapes (PERF.md section 5, PR 46)
SCORE = (3_749_879_296, 304_285_696 + 67_307_008)  # the six-output `score` over `[16, 1024]`
STATES = 4 * 16 * 1024 * 2048 * 2
MARGIN = PPOTrainer.COLLECTION_HBM_MARGIN
# what the rule asks of the device in cell 1: `generate`'s temporaries are the larger
ASKED = GENERATE[0] + GENERATE[1] + SCORE[1] + STATES + MARGIN


def _device(free=FREE, generate=GENERATE, score=SCORE):
    """The parts of the rule's reckoning, as a device and the compiler would
    report them."""
    return dict(CELL, _device_free_bytes=lambda: free, _generate_held_bytes=lambda: generate,
                _score_held_bytes=lambda program=None: score)


PYTHIA = dict(method=dict(num_rollouts=64, chunk_size=16, ppo_epochs=4), train=dict(seq_length=1024),
              model_cfg=dict(d_model=2048, n_layers=24, dtype=jnp.bfloat16), split=22)
ARBITER = {
    # what the schedule observes -> (patch, the cycle trains from the trunk
    # cache, bytes a device holds of it, the score program hands the state out)
    "as_built": (dict(), True, 8 * 32 * 64 * 4, True),
    "one_epoch": (dict(method=dict(ppo_epochs=1)), False, None, False),
    "nothing_frozen": (dict(split=0), False, None, False),
    "seq2seq": (dict(seq2seq=True), False, None, False),
    # MoEMLP's softmax router sows an auxiliary loss from the full forward
    "sows_moe_aux": (dict(model_cfg=dict(moe_experts=2)), False, None, False),
    # n_layers=2, split=1, 2 value layers: the branch taps at layer 0 < split
    "value_branch_below_split": (
        dict(method=dict(num_value_layers_unfrozen=2)), False, None, False),
    "over_hbm_budget": (dict(budget=8 * 32 * 64 * 4 - 1), False, None, False),
    "just_inside_hbm_budget": (dict(budget=8 * 32 * 64 * 4), True, None, True),
    # a backend that reports no capacity (the CPU) bounds nothing
    "no_capacity_known": (dict(budget=0), True, None, True),
    # the scorer's own reasons. A second chunk's generation is in flight while
    # the first is scored: yes since PR 49 where the device has room for both
    # chunks' states beside it (a no until then, whatever the device held),
    # and where it reports nothing (the CPU: the rehearsal's two-chunk recipes)
    "two_chunks": (dict(_device(), method=dict(chunk_size=4)), True, None, True),
    "two_chunks_no_capacity_known": (dict(method=dict(chunk_size=4)), True, None, True),
    "two_chunks_no_room": (dict(_device(free=ASKED - STATES), method=dict(chunk_size=4)), True, None, False),
    # two chunks, the second of one row: both counted, both handed out
    "one_row_over_a_chunk": (dict(_device(), method=dict(num_rollouts=9)), True, None, True),
    # a trainer that builds its own scorer; one pass over a chunk's rows (above)
    "own_score": (dict(_device(), __class__=_OwnScore), True, None, False),
    "own_score_one_chunk": (dict(__class__=_OwnScore), True, None, False),
    # the states just inside what is free, and one byte over
    "states_just_inside": (dict(_device(free=ASKED), **PYTHIA), True, STATES, True),
    "states_one_byte_over": (dict(_device(free=ASKED - 1), **PYTHIA), True, STATES, False),
    # the device keeps one region for temporaries: a scorer whose own are the
    # larger is counted in `generate`'s place, not on top of it
    "score_temporaries_the_larger": (dict(_device(
        free=ASKED + 1, score=(GENERATE[0] + 1, SCORE[1])), **PYTHIA), True, STATES, True),
    "score_temporaries_the_larger_one_byte_over": (dict(_device(
        free=ASKED, score=(GENERATE[0] + 1, SCORE[1])), **PYTHIA), True, STATES, False),
    # a plain no never sizes (and so never compiles) the six-output program
    "no_room_beside_generate_alone": (dict(_device(free=ASKED - SCORE[1] - 1), **PYTHIA,
                                           _score_held_bytes=None), True, STATES, False),
    # a generation that is not on this device (fleet rollouts) says so, and
    # the rule answers from the rest; a program nobody can size declines
    "fleet_rollouts": (dict(CELL, **{**PYTHIA, "train": dict(seq_length=1024, rollout_backend="fleet")},
                            _device_free_bytes=lambda: sum(SCORE) + STATES + MARGIN,
                            _score_held_bytes=lambda program=None: SCORE), True, STATES, True),
    "a_program_without_a_size": (dict(_device(generate=None), **PYTHIA), True, STATES, False),
    # the benchmark's three PPO cells on a v5e's 16 GiB. Cell 1 collects in
    # four chunks: PR 46's sampler leaves room for their states beside a
    # generation in flight (a no until PR 49: the rule read the chunk count
    # alone), PR 42's sampler at the same shapes did not
    "pythia-1.4b.ppo-hh": (dict(_device(), **PYTHIA), True, STATES, True),
    "pythia-1.4b.ppo-hh_pr42_sampler": (dict(_device(generate=GENERATE_PR42), **PYTHIA), True, STATES, False),
    # one chunk a collection: no memory is read (a reckoning would raise here)
    "gpt2-xl.ppo-sentiments": (dict(
        CELL, method=dict(num_rollouts=128, chunk_size=128, ppo_epochs=4), train=dict(seq_length=104),
        model_cfg=dict(d_model=1600, n_layers=48, dtype=jnp.bfloat16), split=46, _device_free_bytes=None),
        True, 42598400, True),
    "lfm2-8b-a1b.ppo-hh": (dict(
        CELL, method=dict(num_rollouts=64, chunk_size=64, ppo_epochs=4), train=dict(seq_length=1024),
        model_cfg=dict(d_model=2048, n_layers=10, dtype=jnp.bfloat16), split=8, _device_free_bytes=None),
        True, 268435456, True),
    # the same recipe at a width and depth of rollouts one chip cannot hold
    "too_many_rollouts": (dict(
        CELL, method=dict(num_rollouts=1024, chunk_size=16, ppo_epochs=4), train=dict(seq_length=1024),
        model_cfg=dict(d_model=2048, n_layers=24, dtype=jnp.bfloat16), split=22),
        False, 4294967296, False),
}


@pytest.mark.parametrize("case", list(ARBITER))
def test_arbiter_table(trainer, monkeypatch, case):
    patch, engages, nbytes, scored = ARBITER[case]
    # one chip's numbers: the rows of the fixture's cache lie on one device
    monkeypatch.setattr(trainer, "_trunk_cache_sharding", lambda shape=None: None)
    _patch(trainer, monkeypatch, **patch)
    assert trainer._trunk_cache_available() is engages
    assert trainer._score_hands_out_trunk_state() is scored
    if nbytes is not None:
        assert trainer._trunk_cache_device_bytes() == nbytes


def test_a_device_holds_its_share_of_the_rows(trainer):
    assert trainer.runtime.dp_size == 8
    assert trainer._trunk_cache_device_bytes() == 8 * 32 * 64 * 4 // 8
    assert len(trainer._trunk_cache.sharding.device_set) == 8


def test_arbiter_reads_the_devices_capacity_and_says_no_to_a_trainer_with_its_own_loss(
        trainer, monkeypatch, tmp_path):
    monkeypatch.setattr(trainer, "_trunk_cache_budget", None)
    monkeypatch.setattr(hbm, "device_hbm_bytes", lambda device=None: V5E)
    assert trainer._trunk_cache_available()
    assert trainer._trunk_cache_budget == V5E // 8
    # a trainer that builds its own loss has no resumed forward (GRPO; the
    # pipelined and sequence-parallel trainers say no themselves)
    own = _make_trainer(tmp_path, cls=_OwnLoss)
    assert not own._trunk_cache_available()
    own.make_experience(8)
    assert own._trunk_cache is None and all(e.trunk_row is None for e in own.store.history)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_cache_holds_the_dtype_the_forward_hands_to_the_split(tmp_path, dtype):
    """Never a narrower one: nothing is rounded that was not rounded."""
    tr = _make_trainer(tmp_path, model=dict(model_extra_configs={"dtype": dtype}))
    tokens = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    fill = tr._build_trunk_cache_fn()
    out = jax.eval_shape(fill, tr.train_params, tr.frozen_params, tokens)
    assert out.dtype == jnp.dtype(dtype) == jnp.dtype(tr.model_cfg.dtype)
    assert out.shape == (8, 32, tr.model_cfg.d_model)
    # the fill's program is found by its name in a device trace
    lowered = jax.jit(fill).lower(tr.train_params, tr.frozen_params, tokens)
    assert "jit_trunk_cache_fill" in lowered.as_text()[:200]
    # and the state the score program hands out is the fill's, dtype and shape
    tr._build_score_fn()
    assert tr._score_with_trunk_state
    state = jax.eval_shape(
        lambda *args: tr._score_fn(*args, trunk_state=True),
        tr.train_params, tr.frozen_params, tr.ref_params, tokens)[5]
    assert (state.dtype, state.shape) == (out.dtype, out.shape)


# ----------------------------------------------------------------------
# The loss
# ----------------------------------------------------------------------


def test_f32_cache_loss_and_grads_exact(trainer, chunk):
    """The resumed loss and EVERY gradient leaf bitwise equal to the
    full-forward path (eager evaluation on both sides, the trunk recomputed
    eagerly); from the rows the cycle's jitted fill left, the same loss
    (jit against jit is the whole-cycle test's to hold bitwise)."""
    loss_fn = trainer.make_loss_fn()
    tokens = jnp.concatenate([chunk.query_tensors, chunk.response_tensors], axis=1)
    eager = chunk.replace(trunk_rows=jnp.arange(8, dtype=jnp.int32),
                          trunk_cache=_eager_trunk(trainer, tokens))
    full = _uncached(chunk)
    l_f, _ = loss_fn(trainer.train_params, trainer.frozen_params, full)
    l_c, _ = loss_fn(trainer.train_params, trainer.frozen_params, eager)
    np.testing.assert_array_equal(np.asarray(l_c), np.asarray(l_f))
    g_c = _grads(trainer, loss_fn, eager)
    g_f = _grads(trainer, loss_fn, full)
    for a, b in zip(jax.tree_util.tree_leaves(g_c), jax.tree_util.tree_leaves(g_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    l_j, _ = loss_fn(trainer.train_params, trainer.frozen_params, chunk)
    np.testing.assert_allclose(float(l_j), float(l_f), rtol=1e-6)


@pytest.mark.parametrize("left", [True, False], ids=["left_queries", "right_queries"])
@pytest.mark.parametrize("gap", [0, 3])
def test_rows_move_to_a_batch_that_pads_queries_wider(left, gap):
    """Rows filled at one query width in a batch that pads queries wider
    (a store whose bucket grew): where the old host collator put them."""
    q, r, d = 4, 3, 2
    h = np.arange(2 * (q + r) * d, dtype=np.float32).reshape(2, q + r, d) + 1
    want = np.zeros((2, q + gap + r, d), np.float32)
    want[:, (gap if left else 0):(gap if left else 0) + q] = h[:, :q]
    want[:, q + gap:] = h[:, q:]
    got = _to_batch_columns(jnp.asarray(h), q, q + gap, q + gap + r, left)
    np.testing.assert_array_equal(np.asarray(got), want)
    # token ids on the host take the same road, pads in the gap
    ids = _to_batch_columns(np.ones((2, q + r), np.int32), q, q + gap, None, left, fill=7)
    assert isinstance(ids, np.ndarray) and ids.shape == (2, q + gap + r)
    assert (ids == 7).sum() == 2 * gap and (want[..., 0] == 0).tolist() == (ids == 7).tolist()
    with pytest.raises(ValueError, match="under the rows'"):
        _to_batch_columns(jnp.asarray(h), q, q - 1, q + r, left)


def test_rows_filled_at_a_narrower_query_width_train_the_same_loss(trainer, chunk):
    """The fill's layout is the train batch's by construction; if a batch
    still comes wider than the cache, the rows move inside the step."""
    loss_fn = trainer.make_loss_fn()
    q = chunk.query_tensors.shape[1]
    narrow = chunk.query_tensors[:, 8:]  # the prompts are 8 wide, left-padded to 26
    assert bool(jnp.all(chunk.query_tensors[:, :8] == trainer.tokenizer.pad_token_id))
    tokens = jnp.concatenate([narrow, chunk.response_tensors], axis=1)
    cached = chunk.replace(trunk_rows=jnp.arange(8, dtype=jnp.int32),
                           trunk_cache=_eager_trunk(trainer, tokens))
    assert cached.trunk_cache.shape[1] == q - 8 + MAX_NEW
    l_c, _ = loss_fn(trainer.train_params, trainer.frozen_params, cached)
    l_f, _ = loss_fn(trainer.train_params, trainer.frozen_params, _uncached(chunk))
    np.testing.assert_allclose(float(l_c), float(l_f), rtol=1e-5)


def test_scored_rows_under_queries_padded_wider_train_the_same_loss(trainer, chunk):
    """The scorer sees queries 8 wide and the loader pads them to its
    bucket, 26: the state the score program handed out moves on the device
    (`trunk_cache_concat`, as chunks of two widths do), its added columns
    hold zeros that nothing reads, and the moved rows train the whole
    forward's loss."""
    assert trainer._score_with_trunk_state and trainer._trunk_scored_rows == 8
    assert trainer._trunk_cache_fn is None and trainer._trunk_concat_fn is not None
    q = chunk.query_tensors.shape[1]
    assert (q, trainer._trunk_cache.shape) == (26, (8, 26 + MAX_NEW, trainer.model_cfg.d_model))
    cache = np.asarray(trainer._trunk_cache)
    assert not cache[:, :q - 8].any() and cache[:, q - 8:].any(axis=-1).all()
    # row for row the fill's state over the tokens as the batch lays them out
    rows = np.asarray(chunk.trunk_rows)
    tokens = jnp.concatenate([chunk.query_tensors, chunk.response_tensors], axis=1)
    want = np.asarray(_filled_trunk(trainer, tokens))
    real = np.asarray(tokens) != trainer.tokenizer.pad_token_id
    np.testing.assert_allclose(cache[rows][real], want[real], rtol=1e-5, atol=1e-6)
    loss_fn = jax.jit(trainer.make_loss_fn())
    l_c, _ = loss_fn(trainer.train_params, trainer.frozen_params, chunk)
    l_f, _ = loss_fn(trainer.train_params, trainer.frozen_params, _uncached(chunk))
    np.testing.assert_allclose(float(l_c), float(l_f), rtol=1e-6)


def test_whiten_with_mask_both_behaviors(trainer, chunk):
    """Satellite: method.whiten_with_mask. Default OFF keeps the
    reference's unmasked whitening (advantage mean ~0 over ALL positions
    including padding); ON whitens over real response tokens only
    (mean ~0 over the mask). Both pinned at the GAE level and the loss
    level (toggling the flag changes the loss on a padded batch)."""
    method = trainer.config.method
    pad = trainer.tokenizer.pad_token_id
    # sampling with suppress_tokens tends to fill every response to
    # max_new_tokens, so synthesize ragged rows: truncate half the batch
    # two tokens early (pad_id -> mask 0 inside loss_fn too)
    resp = np.asarray(chunk.response_tensors).copy()
    resp[: resp.shape[0] // 2, -2:] = pad
    chunk = chunk.replace(response_tensors=jnp.asarray(resp))
    mask = (chunk.response_tensors != pad).astype(jnp.float32)
    assert float(mask.sum()) < mask.size

    adv_u, _ = get_advantages_and_returns(
        chunk.values, chunk.rewards, method.gamma, method.lam
    )
    adv_m, _ = get_advantages_and_returns(
        chunk.values, chunk.rewards, method.gamma, method.lam, mask=mask
    )
    assert abs(float(adv_u.mean())) < 1e-5
    masked_mean = float((adv_m * mask).sum() / mask.sum())
    assert abs(masked_mean) < 1e-5
    assert not np.allclose(np.asarray(adv_u), np.asarray(adv_m))

    full = _uncached(chunk)
    loss_off, _ = jax.jit(trainer.make_loss_fn())(
        trainer.train_params, trainer.frozen_params, full
    )
    on_config = trainer.config
    try:
        trainer.config = on_config.evolve(method=dict(whiten_with_mask=True))
        loss_on, _ = jax.jit(trainer.make_loss_fn())(
            trainer.train_params, trainer.frozen_params, full
        )
    finally:
        trainer.config = on_config
    assert float(loss_on) != float(loss_off)


def test_the_counter_span_says_what_every_dispatch_resumed_from(trainer, monkeypatch):
    """`trlx:ppo.trunk_cache blocks=.. cached_blocks=.. rows=..` in front of
    every train-step dispatch while a profiler session listens (read by
    bench/metrics/ppo.trunk_cached_share.json), and never otherwise."""
    from trlx_tpu.observability import tracing

    seen = []
    monkeypatch.setattr(tracing, "counters", lambda name, **kv: seen.append((name, kv)))
    host = next(iter(trainer.create_train_dataloader()))
    trainer.batch_to_device(host)
    assert seen == []
    monkeypatch.setattr(tracing, "active", lambda: True)
    trainer.batch_to_device(host)
    trainer.batch_to_device(_uncached(host))
    assert seen == [
        ("ppo.trunk_cache", dict(blocks=2, cached_blocks=1, rows=8)),
        ("ppo.trunk_cache", dict(blocks=2, cached_blocks=0, rows=8)),
    ]


@pytest.mark.parametrize("chunk_size, scored", [(8, 8), (4, 0), (4, 8)],
                         ids=["one_chunk", "two_chunks", "two_chunks_handed_out"])
def test_the_counter_span_says_where_the_caches_rows_came_from(
        tmp_path, monkeypatch, chunk_size, scored):
    """`trlx:ppo.trunk_rows rows=.. scored=..` once a collection while a
    profiler session listens (read by bench/metrics/ppo.trunk_scored_share.json);
    off a session the hot path formats nothing."""
    from trlx_tpu.observability import tracing

    tr = _make_trainer(tmp_path, chunk_size=chunk_size, devices=1)
    if not scored:
        _no_room(tr)

    def no_counters(name, **kv):
        raise AssertionError(f"{name} formatted off a session")

    monkeypatch.setattr(tracing, "counters", no_counters)
    tr.make_experience(8)
    tr.store.clear_history()
    seen = []
    monkeypatch.setattr(
        tracing, "counters",
        lambda name, **kv: name == "ppo.trunk_rows" and seen.append(kv))
    monkeypatch.setattr(tracing, "active", lambda: True)
    tr.make_experience(8)
    assert seen == [dict(rows=8, scored=scored)]
    # a collection that caches nothing writes nothing
    _whole_forward(tr).store.clear_history()
    tr.make_experience(8)
    assert len(seen) == 1 and tr._trunk_cache is None


# ----------------------------------------------------------------------
# The scan paths: the store's batches and the fused cycle's device chunks
# carry the cache one way
# ----------------------------------------------------------------------


def test_store_path_trains_from_cache(tmp_path):
    """The store's batches through both scans (`train_inner_epoch_fused`
    stacks host batches; `train_epochs_from_chunk` gathers a device
    chunk): the cache rides beside the stack, the steps gather their rows,
    and the losses are the whole forward's."""
    cached = _make_trainer(tmp_path / "a", devices=1)
    whole = _whole_forward(_make_trainer(tmp_path / "b", devices=1))
    for tr in (cached, whole):
        tr.config = tr.config.evolve(train=dict(batch_size=4))
        tr.make_experience(8)
    assert all(e.trunk_row is not None for e in cached.store.history)
    stats = [tr.train_inner_epoch_fused(tr.create_train_dataloader())[0] for tr in (cached, whole)]
    got, want = (float(np.asarray(s["losses"]["total_loss"])) for s in stats)
    assert np.isfinite(got) and got == pytest.approx(want, rel=1e-6)

    for tr in (cached, whole):
        tr.config = tr.config.evolve(train=dict(batch_size=8))
    chunks = [tr.batch_to_device(next(iter(tr.create_train_dataloader()))) for tr in (cached, whole)]
    assert chunks[0].trunk_cache is not None and chunks[1].trunk_cache is None
    stats = [tr.train_epochs_from_chunk(c, 2) for tr, c in zip((cached, whole), chunks)]
    got, want = (float(np.asarray(s["losses"]["total_loss"])) for s in stats)
    assert np.isfinite(got) and got == pytest.approx(want, rel=1e-5)


def test_pipelined_cycle_with_capture_reuses_h_split(tmp_path_factory):
    """2-cycle end-to-end PPO with the rollout fast path: the sampler's
    captured state IS the trunk cache (the fill never compiles), losses
    are finite, and training moves the params."""
    tr = _make_trainer(tmp_path_factory.mktemp("tc_fast"),
                       capture_rollout_stats=True)
    assert tr._fast_rollout_available() and tr._trunk_cache_available()
    p0 = jax.device_get(next(iter(tr.train_params.values())))
    loss0, pending = tr.pipelined_cycle()
    assert loss0 is None
    loss1, pending = tr.pipelined_cycle(pending)
    assert isinstance(loss1, float) and np.isfinite(loss1)
    assert np.isfinite(float(np.asarray(pending[2][0])))
    # zero extra forwards: the captured activations fed the cache
    assert tr._trunk_cache_fn is None
    assert getattr(tr, "spec_fallbacks", 0) == 0
    p1 = jax.device_get(next(iter(tr.train_params.values())))
    assert not np.allclose(p0, p1)


@pytest.mark.parametrize("chunks", [1, 2])
def test_pipelined_cycle_classic_computes_trunk(tmp_path_factory, chunks):
    """2-cycle end-to-end with NO capture: the cycle fills the cache with
    the jitted trunk pass, one fill a chunk; k chunks' caches are one array
    whose rows the concatenated chunk numbers anew."""
    tr = _make_trainer(tmp_path_factory.mktemp("tc_classic"), num_rollouts=8 * chunks)
    assert not tr._fast_rollout_available() and tr._trunk_cache_available()
    seen = []
    real = tr.train_epochs_from_chunk
    tr.train_epochs_from_chunk = lambda full, n: (seen.append(full), real(full, n))[1]
    loss0, pending = tr.pipelined_cycle()
    assert loss0 is None
    loss1, pending = tr.pipelined_cycle(pending)
    assert isinstance(loss1, float) and np.isfinite(loss1)
    assert tr._trunk_cache_fn is not None
    full = seen[-1]
    assert full.trunk_cache.shape[0] == 8 * chunks
    assert np.asarray(full.trunk_rows).tolist() == list(range(8 * chunks))


# ----------------------------------------------------------------------
# SparseMoE's counters; the sentinel's quarantine
# ----------------------------------------------------------------------


def test_sparse_moe_trains_from_the_cache_and_keeps_its_counters(tmp_path):
    """lfm2's preset at tiny size (convolution layers in the trunk, both
    trained blocks expert layers): the cached cycle's losses are the whole
    forward's, and `moe/*` ride every step, reduced over the blocks the
    step runs."""
    from trlx_tpu.ops import moe

    def build(path):
        return _make_trainer(
            path, tokenizer="char:abcdefgh", prompts=["ab", "cdefg", "e", "ghab"] * 2,
            model=dict(model_path="random:lfm2-tiny", num_layers_unfrozen=2,
                       model_extra_configs=dict(moe_local_experts=2, dtype="float32")),
            train=dict(seq_length=24, batch_size=4), devices=1,
            gen_kwargs=dict(max_new_tokens=MAX_NEW, top_k=0, top_p=1.0, do_sample=True))

    cached, whole = build(tmp_path / "cached"), _whole_forward(build(tmp_path / "whole"))
    cfg = cached.model_cfg
    assert cached._trunk_cache_available() and cfg.has_sparse_moe and cached.split == 4
    assert "conv" in cfg.layer_types[:cached.split]
    (got, stats), (want, whole_stats) = _cycle(cached), _cycle(whole)
    assert got == want and np.all(np.isfinite(got)) and len(got) == 4
    assert cached._trunk_cache.shape == (8, 24, cfg.d_model)
    counters = {k: float(v) for k, v in stats["moe"].items()}
    assert sorted(counters) == sorted(moe.STATS)
    assert counters["dropped_tokens"] == 0.0 and 0.0 < counters["local_assignment_share"] < 1.0
    # blocks 4 and 5 of the whole forward's four expert layers
    assert float(whole_stats["moe"]["dropped_tokens"]) == 0.0
    _assert_same_leaves(cached, whole)


class _DropOneRow:
    """A sentinel that quarantines the second row of the first chunk."""

    def __init__(self):
        self.chunks = 0

    def quarantine_mask(self, scores, lens, reps):
        self.chunks += 1
        drop = np.zeros(len(scores), bool)
        drop[1] = self.chunks == 1
        return drop

    def kl_scale(self, step):
        return 1.0

    def lr_scale(self, step):
        return 1.0

    def observe_rollout(self, stats):
        pass


def test_a_quarantined_row_leaves_the_other_rows_right(tmp_path):
    """A dropped row keeps its slot in the cache and no element names it;
    every other element still names the state of ITS tokens."""
    tr = _make_trainer(tmp_path, chunk_size=4, train=dict(batch_size=4))
    tr._sentinel = _DropOneRow()
    fills = _count_fills(tr)
    tr.make_experience(8)
    rows = [e.trunk_row for e in tr.store.history]
    # chunk 0 lost row 1, so a third chunk made up the count
    assert rows == [0, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]
    assert tr._trunk_cache.shape[0] == 12
    # the rule counted the collection's two planned chunks: their states came
    # from the score program, and the third is filled beside them at the end
    assert tr._score_with_trunk_state and tr._trunk_scored_rows == 8 and fills == [(4, 32)]
    _assert_rows_hold_their_tokens_state(tr)
    # and the batches train from them
    host = next(iter(tr.create_train_dataloader()))
    assert set(host.trunk_rows.tolist()) <= set(rows)
    stats = tr.train_minibatch([host])
    assert np.isfinite(float(stats["losses"]["total_loss"]))


def test_a_quarantine_that_under_fills_one_chunk_adds_a_filled_block(tmp_path):
    """A one-chunk collection that a quarantine leaves one row short: the
    first chunk's state came from the score program, the chunk that makes
    up the count is dispatched after it and filled at the end, and the two
    become one cache whose rows train the whole forward's loss."""
    tr = _make_trainer(tmp_path, devices=1)
    assert tr._score_hands_out_trunk_state()
    tr._sentinel = _DropOneRow()
    fills = _count_fills(tr)
    tr.make_experience(8)
    rows = [e.trunk_row for e in tr.store.history]
    assert rows == [0, *range(2, 16)]
    assert fills == [(8, 32)] and tr._trunk_scored_rows == 8
    assert tr._trunk_cache.shape == (16, 32, tr.model_cfg.d_model)
    _assert_rows_hold_their_tokens_state(tr)
    loss_fn = jax.jit(tr.make_loss_fn())
    for host in tr.create_train_dataloader():
        assert set(host.trunk_rows.tolist()) <= set(rows)
        batch = tr.batch_to_device(host)
        assert batch.trunk_cache is tr._trunk_cache
        l_c, _ = loss_fn(tr.train_params, tr.frozen_params, batch)
        l_f, _ = loss_fn(tr.train_params, tr.frozen_params, _uncached(batch))
        np.testing.assert_allclose(float(l_c), float(l_f), rtol=1e-6)
