"""Rollout chunks grouped by prompt length, generated at a rung of a width
ladder (PromptPipeline.create_loader(group_window=), prompt_width_ladder,
TPUTrainer._ladder_width, PPOTrainer._rollout_generate).

Pinned here:
- the grouped loader hands out, window by window, the prompts the
  ungrouped loader would have, longest first, the same on two loaders of
  one seed, across an epoch's end, and after a save and a restore of the
  stream's place;
- the ladder's rule: few rungs, powers of two of 32 under the pool's
  longest prompt, one rung where a window is one chunk or the pool has one
  length (those recipes keep the parent's batches and its one program);
- a chunk generated at its rung gives the tokens and logprobs of the
  full-width call, and comes back at the caller's width, on either padding
  side and with the captured activations;
- every rung is compiled once the first two chunks are dispatched, whether
  a chunk ran at it or not: collections over a heavy-tailed pool add no
  `generate` compile afterwards;
- the counter in front of every rollout dispatch adds up.
"""

import math
from statistics import NormalDist

import jax
import numpy as np
import pytest

from trlx_tpu.data.configs import TokenizerConfig
from trlx_tpu.models.transformer import live_widths
from trlx_tpu.data.default_configs import default_grpo_config, default_ppo_config
from trlx_tpu.pipeline import LoaderStream, offline_pipeline
from trlx_tpu.pipeline.offline_pipeline import PromptPipeline, prompt_width_ladder
from trlx_tpu.tokenizers import get_tokenizer
from trlx_tpu.trainer import ppo_trainer
from trlx_tpu.trainer.ppo_trainer import PPOTrainer

ROWS, CHUNKS = 4, 4
WINDOW = ROWS * CHUNKS
MAX_NEW = 4


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
# the ladder's rule
# ---------------------------------------------------------------------------

HH = _lognormal_lengths(256, 192, 0.8, 16, 896)  # bench/traffic/ppo-hh.json


@pytest.mark.parametrize("lengths, window, rows, want", [
    (HH, 64, 16, (256, 896)),            # pythia-1.4b.ppo-hh
    (HH, 64, 64, (896,)),                # lfm2-8b-a1b.ppo-hh: one chunk a window
    ([64] * 128, 128, 128, (64,)),       # gpt2-xl.ppo-sentiments: one chunk, one length
    ([64] * 128, 128, 16, (64,)),        # a pool of one length has one width
    (HH, 64, 32, (256, 896)),            # never more rungs than chunks
    (LENGTHS, WINDOW, ROWS, (32, 100)),
    ([], 8, 4, ()),
])
def test_ladder_rule(lengths, window, rows, want):
    ladder = prompt_width_ladder(lengths, window, rows)
    assert ladder == want
    assert len(ladder) <= max(-(-window // rows), 1) and len(ladder) <= 2
    assert all(w % 32 == 0 for w in ladder[:-1])
    assert not lengths or ladder[-1] == max(lengths)


@pytest.mark.parametrize("max_widths, miss, rows, want", [
    (2, 0.01, 16, (256, 896)),             # 512 goes: its one chunk loses 384 columns, 256's two 512
    (3, 0.01, 16, (256, 512, 896)),        # the chunks' own rungs: 256, 256, 512, 896
    (8, 0.01, 8, (128, 256, 512, 896)),
    (4, 0.1, 16, (128, 256, 512, 896)),    # 128 holds the first chunk in 91% of windows
])
def test_ladder_keeps_the_rungs_that_save_the_most_columns(monkeypatch, max_widths, miss, rows, want):
    monkeypatch.setattr(offline_pipeline, "LADDER_MAX_WIDTHS", max_widths)
    monkeypatch.setattr(offline_pipeline, "LADDER_MISS", miss)
    assert prompt_width_ladder(HH, 64, rows) == want


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


@pytest.mark.parametrize("side", ["left", "right"])
def test_narrowed_generate_matches_the_full_width_call(trainer, monkeypatch, side):
    _with_pool(trainer, POOL)
    assert trainer._prompt_ladder == (32, 100)
    ids, mask = _left_padded([5, 30, 17, 23, 8, 2, 29, 11], 100, trainer.tokenizer.pad_token_id)
    if side == "right":
        ids, mask = ids[:, ::-1].copy(), mask[:, ::-1].copy()
        monkeypatch.setattr(trainer.config.tokenizer, "padding_side", "right")
    assert trainer._ladder_width(mask) == 32
    # (another token budget a side: the side is not in a program's key)
    greedy = dict(max_new_tokens=MAX_NEW + (side == "right"), do_sample=False)
    narrow = jax.device_get(trainer.generate(ids, mask, greedy, capture=True))
    monkeypatch.setattr(trainer, "_prompt_ladder", None)
    full = jax.device_get(trainer.generate(ids, mask, greedy, capture=True))
    new = greedy["max_new_tokens"]
    if side == "right":
        # the full-width call keeps `_bucket_prompts`' 28 columns between a
        # right-padded prompt block and the response (it trims left padding only)
        full = {k: np.delete(v, np.s_[100:128], axis=1) if k in ("samples", "samples_mask", "h_split")
                else v for k, v in full.items()}
    assert narrow["samples"].shape == full["samples"].shape == (8, 100 + new)
    assert narrow["h_split"].shape == full["h_split"].shape
    np.testing.assert_array_equal(narrow["samples"][:, :100], ids)
    np.testing.assert_array_equal(narrow["samples_mask"], full["samples_mask"])
    np.testing.assert_array_equal(narrow["samples"], full["samples"])
    np.testing.assert_allclose(narrow["logprobs"], full["logprobs"], atol=2e-5)
    np.testing.assert_allclose(narrow["values"], full["values"], atol=2e-5)
    # activations of the columns that hold a token (the full-width call
    # computes something at padding columns, the narrowed one holds zeros)
    live = full["samples_mask"].astype(bool)[:, :-1]
    np.testing.assert_allclose(narrow["h_split"][:, :-1][live], full["h_split"][:, :-1][live],
                               atol=2e-5)
    names = [n for n in _generate_programs(trainer) if ",cap" in n]
    assert any(n.startswith("generate[b8,p32,out100,lm,cap") for n in names), names
    assert any(n.startswith("generate[b8,p128,lm,cap") for n in names), names


def test_collections_add_no_generate_compile_after_the_first(trainer, monkeypatch):
    _with_pool(trainer, POOL)
    backend = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: backend.append(kw.get("fun_name"))
        if event.endswith("backend_compile_duration") else None)
    logged = []
    monkeypatch.setattr(trainer.tracker, "log", lambda stats, step=None: logged.append(stats))
    # first chunks that all fit the narrowest rung: the other rung's program
    # is there once two of them are dispatched
    gen_kwargs = trainer.generate_experience_kwargs or trainer.generate_kwargs
    short = dict(zip(("input_ids", "attention_mask"),
                     _left_padded([5, 9, 17, 30], 100, trainer.tokenizer.pad_token_id)))
    for left in (1, 0, 0):
        trainer._rollout_generate(short, gen_kwargs)
        assert trainer._ladder_countdown == left
    prepared = _generate_programs(trainer)
    rungs = [n for n in prepared if n.split(",kw")[0] in (
        "generate[b8,p32,out100,lm", "generate[b8,p128,lm")]
    assert len(rungs) == 2 == len(trainer._prompt_ladder) <= CHUNKS, prepared
    del backend[:]
    widths = set()
    for cycle in range(3):
        trainer.store.clear_history()
        trainer.make_experience(WINDOW, cycle)
        assert len(trainer.store) == WINDOW
        widths.add(logged[-1]["rollout/prefill_width"])
        calls, width_sum, padded, pad = (int(x) for x in trainer._prefill_tally)
        assert calls == CHUNKS and logged[-1]["rollout/prefill_width"] == width_sum / CHUNKS
        assert logged[-1]["rollout/prefill_padding_share"] == pad / padded
        assert padded < CHUNKS * 8 * 128  # fewer positions than four chunks at the pool's width
    assert _generate_programs(trainer) == prepared, "a collection compiled a generate program"
    assert not [name for name in backend if "generate" in str(name)], backend
    # the chunks did run at several widths (a mean of 128 would be the pool's width)
    assert max(widths) < 128


@pytest.mark.parametrize("prompts, num_rollouts", [
    (POOL, ROWS),                      # a collection is one chunk
    (_prompts([40] * 24), WINDOW),     # a pool of one length
])
def test_one_chunk_recipe_and_fixed_pool_keep_the_parents_batches_and_program(
        trainer, prompts, num_rollouts):
    _with_pool(trainer, prompts, num_rollouts)
    assert trainer._prompt_ladder is None
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
    spans = []
    monkeypatch.setattr(ppo_trainer.tracing, "active", lambda: True)
    monkeypatch.setattr(ppo_trainer.tracing, "counters",
                        lambda name, **values: spans.append((name, values)))
    gen_kwargs = trainer.generate_experience_kwargs or trainer.generate_kwargs
    tokens = 0
    for _ in range(CHUNKS):
        batch = next(trainer.prompt_iterator)
        tokens += int(batch["attention_mask"].sum())
        out = trainer._rollout_generate(batch, gen_kwargs)
        assert out["samples"].shape == (ROWS, 100 + MAX_NEW)
    assert [name for name, _ in spans] == ["ppo.prefill"] * CHUNKS
    for _, v in spans:
        assert list(v) == ["calls", "rows", "width", "prompt_tokens", "padded_tokens", "pad_tokens"]
        assert v["calls"] == 1 and v["rows"] == 8  # 4 prompts in `generate`'s row bucket of 8
        assert v["width"] in (32, 128)  # the rungs, the last in its 32-column bucket
        assert v["padded_tokens"] == v["rows"] * v["width"]
        assert v["pad_tokens"] == v["padded_tokens"] - v["prompt_tokens"]
    assert sum(v["prompt_tokens"] for _, v in spans) == tokens
    assert [v["width"] for _, v in spans] == sorted((v["width"] for _, v in spans), reverse=True)


def test_trainer_resumes_the_stream_where_it_was(trainer):
    _with_pool(trainer, POOL)
    _take(trainer.prompt_iterator, 3)
    state = trainer._extra_resume_state()
    assert state["prompt_stream"] == {"epoch": 0, "position": 3}
    want = _take(trainer.prompt_iterator, 9)
    _with_pool(trainer, POOL)  # a restarted process: a new loader, at the pool's start
    trainer._load_extra_resume_state(state)
    assert _take(trainer.prompt_iterator, 9) == want


def test_grpo_groups_stay_together_in_sorted_chunks():
    from trlx_tpu.trainer.grpo_trainer import GRPOTrainer

    config = default_grpo_config().evolve(
        model=dict(model_path="random:gpt2-tiny", num_layers_unfrozen=1),
        tokenizer=dict(tokenizer_path="byte"),
        train=dict(seq_length=104, batch_size=8, tracker=None),
        method=dict(num_rollouts=16, chunk_size=4, ppo_epochs=1, group_size=2,
                    gen_kwargs=dict(max_new_tokens=MAX_NEW, do_sample=True)),
    )
    grpo = GRPOTrainer(config, reward_fn=lambda samples, **kw: [0.0] * len(samples),
                       devices=jax.devices()[:1])
    grpo.add_prompt_pipeline(PromptPipeline(POOL, max_prompt_length=100, tokenizer=grpo.tokenizer))
    # a collection is 8 prompts x 2 completions in 4 chunks of 2 prompts
    window = [p for chunk in _take(grpo.prompt_iterator, 4) for p in chunk]
    assert len(window) == 16 and window[0::2] == window[1::2]
    assert [len(p) for p in window] == sorted((len(p) for p in window), reverse=True)
    assert grpo._prompt_ladder is not None and grpo._prompt_ladder[-1] == 100


# ---------------------------------------------------------------------------
# a sampler whose one program follows the chunk's longest prompt (BlockPlan)
# ---------------------------------------------------------------------------

BLOCK = 32  # the pool's width, 100, runs in `generate`'s bucket of 128: four blocks


@pytest.fixture
def block_form(trainer, monkeypatch):
    from trlx_tpu.ops import sampling

    monkeypatch.setattr(sampling, "PREFILL_BLOCK", BLOCK)
    return _with_pool(trainer, POOL)


def test_block_form_model_gets_no_ladder(block_form, monkeypatch):
    trainer = block_form
    assert trainer._prompt_ladder is None and trainer._ladder_countdown == 0
    plan = trainer._rollout_plan(100, trainer.generate_kwargs)
    assert (plan.block, plan.pad, plan.blocks, plan.columns) == (BLOCK, 0, 4, 128 + MAX_NEW)
    # the loader still sorts a collection's prompts: that is what makes a chunk's longest short
    window = [p for chunk in _take(trainer.prompt_iterator, CHUNKS) for p in chunk]
    assert [len(p) for p in window] == sorted((len(p) for p in window), reverse=True)
    # who keeps the ladder: right padding (no block in front is empty), speculative
    # rounds, a pipelined trainer's own `generate`
    monkeypatch.setattr(trainer.config.tokenizer, "padding_side", "right")
    assert _with_pool(trainer, POOL)._prompt_ladder == (32, 100)
    monkeypatch.setattr(trainer.config.tokenizer, "padding_side", "left")
    assert _with_pool(trainer, POOL)._prompt_ladder is None
    assert trainer._rollout_plan(100, trainer.generate_kwargs, spec_k=2) is None
    monkeypatch.setattr(trainer, "_narrows_rollout_chunks", False)
    assert trainer._rollout_plan(100, trainer.generate_kwargs) is None


def test_block_form_runs_four_chunks_through_one_program_and_counts_its_blocks(block_form, monkeypatch):
    trainer = block_form
    spans = []
    monkeypatch.setattr(ppo_trainer.tracing, "active", lambda: True)
    monkeypatch.setattr(ppo_trainer.tracing, "counters",
                        lambda name, **values: spans.append((name, values)))
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


def test_block_form_chunk_matches_the_one_shot_program(block_form, monkeypatch):
    """Through the trainer's own door, with the captured stats: the chunk
    generated by blocks against the same chunk through the one-shot program."""
    from trlx_tpu.ops import sampling

    trainer = block_form
    ids, mask = _left_padded([5, 30, 17, 23, 8, 2, 29, 11], 100, trainer.tokenizer.pad_token_id)
    # greedy, so one more token of budget changes none of the tokens before
    # it (a budget a program: the block is not in a program's name)
    n = MAX_NEW + 2
    blocks = jax.device_get(trainer.generate(ids, mask, dict(max_new_tokens=n, do_sample=False), capture=True))
    monkeypatch.setattr(sampling, "PREFILL_BLOCK", 0)
    whole = jax.device_get(trainer.generate(ids, mask, dict(max_new_tokens=n + 1, do_sample=False),
                                            capture=True))
    assert blocks["samples"].shape == (8, 100 + n) and blocks["h_split"].shape[:2] == (8, 100 + n)
    for name in ("samples", "samples_mask"):
        np.testing.assert_array_equal(blocks[name], whole[name][:, :100 + n])
    np.testing.assert_allclose(blocks["logprobs"], whole["logprobs"][:, :n], atol=2e-5)
    np.testing.assert_allclose(blocks["values"], whole["values"][:, :n], atol=2e-5)
    live = blocks["samples_mask"].astype(bool)[:, :-1]
    np.testing.assert_allclose(blocks["h_split"][:, :-1][live], whole["h_split"][:, :100 + n - 1][live],
                               atol=2e-5)
