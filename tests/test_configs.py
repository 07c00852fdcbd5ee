"""Config hygiene (reference tests/test_configs.py role): every yaml
preset under configs/ parses into a valid TRLConfig (round-tripping
through to_dict/from_dict), sweep yamls drive the sweep sampler, no
preset leaks a tracker entity/secret, and an option the package does not
have is refused by name."""

import glob
import os

import pytest
import yaml

import trlx_tpu.utils.loading  # noqa: F401  (registers trainers + method configs)
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.sweep import sample_trials

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
PRESETS = sorted(glob.glob(os.path.join(REPO, "configs", "*.yml")))
SWEEPS = sorted(glob.glob(os.path.join(REPO, "configs", "sweeps", "*.yml")))


def test_presets_exist():
    assert PRESETS and SWEEPS


def test_presets_parse_and_round_trip():
    for path in PRESETS:
        config = TRLConfig.load_yaml(path)
        rebuilt = TRLConfig.from_dict(config.to_dict())
        assert rebuilt.to_dict() == config.to_dict(), path
        # the parallel section must be a layout the mesh runtime accepts
        pc = config.parallel
        assert pc.data == -1 or pc.data >= 1, path
        for axis in ("fsdp", "tensor", "sequence", "pipeline"):
            size = getattr(pc, axis, 1)
            assert size >= 1, (path, axis, size)


def test_preset_parallel_sections_name_real_trainers():
    from trlx_tpu.trainer import _TRAINERS
    from trlx_tpu.utils.loading import get_trainer

    for path in PRESETS:
        config = TRLConfig.load_yaml(path)
        assert get_trainer(config.train.trainer), (path, sorted(_TRAINERS))


def test_sweep_yamls_drive_sampler():
    from trlx_tpu.sweep import make_searcher

    for path in SWEEPS:
        with open(path) as f:
            config = yaml.safe_load(f)
        tune = config.pop("tune_config")
        alg = tune.get("search_alg", "random")
        if alg in ("random", "grid", "grid_search"):
            trials = sample_trials(config, alg, num_samples=3, seed=0)
        else:
            # model-based algs (tpe) propose through the searcher interface
            searcher = make_searcher(config, alg, num_samples=3, seed=0)
            trials = [searcher.suggest() for _ in range(3)]
        assert len(trials) == 3
        assert all(set(t) == set(config) for t in trials), path


def test_no_entity_leakage():
    for path in PRESETS + SWEEPS:
        text = open(path).read().lower()
        for needle in ("entity_name", "api_key", "wandb.ai/"):
            assert needle not in text, (path, needle)


# ----------------------------------------------------------------------
# The package has no speculative decode: a run that asks for it is told so
# by name at every door, and never quietly decodes plainly
# ----------------------------------------------------------------------

def _from_dict(config, key, value, tmp_path):
    config["method"][key] = value
    return TRLConfig.from_dict(config)


def _load_yaml(config, key, value, tmp_path):
    config["method"][key] = value
    path = tmp_path / "old.yml"
    path.write_text(yaml.safe_dump(config))
    return TRLConfig.load_yaml(str(path))


def _update(config, key, value, tmp_path):
    return TRLConfig.update(config, {f"method.{key}": value})


@pytest.mark.parametrize("door", [_from_dict, _load_yaml, _update], ids=["from_dict", "load_yaml", "update"])
@pytest.mark.parametrize("key,value", [("speculative_decode", True), ("spec_k", 4), ("spec_draft_rank", 64)])
def test_a_config_that_asks_for_speculative_decode_is_refused_by_name(door, key, value, tmp_path):
    from trlx_tpu.data.default_configs import default_ppo_config

    with pytest.raises((TypeError, ValueError), match=rf"\b{key}\b"):
        door(default_ppo_config().to_dict(), key, value, tmp_path)


def _engine():
    from trlx_tpu.inference import InferenceEngine

    InferenceEngine(None, None, None, None, spec_k=2, spec_split=1)


def _sampler():
    from trlx_tpu.ops.sampling import make_generate_fn

    make_generate_fn(None, None, None, spec_k=2, spec_split=1, spec_draft_head=None)


def _decode_step():
    import jax
    import jax.numpy as jnp

    from trlx_tpu.models import config_from_preset, init_kv_cache
    from trlx_tpu.models.transformer import TransformerLM

    cfg = config_from_preset("gpt2-tiny", 64)
    tokens = jnp.zeros((1, 1), jnp.int32)
    jax.eval_shape(lambda: TransformerLM(cfg).apply(
        {"params": {}}, tokens, init_kv_cache(cfg, 1, 4), tokens, method=TransformerLM.decode_step, stop=1))


@pytest.mark.parametrize("call,argument", [(_engine, "spec_k"), (_sampler, "spec_k"), (_decode_step, "stop")],
                         ids=["InferenceEngine", "make_generate_fn", "decode_step"])
def test_a_caller_that_passes_a_speculative_argument_gets_a_type_error(call, argument):
    with pytest.raises(TypeError, match=f"unexpected keyword argument '{argument}'"):
        call()
