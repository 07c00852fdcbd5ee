"""Import the reference trlx (mounted read-only at /root/reference) as a
golden-value ORACLE for parity tests: our JAX loss/advantage math is checked
numerically against the reference's torch implementation on random inputs.

The reference's heavyweight deps (deepspeed, ray, torchtyping) aren't
installed here, so we stub just enough for `trlx.models.modeling_{ppo,ilql}`
to import. If anything fails (e.g. the reference isn't mounted), oracle
tests skip.
"""

import importlib.machinery
import os
import sys
import types

REFERENCE_PATH = "/root/reference"


def _stub(name, **attrs):
    if name in sys.modules:
        return sys.modules[name]
    m = types.ModuleType(name)
    m.__spec__ = importlib.machinery.ModuleSpec(name, None, is_package=True)
    m.__path__ = []
    for k, v in attrs.items():
        setattr(m, k, v)
    sys.modules[name] = m
    return m


def load_reference():
    """Returns (modeling_ppo, modeling_ilql) reference modules, or raises."""
    _stub("torchtyping")

    class TensorType:
        def __class_getitem__(cls, item):
            import torch

            return torch.Tensor

    sys.modules["torchtyping"].TensorType = TensorType
    _stub("deepspeed")

    class _Session:
        @staticmethod
        def get_session():
            return None

    ray = _stub("ray")
    air = _stub("ray.air", session=_Session)
    tune = _stub("ray.tune")
    ray.air = air
    ray.tune = tune

    class _Table:
        def __init__(self, *a, **k):
            pass

    _stub("wandb", Table=_Table, log=lambda *a, **k: None, init=lambda *a, **k: None)

    import peft

    if not hasattr(peft, "prepare_model_for_int8_training"):
        peft.prepare_model_for_int8_training = peft.prepare_model_for_kbit_training

    if REFERENCE_PATH not in sys.path:
        # append, not insert(0): the reference tree also contains an
        # `examples` package which must never shadow this repo's
        sys.path.append(REFERENCE_PATH)
    from trlx.models import modeling_ilql, modeling_ppo  # noqa: E402

    return modeling_ppo, modeling_ilql


def reference_available() -> bool:
    if not os.path.isdir(os.path.join(REFERENCE_PATH, "trlx")):
        # not mounted: said before torch and peft are imported to find it out,
        # 11 s that every xdist worker paid while it collected test_ops.py
        return False
    try:
        load_reference()
        return True
    except Exception:
        return False
