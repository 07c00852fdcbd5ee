"""trlx_tpu — a TPU-native RLHF fine-tuning framework (JAX/Flax/pjit/Pallas)
with the capabilities of trlx: PPO/RFT online RL against a reward function,
ILQL offline RL, and SFT, behind a single `train()` API with registry-based
trainer/pipeline/method plugins, running on one GSPMD device mesh."""

__version__ = "0.1.0"

from trlx_tpu.observability.compile_ledger import install_monitoring
from trlx_tpu.utils import logging  # noqa: F401

install_monitoring()  # the build account hears the process's first jit


def train(*args, **kwargs):
    """Lazy wrapper over trlx_tpu.trlx.train (keeps `import trlx_tpu` light)."""
    from trlx_tpu.trlx import train as _train

    return _train(*args, **kwargs)
