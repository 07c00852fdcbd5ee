"""End-to-end observability for the serving fleet and training loop:
request tracing (`Tracer`/`Span`/`RequestTrace`), the training phase
timeline (`PhaseTimeline`), the goodput ledger (`GoodputLedger` + the
shared FLOP model in `flops`), SLO burn-rate evaluation (`SLOEngine`),
per-component flight recorders, the postmortem bundler, and the
Chrome-trace/Perfetto exporter.

Everything here is dependency-free and OFF by default — components hold
`tracer = None` / `recorder = None` unless `train.tracing` /
`inference.tracing` is set — but for the build account
(`compile_ledger.account()`: four `jax.monitoring` listeners that fire
when a program is built and at no other time), which `import trlx_tpu`
installs. See docs/observability.md.
"""

from trlx_tpu.observability.compile_ledger import (
    CompileLedger,
    arg_signature,
    ledgered_jit,
    signature_diff,
)
from trlx_tpu.observability.flight_recorder import (
    FlightRecorder,
    all_recorders,
    snapshot_all,
)
from trlx_tpu.observability.hbm import (
    HBM_BYTES,
    HBMLedger,
    device_hbm_bytes,
    is_oom_error,
    kv_arena_bytes,
    largest_live_buffers,
    oom_postmortem,
)
from trlx_tpu.observability.flops import (
    PEAK_FLOPS,
    chip_peak_flops,
    flops_per_cycle,
    flops_per_sample,
)
from trlx_tpu.observability.goodput import WASTE_CAUSES, GoodputLedger
from trlx_tpu.observability.postmortem import (
    dump_postmortem,
    maybe_dump,
    reset_triggers,
)
from trlx_tpu.observability.slo import (
    SLO,
    SLOEngine,
    default_slos,
)
from trlx_tpu.observability.tracing import (
    EPOCH_OFFSET,
    PhaseTimeline,
    RequestTrace,
    Span,
    Tracer,
    new_id,
    to_chrome_trace,
    write_chrome_trace,
)

__all__ = [
    "CompileLedger",
    "EPOCH_OFFSET",
    "FlightRecorder",
    "GoodputLedger",
    "HBMLedger",
    "HBM_BYTES",
    "PEAK_FLOPS",
    "PhaseTimeline",
    "RequestTrace",
    "SLO",
    "SLOEngine",
    "Span",
    "Tracer",
    "WASTE_CAUSES",
    "all_recorders",
    "arg_signature",
    "chip_peak_flops",
    "default_slos",
    "device_hbm_bytes",
    "dump_postmortem",
    "flops_per_cycle",
    "flops_per_sample",
    "is_oom_error",
    "kv_arena_bytes",
    "largest_live_buffers",
    "ledgered_jit",
    "maybe_dump",
    "new_id",
    "oom_postmortem",
    "reset_triggers",
    "signature_diff",
    "snapshot_all",
    "to_chrome_trace",
    "write_chrome_trace",
]
