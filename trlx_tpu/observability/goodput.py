"""Goodput ledger: attribute every wall-clock second of learn() to a cause.

The PhaseTimeline (tracing.py) already times every trainer phase and
splits first calls (jit compile) from steady state; this module hangs a
ledger off those same hooks (`PhaseTimeline.ledger`) and turns the span
stream into a running account in the Google-Goodput / MLPerf sense:

    wall time = train + rollout_generate + rollout_score + reward_rtt
              + rollout_other + compile
              + waste/rewind + waste/fleet_degraded + waste/quarantined
              + other_host                       (the unattributed rest)

Attribution is EXCLUSIVE: phase spans nest (make_experience contains
rollout_generate contains nothing; rollout_score contains host_reward),
and spans arrive at END time — children strictly before their parents —
so the ledger keeps a merged list of already-covered intervals and
charges each span only for the part of [t0, t1] not yet covered. The
per-cause seconds therefore sum to the measured wall time exactly (the
remainder is `other_host`), never double-counting nested spans.

Live MFU uses the one FLOP model there is (observability/flops.py, which
also prices whole cycles offline): the trainer notes per-chunk rollout
shapes and per-minibatch train rows, the ledger prices them with
`flops_per_sample`, and the steady-state rate divides by wall time since
the last first-call span ended — a timing window that opens after the
warm-up, so a live and an offline MFU agree by construction for the
same config.

Everything is host-side bookkeeping on phase boundaries (a few dict ops
per chunk); nothing here touches jax.
"""

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from trlx_tpu.observability.flops import chip_peak_flops, flops_per_sample

# causes that represent wasted wall time (the `goodput/wasted_s` rollup)
WASTE_CAUSES = ("waste/rewind", "waste/fleet_degraded", "waste/quarantined")

# phases that are re-rollout work while a sentinel rewind is being
# repaid — their time is waste until the first post-rewind train step
_ROLLOUT_PHASES = (
    "rollout_generate", "rollout_score", "rollout_process", "host_reward",
    "make_experience", "pipelined_fetch",
)
_TRAIN_PHASES = ("train_minibatch", "train_epochs")


class GoodputLedger:
    """Running wall-clock attribution + live MFU for one learn() run.

    Attach with `timeline.ledger = ledger`; the timeline forwards every
    `add()` as `observe_phase`. The trainer additionally notes work
    quantities (`note_rollout_chunk`, `note_train_rows`) and events
    (`note_rewind`, `note_quarantine`).
    """

    def __init__(self, n_chips: Optional[int] = None,
                 peak_flops: Optional[float] = None):
        self._lock = threading.Lock()
        self.t_start = time.monotonic()
        self.causes: Dict[str, float] = {}
        # merged, sorted list of [t0, t1) intervals already charged
        self._covered: List[Tuple[float, float]] = []
        self._rewind_active = False
        self.rewinds = 0
        self.quarantined_rows = 0
        # ---- work accounting (FLOPs / tokens / samples) ----
        self._unit: Optional[Dict[str, float]] = None  # per-sample costs
        self._unit_tokens = 0.0
        self._events: List[Tuple[float, float, float, float]] = []
        self._warmup = [0.0, 0.0, 0.0]  # flops/tokens/samples before steady
        self._totals = [0.0, 0.0, 0.0]  # flops/tokens/samples, lifetime
        self._steady_t0: Optional[float] = None  # end of last first-call span
        if n_chips is None:
            try:
                import jax

                n_chips = jax.device_count()
            except Exception:
                n_chips = 1
        self.n_chips = max(int(n_chips), 1)
        if peak_flops is None:
            try:
                peak_flops = chip_peak_flops()
            except LookupError:
                pass  # a device with no peak row reports no MFU
        self.peak_flops = None if peak_flops is None else float(peak_flops)

    # ------------------------------------------------------------------
    # Span intake (called by PhaseTimeline.add, outside its lock)
    # ------------------------------------------------------------------

    def observe_phase(self, name: str, t0: float, t1: float,
                      first: bool = False,
                      attrs: Optional[Dict[str, Any]] = None) -> None:
        attrs = attrs or {}
        with self._lock:
            cause = self._classify(name, first, attrs)
            exclusive = self._charge_interval(t0, t1)
            if exclusive > 0.0:
                self.causes[cause] = self.causes.get(cause, 0.0) + exclusive
            if first:
                # the live-MFU window opens when the LAST compile ends —
                # so that only post-warmup work is timed
                if self._steady_t0 is None or t1 > self._steady_t0:
                    self._steady_t0 = t1

    def _classify(self, name: str, first: bool, attrs: Dict[str, Any]) -> str:
        if name == "sentinel_restore":
            return "waste/rewind"
        if name in _TRAIN_PHASES:
            # the first train step after a rewind marks the debt repaid
            self._rewind_active = False
            return "compile" if first else "train"
        if self._rewind_active and name in _ROLLOUT_PHASES:
            return "waste/rewind"
        if name == "host_reward":
            # pure host work — its first call compiles nothing
            return "reward_rtt"
        if first:
            return "compile"
        if name in ("rollout_generate", "pipelined_fetch"):
            if attrs.get("degraded"):
                return "waste/fleet_degraded"
            return "rollout_generate"
        if name == "rollout_score":
            return "rollout_score"
        return "rollout_other"

    def _charge_interval(self, t0: float, t1: float) -> float:
        """Insert [t0, t1) into the covered set; return the EXCLUSIVE
        duration (the part not already covered by earlier — i.e. nested —
        spans). The list stays merged and sorted, so it collapses to a
        handful of intervals per cycle."""
        if t1 <= t0:
            return 0.0
        covered = self._covered
        overlap = 0.0
        new: List[Tuple[float, float]] = []
        lo, hi = t0, t1
        placed = False
        for (a, b) in covered:
            if b < lo:
                new.append((a, b))
            elif a > hi:
                if not placed:
                    new.append((lo, hi))
                    placed = True
                new.append((a, b))
            else:  # overlapping or adjacent: merge, count the overlap
                overlap += max(0.0, min(b, hi) - max(a, lo))
                lo, hi = min(a, lo), max(b, hi)
        if not placed:
            new.append((lo, hi))
        # bound memory on very long runs: intervals more than 2h older
        # than the newest span can never overlap future spans
        horizon = hi - 7200.0
        self._covered = [(a, b) for (a, b) in new if b >= horizon]
        return (t1 - t0) - overlap

    # ------------------------------------------------------------------
    # Work + event intake (called by the trainer)
    # ------------------------------------------------------------------

    def configure_unit_flops(self, model_cfg, n_prompt: int, n_new: int,
                             unfrozen: int, window_ok: bool = True,
                             fast_path: bool = False) -> None:
        """Price one sample with the bench FLOP model. ppo_epochs=1: the
        train cost is charged per-minibatch-row as epochs actually run,
        so repeated epochs accumulate naturally."""
        unit = flops_per_sample(
            model_cfg, n_prompt, n_new, ppo_epochs=1, unfrozen=unfrozen,
            window_ok=window_ok, fast_path=fast_path,
        )
        with self._lock:
            self._unit = unit
            self._unit_tokens = float(n_prompt + n_new)

    def note_rollout_chunk(self, rows: int) -> None:
        """One rollout chunk finished: generate+score FLOPs for `rows`
        samples (requires configure_unit_flops first; silently a no-op
        until then)."""
        with self._lock:
            if self._unit is None or rows <= 0:
                return
            fl = rows * (self._unit["generate"] + self._unit["score"])
            self._note_work(fl, rows * self._unit_tokens, float(rows))

    def note_train_rows(self, rows: int) -> None:
        """One train minibatch finished: one epoch's train FLOPs for
        `rows` rows (epochs revisit rows, accumulating the full
        ppo_epochs cost over the cycle)."""
        with self._lock:
            if self._unit is None or rows <= 0:
                return
            self._note_work(rows * self._unit["train"], 0.0, 0.0)

    def _note_work(self, flops: float, tokens: float, samples: float) -> None:
        now = time.monotonic()
        self._totals[0] += flops
        self._totals[1] += tokens
        self._totals[2] += samples
        self._events.append((now, flops, tokens, samples))
        # fold events that predate the (now-final) steady anchor into the
        # warmup bucket; once compiles stop this folds every event
        if self._steady_t0 is not None:
            keep = []
            for ev in self._events:
                if ev[0] <= self._steady_t0:
                    self._warmup[0] += ev[1]
                    self._warmup[1] += ev[2]
                    self._warmup[2] += ev[3]
                else:
                    keep.append(ev)
            self._events = keep

    def note_rewind(self) -> None:
        """A sentinel rewind began: the restore itself plus all rollout
        work until the next completed train step is `waste/rewind`."""
        with self._lock:
            self._rewind_active = True
            self.rewinds += 1

    def note_quarantine(self, rows: int, seconds: float,
                        from_causes: Tuple[str, ...] = (
                            "rollout_generate", "rollout_score",
                            "rollout_other", "reward_rtt")) -> None:
        """`rows` quarantined rollout rows cost roughly `seconds` of the
        chunk's already-attributed rollout time: MOVE those seconds into
        `waste/quarantined` (never add — the total must keep summing to
        wall time)."""
        with self._lock:
            self.quarantined_rows += int(rows)
            remaining = max(float(seconds), 0.0)
            for cause in from_causes:
                if remaining <= 0.0:
                    break
                avail = self.causes.get(cause, 0.0)
                take = min(avail, remaining)
                if take > 0.0:
                    self.causes[cause] = avail - take
                    remaining -= take
            moved = max(float(seconds), 0.0) - remaining
            if moved > 0.0:
                self.causes["waste/quarantined"] = (
                    self.causes.get("waste/quarantined", 0.0) + moved)

    # ------------------------------------------------------------------
    # Output
    # ------------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Full ledger state; `seconds` sums to `wall_s` exactly (the
        remainder — currently-open phases and untimed host work — is
        `other_host`)."""
        with self._lock:
            now = time.monotonic()
            wall = max(now - self.t_start, 1e-9)
            causes = dict(self.causes)
            attributed = sum(causes.values())
            causes["other_host"] = max(wall - attributed, 0.0)
            wasted = sum(causes.get(c, 0.0) for c in WASTE_CAUSES)
            total_fl, total_tok, total_smp = self._totals
            # steady-state rates: work and wall since the last compile
            if self._steady_t0 is not None:
                steady_wall = max(now - self._steady_t0, 1e-9)
                st_fl = sum(e[1] for e in self._events
                            if e[0] > self._steady_t0)
                st_tok = sum(e[2] for e in self._events
                             if e[0] > self._steady_t0)
                st_smp = sum(e[3] for e in self._events
                             if e[0] > self._steady_t0)
            else:  # tracing on but no phase seen yet / no compile split
                steady_wall = wall
                st_fl, st_tok, st_smp = total_fl, total_tok, total_smp
            mfu_keys = {}
            if self.peak_flops is not None:
                mfu_keys = {
                    "mfu": round(
                        st_fl / steady_wall / self.n_chips / self.peak_flops, 6),
                    "mfu_overall": round(
                        total_fl / wall / self.n_chips / self.peak_flops, 6),
                }
            return {
                "wall_s": wall,
                "seconds": {k: round(v, 6) for k, v in sorted(causes.items())},
                "productive_s": round(causes.get("train", 0.0)
                                      + causes.get("rollout_generate", 0.0)
                                      + causes.get("rollout_score", 0.0), 6),
                "wasted_s": round(wasted, 6),
                "goodput_fraction": round(1.0 - wasted / wall, 6),
                **mfu_keys,
                "tokens_per_sec_per_chip": round(
                    st_tok / steady_wall / self.n_chips, 3),
                "samples_per_sec_per_chip": round(
                    st_smp / steady_wall / self.n_chips, 3),
                "flops_total": total_fl,
                "tokens_total": total_tok,
                "samples_total": total_smp,
                "rewinds": self.rewinds,
                "quarantined_rows": self.quarantined_rows,
                "n_chips": self.n_chips,
                "peak_flops_per_chip": self.peak_flops,
                "steady_window_s": round(steady_wall, 6),
            }

    def drain_stats(self) -> Dict[str, float]:
        """`goodput/*` floats for the tracker, logged every stats step
        alongside the timeline's `timing/*`."""
        snap = self.snapshot()
        out: Dict[str, float] = {
            **{f"goodput/{k}": snap[k]
               for k in ("mfu", "mfu_overall") if k in snap},
            "goodput/tokens_per_sec_per_chip":
                snap["tokens_per_sec_per_chip"],
            "goodput/samples_per_sec_per_chip":
                snap["samples_per_sec_per_chip"],
            "goodput/wall_s": snap["wall_s"],
            "goodput/wasted_s": snap["wasted_s"],
            "goodput/fraction": snap["goodput_fraction"],
        }
        for cause, secs in snap["seconds"].items():
            out[f"goodput/{cause.replace('/', '_')}_s"] = secs
        return out

    def render_prometheus(self, ns: str = "trlx_tpu_goodput") -> str:
        """Prometheus text-format gauges for /metrics concatenation."""
        snap = self.snapshot()
        lines = [
            f"# HELP {ns}_seconds_total wall seconds attributed by cause",
            f"# TYPE {ns}_seconds_total gauge",
        ]
        for cause, secs in snap["seconds"].items():
            lines.append(f'{ns}_seconds_total{{cause="{cause}"}} {secs}')
        for key, prom in (
            ("mfu", "mfu"),
            ("mfu_overall", "mfu_overall"),
            ("tokens_per_sec_per_chip", "tokens_per_second_per_chip"),
            ("samples_per_sec_per_chip", "samples_per_second_per_chip"),
            ("wall_s", "wall_seconds"),
            ("wasted_s", "wasted_seconds"),
            ("goodput_fraction", "fraction"),
        ):
            if key not in snap:  # no MFU without a peak row for the device
                continue
            lines.append(f"# HELP {ns}_{prom} goodput ledger {key}")
            lines.append(f"# TYPE {ns}_{prom} gauge")
            lines.append(f"{ns}_{prom} {snap[key]}")
        return "\n".join(lines) + "\n"

    def write(self, path: str,
              extra: Optional[Dict[str, Any]] = None) -> str:
        """Atomic-ish goodput.json dump (tmp + rename so a crash mid-write
        never leaves a truncated artifact — this runs every stats step).
        `extra` sections (compile ledger / hbm ledger snapshots) ride the
        same file so one artifact answers "where did the time, compiles,
        and bytes go"."""
        snap = self.snapshot()
        if extra:
            snap.update(extra)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
        return path
