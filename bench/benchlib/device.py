"""The device gate, the compile cache, the compile log and the peaks."""

import os
import time

from benchlib.files import BENCH_DIR, load_json


def setup_compile_cache() -> str:
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else at
    one fixed path inside the checkout (the path is part of the key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        BENCH_DIR, "_cache", "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it. Anything but a TPU with the chips the
    cell asks for ends the run with no result (the rehearsal excepted, which
    prints no device metric)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    if rehearse:
        if dev.platform != "cpu":
            raise SystemExit("[bench] --rehearse-cpu is for JAX_PLATFORMS=cpu")
        return info
    if dev.platform != "tpu":
        raise SystemExit(
            f"[bench] FAIL: platform is {dev.platform!r} ({dev.device_kind}), not "
            "'tpu': the benchmark measures nothing off the chip")
    if len(devices) < chips:
        raise SystemExit(
            f"[bench] FAIL: the cell asks for {chips} chip(s), JAX sees {len(devices)}")
    return info


def peaks_for(kind: str) -> dict:
    """The peak row of this device kind; an unknown kind is an error."""
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"[bench] FAIL: no row for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


class CompileLog:
    """Backend compiles of this process, from jax.monitoring: (seconds,
    function name, time.monotonic()) per compile. (After chip_smoke.py.)
    Programs read back from the persistent cache are not backend compiles."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT, MISS = "/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.events = []
        self.cache = {self.HIT: 0, self.MISS: 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_count(self, event, **kwargs):
        if event in self.cache:
            self.cache[event] += 1

    def summary(self) -> str:
        slow = sorted(self.events, reverse=True)[:4]
        return (f"{len(self.events)} programs, {self.seconds():.1f} s; persistent cache "
                f"{self.cache[self.HIT]} hits, {self.cache[self.MISS]} misses; slowest "
                f"{[(n, round(s, 1)) for s, n, _ in slow]}")

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.events.append((duration, kwargs.get("fun_name", "?"), time.monotonic()))

    def between(self, t0: float, t1: float):
        return [(name, round(secs, 3)) for secs, name, at in self.events if t0 <= at <= t1]

    def seconds(self) -> float:
        return sum(e[0] for e in self.events)
