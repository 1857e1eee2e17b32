"""Pieces every cell's driver shares: paths, the device check, the compile
clock, percentiles, and the result line."""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

BENCH = Path(__file__).resolve().parents[1]          # <checkout>/bench
ROOT = BENCH.parent                                   # <checkout>
OUT = ROOT / ".bench_out"                             # traces (gitignored)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def seed32(seed: int, *salt: int) -> int:
    """A 31-bit seed from any whole number (a run's seed may exceed 2**31),
    one stream per ``salt``."""
    import numpy as np
    s = abs(int(seed))
    entropy = [s % 2**63, s >> 63, int(seed) < 0, *salt]
    return int(np.random.SeedSequence(entropy).generate_state(1)[0]) >> 1


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_devices(chips: int):
    """The TPU devices a cell runs on; raises NoChip without them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> str:
    """JAX's persistent compilation cache inside the checkout (the
    program's own choice of directory), with every program kept, however
    quickly it compiled, so that only a cell's first run compiles."""
    import jax
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts the programs that reach XLA's backend, and how many of them
    came from the persistent cache, process-wide.

    Copied from the bring-up script's clock: ``jax.monitoring`` reports a
    backend compile event for every new program, whether it is compiled
    or loaded from the persistent cache, and a cache-hit event for the
    loaded ones. So ``count`` counts new shapes, ``count - hits`` real
    compiles."""

    _instance: Optional["CompileClock"] = None

    def __init__(self):
        import jax
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_hit)

    @classmethod
    def get(cls) -> "CompileClock":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.count += 1

    def _on_hit(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.hits += 1


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks, as numpy's default; every value counts."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def device_info(devices, *, busy_s: Optional[float] = None,
                window_s: Optional[float] = None) -> Dict[str, Any]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    info: Dict[str, Any] = {"platform": devices[0].platform,
                            "kind": devices[0].device_kind,
                            "count": len(devices),
                            "memory_peak_bytes": max(peaks)}
    if busy_s is not None:
        info["busy_s"] = busy_s
        info["window_s"] = window_s
    return info


class Check:
    """One number the run compares, with its limit: the run is correct
    only while every value is at most its limit."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def emit(*, checks: List[Check], attempted: int, failed: int,
         metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
         breakdown: Optional[Dict[str, Any]] = None) -> bool:
    """Print the checks as the last lines on stderr and the result as the
    last line on stdout, the checks under their own key, last."""
    correct = bool(checks) and all(c.ok for c in checks)
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}")
    line: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                            "failed": failed, "metrics": metrics,
                            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return correct


def reference_conf(conf: Dict[str, Any]) -> str:
    """The configuration as a plain reference is handed it: every
    top-level key but the program's own, as sorted JSON, which a cache of
    the reference's compiled functions can take as its key."""
    return json.dumps({k: v for k, v in conf.items() if k != "program"},
                      sort_keys=True)


def now() -> float:
    return time.monotonic()
