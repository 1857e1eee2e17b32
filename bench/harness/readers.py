"""Arithmetic the per-layer metric readers share: kernel roofline shares
from the counts in ``bench/counts`` and the peaks table, model FLOP
utilisation of an engine call or a train step, the exposed share of a
train step's collectives, and the device's idle share."""
from __future__ import annotations

import importlib.util
from typing import Callable, List, Optional

from .common import BENCH
from .peaks import peaks_for
from .trace import busy_and_window_s, call_device_s, kernel_s, self_ns


def load_count(name: str):
    path = BENCH / "counts" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_count_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _traced_calls(run, span: str):
    """(call record, its device ops) for every engine call of ``span``
    wholly inside the traced window."""
    if run.trace is None:
        return []
    by_no = {c.call: c for c in run.calls}
    out = []
    for (name, n), ops in sorted(run.trace.calls.items()):
        if name == span and n in by_no and ops:
            out.append((by_no[n], ops))
    return out


def roofline_share(run, kernel: str) -> Optional[float]:
    """Least time the kernel's counted work takes at the chip's peaks,
    over the device time of its events, in %; None where the trace holds
    none of its events."""
    count = load_count(kernel)
    peaks = peaks_for(run.device["kind"])
    least = spent = 0.0
    for call, ops in _traced_calls(run, count.SPAN):
        t = kernel_s(ops, count.MATCH)
        if t <= 0:
            continue
        flops, bytes_ = count.count(run.cfg, call.lengths)
        least += max(flops / peaks["bf16_flops"],
                     bytes_ / peaks["hbm_bytes_per_s"])
        spent += t
    return 100.0 * least / spent if spent > 0 else None


def call_mfu(run, span: str, flops_of: Callable[[object, List[int]], float]
             ) -> Optional[float]:
    """Model FLOPs of the traced engine calls of ``span`` over their
    device time at the chip's bf16 peak, in %."""
    peaks = peaks_for(run.device["kind"])
    flops = spent = 0.0
    for call, ops in _traced_calls(run, span):
        flops += flops_of(run.cfg, call.lengths)
        spent += call_device_s(ops)
    return 100.0 * flops / (spent * peaks["bf16_flops"]) if spent > 0 \
        else None


def _step_runs(run, span: str):
    """Program runs of each traced call of ``span`` (calls wholly inside
    the window), with the call's ops."""
    if run.trace is None:
        return []
    return [(mods, run.trace.calls.get(key, []))
            for key, mods in sorted(run.trace.call_modules.items())
            if key[0] == span and mods]


def step_mfu(run, span: str) -> Optional[float]:
    """Model FLOPs of the traced steps over their programs' device time
    (each step's runs averaged over the chips that ran them) times the
    chips and the bf16 peak, in %."""
    peaks = peaks_for(run.device["kind"])
    steps = _step_runs(run, span)
    spent = sum(sum(m.end - m.start for m in mods)
                / len({m.device for m in mods}) for mods, _ in steps) / 1e9
    if spent <= 0:
        return None
    return 100.0 * len(steps) * run.flops_per_step / (
        spent * run.chips * peaks["bf16_flops"])


COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(op) -> bool:
    """A collective, or the start or wait of an async one, by its HLO
    opcode or, for a fusion the compiler named after one, its name."""
    return any(op.opcode.startswith(c) or op.name.startswith(c)
               for c in COLLECTIVES)


def exposed_collective_share(run, span: str) -> Optional[float]:
    """Self time of the collective ops of the traced steps (no other op
    runs on that chip meanwhile) over the steps' program time, in %."""
    steps = _step_runs(run, span)
    spent = sum(m.end - m.start for mods, _ in steps for m in mods)
    if spent <= 0:
        return None
    exposed = 0
    for _, ops in steps:
        exposed += sum(t for o, t in zip(ops, self_ns(ops))
                       if is_collective(o))
    return 100.0 * exposed / spent


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    busy, window = busy_and_window_s(run.trace)
    if busy <= 0 or window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
