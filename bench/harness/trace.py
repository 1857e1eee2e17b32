"""The profiler window of a ``--trace 1`` run, and its reduction from the
``.xplane.pb`` to device busy time, per-call device ops and idle gaps.

The harness writes its own host spans with ``jax.profiler.TraceAnnotation``
(``bench.window`` around the traced window, ``bench.admit`` and
``bench.step`` around each engine call with its call number, ``bench.submit``
and ``bench.cp`` from the load generators, ``bench.train`` around each
train step and ``bench.feed`` around each batch put on the devices). Host
spans and device ops are on the profiler's one clock, so a device op
belongs to the engine call whose span it starts in, and an idle gap to the
host span it falls in.
"""
from __future__ import annotations

import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

Interval = Tuple[int, int]               # [start_ns, end_ns)


@dataclass
class DeviceOp:
    name: str                            # HLO instruction, e.g. fusion.12
    opcode: str                          # e.g. fusion, copy, custom-call
    start: int
    end: int
    device: int


@dataclass
class Span:
    name: str
    start: int
    end: int
    stats: Dict[str, Any]


@dataclass
class Module:
    """One execution of a compiled program on a device."""
    name: str
    run_id: int
    start: int
    end: int
    device: int
    enqueued: Optional[int] = None       # host time the run was enqueued


@dataclass
class Trace:
    window: Interval
    ops: List[DeviceOp]
    spans: List[Span]
    devices: List[int]
    modules: List[Module] = field(default_factory=list)
    calls: Dict[Tuple[str, int], List[DeviceOp]] = field(default_factory=dict)
    call_modules: Dict[Tuple[str, int], List[Module]] = field(
        default_factory=dict)


class Profiler:
    """Starts and stops the JAX profiler around a window, with the
    Python tracer off (it would bloat the trace and slow the host)."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)
        self._window = None

    def start(self) -> None:
        import jax
        if self.out_dir.exists():
            shutil.rmtree(self.out_dir)
        self.out_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def stop(self) -> Path:
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        found = sorted(self.out_dir.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return found[-1]


def parse_op(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event, whose name is
    the HLO text ``%name = type opcode(operands), ...``."""
    if not text.startswith("%") or " = " not in text:
        return text, ""
    name, rest = text[1:].split(" = ", 1)
    if rest.startswith("("):             # tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return name, rest.strip().split("(", 1)[0]


def _stats(ev) -> Dict[str, Any]:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:
        return {}


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"             # host event, carries the run_id
CALL_SPANS = ("bench.admit", "bench.step", "bench.train")


def read_xplane(path: Path) -> Trace:
    """Device ops and program runs of each TPU plane, the host's enqueue
    of each run, and the harness's ``bench.*`` host spans, from one
    profile."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: List[DeviceOp] = []
    spans: List[Span] = []
    modules: List[Module] = []
    enqueued: Dict[Tuple[int, int], int] = {}
    devices: List[int] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            devices.append(dev)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        start = int(ev.start_ns)
                        name, opcode = parse_op(ev.name)
                        ops.append(DeviceOp(name, opcode, start,
                                            start + int(ev.duration_ns), dev))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        start = int(ev.start_ns)
                        run = _stats(ev).get("run_id")
                        modules.append(Module(
                            ev.name, -1 if run is None else int(run), start,
                            start + int(ev.duration_ns), dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        start = int(ev.start_ns)
                        spans.append(Span(ev.name, start,
                                          start + int(ev.duration_ns),
                                          _stats(ev)))
                    elif ev.name == ENQUEUE:
                        st = _stats(ev)
                        if "run_id" in st:
                            enqueued[(int(st.get("device_ordinal", 0)),
                                      int(st["run_id"]))] = int(ev.start_ns)
    windows = [s for s in spans if s.name == "bench.window"]
    if not windows:
        raise ValueError(f"{path}: no bench.window span")
    w = windows[0]
    ops.sort(key=lambda o: (o.device, o.start))
    spans.sort(key=lambda s: s.start)
    for mod in modules:
        mod.enqueued = enqueued.get((mod.device, mod.run_id))
    modules.sort(key=lambda mod: (mod.device, mod.start))
    trace = Trace((w.start, w.end), ops, spans, sorted(devices), modules)
    trace.calls = ops_by_call(trace)
    return trace


def module_ops(trace: Trace, mod: Module, keys: List[Tuple[int, int]]
               ) -> List[DeviceOp]:
    """The ops that ran inside one program run (same device, same clock);
    ``keys`` are the sorted ops' (device, start)."""
    import bisect
    lo = bisect.bisect_left(keys, (mod.device, mod.start))
    hi = bisect.bisect_right(keys, (mod.device, mod.end))
    return [o for o in trace.ops[lo:hi] if o.end <= mod.end]


def _containing(spans: List[Span], t: int) -> List[Span]:
    return [s for s in spans if s.start <= t <= s.end]


def ops_by_call(trace: Trace) -> Dict[Tuple[str, int], List[DeviceOp]]:
    """Device ops of the program runs each engine call or train step
    enqueued, for the calls whose span lies wholly inside the traced window
    (the runs themselves go to ``trace.call_modules``).

    Host and device clocks differ by a millisecond or two, so a run is
    tied to its call through the host's enqueue of it, not through the
    device's time. An enqueue falls inside the span of the calling thread,
    and maybe inside a span another thread has open: of those, the span of
    the run's own kind, and of these the one that started last (a call
    enqueues its first program right after it starts). A program is of the
    admission kind where most of its runs were enqueued inside admission
    spans."""
    calls = [s for s in trace.spans if s.name in CALL_SPANS
             and "call" in s.stats]
    inside: Dict[str, List[set]] = {}
    for mod in trace.modules:
        if mod.enqueued is None:
            continue
        inside.setdefault(mod.name, []).append(
            {s.name for s in _containing(calls, mod.enqueued)})

    def kind_of(v: List[set]) -> str:
        for name in ("bench.admit", "bench.train"):
            if sum(name in n for n in v) * 2 > len(v):
                return name
        return "bench.step"

    kind = {name: kind_of(v) for name, v in inside.items()}
    w0, w1 = trace.window
    keys = [(o.device, o.start) for o in trace.ops]
    out: Dict[Tuple[str, int], List[DeviceOp]] = {}
    for s in calls:
        if w0 <= s.start and s.end <= w1:
            out[(s.name, int(s.stats["call"]))] = []
            trace.call_modules[(s.name, int(s.stats["call"]))] = []
    for mod in trace.modules:
        if mod.enqueued is None:
            continue
        cands = _containing(calls, mod.enqueued)
        same = [s for s in cands if s.name == kind[mod.name]] or cands
        if not same:
            continue
        s = max(same, key=lambda x: x.start)
        key = (s.name, int(s.stats["call"]))
        if key in out:
            out[key] += module_ops(trace, mod, keys)
            trace.call_modules[key].append(mod)
    return out


def union(intervals: List[Interval], clip: Optional[Interval] = None
          ) -> List[Interval]:
    """Merged, sorted, disjoint intervals, optionally clipped."""
    ivs = sorted(intervals)
    out: List[Interval] = []
    for a, b in ivs:
        if clip is not None:
            a, b = max(a, clip[0]), min(b, clip[1])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def covered_ns(intervals: List[Interval]) -> int:
    return sum(b - a for a, b in union(intervals))


def busy_ns(trace: Trace, device: int) -> int:
    """Union of the device's op intervals inside the window."""
    return sum(b - a for a, b in union([(o.start, o.end) for o in trace.ops
                                        if o.device == device],
                                       clip=trace.window))


def busy_and_window_s(trace: Trace) -> Tuple[float, float]:
    """Device busy seconds in the window, averaged over the traced chips,
    and the window's length."""
    w = (trace.window[1] - trace.window[0]) / 1e9
    if not trace.devices:
        return 0.0, w
    busy = sum(busy_ns(trace, d) for d in trace.devices) / len(trace.devices)
    return busy / 1e9, w


_SUFFIX = re.compile(r"\.\d+$")


def op_kind(op: DeviceOp) -> str:
    """An op's instruction name without its number (``fusion.12`` ->
    ``fusion``), so that the breakdown sums like ops."""
    return _SUFFIX.sub("", op.name) or op.name


def self_ns(ops: List[DeviceOp]) -> List[int]:
    """Each op's duration less the ops nested in it (a ``while`` holds its
    body's ops on the same line), so that no time counts twice."""
    out = [o.end - o.start for o in ops]
    stack: List[int] = []
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].device, ops[i].start, -ops[i].end))
    for i in order:
        o = ops[i]
        while stack and (ops[stack[-1]].device != o.device
                         or ops[stack[-1]].end <= o.start):
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            out[stack[-1]] -= o.end - o.start
        stack.append(i)
    return out


def top_device_ops(trace: Trace, n: int = 10) -> List[List[Any]]:
    """Device seconds (self time, averaged over the traced chips) by the
    engine call an op ran in and the op's kind, largest first."""
    call_of: Dict[int, str] = {}
    for (span, _), ops in trace.calls.items():
        for o in ops:
            call_of[id(o)] = span.split(".", 1)[1]
    own = self_ns(trace.ops)
    tot: Dict[str, int] = {}
    w0, w1 = trace.window
    for o, t in zip(trace.ops, own):
        if o.start < w0 or o.start >= w1:
            continue
        k = f"{call_of.get(id(o), 'other')}:{op_kind(o)}"
        tot[k] = tot.get(k, 0) + t
    nd = max(1, len(trace.devices))
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / nd] for k, v in top]


def idle_gaps(trace: Trace, n: int = 10) -> List[List[Any]]:
    """Idle time of the first traced chip, summed by the harness span the
    host was in at the middle of each gap (``idle`` where it was in none),
    longest first."""
    if not trace.devices:
        return []
    dev = trace.devices[0]
    busy = union([(o.start, o.end) for o in trace.ops if o.device == dev],
                 clip=trace.window)
    gaps: List[Interval] = []
    cur = trace.window[0]
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < trace.window[1]:
        gaps.append((cur, trace.window[1]))
    spans = [s for s in trace.spans if s.name != "bench.window"]
    tot: Dict[str, int] = {}
    for a, b in gaps:
        mid = (a + b) // 2
        names = sorted({s.name for s in spans if s.start <= mid < s.end})
        key = "+".join(names) if names else "idle"
        tot[key] = tot.get(key, 0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def call_device_s(ops: List[DeviceOp]) -> float:
    return covered_ns([(o.start, o.end) for o in ops]) / 1e9


def kernel_s(ops: List[DeviceOp], opcode: str) -> float:
    """Summed device time of the ops of ``opcode`` among ``ops``."""
    return sum(o.end - o.start for o in ops if o.opcode == opcode) / 1e9
