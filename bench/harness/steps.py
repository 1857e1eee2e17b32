"""The program's host spans on the profile's clock, and a train window that
records one span per step.

``repro.core.trace.Tracer`` is the program's one span system; its spans run
on ``time.monotonic``, which the profile's clock is not. The profiler's
``bench.window`` span gives the two clocks one shared instant:
:class:`AnchoredProfiler` reads ``time.monotonic_ns()`` as it opens that span,
and :func:`on_trace_clock` moves every ``Tracer`` span onto the profile's
clock through it. Added to ``trace.spans``, the mapped spans take part in
``idle_gaps`` like the harness's own ``bench.*`` spans.

:func:`window` is the train window of ``train.run`` (the same feed, call
and at most two steps in flight, the profiler over its middle part when
asked), and with a ``Tracer`` it records one ``train.step`` span per step
with ``Tracer.record``, so no context manager runs per step: step n's span
runs from the host's return of step n-1's loss to that of step n's, and
holds the step number and the feed wait and dispatch of the step the host
queued meanwhile. The spans cover the whole window, not only the profiled
part.
"""
from __future__ import annotations

import statistics
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

from .common import OUT, now
from .trace import Profiler, Span, Trace

STEP_SPAN = "train.step"


class AnchoredProfiler(Profiler):
    """The harness's profiler, which also reads the monotonic clock the
    moment its ``bench.window`` span opens."""

    anchor_ns: Optional[int] = None

    def start(self) -> None:
        super().start()
        self.anchor_ns = time.monotonic_ns()


def on_trace_clock(spans: List[Dict[str, Any]], anchor_ns: int,
                   trace: Trace) -> List[Span]:
    """``Tracer`` spans (monotonic seconds) on the profile's clock, given
    the monotonic time at which ``bench.window`` opened."""
    w0 = trace.window[0]
    return [Span(s["name"], w0 + round(s["start"] * 1e9) - anchor_ns,
                 w0 + round(s["end"] * 1e9) - anchor_ns,
                 dict(s.get("attrs", {}))) for s in spans]


@dataclass
class Window:
    steps: int                       # dispatched, all done
    seconds: float                   # window open -> last step done
    losses: List[float]
    opened: float                    # monotonic time the window opened
    profiled: Optional[tuple] = None  # monotonic (start, stop) of profiling
    xplane: Optional[Path] = None
    anchor_ns: Optional[int] = None
    record_s: List[float] = field(default_factory=list)  # Tracer.record


def window(prog, feed, seconds: float, *, trace_s: float = 0.0,
           tracer=None) -> Window:
    """Steps of ``prog`` back to back for ``seconds``, as ``train.run``'s
    window drives them; the profiler over the middle ``trace_s`` seconds
    when that is positive; a ``train.step`` span per step into ``tracer``
    when one is given."""
    import jax
    w0 = now()
    w1 = w0 + seconds
    prof = None
    span = min(seconds, trace_s)
    t_on = w0 + (seconds - span) / 2
    out = Window(0, 0.0, [], w0)
    prev = met = batch = None
    n = 0
    t_loss = w0
    while now() < w1:
        if span > 0 and prof is None and now() >= t_on:
            prof = AnchoredProfiler(OUT / "trace")
            prof.start()
            out.anchor_ns, p0 = prof.anchor_ns, now()
        if prof is not None and out.xplane is None and now() >= t_on + span:
            p1 = now()
            out.xplane, out.profiled = prof.stop(), (p0, p1)
        t0 = now()
        batch = feed.get()
        t1 = now()
        with jax.profiler.TraceAnnotation("bench.train", call=n):
            prog.params, prog.opt, met = prog.step(prog.params, prog.opt,
                                                   batch)
        t2 = now()
        if prev is not None:        # at most two steps in flight
            out.losses.append(float(prev["loss"]))
            t3 = now()
            if tracer is not None:
                tracer.record(STEP_SPAN, t_loss, t3, attrs={
                    "step": n - 1, "feed_s": t1 - t0, "dispatch_s": t2 - t1})
                out.record_s.append(now() - t3)
            t_loss = t3
        prev, n = met, n + 1
    if prev is not None:
        out.losses.append(float(prev["loss"]))
        if tracer is not None:
            tracer.record(STEP_SPAN, t_loss, now(), attrs={"step": n - 1})
    jax.block_until_ready((prog.params, prog.opt))
    out.steps, out.seconds = n, now() - w0
    if prof is not None and out.xplane is None:
        p1 = now()
        out.xplane, out.profiled = prof.stop(), (p0, p1)
    return out


def step_report(spans: List[Dict[str, Any]], win: Window,
                trace: Optional[Trace] = None) -> Dict[str, Any]:
    """Each step's span (seconds from window open, length, feed wait,
    dispatch) and whether it lies wholly inside the profiled part; for
    those, with the trace, the length of the step's program on the first
    chip, the chip's idle time before it, and how long after the program
    ended the span ended (``lag_ms``)."""
    rows = []
    p0, p1 = win.profiled or (None, None)
    mapped = {}
    if trace is not None and win.anchor_ns is not None:
        mapped = {int(s.stats["step"]): s
                  for s in on_trace_clock(spans, win.anchor_ns, trace)}
    dev0 = min(trace.devices) if trace is not None and trace.devices else None
    prev_end = None
    for s in spans:
        a = s.get("attrs", {})
        n = int(a["step"])
        row = {"step": n, "at_s": s["start"] - win.opened,
               "span_s": s["end"] - s["start"],
               "feed_s": a.get("feed_s"), "dispatch_s": a.get("dispatch_s"),
               "profiled": p0 is not None and p0 <= s["start"]
               and s["end"] <= p1}
        mods = trace.call_modules.get(("bench.train", n), []) \
            if trace is not None else []
        mine = [m for m in mods if m.device == dev0]
        if row["profiled"] and mine and n in mapped:
            m = mine[-1]
            row["program_s"] = (m.end - m.start) / 1e9
            row["lag_ms"] = (mapped[n].end - m.end) / 1e6
            if prev_end is not None:
                row["idle_before_ms"] = (m.start - prev_end) / 1e6
            prev_end = m.end
        rows.append(row)
    return {"steps": rows, "summary": summarize(rows)}


def summarize(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    def stats(xs: List[float]) -> Dict[str, float]:
        if not xs:
            return {}
        q = statistics.quantiles(xs, n=10) if len(xs) > 1 else xs * 9
        return {"n": len(xs), "median": statistics.median(xs), "p10": q[0],
                "p90": q[-1], "min": min(xs), "max": max(xs),
                "mean": statistics.fmean(xs)}

    body = rows[1:]                 # the first span starts at window open
    inside = [r["span_s"] for r in body if r["profiled"]]
    outside = [r["span_s"] for r in body if not r["profiled"]]
    med = statistics.median([r["span_s"] for r in body]) if body else 0.0
    return {
        "span_s_profiled": stats(inside),
        "span_s_outside": stats(outside),
        "slow_steps": [r["step"] for r in body if r["span_s"] > 1.05 * med],
        "feed_s": stats([r["feed_s"] for r in body
                         if r["feed_s"] is not None]),
        "dispatch_s": stats([r["dispatch_s"] for r in body
                             if r["dispatch_s"] is not None]),
        "program_s": stats([r["program_s"] for r in body
                            if "program_s" in r]),
        "lag_ms": stats([r["lag_ms"] for r in body if "lag_ms" in r]),
        "idle_before_ms": stats([r["idle_before_ms"] for r in body
                                 if "idle_before_ms" in r]),
    }
