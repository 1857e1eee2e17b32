#!/usr/bin/env python3
"""Time every step of a training cell's window on the host, and line the
steps up with the chip's trace.

    python bench/step_spans.py --workload internvl2-2b-fsdp2x2.train4k \\
        --seed 1 [--seconds 51] [--out FILE]

One process builds the cell's program and state as ``bench/run.py`` does
(its first steps included), then drives its train window three times,
each ``--seconds`` long: ``plain`` as the timed run does it; ``spans``
with a ``train.step`` span per step recorded into the program's
``Tracer``; ``traced`` with the spans and the profiler over the middle
``trace_s`` of the window, as a ``--trace 1`` run has it. For each
window it prints the rate; for a window with spans, each step's span and
whether it lies inside the profiled part; for the traced one, each step's
program on the first chip, how long after it the step's span ended, idle
gaps by host span (``train.step`` included), and the device ops by the
program's scopes (``bench/harness/scopes.py``). The whole report goes to
``--out`` as JSON. It needs the cell's chips, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.harness.common import (OUT, NoChip, check_devices,  # noqa: E402
                                  enable_cache, log, now)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=str(OUT / "step_spans.json"))
    args = ap.parse_args(argv)

    from bench.run import find_cell, load_benchmark
    cell, conf, mix = find_cell(load_benchmark(), args.workload)
    if mix["driver"] != "train":
        raise SystemExit(f"{args.workload} is not a training cell")
    try:
        devices = check_devices(cell["chips"])
    except NoChip as e:
        log(f"step_spans: {e}")
        return 2
    log(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_cache()}")
    from bench.harness import scopes, steps, train
    from bench.harness.trace import idle_gaps, read_xplane
    from repro.core.trace import Tracer

    prog = train.build(conf, mix, devices)
    report = {"workload": args.workload, "seed": args.seed, "windows": {}}
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    with prog.context():
        feed = train.Feed(lambda i: train.batch_of(mix, prog.cfg, args.seed,
                                                    i),
                          prog.shardings["batch"])
        try:
            t = now()
            train.first_steps(prog, conf, args.seed, feed,
                              int(conf["check"]["first_steps"]))
            log(f"setup: first steps in {now() - t:.3f} s")
            for kind in ("plain", "spans", "traced"):
                tracer = Tracer() if kind in ("spans", "traced") else None
                trace_s = float(mix.get("trace_s", 10.0)) \
                    if kind == "traced" else 0.0
                win = steps.window(prog, feed, args.seconds, trace_s=trace_s,
                                   tracer=tracer)
                rate = win.steps * tokens / win.seconds
                log(f"window {kind}: {win.steps} steps in {win.seconds:.3f} "
                    f"s, {rate:.2f} tokens/s")
                rep = {"steps": win.steps, "seconds": win.seconds,
                       "tokens_per_s": rate}
                if tracer is not None:
                    spans = [s for s in tracer.spans()
                             if s["name"] == steps.STEP_SPAN]
                    rep["record_us"] = 1e6 * statistics.fmean(win.record_s) \
                        if win.record_s else None
                    trace = None
                    if win.xplane is not None:
                        t = time.monotonic()
                        trace = read_xplane(win.xplane)
                        rep["read_s"] = time.monotonic() - t
                        run = types.SimpleNamespace(trace=trace, extra={})
                        att = scopes.attribute(run, xplane=win.xplane)
                        if att is not None:
                            rep["scopes"] = {
                                "classify_s": att.seconds,
                                "scoped_share": att.scoped_share(),
                                "recompute_share": att.share(scopes.remat),
                                "attn_core_share": att.share(
                                    scopes.in_scope("attn/core")),
                                "loss_head_share": att.share(
                                    scopes.in_scope("loss")),
                                "device_ops": scopes.scoped_device_ops(
                                    att, trace, n=40)}
                        trace.spans += steps.on_trace_clock(
                            spans, win.anchor_ns, trace)
                        rep["idle_gaps"] = idle_gaps(trace)
                    rep.update(steps.step_report(spans, win, trace))
                    log(f"window {kind}: Tracer.record "
                        f"{rep['record_us']} us a step; summary "
                        f"{json.dumps(rep['summary'])}")
                    if "idle_gaps" in rep:
                        log(f"window {kind}: idle gaps {rep['idle_gaps']}")
                report["windows"][kind] = rep
        finally:
            feed.stop()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    log(f"report: {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
