#!/usr/bin/env python3
"""Measurements that set a serving cell's fixed numbers, made once on the
chip and never by the benchmark's own runs.

    python bench/calibrate.py sweep  --workload W --rates 2,3,4 --seconds 40 \\
        [--seeds 1,2,3 --control-seeds 1,2,3 --drain 30]
    python bench/calibrate.py limits --workload W --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 15
    python bench/calibrate.py limits --workload T --seeds 1,2,3 \\
        --control-seeds 1,2 --fault-seeds 1,2

``sweep`` runs the cell's traffic at each offered rate, in one process,
and prints how the slot scheduler's wait grows over the window. A rate
holds where every request finishes within the drain and the mean wait of
the last third of the requests (by due time) is at most twice that of the
first third, or under ``BACKLOG_FLOOR_S``: a request that waits less than
that waited for the decode steps in flight, not for a slot, so the ratio
of two such waits says nothing of a backlog. The knee is the highest rate
that holds on every seed swept. Each rate also prints every tail at
several percentiles and the run's checks. ``limits`` runs the cell on
each seed and prints the widest logit gap of the served tokens under the
reference, and, for the control seeds (in ``sweep`` too), the widest gap
of the tokens the float8 reference puts first on the same prompts and
tokens, with the verdict the cell's check gives on it: the lower and
upper readings the cell's limit is set between. For a training cell,
``limits`` needs no window: on each seed it takes the program's first steps and the
reference's, and prints the numbers the cell compares; on the control
seeds, the same numbers of the float8 reference put in the program's
place; on the fault seeds, those of the program fed batches with half of
their rows left out of the loss (the mean taken over the rest), which is
also what a 2-way data axis reads with its exchange left out. Beside the
control's and the fault's numbers it prints whether the cell's checks
would pass them.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.harness.common import (check_devices, enable_cache, log,  # noqa: E402
                                  percentile)
from bench.run import find_cell, load_benchmark  # noqa: E402

BACKLOG_FLOOR_S = 0.25


def _control_hook(conf, mix, serve, controls):
    """An ``after`` hook that reads, on the control seeds, the widest gap
    of the tokens the float8 reference puts first, and the verdict the
    cell's own check gives on it."""
    def hook(seed):
        def after(params, sample, requests):
            if seed not in controls:
                return {}
            gaps = serve.logit_gaps(params, conf, mix,
                                    [(q["prompt"], q["tokens"])
                                     for q in sample], control=True)
            check = serve.gap_check(conf, max(gaps))
            return {"control_gap": check.value, "control_tokens": len(gaps),
                    "control_correct": check.ok}
        return after
    return hook


def _sweep(args, conf, mix, devices, serve) -> None:
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    hook = _control_hook(conf, mix, serve, controls)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        seed = seeds[i % len(seeds)] if seeds else args.seed
        m = copy.deepcopy(mix)
        m["rate_rps"] = rate
        if args.drain is not None:
            m["drain_s"] = args.drain
        t0 = time.monotonic()
        run = serve.run(conf=conf, mix=m, seed=seed, seconds=args.seconds,
                        trace=False, t_process=time.monotonic(),
                        devices=devices, hooks={"after": hook(seed)})
        reqs = sorted(run.requests, key=lambda q: q["due"])
        wait = [((q["received"] or run.deadline) - q["due"]) for q in reqs]
        third = max(1, len(wait) // 3)
        first, last = sum(wait[:third]) / third, sum(wait[-third:]) / third
        toks = sum(len(q["tokens"]) for q in reqs)
        out = {"rate_rps": rate, "seed": seed, "requests": len(reqs),
               **{f"ttft_p{q}_ms": percentile(serve.ttft_ms(run), q)
                  for q in serve.PERCENTILES},
               **{f"itl_p{q}_ms": percentile(serve.itl_ms(run), q)
                  for q in serve.PERCENTILES},
               "propagation_p95_ms": percentile(serve.propagation_ms(run),
                                                95),
               "wait_first_third_s": first,
               "wait_last_third_s": last,
               "holds": all(q["finished"] for q in reqs) and (
                   last <= 2 * first or last < BACKLOG_FLOOR_S),
               "tokens_per_s": toks / (max(q["stamps"][-1] for q in reqs
                                           if q["stamps"]) - run.w0),
               "setup_s": run.setup_s,
               "memory_peak_bytes": run.device["memory_peak_bytes"],
               **{c.name: c.value for c in run.checks},
               "correct": all(c.ok for c in run.checks),
               **{k: run.extra[k] for k in ("sampled_tokens", "reference_s",
                                            "control_gap", "control_correct")
                  if k in run.extra},
               "run_s": time.monotonic() - t0}
        print(json.dumps(out), flush=True)


def _limits(args, conf, mix, devices, serve) -> None:
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    hook = _control_hook(conf, mix, serve, controls)
    for seed in [int(s) for s in args.seeds.split(",")]:
        run = serve.run(conf=conf, mix=mix, seed=seed, seconds=args.seconds,
                        trace=False, t_process=time.monotonic(),
                        devices=devices, hooks={"after": hook(seed)})
        out = {"seed": seed, **{c.name: c.value for c in run.checks},
               "tokens": run.extra["sampled_tokens"],
               "control_gap": run.extra.get("control_gap"),
               "control_correct": run.extra.get("control_correct")}
        print(json.dumps(out), flush=True)


def _train_limits(args, conf, mix, devices) -> None:
    import gc
    from bench.harness import train
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = {int(s) for s in args.fault_seeds.split(",") if s}
    n = int(conf["check"]["first_steps"])
    prog = train.build(conf, mix, devices)

    def half(batch):
        b = dict(batch)
        b["mask"] = batch["mask"].copy()
        b["mask"][batch["mask"].shape[0] // 2:] = 0.0
        return b

    def program(seed, alter=lambda b: b):
        with prog.context():
            feed = train.Feed(lambda i: alter(train.batch_of(
                mix, prog.cfg, seed, i)), prog.shardings["batch"])
            try:
                got = train.first_steps(prog, conf, seed, feed, n)
            finally:
                feed.stop()
        prog.params = prog.opt = None
        gc.collect()
        return got

    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        mine = program(seed)
        half_batch = program(seed, half) if seed in faults else None
        ref = train.reference_readings(conf, mix, seed, devices, n)
        out = {"seed": seed, **train.compare(mine, ref),
               "losses": mine.losses, "ref_losses": ref.losses}
        if half_batch is not None:
            out["half_batch"] = train.compare(half_batch, ref)
            out["half_batch_correct"] = all(
                c.ok for c in train.reading_checks(conf, out["half_batch"]))
        if seed in controls:
            low = train.reference_readings(conf, mix, seed, devices, n,
                                           low=True)
            out["control"] = train.compare(low, ref)
            out["control_correct"] = all(
                c.ok for c in train.reading_checks(conf, out["control"]))
        out["run_s"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--drain", type=float, default=None,
                    help="sweep: seconds to wait for requests after the "
                         "window (default the mix's)")
    args = ap.parse_args()
    cell, conf, mix = find_cell(load_benchmark(), args.workload)
    devices = check_devices(cell["chips"])
    log(f"compile cache {enable_cache()}")
    if mix["driver"] == "train":
        _train_limits(args, conf, mix, devices)
        return 0
    from bench.harness import serve
    if args.what == "sweep":
        _sweep(args, conf, mix, devices, serve)
    else:
        _limits(args, conf, mix, devices, serve)
    return 0


if __name__ == "__main__":
    sys.exit(main())
