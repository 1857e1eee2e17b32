#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips it asks for, and print its
result as the last line of standard output.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name: the cell in ``BENCHMARK.json``, the configuration in the file it
names, the mix in ``bench/traffic/<traffic>.json`` (whose ``driver`` names
the module of ``bench/harness`` that runs it), and each per-layer metric in
``bench/metrics/<name>.py``. With ``--trace 0`` the line holds the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiled run.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.harness.common import (BENCH, Check, NoChip, check_devices,  # noqa: E402
                                  emit, enable_cache, load_json, log)


def load_benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(bm: Dict[str, Any], name: str):
    cells = {c["name"]: c for c in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    conf = load_json(ROOT / config["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell, conf, mix


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bm, cell: str, driver, run, trace: bool) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    if not trace:
        for m in bm["end_to_end"]:
            if applies(m, cell):
                out[m["name"]] = {"value": driver.END_TO_END[m["name"]](run),
                                  "unit": m["unit"]}
        return out
    for m in bm["per_layer"]:
        if not applies(m, cell):
            continue
        value = load_metric(m["name"]).read(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bm = load_benchmark()
    cell, conf, mix = find_cell(bm, args.workload)
    try:
        devices = check_devices(cell["chips"])
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    log(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_cache()}")
    driver = importlib.import_module(f"bench.harness.{mix['driver']}")
    run = driver.run(conf=conf, mix=mix, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     t_process=T_PROCESS, devices=devices)
    metrics = metrics_of(bm, cell["name"], driver, run, bool(args.trace))
    device = dict(run.device)
    breakdown = None
    if args.trace:
        from bench.harness import trace as tr
        busy, window = tr.busy_and_window_s(run.trace)
        device["busy_s"], device["window_s"] = busy, window
        breakdown = {"device_ops": tr.top_device_ops(run.trace),
                     "idle_gaps": tr.idle_gaps(run.trace)}
    checks: List[Check] = run.checks
    emit(checks=checks, attempted=run.attempted, failed=run.failed,
         metrics=metrics, device=device, breakdown=breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
