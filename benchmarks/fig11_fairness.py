"""Fig.11: impact of fair queuing on fairness.

Paper §IV-D: 10 greedy tenants issue 900 creations concurrently each; 40
regular tenants issue 10 sequentially each; all weights equal. With WRR fair
queuing the regular tenants' average creation time stays small; with the
shared FIFO they are starved behind the greedy burst.

Beyond the paper, the sweep re-runs the fair configuration across a shard
sweep {1, 2, 4, 8} (tenants hash-partitioned, per-shard WRR) and measures
the **cross-shard isolation win**: shards have disjoint fair queues and
worker pools, so a greedy tenant is confined to the shard its UID hashes
onto — regular tenants on greedy-free shards never even share a queue with
the burst. Each sharded record carries the per-shard tenant map
(``cross_shard_isolation.tenants_per_shard``) and, for both downward queue
wait and end-to-end Ready latency, the regular-tenant split by co-location
with a greedy tenant (``colocated_over_isolated`` mean ratios).

``python -m benchmarks.fig11_fairness [--full]`` appends the sweep to the
tracked ``BENCH_fig11_fairness.json`` history (git sha + timestamp).
"""
from __future__ import annotations

import statistics
import threading
import time
from typing import Dict, List

from repro.core import Namespace
from .common import make_framework, syncer_metrics_summary


def _run_one(fair: bool, greedy: int, greedy_units: int, regular: int,
             regular_units: int, shards: int = 1) -> Dict:
    fw = make_framework(100, fair_queuing=fair, syncer_shards=shards)
    fw.start()
    try:
        gplanes = [fw.add_tenant(f"greedy{i:02d}") for i in range(greedy)]
        rplanes = [fw.add_tenant(f"reg{i:02d}") for i in range(regular)]
        for p in gplanes + rplanes:
            ns = Namespace()
            ns.metadata.name = "bench"
            p.api.create(ns)

        def greedy_submit(plane):
            for j in range(greedy_units):     # burst: all at once
                plane.api.create(fw.make_unit(f"g{j:05d}", "bench", chips=0))

        def regular_submit(plane):
            for j in range(regular_units):    # sequential: wait each Ready
                plane.api.create(fw.make_unit(f"r{j:05d}", "bench", chips=0))
                fw.wait_ready(plane, "bench", f"r{j:05d}", timeout=300)

        threads = [threading.Thread(target=greedy_submit, args=(p,))
                   for p in gplanes]
        threads += [threading.Thread(target=regular_submit, args=(p,))
                    for p in rplanes]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for p in gplanes:
            fw.wait_all_ready(p, "bench", greedy_units, timeout=600)

        def avg_latency(planes) -> Dict[str, float]:
            outs: Dict[str, float] = {}
            for p in planes:
                lats = []
                for u in p.api.list("WorkUnit", "bench"):
                    c = u.status.condition("Ready")
                    if c and c.status == "True":
                        lats.append(c.last_transition_time
                                    - u.metadata.creation_timestamp)
                if lats:
                    outs[p.name] = statistics.mean(lats)
            return outs

        # tenant -> owning downward shard (consistent-hash placement)
        shard_of = {name: shard.shard_id
                    for name, shard in fw.syncer.down.owner.items()}
        # per-tenant DOWNWARD QUEUE WAIT: the layer the paper's fairness
        # mechanism operates on (WRR dispatch delay), and the right place to
        # read cross-shard isolation — end-to-end Ready latency also folds
        # in the shared sequential scheduler, which dominates at this
        # reproduction's syncer throughput and affects every tenant alike
        queue_wait: Dict[str, float] = {}
        for c in fw.syncer.down.shards:
            for tenant, waits in c.queue.per_tenant_wait.items():
                if waits:
                    queue_wait[tenant] = statistics.mean(waits)
        return {"greedy_avg_s": avg_latency(gplanes),
                "regular_avg_s": avg_latency(rplanes),
                "queue_wait_s": queue_wait,
                "shard_of": shard_of,
                "runtime_metrics": syncer_metrics_summary(fw)}
    finally:
        fw.stop()


def _split_means(values: Dict[str, float], shard_of: Dict[str, int],
                 greedy_shards) -> Dict[str, float]:
    colocated = [v for t, v in values.items()
                 if shard_of.get(t) in greedy_shards]
    isolated = [v for t, v in values.items()
                if shard_of.get(t) not in greedy_shards]
    col = statistics.mean(colocated) if colocated else 0.0
    iso = statistics.mean(isolated) if isolated else 0.0
    return {"colocated_n": len(colocated), "isolated_n": len(isolated),
            "colocated_mean_s": col, "isolated_mean_s": iso,
            "colocated_over_isolated": (col / iso) if iso > 0 else 0.0}


def _isolation_split(r: Dict, shards: int) -> Dict:
    """Cross-shard isolation: regular tenants co-located with a greedy
    tenant vs. on greedy-free shards. Shards have disjoint fair queues and
    worker pools, so the isolated group's downward queue wait should not
    see the greedy burst at all; the split is also reported for end-to-end
    Ready latency, where the shared sequential scheduler re-couples the
    groups downstream of the syncer."""
    shard_of = r["shard_of"]
    regular = {t for t in shard_of if not t.startswith("greedy")}
    greedy_shards = {s for t, s in shard_of.items() if t.startswith("greedy")}
    per_shard: Dict[int, Dict[str, int]] = {
        s: {"greedy": 0, "regular": 0} for s in range(shards)}
    for t, s in shard_of.items():
        kind = "greedy" if t.startswith("greedy") else "regular"
        per_shard.setdefault(s, {"greedy": 0, "regular": 0})[kind] += 1
    reg_wait = {t: w for t, w in r["queue_wait_s"].items() if t in regular}
    return {
        "greedy_shards": sorted(greedy_shards),
        "greedy_free_shards": sorted(set(range(shards)) - greedy_shards),
        "tenants_per_shard": {str(s): v for s, v in sorted(per_shard.items())},
        "regular_queue_wait": _split_means(reg_wait, shard_of, greedy_shards),
        "regular_ready_latency": _split_means(r["regular_avg_s"], shard_of,
                                              greedy_shards),
    }


def run(full: bool = False) -> List[Dict]:
    greedy, gu, regular, ru = (10, 900, 40, 10) if full else (4, 150, 12, 5)
    out = []
    # (fair_queuing, syncer_shards): paper's fair-vs-FIFO pair, plus the
    # fair configuration across the shard sweep {1, 2, 4, 8} — fairness is
    # preserved under sharding and greedy tenants are confined to the shard
    # their UID hashes onto (cross-shard isolation)
    for fair, shards in ((True, 1), (False, 1), (True, 2), (True, 4),
                         (True, 8)):
        r = _run_one(fair, greedy, gu, regular, ru, shards=shards)
        reg_lat = list(r["regular_avg_s"].values())
        gr_lat = list(r["greedy_avg_s"].values())
        reg_worst = max(reg_lat) if reg_lat else 0.0
        reg_mean = statistics.mean(reg_lat) if reg_lat else 0.0
        gr_mean = statistics.mean(gr_lat) if gr_lat else 0.0
        qw = r["queue_wait_s"]
        reg_qw = [w for t, w in qw.items() if not t.startswith("greedy")]
        gr_qw = [w for t, w in qw.items() if t.startswith("greedy")]
        reg_qw_mean = statistics.mean(reg_qw) if reg_qw else 0.0
        gr_qw_mean = statistics.mean(gr_qw) if gr_qw else 0.0
        suffix = "" if shards == 1 else f"_shards{shards}"
        rec = {
            "name": f"fig11/{'fair' if fair else 'fifo'}{suffix}",
            "fair_queuing": fair, "syncer_shards": shards,
            "greedy_tenants": greedy, "greedy_units_each": gu,
            "regular_tenants": regular, "regular_units_each": ru,
            "regular_mean_s": reg_mean, "regular_worst_s": reg_worst,
            "greedy_mean_s": gr_mean,
            "regular_queue_wait_mean_s": reg_qw_mean,
            "greedy_queue_wait_mean_s": gr_qw_mean,
            "runtime_metrics": r["runtime_metrics"],
        }
        msg = (f"  fig11 fair={fair} shards={shards}: regular mean "
               f"{reg_mean:.2f}s worst {reg_worst:.2f}s | greedy mean "
               f"{gr_mean:.2f}s | queue wait reg {reg_qw_mean * 1e3:.1f}ms "
               f"vs greedy {gr_qw_mean * 1e3:.1f}ms")
        if fair and shards > 1:
            iso = _isolation_split(r, shards)
            rec["cross_shard_isolation"] = iso
            sp = iso["regular_queue_wait"]
            msg += (f" | reg queue wait isolated "
                    f"{sp['isolated_mean_s'] * 1e3:.1f}ms (n="
                    f"{sp['isolated_n']}) vs co-located "
                    f"{sp['colocated_mean_s'] * 1e3:.1f}ms (n="
                    f"{sp['colocated_n']})")
        out.append(rec)
        print(msg, flush=True)
    return out


if __name__ == "__main__":
    import argparse
    import datetime

    from .syncer_shards import _append_history, _git_sha

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_fig11_fairness.json")
    args = ap.parse_args()
    t0 = time.monotonic()
    recs = run(full=args.full)
    _append_history(args.out, {
        "git_sha": _git_sha(),
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
                     .isoformat(timespec="seconds"),
        "config": {"full": args.full},
        "wall_s": round(time.monotonic() - t0, 1),
        "records": recs,
    }, "latest" if args.full else "latest_small")
    print(f"  appended fig11 sweep to {args.out}", flush=True)
