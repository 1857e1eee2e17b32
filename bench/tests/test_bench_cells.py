"""Every cell of ``BENCHMARK.json`` resolves what a run looks up by name:
its configuration file, its traffic mix, the harness module the mix
names, a function of that module for each end-to-end metric the cell
reports, and a reader in ``bench/metrics/`` for each per-layer metric
that names the cell. Every leaf of each configuration's program, at its
committed widths, has a weight rule. A serving cell's mix, at its
committed rate and the benchmark's window, brings at least ten requests
beyond the TTFT percentile the cell reports. No chip is needed.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_bench_cells.py
"""
import importlib
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.harness import traffic  # noqa: E402
from bench.harness.common import load_json  # noqa: E402
from bench.harness.weights import leaf_rules, program_config  # noqa: E402
from bench.run import (applies, find_cell, load_benchmark,  # noqa: E402
                       load_metric)

BM = load_benchmark()
CELLS = [c["name"] for c in BM["workloads"]]


def harness_name(cell: str) -> str:
    return find_cell(BM, cell)[2]["driver"]


def harness_of(cell: str):
    return importlib.import_module(f"bench.harness.{harness_name(cell)}")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_configuration_mix_and_harness(cell):
    c, conf, mix = find_cell(BM, cell)
    config = {x["name"]: x for x in BM["configs"]}[c["config"]]
    assert (ROOT / config["file"]).is_file()
    assert conf and mix
    assert hasattr(harness_of(cell), "run")


@pytest.mark.parametrize("cell", CELLS)
def test_every_end_to_end_metric_of_the_cell_has_a_function(cell):
    harness = harness_of(cell)
    names = [m["name"] for m in BM["end_to_end"] if applies(m, cell)]
    assert "setup_s" in names and len(names) >= 2
    missing = [n for n in names if not callable(harness.END_TO_END.get(n))]
    assert not missing, missing


@pytest.mark.parametrize("cell", CELLS)
def test_every_per_layer_metric_of_the_cell_has_a_reader(cell):
    names = [m["name"] for m in BM["per_layer"] if applies(m, cell)]
    assert names
    for name in names:
        assert callable(load_metric(name).read), name


@pytest.mark.parametrize("config", [c["name"] for c in BM["configs"]])
def test_every_leaf_of_the_configuration_has_a_weight_rule(config):
    """The program's tree at its committed widths, by ``jax.eval_shape``
    (nothing is allocated): a leaf with no rule fails here, before a chip
    is booked."""
    import jax
    from repro.models import init_params
    conf = load_json(ROOT / {c["name"]: c for c in BM["configs"]}
                     [config]["file"])
    cfg = program_config(conf)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    rules = leaf_rules(conf, shapes)
    assert len(rules) == len(jax.tree.leaves(shapes))


SERVE_CELLS = [c for c in CELLS if harness_name(c) == "serve"]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_ttft_percentile_has_ten_requests_beyond_it(cell):
    _, conf, mix = find_cell(BM, cell)
    ttft = [m["name"] for m in BM["end_to_end"]
            if applies(m, cell) and m["name"].startswith("ttft_p")]
    assert ttft
    sched = traffic.serve_schedule(mix, BM["run_seconds"], 1000,
                                   np.random.default_rng(0))
    for name in ttft:
        q = int(re.fullmatch(r"ttft_p(\d+)_ms", name).group(1))
        assert len(sched) * (100 - q) / 100 >= 10, (name, len(sched))
