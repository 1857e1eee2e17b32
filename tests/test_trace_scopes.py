"""The program names its work for the profiler: the train step's optimized
HLO carries the ``jax.named_scope`` vocabulary in every instruction's
metadata, remat's recompute included; the serving programs have stable
module names; and ``Tracer`` spans land on the profile's clock.

The step is compiled on the CPU at a tiny internvl2-2b shape (vision stub,
2 layers, remat on) and read with the benchmark's own attribution
(``bench/harness/scopes.py``), the reader the chip's traces go through.
"""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.harness import scopes  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402
from repro.models import init_cache, init_params  # noqa: E402
from repro.training import (OptimizerConfig, make_opt_state,  # noqa: E402
                            make_train_step)

# instructions that move nothing: their op_name, where they have one, is an
# argument's name, not a name stack
NO_WORK = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")


@pytest.fixture(scope="module")
def step_hlo():
    cfg = reduced(get_config("internvl2-2b"), n_layers=2)
    assert cfg.frontend == "vit_stub"
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = make_opt_state(params)
    B, S = 2, 64
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "mask": jnp.ones((B, S), jnp.float32),
             "patches": jnp.zeros((B, cfg.frontend_tokens, cfg.frontend_dim),
                                  jnp.float32)}
    step = make_train_step(cfg, OptimizerConfig(), remat=True)
    text = jax.jit(step).lower(params, opt, batch).compile().as_text()
    mod = scopes.parse_hlo_text(text)
    labels = mod.labels()
    ops = [i for i in mod.executed() if i.opcode not in NO_WORK]
    named = [i for i in ops if any("/" in n for n in mod.names_of(i))]
    return mod, labels, ops, named


def test_every_named_instruction_maps_to_a_phase_and_a_scope(step_hlo):
    _, labels, _, named = step_hlo
    assert len(named) > 100
    bad = []
    for i in named:
        phase, scope = labels[i.name].split("/", 1)
        if phase not in scopes.PHASES or scope not in scopes.SCOPES:
            bad.append((i.name, labels[i.name], i.op_name))
    assert not bad, bad[:10]


def test_attention_and_mlp_run_in_the_forward_remat_and_backward(step_hlo):
    _, labels, ops, _ = step_hlo
    seen = {labels[i.name] for i in ops}
    for scope in ("attn/core", "mlp"):
        for phase in ("fwd", "remat", "bwd"):
            assert f"{phase}/{scope}" in seen, (phase, scope)
    assert "opt/optimizer" in seen
    for scope in ("frontend", "embed", "blocks", "attn/proj", "norm",
                  "loss"):
        assert {f"fwd/{scope}", f"bwd/{scope}"} <= seen, scope


def test_at_least_95_percent_of_the_program_instructions_are_scoped(
        step_hlo):
    """Over the device ops that carry the program's name stack: XLA adds
    copies and, on the CPU, split reductions with no metadata at all, which
    no scope in the program can name (they are ``unscoped`` in a trace)."""
    _, labels, ops, named = step_hlo
    scoped = [i for i in named if scopes.is_scoped(labels[i.name])]
    assert len(scoped) >= 0.95 * len(named), (len(scoped), len(named))
    assert len(named) >= 0.75 * len(ops)


def test_serving_programs_have_stable_module_names():
    from repro.serving.engine import _compiled
    cfg = reduced(get_config("qwen2-7b"), n_layers=2)
    slots, max_len = 2, 32
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, slots, max_len))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    state = (cache, i32(slots), i32(slots),
             jax.ShapeDtypeStruct((slots,), bool), i32(slots, 1))
    admit, step = _compiled(cfg, max_len, jnp.float32)
    assert "module @jit_engine_step" in step.lower(params, *state).as_text()
    low = admit.lower(params, *state, i32(1, 16), i32(1), i32(1), i32(1))
    assert "module @jit_engine_admit" in low.as_text()


def test_tracer_spans_land_on_the_profile_clock(tmp_path):
    """A span recorded with ``Tracer.record`` inside a ``TraceAnnotation``
    maps, through the window's anchor, to within 1 ms of the annotation in
    the ``.xplane.pb``."""
    from bench.harness.steps import AnchoredProfiler, on_trace_clock
    from bench.harness.trace import read_xplane
    from repro.core.trace import Tracer

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tracer = Tracer()
    prof = AnchoredProfiler(tmp_path / "trace")
    prof.start()
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.probe", call=i):
            t0 = time.monotonic()
            f(x).block_until_ready()
            time.sleep(0.002)
            tracer.record("probe", t0, time.monotonic(), attrs={"call": i})
    trace = read_xplane(prof.stop())
    marks = {int(s.stats["call"]): s for s in trace.spans
             if s.name == "bench.probe"}
    mapped = on_trace_clock(tracer.spans(), prof.anchor_ns, trace)
    assert len(mapped) == 3 and sorted(marks) == [0, 1, 2]
    for s in mapped:
        mark = marks[s.stats["call"]]
        assert abs(s.start - mark.start) < 1_000_000, (s, mark)
        assert abs(s.end - mark.end) < 1_000_000, (s, mark)
