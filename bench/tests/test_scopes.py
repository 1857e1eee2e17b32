"""Checks of the attribution by the program's names: phase and scope of an
op_name, the HLO readers (text, ``HloModuleProto``, a chip's profile), the
three readers and the finer ``device_ops`` keys on a synthetic trace, and
the program's host spans on the profile's clock.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_scopes.py
"""
import gzip
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.harness import scopes, steps  # noqa: E402
from bench.harness import trace as tr  # noqa: E402
from bench.run import load_metric  # noqa: E402

J = "jit(train_step)"
BODY = "while/body/closed_call"


@pytest.mark.parametrize("names, want", [
    ([f"{J}/jvp(blocks)/{BODY}/attn/core/{BODY}/dot_general"],
     "fwd/attn/core"),
    ([f"{J}/transpose(jvp(blocks))/{BODY}/checkpoint/rematted_computation/"
      f"attn/core/exp"], "remat/attn/core"),
    ([f"{J}/transpose(jvp(blocks))/{BODY}/mlp/dot_general"], "bwd/mlp"),
    # a custom VJP's backward, traced outside the transpose
    ([f"{J}/attn/core/bwd/{BODY}/dot_general"], "bwd/attn/core"),
    ([f"{J}/optimizer/jit(clip)/max"], "opt/optimizer"),
    # a function's name inside jit(...) is no scope, nor the primitive
    ([f"{J}/optimizer/jit(norm)/sqrt"], "opt/optimizer"),
    ([f"{J}/jvp(mlp)/transpose"], "fwd/mlp"),
    ([f"{J}/jvp(norm)/mul"], "fwd/norm"),
    # names joined by a fusion: their common scope, the first one's phase
    ([f"{J}/jvp(blocks)/{BODY}/attn/proj/dot_general",
      f"{J}/jvp(blocks)/{BODY}/attn/core/exp"], "fwd/attn"),
    ([f"{J}/transpose(jvp(blocks))/{BODY}/mlp/mul",
      f"{J}/transpose(jvp(blocks))/{BODY}/norm/mul"], "bwd/blocks"),
    ([f"{J}/jvp(frontend)/concatenate", f"{J}/jvp(embed)/convert"],
     "fwd/frontend"),
    ([f"{J}/jvp()/iota"], "fwd/unscoped"),
    # no name stack: no metadata, an argument's name, an instruction's name
    ([], "unscoped"),
    (["params['embed']['table']"], "unscoped"),
    (["convert.7"], "unscoped"),
])
def test_label_of_op_names(names, want):
    assert scopes.label(names) == want


def test_a_joined_op_name_takes_the_common_scope():
    mod = scopes.parse_hlo_text(SNIPPET)
    assert scopes.label(mod.names_of(mod.instrs["fusion.4"])) == "bwd/loss"


SNIPPET = f"""HloModule jit_train_step, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {{
  %param_0 = f32[4]{{0}} parameter(0)
  ROOT %tanh.1 = f32[4]{{0}} tanh(f32[4]{{0}} %param_0), metadata={{op_name="{J}/jvp(blocks)/{BODY}/mlp/tanh"}}
}}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {{
  %param_0.1 = f32[4]{{0}} parameter(0)
  ROOT %exp.1 = f32[4]{{0}} exponential(f32[4]{{0}} %param_0.1), metadata={{op_name="convert.9"}}
}}

ENTRY %main.1 (a: f32[4]) -> f32[4] {{
  %a = f32[4]{{0}} parameter(0), metadata={{op_name="params['blocks']"}}
  %fusion.1 = f32[4]{{0}} fusion(f32[4]{{0}} %a), kind=kLoop, calls=%fused_computation, metadata={{op_name="{J}/jvp(blocks)/{BODY}/attn/core/dot_general"}}
  %fusion.2 = f32[4]{{0}} fusion(f32[4]{{0}} %fusion.1), kind=kLoop, calls=%fused_computation, metadata={{op_name="{J}/transpose(jvp(blocks))/{BODY}/checkpoint/rematted_computation/attn/core/exp"}}
  %fusion.3 = f32[4]{{0}} fusion(f32[4]{{0}} %fusion.2), kind=kLoop, calls=%fused_computation
  %all-gather.1 = f32[8]{{0}} all-gather(f32[4]{{0}} %fusion.3), dimensions={{0}}, metadata={{op_name="{J}/transpose(jvp(blocks))/{BODY}/checkpoint/rematted_computation/attn/proj/dot_general"}}
  %fusion.4 = f32[4]{{0}} fusion(f32[4]{{0}} %fusion.3), kind=kLoop, calls=%fused_computation, metadata={{op_name="{J}/transpose(jvp(loss))/{BODY}/loss/dot_general;{J}/transpose(jvp(loss))/{BODY}/loss/exp"}}
  %copy.1 = f32[4]{{0}} copy(f32[4]{{0}} %fusion.4)
  %all-to-all.1 = f32[4]{{0}} all-to-all(f32[4]{{0}} %copy.1), dimensions={{0}}, metadata={{op_name="convert.7"}}
  %fusion.6 = f32[4]{{0}} fusion(f32[4]{{0}} %all-to-all.1), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.5 = f32[4]{{0}} fusion(f32[4]{{0}} %fusion.6), kind=kLoop, calls=%fused_computation, metadata={{op_name="{J}/optimizer/sqrt"}}
}}
"""

LABELS = {"fusion.1": "fwd/attn/core", "fusion.2": "remat/attn/core",
          "fusion.3": "fwd/mlp", "all-gather.1": "remat/attn/proj",
          "fusion.4": "bwd/loss", "copy.1": "unscoped",
          "all-to-all.1": "unscoped", "fusion.6": "unscoped",
          "fusion.5": "opt/optimizer"}


def test_hlo_text_labels_and_executed_instructions():
    mod = scopes.parse_hlo_text(SNIPPET)
    assert mod.name == "jit_train_step" and mod.entry == "main.1"
    labels = mod.labels()
    for name, want in LABELS.items():
        assert labels[name] == want, name
    assert mod.comps["main.1"][0] == "fusion.5"            # root first
    executed = {i.name for i in mod.executed()}
    assert set(LABELS) <= executed and "tanh.1" not in executed


def test_the_proto_reader_agrees_with_the_text():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, w):
        with jax.named_scope("mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("loss"):
            return jax.nn.logsumexp(h, axis=-1).sum()

    c = f.lower(jnp.ones((8, 16)), jnp.ones((16, 16))).compile()
    from_text = scopes.parse_hlo_text(c.as_text())
    proto = c.runtime_executable().hlo_modules()[0]
    from_proto = scopes.module_from_proto(
        proto.as_serialized_hlo_module_proto())
    assert from_proto.name == from_text.name
    assert from_proto.entry == from_text.entry
    assert from_proto.labels() == from_text.labels()
    assert {c: v[0] for c, v in from_proto.comps.items()} == \
        {c: v[0] for c, v in from_text.comps.items()}
    assert {"fwd/mlp", "fwd/loss"} <= set(from_proto.labels().values())


def test_a_chip_profile_embeds_the_hlo_of_every_program_that_ran(tmp_path):
    """The sample profile from the chip: each op of each program run is an
    instruction of that program's embedded HLO."""
    gz = ROOT / "bench/tests/data/chat_trace.xplane.pb.gz"
    mods = scopes.modules_from_xplane(gz)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(gz.read_bytes()))
    trace = tr.read_xplane(path)
    assert {m.name for m in trace.modules} <= set(mods)
    keys = [(o.device, o.start) for o in trace.ops]
    n = 0
    for m in trace.modules:
        for o in tr.module_ops(trace, m, keys):
            assert o.name in mods[m.name].instrs, (m.name, o.name)
            n += 1
    assert n > 1000


def synthetic_run(text=SNIPPET):
    """One traced train step on two chips, its program the snippet's; each
    op's device time in ns on both chips."""
    times = [("fusion.1", 100), ("fusion.2", 200), ("fusion.3", 50),
             ("all-gather.1", 100), ("fusion.4", 150), ("copy.1", 50),
             ("all-to-all.1", 30), ("fusion.6", 20), ("fusion.5", 300)]
    ops, mods = [], []
    for dev in (0, 1):
        t = 1000
        mods.append(tr.Module("jit_train_step(1)", 7, t, t + 1000, dev, t))
        for name, dt in times:
            ops.append(tr.DeviceOp(name, name.split(".")[0], t, t + dt, dev))
            t += dt
    trace = tr.Trace((0, 5000), ops, [], [0, 1], mods)
    trace.calls = {("bench.train", 0): list(ops)}
    trace.call_modules = {("bench.train", 0): list(mods)}
    run = types.SimpleNamespace(trace=trace, extra={})
    scopes.attribute(run, modules={
        "jit_train_step(1)": scopes.parse_hlo_text(text)})
    return run


def test_the_three_readers_on_a_synthetic_trace():
    run = synthetic_run()
    # over the program time of both chips, 2 x 1000 ns
    assert load_metric("recompute_share.train").read(run) == \
        pytest.approx(100.0 * 2 * (200 + 100) / 2000)
    assert load_metric("attn_core_share.train").read(run) == \
        pytest.approx(100.0 * 2 * (100 + 200) / 2000)
    assert load_metric("loss_head_share.train").read(run) == \
        pytest.approx(100.0 * 2 * 150 / 2000)
    assert run.extra["scopes"].scoped_share() == \
        pytest.approx(100.0 * 900 / 1000)


def test_the_readers_find_nothing_in_a_program_without_scopes():
    """The parent's program: remat's marks but none of the scopes."""
    import re
    text = re.sub(r"(attn/core|attn/proj|mlp|loss|optimizer)/", "", SNIPPET)
    run = synthetic_run(re.sub(r"jvp\((blocks|loss)\)", "jvp()", text))
    assert set(run.extra["scopes"].ops[i][2] for i in range(9)) == {
        "fwd/unscoped", "remat/unscoped", "bwd/unscoped", "unscoped"}
    assert load_metric("attn_core_share.train").read(run) is None
    assert load_metric("loss_head_share.train").read(run) is None
    assert load_metric("recompute_share.train").read(run) == \
        pytest.approx(30.0)
    for name in ("recompute_share.train", "attn_core_share.train",
                 "loss_head_share.train"):
        assert load_metric(name).read(
            types.SimpleNamespace(trace=None, extra={})) is None


def test_an_unreadable_profile_reads_as_nothing(tmp_path):
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\x0a\xff\xff\xff\xff\xff")
    run = synthetic_run()
    run.extra.clear()
    assert scopes.attribute(run, xplane=bad) is None
    assert load_metric("attn_core_share.train").read(run) is None


def test_the_device_ops_breakdown_is_keyed_by_phase_and_scope():
    run = synthetic_run()
    att = run.extra["scopes"]
    top = scopes.scoped_device_ops(att, run.trace, n=20)
    got = {k: v for k, v in top}
    # self time a chip, averaged over the two chips
    assert got["train:opt/optimizer:fusion"] == pytest.approx(300e-9)
    assert got["train:remat/attn/core:fusion"] == pytest.approx(200e-9)
    assert got["train:remat/attn/proj:all-gather"] == pytest.approx(100e-9)
    assert got["train:unscoped:copy"] == pytest.approx(50e-9)
    assert [k for k, _ in top][0] == "train:opt/optimizer:fusion"
    assert sum(got.values()) == pytest.approx(1000e-9)


def test_tracer_spans_move_to_the_profile_clock_through_the_anchor():
    trace = tr.Trace((1_000, 9_000_000_000), [], [], [0])
    spans = [{"name": "train.step", "start": 10.0, "end": 10.5,
              "attrs": {"step": 3}}]
    out = steps.on_trace_clock(spans, 9_000_000_000, trace)
    assert (out[0].start, out[0].end) == (1_000_001_000, 1_500_001_000)
    assert out[0].stats == {"step": 3}


def test_step_report_lines_spans_up_with_the_first_chip():
    trace = tr.Trace((0, 10_000_000_000), [], [], [0, 1])
    # window opened at 9.0 s monotonic; profiling from 9.5 s, anchor there
    win = steps.Window(3, 3.0, [], 9.0, profiled=(9.5, 11.5),
                       anchor_ns=9_500_000_000)
    spans = [{"name": "train.step", "start": 9.0, "end": 9.9,
              "attrs": {"step": 0}},
             {"name": "train.step", "start": 9.9, "end": 10.8,
              "attrs": {"step": 1, "feed_s": 0.001, "dispatch_s": 0.002}},
             {"name": "train.step", "start": 10.8, "end": 11.7,
              "attrs": {"step": 2, "feed_s": 0.001, "dispatch_s": 0.002}}]
    # step 1's program ends 1.5 ms before its span on chip 0
    end1 = round(1.3e9) - 1_500_000
    trace.call_modules = {("bench.train", 1): [
        tr.Module("p", 1, end1 - 800_000_000, end1, 0),
        tr.Module("p", 1, end1 - 800_000_000, end1 + 5_000_000, 1)]}
    rep = steps.step_report(spans, win, trace)
    rows = {r["step"]: r for r in rep["steps"]}
    assert not rows[0]["profiled"] and not rows[2]["profiled"]
    assert rows[1]["profiled"]
    assert rows[1]["lag_ms"] == pytest.approx(1.5)
    assert rows[1]["program_s"] == pytest.approx(0.8)
    assert rep["summary"]["span_s_profiled"]["n"] == 1
    assert rep["summary"]["span_s_outside"]["median"] == pytest.approx(0.9)
