"""Checks of the yardstick itself: percentiles, the traffic
generator, the kernel counts, the trace reduction on a trace recorded on
the chip, and the refusal to run without a TPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import gzip
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.harness import common, serve, trace as tr, traffic  # noqa: E402
from bench.harness.readers import load_count, roofline_share  # noqa: E402

QWEN = types.SimpleNamespace(n_layers=4, n_heads=28, n_kv_heads=4,
                             head_dim=128, d_model=3584, d_ff=18944,
                             vocab=152064)


# ---------------------------------------------------------------- arithmetic

def test_percentile_interpolates_between_ranks():
    vals = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert common.percentile(vals, 50) == 30.0
    assert common.percentile(vals, 90) == pytest.approx(46.0)
    assert common.percentile(vals, 100) == 50.0
    assert common.percentile([7.0], 95) == 7.0
    assert common.percentile(vals, 90) == pytest.approx(
        float(np.percentile(vals, 90)))


def _run(requests, deadline=100.0):
    return types.SimpleNamespace(requests=requests, units=[],
                                 deadline=deadline)


def test_ttft_counts_requests_that_never_finish_at_the_deadline():
    reqs = [{"due": 0.0, "stamps": [0.1, 0.2]} for _ in range(9)]
    reqs.append({"due": 10.0, "stamps": []})        # never served
    ttft = serve.ttft_ms(_run(reqs))
    assert len(ttft) == 10
    assert max(ttft) == pytest.approx(90_000.0)
    assert common.percentile(ttft, 90) > 100.0       # the miss moves the tail


def test_itl_takes_every_gap_of_every_request():
    reqs = [{"due": 0.0, "stamps": [1.0, 1.05, 1.15]},
            {"due": 0.0, "stamps": [2.0]}]
    assert serve.itl_ms(_run(reqs)) == pytest.approx([50.0, 100.0])


def test_tails_are_named_by_percentile():
    reqs = [{"due": 0.0, "stamps": [0.01 * (i + 1)]} for i in range(20)]
    run = types.SimpleNamespace(requests=reqs, units=[], deadline=100.0)
    for q in serve.PERCENTILES:
        assert serve.END_TO_END[f"ttft_p{q}_ms"](run) == pytest.approx(
            common.percentile(serve.ttft_ms(run), q))
    assert "p99" in serve.tails_line(run)


def test_propagation_counts_units_never_ready_at_the_deadline():
    run = types.SimpleNamespace(deadline=20.0, units=[
        {"created": 1.0, "ready": 1.02}, {"created": 2.0, "ready": None}])
    assert serve.propagation_ms(run) == pytest.approx([20.0, 18_000.0])


# ------------------------------------------------------------------ traffic

MIX = json.loads((ROOT / "bench/traffic/chat.json").read_text())


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.serve_schedule(MIX, 51.0, 1000, np.random.default_rng(1))
    b = traffic.serve_schedule(MIX, 51.0, 1000, np.random.default_rng(2))
    assert len(a) == len(b) == round(MIX["rate_rps"] * 51.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(0.0 <= r.offset < 51.0 for r in a)
    assert all(MIX["prompt_len"]["min"] <= len(r.prompt)
               <= MIX["prompt_len"]["max"] for r in a)
    again = traffic.serve_schedule(MIX, 51.0, 1000, np.random.default_rng(1))
    assert all((x.offset, x.tenant, x.max_new) == (y.offset, y.tenant,
                                                   y.max_new)
               and np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, again))


def test_arrivals_are_a_poisson_stream_of_fixed_count():
    """Gaps of a Poisson stream are exponential: their spread equals their
    mean, and short gaps bunch as often as the distribution says; only
    the count is fixed."""
    gaps = []
    for seed in range(40):
        offs = traffic.poisson_offsets(1.5, 51.0, np.random.default_rng(seed))
        assert len(offs) == 76 and offs[0] == 0.0
        assert np.all(np.diff(offs) > 0) and offs[-1] < 51.0
        gaps += list(np.diff(offs))
    gaps = np.asarray(gaps)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)
    short = np.mean(gaps < 0.1 * np.mean(gaps))
    assert short == pytest.approx(1 - np.exp(-0.1), abs=0.02)


def test_tenant_shares_and_zipf_churn():
    a = traffic.serve_schedule(MIX, 51.0, 1000, np.random.default_rng(3))
    n_b = sum(r.tenant == "tenant-b" for r in a)
    assert n_b == pytest.approx(2 * len(a) / 3, abs=1)
    units = traffic.unit_schedule(MIX["control_plane"], 51.0,
                                  np.random.default_rng(3))
    assert len(units) == 20 * 51
    counts = [sum(u.tenant == t for u in units)
              for t in MIX["control_plane"]["tenants"]]
    assert counts == sorted(counts, reverse=True)


def test_seed32_takes_large_and_negative_seeds():
    seeds = {common.seed32(s, 1) for s in (0, 1, 2**31 + 5, 2**40, -3)}
    assert len(seeds) == 5 and all(0 <= s < 2**31 for s in seeds)
    assert common.seed32(2**31 + 5, 1) != common.seed32(2**31 + 5, 2)


# ------------------------------------------------------------- kernel counts

def test_decode_count_reads_true_lengths_not_max_len():
    fd = load_count("flash_decode")
    short = fd.count(QWEN, [100])
    full = fd.count(QWEN, [2048])
    assert short[0] == pytest.approx(full[0] * 100 / 2048)
    # live K and V of 100 positions, in each of 4 layers, plus q and out
    assert short[1] == pytest.approx(4 * (2 * 100 * 4 * 128 * 2
                                          + 2 * 28 * 128 * 2))


def test_prefill_count_is_causal_over_the_true_length():
    fa = load_count("flash_attention")
    f, _ = fa.count(QWEN, [512])
    assert f == pytest.approx(4 * 4.0 * 28 * 128 * 512 * 513 / 2)
    # padding a 300-token prompt to its 512 bucket counts nothing more
    assert fa.count(QWEN, [300])[0] < f


def test_padded_work_never_raises_a_share():
    """Kernel time fixed: a call counted at its true lengths reads the
    share it earns; the share can only fall if the kernel wastes time."""
    op = tr.DeviceOp("k.1", "custom-call", 0, 1_000_000, 0)   # 1 ms
    call = types.SimpleNamespace(call=1, lengths=[512] * 8)
    tr_ = types.SimpleNamespace(calls={("bench.step", 1): [op]})
    run = types.SimpleNamespace(trace=tr_, calls=[call], cfg=QWEN,
                                device={"kind": "TPU v5 lite"})
    share = roofline_share(run, "flash_decode")
    _, b = load_count("flash_decode").count(QWEN, [512] * 8)
    assert share == pytest.approx(100 * b / 819e9 / 1e-3)
    call.lengths = [256] * 8
    assert roofline_share(run, "flash_decode") < share


def test_model_flops_count_active_slots_only():
    dd = load_count("dense_decoder")
    assert dd.decode_flops(QWEN, []) == 0
    one = dd.decode_flops(QWEN, [1000])
    assert dd.decode_flops(QWEN, [1000, 1000]) == pytest.approx(2 * one)
    assert one > 2 * 4 * dd.layer_matmul_params(QWEN)


INTERN = types.SimpleNamespace(n_layers=24, n_heads=16, n_kv_heads=8,
                               head_dim=128, d_model=2048, d_ff=8192,
                               vocab=92553, frontend_tokens=256,
                               frontend_dim=1024)


def test_train_count_is_causal_and_counts_loss_positions_only():
    ts, dd = load_count("train_step"), load_count("dense_decoder")
    f = ts.step_flops(INTERN, 4, 4096)
    six_n = 6 * 4 * 4096 * 24 * dd.layer_matmul_params(INTERN)
    attn = 3 * 4 * 24 * 4.0 * 16 * 128 * 4096 * 4097 / 2
    head = 6 * 4 * (4096 - 256) * dd.head_params(INTERN)
    proj = 6 * 4 * 256 * 1024 * 2048
    assert f == pytest.approx(six_n + attn + head + proj)
    assert f == pytest.approx(1.857e14, rel=1e-3)
    assert ts.step_flops(INTERN, 8, 4096) == pytest.approx(2 * f)


def _train_trace(collective_ns):
    """Two traced steps on two chips, each a 10 ms program run holding a
    6 ms matmul fusion and a collective wait of ``collective_ns``."""
    ops, mods = [], []
    for step, t0 in ((1, 0), (2, 20_000_000)):
        for dev in (0, 1):
            mods.append(tr.Module("jit_train_step", step, t0,
                                  t0 + 10_000_000, dev))
            ops.append(tr.DeviceOp("fusion.1", "fusion", t0,
                                   t0 + 6_000_000, dev))
            ops.append(tr.DeviceOp("all-gather-done.2", "all-gather-done",
                                   t0 + 6_000_000,
                                   t0 + 6_000_000 + collective_ns, dev))
    calls = {("bench.train", 1): ops[:4], ("bench.train", 2): ops[4:]}
    cm = {("bench.train", 1): mods[:2], ("bench.train", 2): mods[2:]}
    trace = types.SimpleNamespace(calls=calls, call_modules=cm)
    return types.SimpleNamespace(trace=trace, device={"kind": "TPU v5 lite"},
                                 chips=2, flops_per_step=197e12 * 2 * 0.01)


def test_train_mfu_and_exposed_collectives():
    from bench.harness.readers import exposed_collective_share, step_mfu
    run = _train_trace(2_000_000)
    # each step: 2 chips x 10 ms at 197 TFLOP/s, counted work half of that
    assert step_mfu(run, "bench.train") == pytest.approx(100.0)
    run.flops_per_step /= 2
    assert step_mfu(run, "bench.train") == pytest.approx(50.0)
    assert exposed_collective_share(run, "bench.train") == pytest.approx(20.0)
    assert exposed_collective_share(_train_trace(0), "bench.train") == 0.0
    assert step_mfu(_train_trace(0), "bench.step") is None


def test_train_batches_differ_by_step_and_repeat_by_seed():
    mix = {"seq_len": 64, "global_batch": 4, "zipf_a": 1.3}
    a = traffic.train_batch(mix, 2**33 + 1, 0, 1000, 8, 16)
    b = traffic.train_batch(mix, 2**33 + 1, 0, 1000, 8, 16)
    c = traffic.train_batch(mix, 2**33 + 1, 1, 1000, 8, 16)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["tokens"] == c["tokens"]).all()
    assert a["tokens"].shape == (4, 64) and a["patches"].shape == (4, 8, 16)
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 1000
    # the loss counts the positions whose next token is text
    assert (a["mask"][:, :7] == 0).all() and (a["mask"][:, 7:] == 1).all()
    rows = {tuple(r) for r in np.concatenate([a["tokens"], c["tokens"]])}
    assert len(rows) == 8


def test_train_compare_takes_the_worst_leaf_and_skips_round_off():
    from bench.harness.train import Readings, compare
    ref = Readings([10.0, 9.0, 8.0],
                   {"a": np.array([1.0, 2.0]), "b": np.array([1e-6])},
                   {"a": np.array([0.1, 0.2]), "b": np.array([0.1])})
    same = compare(ref, ref)
    assert same == {"loss_gap": 0.0, "grad_norm_gap": 0.0,
                    "update_norm_gap": 0.0}
    got = Readings([10.0, 9.5, 8.0],
                   {"a": np.array([1.0, 2.2]), "b": np.array([0.5])},
                   {"a": np.array([0.1, 0.2]), "b": np.array([0.3])})
    gaps = compare(got, ref)
    assert gaps["loss_gap"] == pytest.approx(0.5)
    # leaf b's norm is far under the median's (1.0): its gap is over that
    assert gaps["grad_norm_gap"] == pytest.approx(0.5, abs=1e-5)
    # leaf b moves by round-off alone (its gradient < 1e-3 of the median)
    assert gaps["update_norm_gap"] == 0.0
    frozen = Readings(got.losses, {k: 0 * v for k, v in ref.grad.items()},
                      {k: 0 * v for k, v in ref.change.items()})
    assert compare(frozen, ref)["update_norm_gap"] == pytest.approx(1.0)


def test_peaks_refuse_an_unknown_device():
    from bench.harness.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


# ----------------------------------------------------------- trace reduction

@pytest.fixture(scope="module")
def chat_trace(tmp_path_factory):
    """Four seconds of the chat cell on one TPU v5e (its first traced
    chip run), gzipped."""
    path = tmp_path_factory.mktemp("trace") / "chat.xplane.pb"
    with gzip.open(ROOT / "bench/tests/data/chat_trace.xplane.pb.gz") as a, \
            open(path, "wb") as b:
        shutil.copyfileobj(a, b)
    return tr.read_xplane(path)


def test_trace_busy_and_window(chat_trace):
    busy, window = tr.busy_and_window_s(chat_trace)
    assert window == pytest.approx(3.939469771)
    assert busy == pytest.approx(3.681323704)
    gaps = tr.idle_gaps(chat_trace)
    assert sum(g[1] for g in gaps) == pytest.approx(window - busy)
    assert gaps[0][0] == "bench.step"


def test_trace_ties_each_program_run_to_one_call(chat_trace):
    steps = {k: v for k, v in chat_trace.calls.items() if k[0] == "bench.step"}
    admits = {k: v for k, v in chat_trace.calls.items()
              if k[0] == "bench.admit" and v}
    assert len(steps) == 166 and len(admits) == 6
    per_step = [tr.call_device_s(v) for v in steps.values() if v]
    assert np.median(per_step) == pytest.approx(0.0204, rel=0.02)
    step_s = sum(per_step)
    admit_s = sum(tr.call_device_s(v) for v in admits.values())
    busy, _ = tr.busy_and_window_s(chat_trace)
    assert step_s + admit_s <= busy           # no run counted twice
    assert step_s == pytest.approx(3.410145864)
    kern = sum(tr.kernel_s(v, "custom-call") for v in steps.values())
    assert kern == pytest.approx(0.05209186)


def test_self_time_counts_no_op_twice(chat_trace):
    own = tr.self_ns(chat_trace.ops)
    assert min(own) >= 0
    busy, _ = tr.busy_and_window_s(chat_trace)
    assert sum(own) / 1e9 == pytest.approx(busy, rel=0.01)
    top = tr.top_device_ops(chat_trace)
    assert len(top) == 10 and top[0][0] == "step:convert"


def test_parse_op_names():
    assert tr.parse_op("%fusion.12 = f32[8]{0} fusion(f32[8] %a), kind=k") \
        == ("fusion.12", "fusion")
    assert tr.parse_op("%while.3 = (s32[], bf16[2]{0}) while((s32[], "
                       "bf16[2]) %t), body=%b") == ("while.3", "while")
    assert tr.parse_op("%c.1 = bf16[8,4]{1,0} custom-call(s32[8] %x)") \
        == ("c.1", "custom-call")


# --------------------------------------------------------------------- no chip

@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_run_refuses_without_a_tpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
