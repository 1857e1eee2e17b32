"""Serving data plane: fused-admission engine exactness vs the models-API
reference loop (ragged attention batches + recurrent exact-length buckets),
the shared compute-dtype weight copy (bit-exact against the per-use cast
for every layer pattern, made once across engines and threads, freed with
the last engine, skipped for bf16 params), admission under full slots with
slot reuse, thread-safe batcher submits with TTFT stamps, WRR
slot-scheduler fairness vs the FIFO baseline, greedy-flood starvation
regression, the control→data plane bridge (engine replicas as WorkUnits,
per-tenant metrics), agent cleanup of deleted units, and the autoscaler's
fourth (engine-replica) actuator."""
import gc
import sys
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import (APIServer, Autoscaler, CooperativeExecutor,
                        ScalingPolicy, Syncer, TenantControlPlane,
                        VirtualClusterFramework)
from repro.core.agent import MockProvider
from repro.models import (cast_params, compute_read, decode_step, init_cache,
                          init_params, prefill)
from repro.serving import (ContinuousBatcher, GenerationEngine, Request,
                           ServingFleet, SlotScheduler, generate)

F32 = jnp.float32
BF16 = jnp.bfloat16
MAX_LEN = 48


def wait_for(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen2-7b"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _ref_generate(cfg, params, prompt, max_new, max_len=MAX_LEN):
    """Independent oracle: the hand-rolled per-request prefill+decode loop
    over the raw models API (the seed ``generate()`` path)."""
    cache = init_cache(cfg, 1, max_len, enc_len=max_len)
    logits, cache, lengths = prefill(
        params, cfg, jnp.asarray(np.asarray(prompt)[None], jnp.int32),
        cache, compute_dtype=F32)
    toks = [int(jnp.argmax(logits[0, -1, :cfg.vocab]))]
    lengths = lengths + 1
    for _ in range(max_new - 1):
        logits, cache, lengths = decode_step(
            params, cfg, jnp.asarray([[toks[-1]]], jnp.int32), cache,
            lengths, compute_dtype=F32)
        toks.append(int(jnp.argmax(logits[0, 0, :cfg.vocab])))
    return toks


# ------------------------------------------------------------ slot scheduler

def _req(uid, tenant="t"):
    return Request(uid, np.zeros(4, np.int32), 4, tenant=tenant)


def test_slot_scheduler_wrr_interleaves_tenants():
    s = SlotScheduler()
    for i in range(6):
        s.submit("greedy", _req(i, "greedy"))
    s.submit("steady", _req(100, "steady"))
    s.submit("steady", _req(101, "steady"))
    # WRR with equal weights alternates tenants: the steady tenant gets a
    # slot in the first dispatch pair despite 6 queued greedy requests
    first_pair = [r.tenant for r in s.take(2)]
    assert "steady" in first_pair
    rest = s.take(10)
    assert len(rest) == 6
    assert s.pending() == 0
    assert s.dispatched == 8


def test_slot_scheduler_fifo_baseline_starves():
    s = SlotScheduler(fair=False)
    for i in range(6):
        s.submit("greedy", _req(i, "greedy"))
    s.submit("steady", _req(100, "steady"))
    order = [r.tenant for r in s.take(7)]
    assert order.index("steady") == 6     # strictly behind the flood


def test_slot_scheduler_weights_and_drain():
    s = SlotScheduler()
    s.register_tenant("a", weight=2)
    s.register_tenant("b", weight=1)
    for i in range(4):
        s.submit("a", _req(i, "a"))
        s.submit("b", _req(10 + i, "b"))
    got = [r.tenant for r in s.take(3)]
    assert got.count("a") == 2 and got.count("b") == 1   # 2:1 credit split
    assert s.set_weight("b", 3) is True
    assert s.set_weight("b", 3) is False                 # no-op
    drained = s.drain_tenant("a")
    assert len(drained) == 2 and all(r.tenant == "a" for r in drained)
    assert s.pending_by_tenant() == {"b": 3}
    stats = s.tenant_wait_stats()
    assert set(stats) == {"a", "b"} and stats["a"][0] == 2
    assert s.tenant_wait_stats() == {}                   # drained


# ------------------------------------------------------------ engine exactness

def test_ragged_batch_exactness_vs_reference(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 3, 12)]
    eng = GenerationEngine(cfg, params, slots=4, max_len=MAX_LEN,
                           compute_dtype=F32)
    reqs = [Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)]
    eng.admit_many(reqs)
    while eng.active_slots():
        eng.step()
    for r, p in zip(reqs, prompts):
        assert r.tokens == _ref_generate(cfg, params, p, 6)
    # fused admission: buckets {8, 16} -> 2 jitted calls, zero full-cache
    # rescatter copies, one host sync per admit call / decode step
    assert eng.admit_calls == 2
    assert eng.full_cache_copies == 0
    assert eng.host_syncs == eng.admit_calls + eng.steps


def test_recurrent_pattern_exact_length_buckets():
    """Patterns with recurrent layers fold pad tokens into their state, so
    the engine buckets them by exact prompt length — outputs must still
    match the per-request reference exactly."""
    cfg = reduced(get_config("rwkv6-7b"), n_layers=2)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 5)]
    eng = GenerationEngine(cfg, params, slots=3, max_len=MAX_LEN,
                           compute_dtype=F32)
    assert eng._exact_buckets
    reqs = [Request(i, p, max_new_tokens=4) for i, p in enumerate(prompts)]
    eng.admit_many(reqs)
    while eng.active_slots():
        eng.step()
    for r, p in zip(reqs, prompts):
        assert r.tokens == _ref_generate(cfg, params, p, 4)
    assert eng.admit_calls == 2       # lengths {5, 5} and {9}


def test_generate_routes_through_engine(model):
    cfg, params = model
    rng = np.random.default_rng(2)
    batch = np.stack([rng.integers(0, cfg.vocab, 8).astype(np.int32)
                      for _ in range(3)])
    out = generate(cfg, params, batch, max_new_tokens=5, max_len=MAX_LEN,
                   compute_dtype=F32)
    assert out.shape == (3, 5)
    for i in range(3):
        assert list(out[i]) == _ref_generate(cfg, params, batch[i], 5)
    with pytest.raises(ValueError):
        generate(cfg, params, batch, max_new_tokens=MAX_LEN,
                 max_len=MAX_LEN, compute_dtype=F32)


# ------------------------------------------------- compute-dtype weights

# one config per layer pattern the engine serves: dense 'g'; gemma2 'lg'
# (soft-caps, tied head, scaled embedding); MoE blocks; jamba 'm' with MoE;
# rwkv 'r'; the encoder-decoder
COPY_ARCHS = ["qwen2-7b", "gemma2-9b", "olmoe-1b-7b", "jamba-v0.1-52b",
              "rwkv6-7b", "seamless-m4t-large-v2"]


def _f32_model(name, seed=0):
    cfg = get_config(name)
    cfg = reduced(cfg, n_layers=cfg.block_period)
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def _paths(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _greedy_bf16(cfg, params, prompts, max_new):
    """The models API, jitted, with bf16 compute over ``params`` as given
    (so float32 leaves are cast at every use): the first prefill and
    decode logits, and every greedy token, at the engine's shapes."""
    pf = jax.jit(lambda p, t, c: prefill(p, cfg, t, c, compute_dtype=BF16))
    ds = jax.jit(lambda p, t, c, n: decode_step(p, cfg, t, c, n,
                                                compute_dtype=BF16))
    B = prompts.shape[0]
    cache = init_cache(cfg, B, MAX_LEN, enc_len=MAX_LEN)
    logits, cache, lengths = pf(params, jnp.asarray(prompts), cache)
    first = [logits]
    toks = [jnp.argmax(logits[:, -1, :cfg.vocab], axis=-1)]
    lengths = lengths + 1
    for _ in range(max_new - 1):
        logits, cache, lengths = ds(params, toks[-1][:, None].astype(
            jnp.int32), cache, lengths)
        first.append(logits)
        toks.append(jnp.argmax(logits[:, 0, :cfg.vocab], axis=-1))
    return first[:2], np.stack([np.asarray(t) for t in toks], axis=1)


@pytest.mark.parametrize("name", COPY_ARCHS)
def test_compute_copy_serves_f32_params_exactly(name):
    """Over float32-held params the engine serves from its bf16 copy: the
    prefill and first decode logits equal, bit for bit, those of the model
    casting each float32 leaf where it reads it, and every token matches."""
    cfg, params = _f32_model(name)
    prompts = np.random.default_rng(7).integers(
        0, cfg.vocab, (3, 8)).astype(np.int32)
    eng = GenerationEngine(cfg, params, slots=3, max_len=MAX_LEN,
                           compute_dtype=BF16)
    assert eng.counters()["weight_casts"] == 1
    ref_logits, ref_toks = _greedy_bf16(cfg, params, prompts, 5)
    copy_logits, copy_toks = _greedy_bf16(cfg, eng.params, prompts, 5)
    for a, b in zip(ref_logits, copy_logits):
        assert jnp.array_equal(a, b)
    reqs = [Request(i, p, max_new_tokens=5) for i, p in enumerate(prompts)]
    eng.admit_many(reqs)      # equal lengths: one bucket, slots 0..2
    while eng.active_slots():
        eng.step()
    assert np.array_equal(copy_toks, ref_toks)
    assert [r.tokens for r in reqs] == ref_toks.tolist()


def test_f32_read_leaves_keep_dtype():
    """Leaves the model reads in float32 are served as held (the very
    arrays); leaves read through the compute-dtype cast are bf16."""
    for name in ("jamba-v0.1-52b", "rwkv6-7b"):
        cfg, params = _f32_model(name)
        eng = GenerationEngine(cfg, params, slots=1, max_len=MAX_LEN,
                               compute_dtype=BF16)
        src, served = _paths(params), _paths(eng.params)
        reads = _paths(compute_read(params))
        for path, x in served.items():
            if reads[path]:
                assert x.dtype == BF16, path
            else:
                assert x is src[path], path
        assert not any(reads[p] for p in src
                       if p.endswith(("['ln1']", "['ln2']", "['final_norm']",
                                      "['router']", "['dt_proj']['w']",
                                      "['dt_proj']['b']", "['decay_w1']",
                                      "['decay_w2']")))
    jamba = _paths(compute_read(_f32_model("jamba-v0.1-52b")[1]))
    for leaf in ("['embed']['table']", "['blocks']['sub0']['mamba']"
                 "['x_proj']['w']", "['blocks']['sub1']['ffn']['w1']",
                 "['blocks']['sub4']['attn']['wq']['w']"):
        assert jamba[leaf], leaf


def test_engines_share_one_compute_copy(model):
    cfg, params = model
    a = GenerationEngine(cfg, params, slots=1, max_len=MAX_LEN,
                         compute_dtype=BF16)
    b = GenerationEngine(cfg, params, slots=2, max_len=MAX_LEN,
                         compute_dtype=BF16)
    assert a.weight_casts + b.weight_casts == 1
    pa, pb = jax.tree.leaves(a.params), jax.tree.leaves(b.params)
    assert all(x is y for x, y in zip(pa, pb))
    assert sum(x.dtype == BF16 for x in pa) > 0


def test_compute_copy_made_once_across_threads():
    """Fleet replicas come up from their own threads: engines built at
    once still make one copy between them."""
    cfg, params = _f32_model("qwen2-7b", seed=3)
    engines, errors = [], []
    gate = threading.Barrier(4)

    def build():
        try:
            gate.wait(timeout=30)
            engines.append(GenerationEngine(cfg, params, slots=1,
                                            max_len=MAX_LEN,
                                            compute_dtype=BF16))
        except Exception as e:       # reported below, not lost in a thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=build) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors and len(engines) == 4
    assert sum(e.weight_casts for e in engines) == 1
    first = jax.tree.leaves(engines[0].params)
    for e in engines[1:]:
        assert all(x is y for x, y in zip(first, jax.tree.leaves(e.params)))


def test_compute_copy_freed_with_last_engine():
    cfg, params = _f32_model("qwen2-7b", seed=4)
    a = GenerationEngine(cfg, params, slots=1, max_len=MAX_LEN,
                         compute_dtype=BF16)
    b = GenerationEngine(cfg, params, slots=1, max_len=MAX_LEN,
                         compute_dtype=BF16)
    table = weakref.ref(a.params["embed"]["table"])
    assert table().dtype == BF16
    del a
    gc.collect()
    assert table() is not None           # b still serves from it
    del b
    gc.collect()
    assert table() is None
    c = GenerationEngine(cfg, params, slots=1, max_len=MAX_LEN,
                         compute_dtype=BF16)
    assert c.weight_casts == 1           # made anew, not found stale


def test_bf16_params_need_no_copy(model):
    cfg, params = model
    held = cast_params(params)
    eng = GenerationEngine(cfg, held, slots=1, max_len=MAX_LEN,
                           compute_dtype=BF16)
    assert eng.counters()["weight_casts"] == 0
    assert all(x is y for x, y in zip(jax.tree.leaves(eng.params),
                                      jax.tree.leaves(held)))


# ------------------------------------------------- admission under full slots

def test_admission_under_full_slots_and_slot_reuse(model):
    cfg, params = model
    eng = GenerationEngine(cfg, params, slots=2, max_len=MAX_LEN,
                           compute_dtype=F32)
    batcher = ContinuousBatcher(eng)
    rng = np.random.default_rng(3)
    uids = [batcher.submit(rng.integers(0, cfg.vocab, 8), max_new_tokens=4)
            for _ in range(6)]
    assert len(set(uids)) == 6
    # only 2 slots: the first pump admits 2 and leaves 4 queued
    batcher.pump()
    assert eng.active_slots() == 2
    assert batcher.scheduler.pending() == 4
    batcher.run_until_drained()
    assert len(batcher.completed) == 6
    assert eng.admitted == 6
    assert eng.full_cache_copies == 0
    for uid in uids:
        req = batcher.completed[uid]
        assert req.done and len(req.tokens) == 4
        # exactness survives slot reuse
        assert req.tokens == _ref_generate(cfg, params, req.prompt, 4)


def test_engine_rejects_overlong_prompt(model):
    cfg, params = model
    eng = GenerationEngine(cfg, params, slots=1, max_len=16,
                           compute_dtype=F32)
    with pytest.raises(ValueError):
        eng.admit_many([Request(0, np.zeros(16, np.int32), 4)])
    batcher = ContinuousBatcher(eng)
    with pytest.raises(ValueError):
        batcher.submit(np.zeros(16, np.int32))


def test_batcher_thread_safe_submit_with_ttft(model):
    cfg, params = model
    eng = GenerationEngine(cfg, params, slots=2, max_len=MAX_LEN,
                           compute_dtype=F32)
    batcher = ContinuousBatcher(eng)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, 8) for _ in range(12)]
    uids, ulock = [], threading.Lock()

    def submit(chunk):
        for p in chunk:
            uid = batcher.submit(p, max_new_tokens=3)
            with ulock:
                uids.append(uid)

    threads = [threading.Thread(target=submit, args=(prompts[i::4],))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # concurrent submits must never reuse a uid (the seed batcher bumped
    # _uid without a lock)
    assert sorted(uids) == list(range(1, 13))
    batcher.run_until_drained()
    assert len(batcher.completed) == 12
    for req in batcher.completed.values():
        assert req.first_token_at >= req.submitted_at
        assert req.finished_at >= req.first_token_at
        assert req.first_token_at > 0.0


# ------------------------------------------------- starvation regression

def _flood_ttfts(cfg, params, fair):
    """Greedy tenant floods 10 requests ahead of 2 steady ones; return the
    steady tenant's worst TTFT under the given scheduling mode."""
    eng = GenerationEngine(cfg, params, slots=2, max_len=MAX_LEN,
                           compute_dtype=F32)
    batcher = ContinuousBatcher(eng, scheduler=SlotScheduler(fair=fair))
    rng = np.random.default_rng(5)
    steady = []
    for _ in range(10):
        batcher.submit(rng.integers(0, cfg.vocab, 8), max_new_tokens=6,
                       tenant="greedy")
    for _ in range(2):
        steady.append(batcher.submit(rng.integers(0, cfg.vocab, 8),
                                     max_new_tokens=6, tenant="steady"))
    batcher.run_until_drained()
    return max(batcher.completed[uid].first_token_at
               - batcher.completed[uid].submitted_at for uid in steady)


def test_wrr_bounds_steady_tenant_ttft_under_flood(model):
    """The fig11 data-plane analog: under a greedy flood, WRR admission
    dispatches the steady tenant ahead of the backlog while FIFO serves it
    dead last — its TTFT must be strictly better under WRR."""
    cfg, params = model
    _flood_ttfts(cfg, params, fair=True)   # warm the XLA compile cache so
    fair = _flood_ttfts(cfg, params, fair=True)   # neither timed run pays it
    fifo = _flood_ttfts(cfg, params, fair=False)
    assert fair < fifo


# ------------------------------------------------- control→data plane bridge

def test_fleet_bridge_replicas_metrics_and_scaledown(model):
    cfg, params = model
    fleet = ServingFleet(
        lambda: GenerationEngine(cfg, params, slots=2, max_len=MAX_LEN,
                                 compute_dtype=F32),
        replicas=2, scan_interval=0.05)
    fw = VirtualClusterFramework(num_nodes=2, scan_interval=0.0,
                                 heartbeat_interval=3600)
    fleet.attach(fw)
    with fw:
        plane_a = fw.add_tenant("alpha", weight=2)
        fleet.register_tenant(plane_a)
        fleet.register_tenant("beta")
        with pytest.raises(PermissionError):
            fleet.submit("ghost", np.zeros(4, np.int32))
        # replicas ride the control plane: engine-0/1 WorkUnits scheduled
        # onto nodes, provider spawns the live engines
        assert wait_for(lambda: fleet.live_replicas() == 2, timeout=20)
        assert wait_for(lambda: all(
            u.status.phase == "Ready"
            for u in fw.super_api.list("WorkUnit", "vc-serving")), timeout=20)
        units = fw.super_api.list("WorkUnit", "vc-serving")
        assert sorted(u.metadata.name for u in units) == \
            ["engine-0", "engine-1"]
        rng = np.random.default_rng(6)
        for _ in range(4):
            fleet.submit("alpha", rng.integers(0, cfg.vocab, 8),
                         max_new_tokens=4)
        for _ in range(2):
            fleet.submit("beta", rng.integers(0, cfg.vocab, 8),
                         max_new_tokens=4)
        done = fleet.wait_completed(6, timeout=60)
        assert len(done) == 6
        assert all(r.done and len(r.tokens) == 4 for r in done.values())
        # per-tenant serving metrics landed in the shared registry
        snap = fw.metrics.snapshot()
        assert snap["summaries"]["serving_ttft_seconds{tenant=alpha}"][
            "count"] == 4
        assert snap["counters"]["serving_tokens_total{tenant=beta}"] == 8.0
        assert snap["counters"]["serving_requests_total{tenant=alpha}"] == 4.0
        assert snap["gauges"]["serving_live_replicas"] == 2.0
        assert snap["gauges"]["serving_pending_requests"] == 0.0
        # scale down: surplus unit deleted, its replica drained + retired
        fleet.resize(1)
        assert wait_for(lambda: fleet.live_replicas() == 1, timeout=20)
        assert wait_for(lambda: len(
            fw.super_api.list("WorkUnit", "vc-serving")) == 1, timeout=20)
        assert fleet.retired == 1


class _FailingStepEngine:
    """Engine stand-in whose decode step raises (a compile or device
    error on the replica's drive thread)."""
    slots = 1

    def __init__(self):
        self.req = None

    def free_slots(self):
        return [] if self.req is not None else [0]

    def admit_many(self, reqs):
        self.req = reqs[0] if reqs else None
        return reqs

    def active_slots(self):
        return int(self.req is not None)

    def step(self):
        raise RuntimeError("decode step failed")


def _fleet_rig(factory):
    fleet = ServingFleet(factory, replicas=1, scan_interval=0.05)
    fw = VirtualClusterFramework(num_nodes=1, scan_interval=0.0,
                                 heartbeat_interval=3600)
    fleet.attach(fw)
    return fleet, fw


def test_fleet_wait_completed_raises_when_drive_loop_fails():
    fleet, fw = _fleet_rig(_FailingStepEngine)
    with fw:
        fleet.register_tenant("alpha")
        fleet.wait_replicas(1, timeout=20)
        fleet.submit("alpha", np.zeros(4, np.int32))
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="decode step failed"):
            fleet.wait_completed(1, timeout=30)
        assert time.monotonic() - t0 < 1.0
        assert len(fleet.failures) == 1


def test_fleet_wait_replicas_raises_on_failed_engine_unit():
    def factory():
        raise MemoryError("engine does not fit")

    fleet, fw = _fleet_rig(factory)
    with fw:
        with pytest.raises(RuntimeError, match="engine does not fit"):
            fleet.wait_replicas(1, timeout=20)
        assert fleet.live_replicas() == 0


def test_agent_stops_deleted_units():
    """A DELETED WorkUnit reaches the node agent, which releases the
    provider's resources (and forgets the key so a recreate can run)."""
    stopped = []

    class RecordingProvider(MockProvider):
        def stop(self, unit):
            stopped.append(unit.metadata.key)

    fw = VirtualClusterFramework(
        num_nodes=1, scan_interval=0.0, heartbeat_interval=3600,
        provider_factory=lambda name: RecordingProvider())
    from repro.core import WorkUnit
    with fw:
        unit = WorkUnit()
        unit.metadata.name = "w0"
        unit.metadata.namespace = "default"
        fw.super_api.create(unit)
        agent = next(iter(fw.agents.values()))
        assert wait_for(lambda: "default/w0" in agent._running_units)
        fw.super_api.delete("WorkUnit", "default", "w0")
        assert wait_for(lambda: stopped == ["default/w0"])
        assert "default/w0" not in agent._running_units


# ------------------------------------------------- fourth actuator

class _FakeFleet:
    """Stands in for ServingFleet in actuator unit tests."""

    def __init__(self, replicas=1, pending=0):
        self.desired_replicas = replicas
        self.pending_n = pending
        self.resizes = []
        self.scheduler = self

    def pending(self):
        return self.pending_n

    def live_replicas(self):
        return self.desired_replicas

    def resize(self, n):
        self.resizes.append(n)
        self.desired_replicas = n
        return n


def _scaler_rig():
    ex = CooperativeExecutor(pool_size=2, name="srv-as-test")
    api = APIServer("super")
    syncer = Syncer(api, downward_workers=2, upward_workers=2,
                    scan_interval=0.0, shards=1, executor=ex)
    syncer.register_tenant(TenantControlPlane("t0"), "uid-0")
    syncer.start()
    policy = ScalingPolicy(min_engine_replicas=1, max_engine_replicas=4,
                           engine_up_pending=2.0, engine_down_pending=0.25,
                           engine_up_ttft_s=10.0, hysteresis=2,
                           up_cooldown_s=0.1, down_cooldown_s=0.2,
                           window_s=1.5)
    return ex, syncer, Autoscaler(syncer, None, policy=policy,
                                  interval=3600)


def test_engine_actuator_scales_fleet_up_and_down():
    ex, syncer, scaler = _scaler_rig()
    fleet = _FakeFleet(replicas=1, pending=10)
    try:
        scaler.set_engine_fleet(fleet)
        # backlog of 10 pending on 1 replica breaches for 2 ticks -> x2
        scaler.tick(now=0.0)
        scaler.tick(now=0.05)
        assert fleet.resizes == [2]
        assert scaler.scale_events()[-1]["actuator"] == "engine_replicas"
        assert scaler.scale_events()[-1]["direction"] == "up"
        # drain: pending drops to zero; after the down-cooldown the fleet
        # halves back toward the floor
        fleet.pending_n = 0
        t = 10.0
        while fleet.desired_replicas > 1 and t < 60.0:
            scaler.tick(now=t)
            t += 0.3
        assert fleet.desired_replicas == 1
        assert scaler.scale_events()[-1]["direction"] == "down"
    finally:
        syncer.stop()
        ex.shutdown()


def test_engine_actuator_absent_fleet_is_noop():
    ex, syncer, scaler = _scaler_rig()
    try:
        assert scaler.engine_fleet is None
        scaler.tick(now=0.0)
        scaler.tick(now=0.1)
        assert all(e["actuator"] != "engine_replicas"
                   for e in scaler.scale_events())
        assert scaler.state()["targets"]["engine_replicas"] is None
    finally:
        syncer.stop()
        ex.shutdown()
