"""The comparison that decides ``correct``, driven through a whole serving
run at a tiny size on the CPU, past the harness's look for a chip.

A sound run is correct; the float8 control fails the cell's check; and
each fault planted in the timed path underneath makes ``correct`` false:
a decode step that returns its state unchanged, half of an admitted batch
left out, a token altered where it is produced, and deletes the control
plane loses. (One chip: there is no exchange between chips to leave out.)

A configuration of sparse experts (``bench/tests/data/tiny-moe.json``)
enters as files alone: its weights from its own rules, its reference
handed every key of its configuration but the program's, and a whole
serving run of it finishes with every check read.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_checks.py
"""
import json
import math
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.harness import serve  # noqa: E402

SECONDS = 3.0


def tiny():
    """The chat cell's configuration and mix, cut to a size a CPU runs."""
    conf = json.loads((ROOT / "bench/configs/qwen2-7b-d4.json").read_text())
    small = {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 2, "intermediate_size": 128,
             "vocab_size": 256, "num_hidden_layers": 2}
    conf.update(small)
    conf["program"].update({"d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                            "head_dim": 16, "d_ff": 128, "vocab": 256,
                            "n_layers": 2})
    conf["serve"].update({"slots": 4, "max_len": 64, "replicas": 2})
    # set as the cell's is, from this size's own readings on 3 seeds: sound
    # runs 0.009-0.015, the float8 control 0.069-0.168
    conf["check"]["logit_gap_limit"] = 0.03
    mix = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    mix.update({"rate_rps": 4.0, "drain_s": 30.0})
    mix["prompt_len"].update({"median": 12, "min": 4, "max": 40})
    mix["output_len"].update({"median": 6, "min": 3, "max": 16})
    mix["control_plane"].update({"nodes": 8, "create_rps": 10.0})
    return conf, mix


def run(hooks=None, seed=2**31 + 12):
    conf, mix = tiny()
    return serve.run(conf=conf, mix=mix, seed=seed, seconds=SECONDS,
                     trace=False, t_process=time.monotonic(),
                     devices=jax.devices(), hooks=hooks)


def correct(r) -> bool:
    return all(c.ok for c in r.checks)


class Wrap:
    """An engine with one call replaced; everything else passes through."""

    def __init__(self, eng):
        self._eng = eng

    def __getattr__(self, name):
        return getattr(self._eng, name)


def test_sound_run_is_correct_and_the_control_is_not():
    """The float8 control, put in the program's place on the same prompts
    and tokens, fails the cell's own check under the cell's own limit."""
    conf, mix = tiny()
    seen = {}

    def after(params, sample, requests):
        gaps = serve.logit_gaps(params, conf, mix,
                                [(q["prompt"], q["tokens"]) for q in sample],
                                control=True)
        seen["control"] = serve.gap_check(conf, max(gaps))
        return {}

    r = run({"after": after})
    gap = next(c for c in r.checks if c.name == "logit_gap")
    assert correct(r), [(c.name, c.value) for c in r.checks]
    assert r.extra["sampled_tokens"] > 20
    control = seen["control"]
    assert control.limit == conf["check"]["logit_gap_limit"]
    assert math.isfinite(control.value) and control.value > gap.value
    assert not control.ok, (control.value, control.limit)


def test_token_altered_where_produced():
    class Alter(Wrap):
        def step(self):
            live = [q for q in self._eng.slot_req if q is not None]
            done = self._eng.step()
            for q in live:
                if len(q.tokens) == 3:
                    q.tokens[-1] = (q.tokens[-1] + 1) % 256
            return done

    r = run({"engine": Alter})
    assert not correct(r)
    assert next(c for c in r.checks if c.name == "logit_gap").value > 0.1


def test_step_that_returns_its_state_unchanged():
    class Frozen(Wrap):
        def __init__(self, eng):
            super().__init__(eng)
            real = eng._step_fn

            def frozen(params, cache, lengths, budget, active, last):
                copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
                out = real(params, copy(cache), copy(lengths), budget,
                           active, copy(last))
                return (cache, lengths, out[2], out[3], last, out[5], out[6])
            eng._step_fn = frozen

    r = run({"engine": Frozen})
    assert not correct(r)


def test_half_of_the_batch_left_out():
    class Half(Wrap):
        """Admits half of the requests handed to it (every other one, as
        they arrive) and reports the rest admitted too."""

        def admit_many(self, reqs):
            keep = [q for q in reqs if q.uid % 2]
            return self._eng.admit_many(keep) + [q for q in reqs
                                                 if not q.uid % 2]

    r = run({"engine": Half})
    assert not correct(r)
    assert next(c for c in r.checks
                if c.name == "unfinished_requests").value > 0


def test_control_plane_loses_deletes(monkeypatch):
    from repro.core.apiserver import APIClient
    real = APIClient.delete

    def lossy(self, kind, namespace, name):
        if kind == "WorkUnit" and namespace == "bench":
            return None
        return real(self, kind, namespace, name)

    monkeypatch.setattr(APIClient, "delete", lossy)
    r = run()
    assert not correct(r)
    assert next(c for c in r.checks
                if c.name == "units_left_after_delete").value > 0


def moe_conf():
    return json.loads((ROOT / "bench/tests/data/tiny-moe.json").read_text())


def test_reference_is_handed_the_whole_configuration(monkeypatch):
    """What ``logits`` receives: every top-level key of the configuration
    file except ``program``, the experts per token among them."""
    from bench.harness.weights import make_params
    conf, (_, mix) = moe_conf(), tiny()
    seen = []

    class Stub:
        @staticmethod
        def logits(params, conf, tokens, read, low=False):
            seen.append(conf)
            return jnp.zeros((read.shape[0], conf["vocab_size"]))

    monkeypatch.setattr(serve, "load_reference", lambda name: Stub)
    serve._gap_fns.cache_clear()
    try:
        gaps = serve.logit_gaps(make_params(conf, 5), conf, mix,
                                [([1, 2, 3], [4, 5])])
    finally:
        serve._gap_fns.cache_clear()
    assert gaps == [0.0, 0.0]
    assert seen and all(c == {k: v for k, v in conf.items()
                              if k != "program"} for c in seen)
    assert seen[0]["num_experts_per_tok"] == 2


def test_sparse_expert_configuration_serves_a_whole_run():
    """tiny-moe through the whole serving path with its own rules and its
    own reference. Every check is read and finite; ``correct`` is not
    asserted, since the program's capacity dispatch may drop a token that
    its experts overflow."""
    _, mix = tiny()
    r = serve.run(conf=moe_conf(), mix=mix, seed=2**31 + 12,
                  seconds=SECONDS, trace=False, t_process=time.monotonic(),
                  devices=jax.devices())
    assert {c.name for c in r.checks} >= {"logit_gap", "unfinished_requests"}
    assert all(math.isfinite(c.value) for c in r.checks), \
        [(c.name, c.value) for c in r.checks]
    assert r.extra["sampled_tokens"] > 20


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
