"""The comparison that decides ``correct`` in a training cell, driven
through a whole run at a tiny size on four CPU devices (a 2x2 mesh), past
the harness's look for a chip.

A sound run is correct, and the float8 control fails its checks; each
fault planted underneath makes ``correct`` false: a step that
returns its state unchanged, half of the batch left out with the mean
taken over the rest, and the exchange between chips left out. (A training
run produces no token; its answers are its losses and its state.)

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_train_checks.py
"""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.harness import train  # noqa: E402

SEED = 2**33 + 7


def tiny():
    """The train cell's configuration and feed, cut to a size a CPU runs."""
    conf = json.loads(
        (ROOT / "bench/configs/internvl2-2b-fsdp2x2.json").read_text())
    conf.update({"hidden_size": 64, "num_attention_heads": 4,
                 "num_key_value_heads": 2, "intermediate_size": 128,
                 "vocab_size": 256, "num_hidden_layers": 2,
                 "reference_block": 16})
    conf["program"].update({"d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                            "head_dim": 16, "d_ff": 128, "vocab": 256,
                            "n_layers": 2, "frontend_tokens": 4,
                            "frontend_dim": 8})
    conf["check"]["limits"] = {"loss_gap": 0.01, "grad_norm_gap": 0.02,
                               "update_norm_gap": 0.02}
    mix = json.loads((ROOT / "bench/traffic/train4k.json").read_text())
    mix.update({"seq_len": 32, "global_batch": 4})
    return conf, mix


def run(hooks=None):
    if len(jax.devices()) < 4:
        pytest.skip("needs four host devices (bench/tests/conftest.py)")
    conf, mix = tiny()
    return train.run(conf=conf, mix=mix, seed=SEED, seconds=1.0, trace=False,
                     t_process=time.monotonic(), devices=jax.devices()[:4],
                     hooks=hooks)


def correct(r) -> bool:
    return all(c.ok for c in r.checks)


def first_half(mask):
    rows = jnp.arange(mask.shape[0])[:, None] < mask.shape[0] // 2
    return mask * rows


def test_sound_run_is_correct_and_the_control_is_not():
    """The float8 control, put in the program's place, fails the cell's
    own checks under the limits the run holds the program to."""
    conf, mix = tiny()
    seen = {}

    def after(mine, ref):
        low = train.reference_readings(conf, mix, SEED, jax.devices()[:4],
                                       len(ref.losses), low=True)
        seen.update(train.compare(low, ref))
        return {}

    r = run({"after": after})
    assert correct(r), [(c.name, c.value) for c in r.checks]
    assert r.steps > 0 and r.failed == 0
    got = r.extra["readings"]
    assert any(seen[k] > 3 * got[k] for k in got), (seen, got)
    control = train.reading_checks(conf, seen)
    assert not all(c.ok for c in control), [(c.name, c.value, c.limit)
                                            for c in control]


def test_step_that_returns_its_state_unchanged():
    from repro.training import make_train_step

    def frozen(cfg, opt_cfg, mesh):
        real = make_train_step(cfg, opt_cfg, mesh=mesh)
        return lambda p, o, b: (p, o, real(p, o, b)[2])

    r = run({"make_step": frozen})
    assert not correct(r)
    assert r.extra["readings"]["update_norm_gap"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out():
    def half(batch):
        return dict(batch, mask=first_half(batch["mask"]))

    r = run({"batch": half})
    assert not correct(r)


def test_exchange_between_chips_left_out():
    """Without its exchange over the 2-way data axis, the gradient a chip
    applies is the mean over the rows of its own data shard: planted in
    the step, as the first data shard's."""
    from repro.training import make_train_step

    def alone(cfg, opt_cfg, mesh):
        real = make_train_step(cfg, opt_cfg, mesh=mesh)
        return lambda p, o, b: real(p, o, dict(b, mask=first_half(b["mask"])))

    r = run({"make_step": alone})
    assert not correct(r)
