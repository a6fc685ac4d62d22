"""``correct`` at a size the CPU holds: a sound run passes, and the
control and every fault a one-chip training cell can have fail.

The harness's look for a chip is skipped (the run gets the CPU device);
everything else is a whole run: set-up, window, reference, comparison.
The faults are planted in the program underneath the timed path:

* a step that returns its state unchanged (``run_training`` hands its
  input weights back);
* half of each batch left out, the mean taken over the rest (the
  model's loss sees the first half of its rows);
* an answer altered where it is produced (the Pallas fold adds every
  update at half its weight).

A one-chip cell has no exchange between chips to leave out.
"""
import types

import jax
import pytest

from bench import correct, reference, run
from bench import traffic as tr
from bench.tests import tiny

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


def run_tiny(cfgmod=None, seed=5):
    out = run.run_cell("tiny", tiny.conf(), tiny.traffic(),
                       cfgmod or tiny.cfgmod(), seed, 0.2, False,
                       jax.devices(), PEAKS, per_layer=[],
                       end_to_end=[{"name": "rounds_per_s"},
                                   {"name": "setup_s"}])
    assert out["attempted"] > 0 and out["failed"] == 0
    return out


def test_sound_run_is_correct():
    out = run_tiny(seed=2**33 + 1)
    assert out["correct"], out["compared"]
    assert list(out)[-1] == "compared"


def test_control_is_not_correct():
    """The reference in bfloat16 put in the program's place."""
    t = tiny.traffic()
    refer = reference.Reference(tiny.cfgmod(), tiny.conf(), t,
                                tr.federation_data(t, 256))
    ref, ctl = refer.readings(7), refer.readings(7, dtype="bfloat16")
    ok, _ = correct.judge(correct.numbers(ctl, ref), t["limits"])
    assert not ok


def test_unchanged_state_is_not_correct(monkeypatch):
    from repro.fl import RoundEngine
    orig = RoundEngine.run_training

    def stuck(self, params, key, lrs, scen=None):
        _, key, metrics, rounds = orig(self, params, key, lrs, scen)
        return params, key, metrics, rounds
    monkeypatch.setattr(RoundEngine, "run_training", stuck)
    out = run_tiny()
    assert not out["correct"]
    assert out["compared"]["grad1_gap"]["value"] == pytest.approx(1.0)


class HalfBatch:
    """The program's model, its loss taken over half of the rows."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def loss(self, params, x, y, l2=0.0):
        h = max(1, x.shape[0] // 2)
        return self._model.loss(params, x[:h], y[:h], l2)


def test_half_batch_is_not_correct():
    mod = tiny.cfgmod()
    half = types.SimpleNamespace(**{k: getattr(mod, k) for k in (
        "init_params", "reference_loss", "forward_flops")})
    half.build = lambda conf, traffic: HalfBatch(mod.build(conf, traffic))
    assert not run_tiny(half)["correct"]


def test_altered_fold_is_not_correct(monkeypatch):
    from repro.kernels import ops
    orig = ops.masked_agg_update
    monkeypatch.setattr(ops, "masked_agg_update",
                        lambda u, w, acc, **kw: orig(u, 0.5 * w, acc, **kw))
    assert not run_tiny()["correct"]
