"""Memory envelopes of the round program, scale-free.

The streaming fold exists so that a round's working set does not grow
with the cohort: updates and guides live one ``client_chunk`` block at
a time, never as (N, D) matrices (DESIGN.md §6, §9, §10, §13).  Each
case compiles — and does not run — the one-dispatch training program at
two cohort sizes and reads XLA's temp bytes from
``RoundEngine.lower_training(...).compile().memory_analysis()``.  The
growth from the small cohort to the large one, per added client, is
counted in update rows (4·D bytes, one float32 client update):

  * a streaming program grows by at most ``SLACK_ROWS`` rows per client
    beyond the rows its carried state is allowed — only its O(N)
    per-client scalars and minibatches scale with N (measured at most
    0.0004 rows on the CPU);
  * the dense (N, D) program grows by at least one row per client
    (measured about 3), so the measure can tell the two apart.

The int8 codec's error-feedback residual is an (N, D) plane by
definition; its round program holds two copies of the cohort's rows
(the gather ``resid[sel]`` and the scatter back), so that case is
allowed exactly those two rows per client and nothing for the fold.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.attacks import AttackConfig
from repro.data import FederatedData
from repro.fl import FaultConfig, Federation, FLConfig, RoundEngine
from repro.fl.small_models import mlp3

DIM, N_CLASSES, HIDDEN = 8, 4, 256          # D = 69,124: rows >> minibatches
SMALL, LARGE = 64, 512
SLACK_ROWS = 0.01

MODEL = mlp3(input_dim=DIM, n_classes=N_CLASSES, hidden=HIDDEN)


def _federation(n):
    kx, ky, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    data = FederatedData(x=jax.random.normal(kx, (n, 8, DIM)),
                         y=jax.random.randint(ky, (n, 8), 0, N_CLASSES),
                         n_classes=N_CLASSES)
    tx = jax.random.normal(kt, (16, DIM))
    ty = jnp.arange(16) % N_CLASSES
    return Federation.create(MODEL, data, tx, ty, _cfg(n),
                             jax.random.PRNGKey(2))


def _cfg(n, **kw):
    return FLConfig(n_clients=n, f=n // 8, rounds=1, eval_every=1,
                    batch_size=2, l2=0.0, sample_frac=0.25,
                    attack=AttackConfig(kind="sign_flip"), **kw)


@pytest.fixture(scope="module")
def setup():
    """The weights and one federation per cohort size, shared by every
    case: what a federation holds (the data, the sealed samples, the
    Byzantine mask) reads no field a case sets."""
    return MODEL.init(jax.random.PRNGKey(1)), {
        n: _federation(n) for n in (SMALL, LARGE)}


def _temp_bytes(setup, n, **kw):
    """XLA temp bytes of one round of the one-dispatch program at cohort
    size ``n``, compiled for this backend and never run."""
    params, feds = setup
    engine = RoundEngine(MODEL, feds[n], _cfg(n, **kw))
    lowered = engine.lower_training(params, jax.random.PRNGKey(0),
                                    jnp.full((1,), 0.05, jnp.float32))
    return lowered.compile().memory_analysis().temp_size_in_bytes


def _rows_per_client(setup, **kw):
    grow = _temp_bytes(setup, LARGE, **kw) - _temp_bytes(setup, SMALL, **kw)
    row_bytes = 4 * sum(p.size for p in jax.tree.leaves(setup[0]))
    return grow / (LARGE - SMALL) / row_bytes


STREAM = {"streaming": True, "client_chunk": 8}


@pytest.mark.parametrize("kw,low,high", [
    pytest.param(STREAM, 0, SLACK_ROWS, id="streaming"),
    pytest.param(dict(STREAM, pods=2), 0, SLACK_ROWS, id="pods2"),
    pytest.param(dict(STREAM, compression="int8"), 0, 2 + SLACK_ROWS,
                 id="int8"),
    pytest.param(dict(STREAM, staleness_buffer=4,
                      fault=FaultConfig(kind="straggler", rate=0.25,
                                        delay=1)), 0, SLACK_ROWS,
                 id="async_slab"),
    pytest.param({}, 1, float("inf"), id="dense_grows"),
])
def test_round_temp_growth_with_cohort(setup, kw, low, high):
    rows = _rows_per_client(setup, **kw)
    assert low <= rows <= high, (
        f"{kw}: temps grow {rows:.4f} update rows per added client, "
        f"outside [{low}, {high}]")
