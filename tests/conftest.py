import os

# Keep the default single CPU device for unit/smoke tests (the dry-run and
# the mesh integration tests set device counts in their own subprocesses).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import jax
import jax.numpy as jnp
import pytest

jax.config.update("jax_enable_x64", False)


def run_seed_loop(model, fed, cfg, lr_schedule):
    """The per-round reference the engine must reproduce bit for bit: the
    engine's own round body jitted one round at a time on the seed's
    ``key, sub = split(key)`` chain, its carry from ``RoundEngine``, and
    the eval on the host every ``eval_every`` rounds and after the last."""
    from repro.fl import RoundEngine, make_round_body, make_scenario
    from repro.fl.metrics import make_eval_fn
    engine = RoundEngine(model, fed, cfg)
    body = jax.jit(make_round_body(model, fed, cfg,
                                   client_chunk=cfg.client_chunk))
    eval_fn = jax.jit(make_eval_fn(model, fed, cfg))
    scen = make_scenario(cfg, fed)
    lrs = jax.vmap(lr_schedule)(jnp.arange(1, cfg.rounds + 1))
    key = jax.random.PRNGKey(cfg.seed)
    carry = engine.init_carry(model.init(jax.random.PRNGKey(cfg.seed + 1)))
    hist = {"round": [], "acc": [], "mask_tpr": [], "mask_fpr": [], "c1c2": []}
    for i in range(1, cfg.rounds + 1):
        key, sub = jax.random.split(key)
        carry, logs = body(carry, sub, lrs[i - 1].astype(jnp.float32), scen)
        if i % cfg.eval_every == 0 or i == cfg.rounds:
            hist["round"].append(i)
            metrics = eval_fn(engine.carry_params(carry), logs)
            for k, v in jax.device_get(metrics).items():
                hist.setdefault(k, []).append(v)
    hist["params"] = engine.carry_params(carry)
    return hist


@pytest.fixture(scope="session")
def seed_loop():
    """:func:`run_seed_loop`, for tests that compare the engine with it."""
    return run_seed_loop
