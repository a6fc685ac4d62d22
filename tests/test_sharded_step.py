"""Mesh integration tests — run in subprocesses so the multi-device
XLA host platform doesn't leak into the single-device unit tests."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-4000:]}"
    return p.stdout


def test_fl_round_step_filters_byzantine_and_learns():
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro import configs, models
    from repro.core.diversefl import DiverseFLConfig
    from repro.launch.train import make_fl_round_step
    from repro.sharding import partition_pytree

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = configs.get("gemma-2b", smoke=True)
    params = models.init(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), partition_pytree(params)))
    key = jax.random.PRNGKey(1)
    inputs = {
        "tokens": jax.random.randint(key, (8, 64), 0, cfg.vocab_size),
        "guide_tokens": jax.random.randint(key, (4, 1, 64), 0, cfg.vocab_size),
        "byz_kind": jnp.array([0, 1, 3, 0], jnp.int32),
        "rng": jnp.zeros((2,), jnp.uint32),
    }
    step = make_fl_round_step(cfg, mesh, DiverseFLConfig(), lr=0.1,
                              donate=False)
    p, m = step(params, inputs)
    mask = [bool(x) for x in m["mask"]]
    assert mask == [True, False, False, True], mask   # sign-flip + x5 caught
    l0 = float(m["loss"])
    for _ in range(5):
        p, m = step(p, inputs)
    assert float(m["loss"]) < l0, (l0, float(m["loss"]))
    print("OK", l0, float(m["loss"]))
    """)
    assert "OK" in out


def test_multipod_mesh_round_step():
    """3-axis (pod, data, model) mesh: the pod axis participates in client
    indexing and the masked aggregation psum."""
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro import configs, models
    from repro.core.diversefl import DiverseFLConfig
    from repro.launch.train import make_fl_round_step
    from repro.launch.mesh import n_clients
    from repro.sharding import partition_pytree

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    assert n_clients(mesh) == 4
    cfg = configs.get("deepseek-moe-16b", smoke=True)
    params = models.init(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), partition_pytree(params)))
    key = jax.random.PRNGKey(1)
    inputs = {
        "tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
        "guide_tokens": jax.random.randint(key, (4, 1, 32), 0, cfg.vocab_size),
        "byz_kind": jnp.array([0, 0, 1, 0], jnp.int32),
        "rng": jnp.zeros((2,), jnp.uint32),
    }
    step = make_fl_round_step(cfg, mesh, DiverseFLConfig(), lr=0.05,
                              donate=False)
    p, m = step(params, inputs)
    assert float(m["kept"]) == 3.0, float(m["kept"])
    # params stay replicated across clients: all client slices identical
    print("OK")
    """)
    assert "OK" in out


def test_serve_step_all_families_on_mesh():
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro import configs, models
    from repro.launch.shapes import InputShape, serve_inputs
    from repro.launch.serve import make_serve_step
    from repro.sharding import partition_pytree

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for aid in ["falcon-mamba-7b", "jamba-v0.1-52b", "whisper-medium"]:
        cfg = configs.get(aid, smoke=True)
        params = models.init(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), partition_pytree(params)))
        specs, _ = serve_inputs(cfg, InputShape("d", "decode", 64, 8), mesh)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             specs["cache"])
        step = make_serve_step(cfg, mesh, donate_cache=False)
        nt, _ = step(params, jnp.ones((8, 1), jnp.int32), cache, jnp.int32(3))
        assert nt.shape == (8, 1)
    print("OK")
    """)
    assert "OK" in out


def test_median_mode_round_step():
    """Cross-client baseline mode: coordinate median across clients
    neutralizes a sign-flipping minority (and exists to quantify its
    N x exchange cost at scale — EXPERIMENTS.md §Perf)."""
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro import configs, models
    from repro.core.diversefl import DiverseFLConfig
    from repro.launch.train import make_fl_round_step
    from repro.sharding import partition_pytree

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = configs.get("gemma-2b", smoke=True)
    params = models.init(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), partition_pytree(params)))
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (8, 64), 0, cfg.vocab_size)
    inputs = {
        "tokens": tokens,
        "guide_tokens": tokens.reshape(4, 2, 64)[:, :1],
        "byz_kind": jnp.array([0, 1, 0, 0], jnp.int32),
        "rng": jnp.zeros((2,), jnp.uint32),
    }
    step = make_fl_round_step(cfg, mesh, DiverseFLConfig(), lr=0.1,
                              donate=False, robust_mode="median")
    p, m = step(params, inputs)
    l0 = float(m["loss"])
    for _ in range(5):
        p, m = step(p, inputs)
    assert float(m["loss"]) < l0, (l0, float(m["loss"]))
    print("OK", l0, float(m["loss"]))
    """)
    assert "OK" in out


def test_dryrun_entrypoint_smoke():
    """The actual dryrun module (512 fake devices) on one cheap combo."""
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "h2o-danube-1.8b", "--shape", "decode_32k", "--mesh", "pod"],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "TF_CPP_MIN_LOG_LEVEL": "2"})
    assert p.returncode == 0, p.stderr[-4000:]
    assert "[ok  ]" in p.stdout
