"""Stage scopes and profiler-clock spans (DESIGN.md §11).

Contracts:

  * **every stage is named in the compiled round** — for a dense and a
    streaming DiverseFL federation under a sign-flip attack, with the
    Pallas kernels (interpret mode off the TPU), each name of
    ``telemetry.SCOPES`` is a component of some op's ``op_name`` in the
    executable ``RoundEngine.lower_training`` gives;
  * **the kernels sit in their stages** — the similarity kernel's
    innermost stage is ``step4_filter``, the fold's ``step5_fold``, and
    every ``pallas_call`` carries its kernel's name;
  * **one list** — every ``jax.named_scope`` the program opens is a name
    of ``telemetry.SCOPES`` (stages) or ``telemetry.LAYER_SCOPES`` (model
    layers inside them);
  * **spans reach the profiler** — a ``telemetry.span`` recorded under
    ``jax.profiler`` is an event of the trace's host plane, recorder on
    or off, and ``recording()`` turns each backend compile into a
    ``compile`` event.
"""
import glob
import os
import re
from pathlib import Path

import jax
import pytest

from repro.core.attacks import AttackConfig
from repro.data import (FederatedData, make_classification,
                        partition_sorted_shards)
from repro.fl import (FLConfig, Federation, RoundEngine, softmax_regression,
                      telemetry)

N_CLIENTS, DIM, N_CLASSES = 6, 16, 4
SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


@pytest.fixture(scope="module")
def fed_data():
    x, y = make_classification(jax.random.PRNGKey(0), N_CLIENTS * 8,
                               N_CLASSES, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N_CLIENTS), N_CLASSES)
    tx, ty = make_classification(jax.random.PRNGKey(9), 32, N_CLASSES, DIM)
    return data, tx, ty


def _op_names(fed_data, **kw):
    """The op_name of each instruction of the compiled training
    program."""
    data, tx, ty = fed_data
    cfg = FLConfig(n_clients=N_CLIENTS, f=2, rounds=2, batch_size=2,
                   eval_every=2, l2=0.0, attack=AttackConfig(kind="sign_flip"),
                   use_kernel_stats=True, use_kernel_agg=True,
                   client_chunk=4, **kw)
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    engine = RoundEngine(model, fed, cfg)
    text = engine.lower_training(model.init(jax.random.PRNGKey(1)),
                                 jax.random.PRNGKey(3),
                                 [0.1, 0.1]).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _path(op_name):
    """The components of an op_name path, transform wrappers such as
    ``vmap(client_sgd)`` read as the name inside."""
    out = []
    for c in op_name.split("/"):
        while (m := re.fullmatch(r"[\w.-]+\((.*)\)", c)):
            c = m.group(1)
        out.append(c)
    return out


def _stages(op_name):
    return [c for c in _path(op_name) if c in telemetry.SCOPES]


@pytest.mark.parametrize("streaming", [False, True],
                         ids=["dense", "streaming"])
def test_every_stage_is_named_in_the_compiled_round(fed_data, streaming):
    names = _op_names(fed_data, streaming=streaming)
    seen = {s for n in names for s in _stages(n)}
    assert seen == set(telemetry.SCOPES)


@pytest.mark.parametrize("streaming", [False, True],
                         ids=["dense", "streaming"])
def test_kernels_sit_in_their_stages(fed_data, streaming):
    """The innermost stage around each kernel's ops (named by the
    kernel's ``name=``; off the TPU the interpreter's ops sit under it)
    is its own: the dense fused Step 4+5 runs inside
    ``SecureServer.aggregate``'s step5_fold, and its similarity half is
    still Step 4."""
    names = _op_names(fed_data, streaming=streaming)
    for kernel, stage in (("similarity", "step4_filter"),
                          ("masked_agg", "step5_fold")):
        around = [[c for c in p[:p.index(kernel)] if c in telemetry.SCOPES]
                  for p in map(_path, names) if kernel in p]
        assert around and all(a[-1] == stage for a in around), kernel


def test_pallas_calls_carry_their_kernel_names():
    for path in sorted((SRC / "kernels").glob("*.py")):
        src = path.read_text()
        if "pl.pallas_call(" in src:
            assert f'name="{path.stem}"' in src, path.name


def test_every_named_scope_is_a_listed_stage():
    used = set()
    for path in SRC.rglob("*.py"):
        used |= set(re.findall(r'named_scope\("([^"]+)"\)',
                               path.read_text()))
    assert used == set(telemetry.SCOPES) | set(telemetry.LAYER_SCOPES)
    names = telemetry.SCOPES + telemetry.LAYER_SCOPES
    assert len(set(names)) == len(names)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    return [e.name for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events]


@pytest.mark.parametrize("recorder", [False, True], ids=["off", "on"])
def test_span_lands_on_the_profiler_host_plane(tmp_path, recorder):
    jax.profiler.start_trace(str(tmp_path))
    try:
        if recorder:
            with telemetry.recording() as rec:
                with telemetry.span("fl.probe_span"):
                    jax.block_until_ready(jax.numpy.ones(3) * 2)
            assert [r["name"] for r in rec.records
                    if r["type"] == "span"] == ["fl.probe_span"]
        else:
            with telemetry.span("fl.probe_span"):
                jax.block_until_ready(jax.numpy.ones(3) * 2)
    finally:
        jax.profiler.stop_trace()
    assert "fl.probe_span" in _host_events(str(tmp_path))


def test_recording_counts_compiles():
    def fresh(x):
        return x * 3.0 + 1.0

    with telemetry.recording() as rec:
        jax.block_until_ready(jax.jit(fresh)(jax.numpy.ones(5)))
    comp = [r for r in rec.records if r.get("kind") == "compile"]
    assert comp[-1]["program"] == "jit(fresh)"
    assert all(r["dur"] >= 0.0 and isinstance(r["cache_hit"], bool)
               for r in comp)
    # the listeners leave with the recording: no event afterwards
    n = len(rec.records)
    jax.block_until_ready(jax.jit(lambda x: x - 7.0)(jax.numpy.ones(5)))
    assert len(rec.records) == n
