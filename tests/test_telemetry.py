"""Flight recorder: spans/events, on-device round telemetry, audit chain
(ISSUE 8, DESIGN.md §11).

Contracts:

  * **disabled == free and silent** — no records, spans pass through,
    instrumented code paths unchanged.
  * **the audit chain binds** — every entry commits to its predecessor's
    digest; mutation, reordering, truncation-from-the-middle and forged
    prev-links are all detected, naming the first bad entry.
  * **SecureServer wires the log** — attestation, seals, guide-cache
    rebuilds and round tags appear as chained entries.
  * **the telemetry block matches the memory model** —
    ``metrics.round_telemetry_bytes`` == 4 bytes × the field count
    ``make_round_telemetry_fn`` actually emits for that config.
  * **telemetry does not perturb training** — histories bitwise-equal
    on/off (the sync-count half lives in tests/test_dispatch_eval.py).
  * **export/load roundtrip** — JSONL out, identical records + audit in.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attacks import AttackConfig
from repro.data import (FederatedData, make_classification,
                        partition_sorted_shards)
from repro.fl import (FLConfig, Federation, run_federated_training,
                      softmax_regression, telemetry, trace_counter)
from repro.fl.engine import TRACE_COUNTS
from repro.fl.metrics import round_telemetry_bytes
from repro.fl.telemetry import (AuditLog, GENESIS, Recorder,
                                make_round_telemetry_fn, verify_entries)
from repro.optim import inv_sqrt_lr

N_CLIENTS, DIM, N_CLASSES = 12, 8, 3


@pytest.fixture(scope="module")
def fed_data():
    x, y = make_classification(jax.random.PRNGKey(0), N_CLIENTS * 8,
                               N_CLASSES, DIM)
    data = FederatedData.from_partitions(
        partition_sorted_shards(x, y, N_CLIENTS), N_CLASSES)
    tx, ty = make_classification(jax.random.PRNGKey(9), 64, N_CLASSES, DIM)
    return data, tx, ty


def _cfg(**kw):
    kw.setdefault("n_clients", N_CLIENTS)
    kw.setdefault("f", 3)
    kw.setdefault("rounds", 4)
    kw.setdefault("batch_size", 2)
    kw.setdefault("eval_every", 2)
    kw.setdefault("l2", 0.0)
    kw.setdefault("attack", AttackConfig(kind="sign_flip"))
    return FLConfig(**kw)


def _train(fed_data, cfg):
    data, tx, ty = fed_data
    model = softmax_regression(input_dim=DIM, n_classes=N_CLASSES)
    fed = Federation.create(model, data, tx, ty, cfg, jax.random.PRNGKey(2))
    return run_federated_training(model, fed, cfg, inv_sqrt_lr(0.05)), fed


# ----------------------------------------------------------------------
# Recorder: spans + events
# ----------------------------------------------------------------------

def test_disabled_recorder_is_silent():
    rec = Recorder()
    rec.event("x", a=1)
    with rec.span("s"):
        pass
    assert rec.records == [] and not rec.enabled
    # the module-level API is equally inert outside recording()
    telemetry.event("orphan")
    with telemetry.span("orphan"):
        pass
    assert not telemetry.enabled()


def test_spans_nest_and_events_interleave():
    with telemetry.recording() as rec:
        with rec.span("outer", n=2):
            rec.event("tick", i=0)
            with rec.span("inner"):
                rec.event("tick", i=1)
    assert not rec.enabled                       # recording() stopped it
    kinds = [(r["type"], r.get("name") or r.get("kind")) for r in rec.records]
    # spans append at exit: inner closes before outer
    assert kinds == [("event", "tick"), ("event", "tick"),
                     ("span", "inner"), ("span", "outer")]
    inner = rec.records[2]
    outer = rec.records[3]
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]
    assert outer["n"] == 2
    assert [r.get("i") for r in rec.records] == [0, 1, None, None]


def test_recording_resets_between_uses():
    with telemetry.recording() as rec:
        rec.event("a")
    with telemetry.recording() as rec2:
        rec2.event("b")
    assert [r["kind"] for r in rec2.records] == ["b"]


# ----------------------------------------------------------------------
# trace_counter: the supported compile-count API
# ----------------------------------------------------------------------

def test_trace_counter_scoped_and_nested():
    with trace_counter() as outer:
        TRACE_COUNTS["segment"] += 2             # simulate two traces
        with trace_counter() as inner:
            TRACE_COUNTS["training"] += 1
        assert inner.snapshot() == {"segment": 0, "training": 1, "eval": 0}
        assert outer["segment"] == 2             # live read inside the block
    assert outer.total() == 3
    # the globals keep counting — the API never resets them
    assert TRACE_COUNTS["segment"] >= 2


# ----------------------------------------------------------------------
# AuditLog: the hash chain binds
# ----------------------------------------------------------------------

def _chain(n=5):
    log = AuditLog()
    for i in range(n):
        log.append("step", i=i)
    return log


def test_audit_chain_verifies_and_heads():
    log = AuditLog()
    assert log.head == GENESIS and bool(log.verify())
    log.append("attestation", measurement="m")
    log.append("seal", client=0)
    v = log.verify()
    assert v and v.entries == 2
    assert log.entries[0]["prev"] == GENESIS
    assert log.entries[1]["prev"] == log.entries[0]["digest"]
    assert log.head == log.entries[1]["digest"]
    assert log.counts() == {"attestation": 1, "seal": 1}


def test_audit_mutation_detected():
    entries = [dict(e) for e in _chain().entries]
    entries[2] = dict(entries[2], data={"i": 99})
    v = verify_entries(entries)
    assert not v and v.bad_index == 2 and "mutated" in v.reason


def test_audit_reorder_detected():
    entries = [dict(e) for e in _chain().entries]
    entries[1], entries[2] = entries[2], entries[1]
    assert not verify_entries(entries)


def test_audit_middle_deletion_detected():
    entries = [dict(e) for e in _chain().entries]
    del entries[2]
    assert not verify_entries(entries)
    # truncation from the END is *not* detectable from the list alone —
    # that is what committing the head digest elsewhere is for
    assert verify_entries(_chain().entries[:3])


def test_audit_forged_tail_detected():
    log = _chain(3)
    forged = dict(log.entries[-1])
    forged = {**forged, "index": 3, "data": {"i": 3}, "prev": "f" * 64}
    assert not verify_entries(log.entries + [forged])


def test_audit_malformed_entry_reported():
    v = verify_entries([{"kind": "x"}])
    assert not v and "malformed" in v.reason


# ----------------------------------------------------------------------
# SecureServer wiring
# ----------------------------------------------------------------------

def test_secure_server_audits_lifecycle(fed_data):
    cfg = _cfg(telemetry=True)
    h, fed = _train(fed_data, cfg)
    kinds = fed.server.audit.counts()
    assert kinds["attestation"] == 1
    assert kinds["seal"] == N_CLIENTS
    assert kinds["guide_cache_rebuild"] >= 1
    assert kinds["round_tags"] == cfg.rounds
    assert fed.server.audit.verify()
    tags = [e for e in fed.server.audit.entries if e["kind"] == "round_tags"]
    assert [e["data"]["round"] for e in tags] == [1, 2, 3, 4]
    for e in tags:
        assert e["data"]["kept"] + e["data"]["tagged"] == N_CLIENTS
    # drop after training extends the same chain
    fed.server.drop_client(0)
    assert fed.server.audit.verify()
    assert fed.server.audit.entries[-1]["kind"] == "drop"


def test_telemetry_off_appends_no_round_tags(fed_data):
    _, fed = _train(fed_data, _cfg())
    assert "round_tags" not in fed.server.audit.counts()
    assert fed.server.audit.verify()


# ----------------------------------------------------------------------
# the on-device block: fields, values, memory model
# ----------------------------------------------------------------------

def test_round_telemetry_fn_matches_reference():
    cfg = _cfg(telemetry=True)
    tel_fn = make_round_telemetry_fn(cfg)
    n = 6
    k = jax.random.PRNGKey(0)
    dot = jax.random.normal(k, (n,))
    z_sq = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (n,))) + 0.1
    g_sq = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (n,))) + 0.1
    from repro.core.diversefl import criterion_logs, diversefl_mask
    mask = diversefl_mask(dot, z_sq, g_sq, cfg.dfl)
    logs = {"mask": mask, "z_sq": z_sq, "g_sq": g_sq,
            **criterion_logs(dot, z_sq, g_sq)}
    t = jax.jit(tel_fn)(logs)                      # jittable by contract
    mask_np = np.asarray(mask)
    assert int(t["kept"]) == mask_np.sum()
    assert int(t["tagged"]) == n - mask_np.sum()
    assert int(t["c1_pass"]) == (np.asarray(dot) > 0).sum()
    c2 = np.asarray(logs["c2"])
    assert int(t["c2_pass"]) == ((c2 > cfg.dfl.eps2)
                                 & (c2 < cfg.dfl.eps3)).sum()
    np.testing.assert_allclose(float(t["upd_norm_mean"]),
                               np.sqrt(np.asarray(z_sq)).mean(), rtol=1e-6)
    np.testing.assert_allclose(float(t["guide_norm_max"]),
                               np.sqrt(np.asarray(g_sq)).max(), rtol=1e-6)


@pytest.mark.parametrize("agg,log_keys", [
    ("diversefl", ("mask", "c1", "c2", "c1c2", "z_sq", "g_sq")),
    ("oracle", ("mask",)),
    ("mean", ()),
])
def test_round_telemetry_bytes_matches_fn(agg, log_keys):
    """The §11 memory model and the actual block agree field-for-field:
    4 bytes per emitted scalar, independent of N."""
    cfg = _cfg(aggregator=agg, telemetry=True)
    logs = {k: jnp.ones((N_CLIENTS,)) for k in log_keys}
    fields = len(make_round_telemetry_fn(cfg)(logs))
    assert round_telemetry_bytes(cfg) == 4 * fields


# ----------------------------------------------------------------------
# end-to-end: bitwise histories, fallback reporting, export/load
# ----------------------------------------------------------------------

def test_histories_bitwise_with_telemetry(fed_data):
    h_off, _ = _train(fed_data, _cfg())
    with telemetry.recording():
        h_on, _ = _train(fed_data, _cfg(telemetry=True))
    assert h_off["round"] == h_on["round"]
    for k in ("acc", "mask_tpr", "mask_fpr", "final_acc"):
        assert np.array_equal(np.asarray(h_off[k]), np.asarray(h_on[k])), k
    for a, b in zip(h_off["c1c2"], h_on["c1c2"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = lambda p: np.concatenate(                            # noqa: E731
        [np.asarray(v).ravel() for v in jax.tree.leaves(p)])
    assert np.array_equal(flat(h_off["params"]), flat(h_on["params"]))


def test_streaming_fallback_reported_in_history(fed_data):
    # median cannot stream -> the reason lands on the history now, not
    # just the engine instance (ISSUE 8 satellite)
    h, _ = _train(fed_data, _cfg(aggregator="median", streaming=True,
                                 rounds=2))
    assert isinstance(h["streaming_fallback"], str)
    h2, _ = _train(fed_data, _cfg(rounds=2))
    assert h2["streaming_fallback"] is None


def test_export_load_roundtrip(tmp_path, fed_data):
    path = tmp_path / "run.jsonl"
    with telemetry.recording() as rec:
        h, fed = _train(fed_data, _cfg(telemetry=True))
        telemetry.export_jsonl(path, recorder=rec, audit=fed.server.audit,
                               meta={"suite": "test"})
    run = telemetry.load_jsonl(path)
    assert run["header"]["schema"] == telemetry.SCHEMA_VERSION
    assert run["header"]["meta"] == {"suite": "test"}
    assert verify_entries(run["audit"])
    assert run["audit"] == [
        {k: e[k] for k in ("index", "kind", "data", "prev", "digest")}
        for e in fed.server.audit.entries]
    assert len([e for e in run["events"] if e["kind"] == "sync"]) == 1
    assert len([e for e in run["events"] if e["kind"] == "round"]) == 4
    names = [s["name"] for s in run["spans"]]
    assert {"run_training", "fl.prepare", "fl.launch"} <= set(names)


def test_observe_cli_renders_and_verifies(tmp_path, fed_data, capsys):
    from repro.launch import observe

    path = tmp_path / "run.jsonl"
    with telemetry.recording() as rec:
        h, fed = _train(fed_data, _cfg(telemetry=True))
        telemetry.export_jsonl(path, recorder=rec, audit=fed.server.audit)
    assert observe.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "span waterfall" in out and "round timeline" in out
    assert "VERIFIED" in out
    assert observe.main([str(path), "--summary"]) == 0
    # a tampered file exits non-zero
    lines = path.read_text().splitlines()
    import json
    for i, line in enumerate(lines):
        rec_l = json.loads(line)
        if rec_l.get("type") == "audit" and rec_l["kind"] == "round_tags":
            rec_l["data"]["kept"] = 999
            lines[i] = json.dumps(rec_l)
            break
    bad = tmp_path / "tampered.jsonl"
    bad.write_text("\n".join(lines) + "\n")
    assert observe.main([str(bad)]) == 1
    assert "BROKEN" in capsys.readouterr().out
