"""Flight recorder — structured telemetry, round tracing, enclave audit
(DESIGN.md §11).

The compiled engine (§5-§10) is deliberately a black box between
``run_training`` and the single ``host_sync``: nothing observable leaves
the device mid-run.  That is the right execution model and the wrong
observability model — the paper's core claim (the per-client C1/C2
criterion tags exactly the faulty clients) was only visible by digging
through raw history arrays, and production TEE-FL deployments (SecFL,
Separation-of-Powers in PAPERS.md) treat an inspectable trail as a
first-class requirement.  This module is that trail, in four parts:

  * **Spans + events** — a process-wide :class:`Recorder`.
    ``span(name)``/``event(...)`` emit structured records (monotonic
    wall time, kind, static metadata such as N/D/chunk/pods/codec).
    Recording is OFF by default and every instrumentation site is a
    cheap ``enabled()`` check, so the disabled recorder costs one
    attribute read — the instrumented seams (engine trace counters,
    ``simulator.host_sync``, sweep group compiles, streaming fallbacks)
    stay on the exact pre-telemetry code paths.  Every ``span`` also
    enters a ``jax.profiler.TraceAnnotation`` of the same name, recorder
    on or off, so under a profiler session the program's host spans land
    on the trace's host plane, on the device ops' clock.  While
    :func:`recording` is on, each backend compile adds a ``compile``
    event (its seconds, and whether the persistent cache served it).
  * **Stage scopes** — :data:`SCOPES` names the stages of a round; each
    is a ``jax.named_scope`` in the function that does the work, so every
    compiled op carries its stage in its ``op_name`` metadata and a
    device trace can be split by stage (DESIGN.md §11); inside them
    :data:`LAYER_SCOPES` names model layers (MLA, routed experts).
  * **On-device round telemetry** — :func:`make_round_telemetry_fn`
    builds the per-round telemetry block the engine accumulates
    *inside* the scan (C1/C2 pass counts, tagged-client popcount,
    update/guide norm summaries): a handful of device scalars per round
    riding the existing metric buffer, drained at the existing single
    ``host_sync``.  Zero new host round-trips — CI-gated by the
    dispatch bench's sync counter.
  * **Enclave audit log** — :class:`AuditLog`, an append-only
    hash-chained record (each entry commits to the previous digest) the
    ``SecureServer`` writes attestation, seal/unseal, guide-cache
    rebuilds and per-round tag decisions into.  ``verify_entries``
    recomputes the chain; ``launch/observe.py`` renders a recorded run
    (span waterfall, round tag timeline, comm columns) from the JSONL
    export and verifies the chain end-to-end.

**What is deliberately NOT recorded** (DESIGN.md §11): raw client
updates, guide samples, or anything derived from unsealed enclave data
beyond aggregate counts and norm summaries — the audit trail must be
publishable without weakening the trust boundary it documents.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

SCHEMA_VERSION = 1

# The stages of a round, each a ``jax.named_scope`` in the function that
# does the work (DESIGN.md §11).  An op belongs to the innermost of these
# that its ``op_name`` path holds; a device trace is split by them, so
# the names are a contract with its readers and do not change.
SCOPES = ("client_sgd", "attack", "flatten", "guide_sgd", "step4_filter",
          "step5_fold", "eval")

# The model layers the program names inside those stages (DESIGN.md
# §12): an op under one of these still belongs to its stage, so a split
# by stage does not move; a per-layer reading looks for the name itself.
LAYER_SCOPES = ("mla", "routed_experts")

# The hash chain's genesis digest: the first entry commits to this.
GENESIS = "0" * 64


# ----------------------------------------------------------------------
# Recorder — spans + events
# ----------------------------------------------------------------------

class Recorder:
    """Process-wide flight recorder: structured spans and events.

    Records are plain dicts (JSON-ready).  An **event** is a point in
    time: ``{"type": "event", "kind", "t", **meta}``.  A **span** is an
    interval: ``{"type": "span", "name", "t0", "t1", "dur", "depth",
    **meta}`` — ``depth`` is the nesting level at entry, which is all
    ``launch/observe.py`` needs to indent the waterfall.  Times are
    seconds since :meth:`start` (monotonic clock); the wall-clock epoch
    of ``t=0`` is kept once in :attr:`wall0` so exports stay
    correlatable across processes without every record paying a
    wall-clock read."""

    def __init__(self):
        self.enabled = False
        self.records: List[dict] = []
        self.wall0 = 0.0
        self._t0 = 0.0
        self._depth = 0

    # --- lifecycle ----------------------------------------------------
    def start(self) -> "Recorder":
        self.records = []
        self.enabled = True
        self.wall0 = time.time()
        self._t0 = time.monotonic()
        self._depth = 0
        return self

    def stop(self) -> None:
        self.enabled = False

    def now(self) -> float:
        return time.monotonic() - self._t0

    # --- emission -----------------------------------------------------
    def event(self, kind: str, **meta) -> None:
        if not self.enabled:
            return
        self.records.append({"type": "event", "kind": kind,
                             "t": round(self.now(), 6), **meta})

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        if not self.enabled:
            yield
            return
        rec = {"type": "span", "name": name, "t0": round(self.now(), 6),
               "depth": self._depth, **meta}
        self._depth += 1
        try:
            yield
        finally:
            self._depth -= 1
            rec["t1"] = round(self.now(), 6)
            rec["dur"] = round(rec["t1"] - rec["t0"], 6)
            self.records.append(rec)

    # --- introspection ------------------------------------------------
    def snapshot(self) -> List[dict]:
        """The records so far (a copy — safe to mutate/serialize)."""
        return [dict(r) for r in self.records]


_RECORDER = Recorder()


def get_recorder() -> Recorder:
    return _RECORDER


def enabled() -> bool:
    return _RECORDER.enabled


def event(kind: str, **meta) -> None:
    """Emit one event on the process recorder (no-op when disabled)."""
    _RECORDER.event(kind, **meta)


@contextlib.contextmanager
def span(name: str, **meta):
    """Open one span on the process recorder (no-op when disabled) and a
    profiler annotation of the same name (a no-op check when no profiler
    session is active)."""
    with jax.profiler.TraceAnnotation(name), _RECORDER.span(name, **meta):
        yield


@contextlib.contextmanager
def recording(path: Optional[str] = None, audit: Optional["AuditLog"] = None,
              **meta):
    """Enable the process recorder for the ``with`` body.

    ``path`` exports the flight record as JSONL on exit (including the
    ``audit`` log's hash chain when one is passed); the records also
    stay on the recorder for in-process inspection until the next
    :func:`recording`.  ``meta`` lands in the export header.

    While it is on, every backend compile emits a ``compile`` event:
    ``program`` (the compiled function's name), ``dur`` (seconds, a
    persistent-cache read included) and ``cache_hit``."""
    rec = _RECORDER.start()
    hits = []

    def on_event(event, **_):
        if event == _CACHE_HIT:
            hits.append(True)

    def on_duration(event, duration, fun_name="", **_):
        if event == _BACKEND_COMPILE:
            rec.event("compile", program=fun_name, dur=round(duration, 6),
                      cache_hit=bool(hits))
            hits.clear()

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield rec
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
        rec.stop()
        if path is not None:
            export_jsonl(path, recorder=rec, audit=audit, meta=meta)


# the events JAX records around each backend compile: the compile's
# duration (a persistent-cache read included), and a cache hit inside it
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


# ----------------------------------------------------------------------
# On-device round telemetry — the block the engine scan accumulates
# ----------------------------------------------------------------------

def make_round_telemetry_fn(cfg):
    """Build ``tel_fn(logs) -> {name: device scalar}`` — the per-round
    telemetry block ``RoundEngine`` accumulates inside the training scan
    when ``cfg.telemetry`` is on.

    The block is a *pure function of the round's log dict* (the same
    logs the eval tail reads), so it adds reductions, never new
    computation paths: ``kept``/``tagged`` popcount the aggregator's
    keep-mask, ``c1_pass``/``c2_pass`` count clients passing each
    DiverseFL criterion against ``cfg.dfl``'s thresholds, and the
    update/guide norm summaries reduce the ``z_sq``/``g_sq`` statistics
    the DiverseFL rules already compute (and now log).  Which keys exist
    is static per config — exactly like ``make_eval_fn``'s metric set —
    so the block has a fixed structure the scan can stack.  Everything
    is int32 counts or one fp32 sqrt/mean at the end: a few dozen bytes
    per round (``fl/metrics.round_telemetry_bytes`` is the exact
    model), accumulated on device and drained at the one host sync."""
    dfl = cfg.dfl

    def tel_fn(logs):
        t: Dict[str, Any] = {}
        if "mask" in logs:
            mask = logs["mask"].astype(bool)
            if "cand" in logs:
                # async rounds: only rows that actually participated
                # (live cohort + landed stale updates) count — slot
                # rows that landed nothing are neither kept nor tagged
                cand = logs["cand"].astype(bool)
                kept = jnp.sum((mask & cand).astype(jnp.int32))
                t["kept"] = kept
                t["tagged"] = jnp.sum((cand & ~mask).astype(jnp.int32))
            else:
                kept = jnp.sum(mask.astype(jnp.int32))
                t["kept"] = kept
                t["tagged"] = jnp.int32(mask.shape[0]) - kept
        if "nonfinite" in logs:
            # the streaming fold's non-finite guard: clients whose
            # update arrived NaN/Inf and was masked to zero weight
            t["nonfinite"] = jnp.sum(
                logs["nonfinite"].astype(jnp.int32))
        for k in ("cohort", "stale_buffered", "stale_folded",
                  "stale_expired"):
            if k in logs:
                t[k] = logs[k].astype(jnp.int32)
        if "c1" in logs:
            # c1 = sign(dot): the paper's eps1=0 direction test passes
            # iff the sign is positive (Eq. 2/4)
            t["c1_pass"] = jnp.sum((logs["c1"] > 0).astype(jnp.int32))
        if "c2" in logs:
            c2 = logs["c2"]
            t["c2_pass"] = jnp.sum(
                ((c2 > dfl.eps2) & (c2 < dfl.eps3)).astype(jnp.int32))
        if "z_sq" in logs:
            zn = jnp.sqrt(logs["z_sq"].astype(jnp.float32))
            t["upd_norm_mean"] = jnp.mean(zn)
            t["upd_norm_max"] = jnp.max(zn)
        if "g_sq" in logs:
            gn = jnp.sqrt(logs["g_sq"].astype(jnp.float32))
            t["guide_norm_mean"] = jnp.mean(gn)
            t["guide_norm_max"] = jnp.max(gn)
        return t

    return tel_fn


# ----------------------------------------------------------------------
# Enclave audit log — append-only, hash-chained
# ----------------------------------------------------------------------

def _canonical(obj) -> str:
    """Deterministic JSON: the byte string the chain digests commit to."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def entry_digest(index: int, kind: str, data: dict, prev: str) -> str:
    """sha256 over (previous digest ‖ canonical entry body)."""
    body = _canonical({"index": index, "kind": kind, "data": data})
    return hashlib.sha256((prev + body).encode()).hexdigest()


@dataclasses.dataclass(frozen=True)
class AuditVerdict:
    ok: bool
    entries: int
    bad_index: int = -1          # first entry whose digest fails (-1: none)
    reason: str = ""

    def __bool__(self):
        return self.ok


class AuditLog:
    """Append-only hash-chained log of enclave-side decisions.

    Each entry is ``{"index", "kind", "data", "prev", "digest"}`` with
    ``digest = sha256(prev ‖ canonical_json({index, kind, data}))`` and
    entry 0 committing to the :data:`GENESIS` digest — so any mutation,
    deletion or reordering of a committed entry breaks every digest
    after it.  ``data`` values must be JSON-serializable scalars (the
    SecureServer only logs ids, counts, versions and measurements —
    never samples or updates).  This is the simulation analogue of
    SecFL's attested aggregation log: the aggregator cannot silently
    rewrite which clients it tagged."""

    def __init__(self):
        self.entries: List[dict] = []

    def append(self, kind: str, **data) -> dict:
        prev = self.entries[-1]["digest"] if self.entries else GENESIS
        index = len(self.entries)
        entry = {"index": index, "kind": kind, "data": data, "prev": prev,
                 "digest": entry_digest(index, kind, data, prev)}
        self.entries.append(entry)
        return entry

    @property
    def head(self) -> str:
        """The chain head digest (GENESIS when empty) — committing to it
        commits to the whole log."""
        return self.entries[-1]["digest"] if self.entries else GENESIS

    def verify(self) -> AuditVerdict:
        return verify_entries(self.entries)

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.entries:
            out[e["kind"]] = out.get(e["kind"], 0) + 1
        return out


def verify_entries(entries: List[dict]) -> AuditVerdict:
    """Recompute the hash chain of a (possibly deserialized) entry list.

    Checks, per entry: the index is sequential, ``prev`` equals the
    previous entry's digest (GENESIS for entry 0), and the stored digest
    matches the recomputed one.  Returns an :class:`AuditVerdict`
    (truthy iff the chain verifies) naming the first bad entry."""
    prev = GENESIS
    for i, e in enumerate(entries):
        try:
            if e["index"] != i:
                return AuditVerdict(False, len(entries), i,
                                    f"index {e['index']} != position {i}")
            if e["prev"] != prev:
                return AuditVerdict(False, len(entries), i,
                                    "prev digest does not chain")
            want = entry_digest(i, e["kind"], e["data"], prev)
            if e["digest"] != want:
                return AuditVerdict(False, len(entries), i,
                                    "digest mismatch (entry mutated)")
            prev = e["digest"]
        except (KeyError, TypeError) as exc:
            return AuditVerdict(False, len(entries), i,
                                f"malformed entry: {exc}")
    return AuditVerdict(True, len(entries))


# ----------------------------------------------------------------------
# JSONL export / import — what launch/observe.py renders
# ----------------------------------------------------------------------

def export_jsonl(path, recorder: Optional[Recorder] = None,
                 audit: Optional[AuditLog] = None,
                 meta: Optional[dict] = None) -> None:
    """Write one recorded run as JSONL: a header line (schema version,
    wall-clock epoch, run metadata), then every span/event record, then
    the audit chain entries (``"type": "audit"``)."""
    rec = recorder if recorder is not None else _RECORDER
    lines = [{"type": "header", "schema": SCHEMA_VERSION,
              "wall0": rec.wall0, "meta": meta or {}}]
    lines += rec.snapshot()
    if audit is not None:
        lines += [{"type": "audit", **e} for e in audit.entries]
    with open(path, "w") as f:
        for line in lines:
            f.write(_canonical(line) + "\n")


def load_jsonl(path) -> Dict[str, Any]:
    """Load an exported run: ``{"header", "spans", "events", "audit"}``
    (audit entries stripped back to the shape :func:`verify_entries`
    checks)."""
    header, spans, events, audit = {}, [], [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            kind = rec.get("type")
            if kind == "header":
                header = rec
            elif kind == "span":
                spans.append(rec)
            elif kind == "event":
                events.append(rec)
            elif kind == "audit":
                audit.append({k: rec[k] for k in
                              ("index", "kind", "data", "prev", "digest")})
    return {"header": header, "spans": spans, "events": events,
            "audit": audit}
