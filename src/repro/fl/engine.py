"""Scan-compiled, chunked, mesh-sharded federated round engine.

The seed simulator dispatched one jitted call per round and vmapped
client local training over the *entire* federation, so (i) every round
paid a Python dispatch + host sync and (ii) peak memory was
O(N x model) — N capped at what one device holds.  The engine removes
both limits while keeping the round math — Algorithm 1 Steps 2-5 —
byte-identical to the per-round path:

  * **Scan segmentation** — ``eval_every`` rounds compile into a single
    donated ``jax.lax.scan``: one dispatch and one host sync per eval
    segment.  Per-round RNG subkeys and learning rates are precomputed
    host-side with exactly the legacy ``key, sub = split(key)`` chain,
    so the scan consumes the same key sequence the Python loop would.
  * **Client chunking** — local training and guiding updates run in
    ``client_chunk``-sized blocks via ``jax.lax.map``
    (fl/chunking.chunked_vmap), so a 1000-client federation peaks at
    O(chunk x model) working memory while still producing the stacked
    (N, D) update matrix the aggregator registry expects.  Guides are
    threaded through ``SecureServer.compute_guides`` — the enclave stays
    the only source of guide data.
  * **Client-axis sharding** — when a mesh is active the client axis of
    the stacked batches/updates is sharded over the ``("data",)`` axes
    via sharding/api.py NamedShardings, unifying the simulator's
    semantics with launch/train.py's one-client-per-mesh-coordinate
    shard_map path.

``make_round_body`` is the single round-step definition: the legacy
per-round path (fl/simulator.py, the benchmark baseline) jits it
directly; the engine scans it.  Equivalence is enforced by
tests/test_engine.py.
"""
from __future__ import annotations

import contextlib
import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import aggregators as agg
from ..core.attacks import (UPDATE_ATTACKS, attack_update, flip_labels,
                            make_byzantine_mask, poison_backdoor)
from ..sharding import (flatten_updates_sharded, get_mesh,
                        model_shard_count, place_params, ravel_sharded,
                        shard_clients, shard_flat, shard_params,
                        shard_updates, sweep_put, use_mesh)
from . import telemetry
from .chunking import chunked_vmap
from .compression import encode_with_feedback, get_codec
from .faults import (corrupt_updates, draw_faults, init_async_state,
                     make_cohort_chain, validate_cohort_chain)
from .metrics import make_eval_fn
from .server import AggregationContext, get_aggregator
from .streaming import fallback_reason, get_streaming, stream_aggregate

logger = logging.getLogger(__name__)


# ----------------------------------------------------------------------
# Scenario operands — the per-run values that are data, not structure.
# ----------------------------------------------------------------------

# fold constant separating the cohort chain's RNG stream from the
# training chain (both start at PRNGKey(cfg.seed))
_COHORT_FOLD = 0x0C0407


def make_scenario(cfg, fed=None, byz_mask=None, cohort=None):
    """The round body's *traced* per-run operands as a pytree.

    ``sigma``/``scale`` are the attack magnitudes (f32 scalars) and
    ``byz`` the (N,) Byzantine identity mask — everything about a run
    that changes its *numbers* without changing its *trace*.  Baking
    them into the jaxpr (the pre-sweep status quo) meant any sigma
    change recompiled and no two runs could batch; as operands, a run
    is one point on a vmappable scenario axis (fl/sweep.py) and
    magnitude changes are jit cache hits (DESIGN.md §8).

    ``byz_mask`` overrides; else ``fed.byz_mask`` (the federation's
    ground truth — what every solo path uses); else the deterministic
    ``make_byzantine_mask(n_clients, f)`` a ``Federation.create`` with
    this cfg would have produced (what sweep cells use, so a batched
    cell and its solo twin see the same bits).

    With ``cfg.cohort_participation`` set, the scenario additionally
    carries ``"cohort"`` — the precomputed ``(R, N)`` per-round
    participation-mask chain (fl/faults.make_cohort_chain), derived
    deterministically from ``cfg.seed`` on an RNG stream folded away
    from the training chain.  An explicit ``cohort`` overrides and is
    validated host-side (``DegenerateCohortError`` on any zero-client
    round).  As a traced operand the whole chain batches along the
    sweep axis like the byz mask — per-round resampling costs zero
    retraces (DESIGN.md §13)."""
    if byz_mask is None:
        byz_mask = fed.byz_mask if fed is not None else \
            make_byzantine_mask(cfg.n_clients, cfg.f)
    scen = {"sigma": jnp.float32(cfg.attack.sigma),
            "scale": jnp.float32(cfg.attack.scale),
            "byz": jnp.asarray(byz_mask, bool)}
    cp = getattr(cfg, "cohort_participation", None)
    if cohort is not None:
        validate_cohort_chain(cohort, cfg.n_clients, cfg.rounds)
        scen["cohort"] = jnp.asarray(cohort, bool)
    elif cp is not None:
        scen["cohort"] = make_cohort_chain(
            cfg.n_clients, cfg.rounds, cp,
            jax.random.fold_in(jax.random.PRNGKey(cfg.seed), _COHORT_FOLD))
    return scen


# Compiles are counted, not inferred: each outer jitted program calls
# its Python body exactly once per cache miss (trace), so a counter
# bumped inside the body is a compile counter.  benchmarks/sweep_bench
# snapshots it to enforce "one compile per structural group"; the
# no-recompile-on-sigma-change regression test reads it too.
TRACE_COUNTS = {"segment": 0, "training": 0, "eval": 0}


def trace_counts():
    """Snapshot of the engine's compile counters (copies, not views)."""
    return dict(TRACE_COUNTS)


class TraceDelta:
    """Live view of compile counts since a :func:`trace_counter` entry.

    ``delta["segment"]`` reads the *current* delta — valid both inside
    and after the ``with`` block; ``snapshot()``/``total()`` summarize."""

    def __init__(self, start):
        self._start = start

    def __getitem__(self, kind):
        return TRACE_COUNTS[kind] - self._start.get(kind, 0)

    def snapshot(self):
        return {k: self[k] for k in TRACE_COUNTS}

    def total(self):
        return sum(self.snapshot().values())


@contextlib.contextmanager
def trace_counter():
    """Scoped compile counting — the supported alternative to poking
    ``TRACE_COUNTS`` directly.

    ``with trace_counter() as tc: ...`` yields a :class:`TraceDelta`
    whose lookups are always relative to the entry snapshot, so nested
    or concurrent-in-sequence counters never clobber each other the way
    ad-hoc reset/re-read of the module dict did.  The global counters
    themselves keep monotonically counting (they are compile *totals*,
    and resetting them under someone else's nose was the bug this API
    exists to prevent)."""
    yield TraceDelta(dict(TRACE_COUNTS))


def _counted(kind, fn):
    """Bump the compile counter for ``kind`` on every trace of ``fn``,
    and — when the flight recorder is on — emit a ``trace`` event with
    the trace wall time and the program's operand/output leaf counts
    (the trace-time proxy for jaxpr size; benches that ``.lower()``
    programs attach exact HLO/memory sizes via their own events)."""
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        TRACE_COUNTS[kind] += 1
        rec = telemetry.get_recorder()
        if not rec.enabled:
            return fn(*a, **kw)
        t0 = rec.now()
        out = fn(*a, **kw)
        rec.event("trace", program=kind, dur=round(rec.now() - t0, 6),
                  in_leaves=len(jax.tree.leaves((a, kw))),
                  out_leaves=len(jax.tree.leaves(out)))
        return out
    return wrapped


# ----------------------------------------------------------------------
# The round body — one definition for every execution mode.
# ----------------------------------------------------------------------

def _apply_update_attacks(U, byz_rows, keys_rows, ka, acfg, scen):
    """Byzantine update corruption on a stack of flattened updates.

    One definition for the dense (N, D) matrix and the streaming
    (chunk, D) blocks — the streaming == dense bitwise contract depends
    on both paths tracing the identical per-row attack graph.
    ``keys_rows`` carries the per-client gaussian subkeys (row-aligned
    with ``U``); every other attack kind ignores the key, so the C-way
    split is skipped and ``ka`` is passed through.  The attack
    magnitudes come from the ``scen`` operands, never from ``acfg``'s
    baked constants — only ``kind`` (graph structure) is static."""
    if acfg.kind not in UPDATE_ATTACKS and acfg.kind != "backdoor":
        return U
    sigma, scale = scen["sigma"], scen["scale"]
    if acfg.kind == "gaussian":          # the only RNG-consuming attack
        U_att = jax.vmap(
            lambda u, k: attack_update(u, acfg.kind, k, acfg,
                                       sigma=sigma, scale=scale))(U, keys_rows)
    else:
        U_att = jax.vmap(
            lambda u: attack_update(u, acfg.kind, ka, acfg,
                                    sigma=sigma, scale=scale))(U)
    # (c, 1) on the classic flat layout — a[:, None] verbatim — and
    # (c, 1, 1) on the blocked (c, ms, L) layout (DESIGN.md §12)
    bsel = byz_rows.reshape(byz_rows.shape + (1,) * (U.ndim - 1))
    return jnp.where(bsel, U_att, U)

def make_round_body(model, fed, cfg, *, client_chunk: Optional[int] = None):
    """Build ``body(params, sub, lr, batch) -> (new_params, logs)``.

    ``sub`` is the round's RNG key, ``lr`` its learning rate, ``batch``
    an optional precomputed ``(xb, yb)`` minibatch stack (shape
    (N, E*m, ...)) — ``None`` samples inside the traced body with the
    same ``kb`` subkey the precomputed path derives, so the two modes
    are bit-identical.  ``scen`` carries the run's traced operands
    (:func:`make_scenario`: attack sigma/scale, the Byzantine mask);
    ``None`` closes over the federation's own values — same bits, but
    baked into the trace (the seed per-round path; every engine path
    threads ``scen`` through as a jit argument instead).

    With ``cfg.streaming`` and an associative aggregator, Steps 2-5 run
    through the streaming subsystem (fl/streaming.py): client updates
    and guiding updates are computed block by block inside one scan and
    folded straight into an O(D) AggState — the (N, D) update/guide
    matrices never materialize, and the result is bit-identical to the
    dense path (DESIGN.md §6).  Non-associative rules fall back to the
    dense path; the reason is logged and exposed as
    ``body.streaming_fallback``.

    With a **lossy** ``cfg.compression`` codec (fl/compression.py) the
    round carry becomes ``(params, resid)``: each selected client
    encodes ``u_i + resid_i`` at the client→server boundary (after the
    Byzantine update attacks — the adversary corrupts the true update,
    then the client's codec compresses whatever it is sending) and keeps
    the quantization error ``resid_i' = v_i − decode(encode(v_i))`` for
    the next round it participates in (error feedback; non-selected
    clients' residuals persist untouched).  The server side only ever
    sees the encoded stream: the streaming fold decodes in-fold (fused
    kernels under ``use_kernel_agg``), the dense registry rules receive
    the decoded values from the shared reference decoder — same bits
    either way (DESIGN.md §10).  Guides are quantize-dequantized with
    the *same* codec inside the enclave (``SecureServer.compute_guides``)
    but carry NO residual — they are recomputed from the root sample
    every round, so there is no error to feed back.  A lossless codec
    (the ``"f32"`` default) skips ALL of this structurally: the body
    keeps the bare-params carry and traces the identical jaxpr as before
    compression existed — bitwise is trivial, not tested-for.
    ``body.lossy``/``body.codec`` expose the resolution.
    """
    E, m = cfg.local_steps, cfg.batch_size
    acfg = cfg.attack
    n_classes = fed.data.n_classes
    entry = get_aggregator(cfg.aggregator)   # fails fast on unknown rules
    C = cfg.n_selected
    codec = get_codec(getattr(cfg, "compression", "f32"))
    lossy = not codec.lossless
    # async rounds (DESIGN.md §13): per-round cohorts / fault injection /
    # staleness buffering.  Everything below is Python-gated on these
    # trace-time constants, so async_mode=False traces the exact PR-9
    # jaxpr — the structural half of the §13 bitwise contract.
    async_mode = bool(getattr(cfg, "async_rounds", False))
    fcfg = getattr(cfg, "fault", None)
    straggler = async_mode and fcfg.kind == "straggler"
    B = int(getattr(cfg, "staleness_buffer", 0)) if async_mode else 0
    cap = int(getattr(cfg, "staleness_cap", 0))
    # stragglers expire wholesale when there is nowhere to land them or
    # the hard cap forbids their age — a static (trace-time) decision
    expire_all = straggler and (B == 0 or (cap > 0 and fcfg.delay > cap))
    # every buffered update lands at age == delay, so the staleness
    # discount is one static factor riding the fold's valid channel
    discount_w = (float(getattr(cfg, "staleness_discount", 1.0))
                  ** fcfg.delay) if async_mode else 1.0
    default_scen = make_scenario(cfg, fed)
    stream_entry, streaming_fallback = None, None
    if getattr(cfg, "streaming", False):
        stream_entry = get_streaming(cfg.aggregator)
        if stream_entry is None:
            streaming_fallback = fallback_reason(cfg.aggregator)
            logger.warning(
                "FLConfig.streaming=True but aggregator %r cannot stream "
                "(%s); falling back to the dense (N, D) aggregation path",
                cfg.aggregator, streaming_fallback)
            telemetry.event("streaming_fallback", aggregator=cfg.aggregator,
                            reason=streaming_fallback)
    if entry.needs_guides:
        # Unseal + cache the guide batches *eagerly*, outside any trace:
        # building the device-side cache under jit/scan tracing would
        # cache tracers (and leak them into later compilations).
        fed.server.guide_batches()

    def grad_fn(params, batch):
        x, y = batch
        return jax.grad(lambda p: model.loss(p, x, y, cfg.l2))(params)

    def client_update(params, xs, ys, lr):
        """xs: (E, m, ...) — E local SGD iterations, fresh batch each.
        The trailing ``astype`` keeps the scan carry dtype-stable for
        low-precision zoo params (bf16 - f32*bf16 promotes); identity —
        and jaxpr-invisible — for the f32 small models."""
        def step(theta, b):
            g = grad_fn(theta, b)
            return jax.tree.map(
                lambda t, gg: (t - lr * gg).astype(t.dtype), theta, g), None
        theta, _ = jax.lax.scan(step, params, (xs, ys))
        return jax.tree.map(lambda a, b: a - b, params, theta)

    def body(carry, sub, lr, batch=None, scen=None):
        astate = None
        if lossy:
            params, resid = carry       # resid: (N, d) f32 EF residuals
        elif async_mode:
            # async and lossy carries are mutually exclusive
            # (FLConfig.__post_init__), so the pair is unambiguous
            params, astate = carry
            resid = None
        else:
            params, resid = carry, None     # bare-params carry, as ever
        if scen is None:
            scen = default_scen
        kb, ka, kr, ks = jax.random.split(sub, 4)
        if batch is None:
            xb, yb = fed.data.minibatch(kb, E * m)
        else:
            xb, yb = batch
        xb = xb.reshape((cfg.n_clients, E, m) + xb.shape[2:])
        yb = yb.reshape((cfg.n_clients, E, m))
        # Step 2 preamble: server samples the participating subset S^i
        sel = jax.random.choice(ks, cfg.n_clients, (C,), replace=False) \
            if C < cfg.n_clients else jnp.arange(cfg.n_clients)
        xb, yb = xb[sel], yb[sel]
        xb, yb = shard_clients(xb), shard_clients(yb)
        byz = scen["byz"][sel]

        live = fault_rows = strag = None
        if async_mode:
            # async mode enforces participation == 1.0, so `sel` is
            # arange(N) and `ks` — the selection subkey — is free: it
            # becomes the fault draw's per-round key.  The 4-way split
            # above stays untouched, which is why a trivial-async run
            # consumes the identical RNG chain as the PR-9 path (the
            # value-bitwise half of the §13 contract).
            if "cohort" in scen:
                m_r = jax.lax.dynamic_index_in_dim(
                    scen["cohort"], astate["r"], axis=0, keepdims=False)
            else:
                m_r = jnp.ones((cfg.n_clients,), bool)
            fault_rows = draw_faults(ks, cfg.n_clients, fcfg)
            if fcfg.kind in ("dropout", "straggler"):
                # the update never arrives this round: drop the client
                # from the live set (zero fold weight via the `live`
                # context channel)
                live = m_r & ~fault_rows
            else:
                live = m_r
            if straggler:
                strag = m_r & fault_rows

        # ---- data-level attacks ----
        if acfg.kind == "label_flip":
            yb = jnp.where(byz[:, None, None], flip_labels(yb, n_classes), yb)
        elif acfg.kind == "backdoor":
            def poison(xc, yc):
                xf = xc.reshape((E * m,) + xc.shape[2:])
                yf = yc.reshape(E * m)
                xp, yp = poison_backdoor(xf, yf, acfg)
                return xp.reshape(xc.shape), yp.reshape(yc.shape)
            xp, yp = jax.vmap(poison)(xb, yb)
            bsel = byz.reshape((-1,) + (1,) * (xb.ndim - 1))
            xb = jnp.where(bsel, xp, xb)
            yb = jnp.where(byz[:, None, None], yp, yb)

        logs = {"byz": byz, "sel": sel}
        root = None
        if entry.needs_root:
            root_tree = fed.server.compute_root_update(
                params, grad_fn, lr, E, fed.root_x, fed.root_y)
            if model_shard_count() > 1:
                # blocked (ms, L) layout, same column offsets as the
                # client update blocks — the fltrust dot aligns
                # element-for-element (DESIGN.md §12)
                root = ravel_sharded(root_tree)
            else:
                r, _ = agg.flatten_updates(
                    jax.tree.map(lambda a: a[None], root_tree))
                root = shard_flat(r[0])

        if stream_entry is not None:
            # ---- Steps 2-5, streaming: fold blocks into an AggState ----
            # Only O(C) per-client scalars (selection ids, Byzantine bits,
            # attack keys) and the O(C·batch) minibatch stack persist
            # across blocks; updates and guides live one chunk at a time.
            ctx = AggregationContext(
                key=kr, f=cfg.f, dfl=cfg.dfl, byz_mask=byz, guides=None,
                root_update=root, resample_s=cfg.resample_s,
                use_kernel_stats=cfg.use_kernel_stats,
                use_kernel_agg=cfg.use_kernel_agg,
                stream_shards=getattr(cfg, "stream_shards", None),
                stream_pods=getattr(cfg, "pods", None),
                codec=codec if lossy else None)
            rule = fed.server.streaming_aggregator(cfg.aggregator, ctx)
            keys = jax.random.split(ka, C) if acfg.kind == "gaussian" else None

            def block_fn(blk, valid):
                live_b = fault_b = None
                if lossy:
                    xs, ys, byz_b, sel_b, keys_b, resid_b = blk
                elif async_mode:
                    xs, ys, byz_b, sel_b, keys_b, live_b, fault_b = blk
                else:
                    xs, ys, byz_b, sel_b, keys_b = blk
                upd = jax.vmap(
                    lambda x, y: client_update(params, x, y, lr))(xs, ys)
                if model_shard_count() > 1:
                    # blocked (chunk, ms, L) build: the concat runs
                    # along the unsharded column dim, so no unsharded
                    # (chunk, D) fp32 temp ever materializes — the
                    # envelope difference at zoo scale (DESIGN.md §12)
                    U_blk, _ = flatten_updates_sharded(upd)
                else:
                    U_blk, _ = agg.flatten_updates(upd)
                U_blk = _apply_update_attacks(U_blk, byz_b, keys_b, ka, acfg,
                                              scen)
                if async_mode and fcfg.kind == "intermittent":
                    # device malfunction at the client boundary, AFTER
                    # the adversarial attack: the corruption hits
                    # whatever bits the client actually transmits
                    U_blk = corrupt_updates(U_blk, fault_b, fcfg)
                # same client x model sharding contract as the dense
                # branch, per block: client dim over the data axes, flat
                # D over the model axis (each no-op without a mesh /
                # when its dim won't tile — DESIGN.md §12)
                U_blk = shard_updates(U_blk)
                ctx_blk = {"byz": byz_b}
                if async_mode:
                    # cohort membership minus this round's dropouts —
                    # the fold's second multiplicative weight channel
                    ctx_blk["live"] = live_b
                if entry.needs_guides:
                    # flat=True: the enclave ravels (and quantizes) each
                    # guide inside its chunked map, so the block's guide
                    # working set is O(chunk x model) — the stacked guide
                    # pytree never coexists with its flat copy
                    ctx_blk["guide"] = fed.server.compute_guides(
                        params, grad_fn, lr, E, select=sel_b,
                        codec=codec if lossy else None, flat=True)
                if lossy:
                    # client→server boundary: encode v = u + resid, keep
                    # the new quantization error; ONLY the encoded pytree
                    # enters the fold (the rule decodes it in-fold).  On
                    # the blocked layout the residual plane stays (N, d)
                    # flat in blocked element order (d == ms·L — lossy +
                    # model sharding requires pad-free leaves, enforced
                    # by FLConfig.validate_model_sharding)
                    if U_blk.ndim == 3:
                        resid_b = resid_b.reshape(U_blk.shape)
                    enc, _, new_resid_b = encode_with_feedback(
                        codec, U_blk, resid_b)
                    enc = jax.tree.map(shard_updates, enc)
                    if new_resid_b.ndim == 3:
                        new_resid_b = new_resid_b.reshape(
                            new_resid_b.shape[0], -1)
                    return enc, ctx_blk, new_resid_b
                return U_blk, ctx_blk

            d = sum(p.size for p in jax.tree.leaves(params))
            # flat output unused -> DCE'd; only the unravel closure (and
            # the blocked layout's static (ms, L) state shape) is kept
            if model_shard_count() > 1:
                f0, unravel = flatten_updates_sharded(
                    jax.tree.map(lambda p: p[None], params))
                d = f0.shape[1:]
            else:
                _, unravel = agg.flatten_updates(
                    jax.tree.map(lambda p: p[None], params))
            # ---- bounded-staleness landing (DESIGN.md §13) ----------
            # Buffered straggler updates whose TTL hits zero this round
            # fold through the SAME AggState monoid as the live cohort,
            # with guides recomputed at the LANDING round's params — so
            # Eq. 6 filters stale-and-diverged updates per client.  The
            # partial state merges into the block sweep's result just
            # before finalize (stream_aggregate's extra_state hook).
            extra_state = None
            stale_logs = None
            landed = ttl1 = None
            stale_folded = jnp.zeros((), jnp.int32) if async_mode else None
            land_cid = None
            if B > 0:
                ttl1 = astate["ttl"] - 1
                landed = astate["on"] & (ttl1 <= 0)
                land_cid = astate["cid"]
                land_ctx = {"byz": scen["byz"][land_cid],
                            # the staleness discount rides the exact 0/1
                            # valid channel as a static factor — no rule
                            # changes, dead slots get weight 0.0
                            "valid": landed.astype(jnp.float32)
                            * jnp.float32(discount_w)}
                if entry.needs_guides:
                    land_ctx["guide"] = fed.server.compute_guides(
                        params, grad_fn, lr, E, select=astate["cid"],
                        flat=True)
                extra_state, stale_logs = jax.lax.scan(
                    lambda st, uc: rule.update(st, uc[0], uc[1]),
                    rule.init(d), (astate["u"], land_ctx), unroll=1)
                stale_folded = jnp.sum(landed.astype(jnp.int32))

            # pods > 1 runs the two-tier fold: block_fn — and with it the
            # enclave's guide computation — executes inside the pod-local
            # scan, so guides and updates are chunked *per pod* and the
            # enclave memory model holds per-pod (DESIGN.md §9)
            if lossy:
                delta, agg_logs, client_logs, new_resid = stream_aggregate(
                    rule, block_fn,
                    (xb, yb, byz, sel, keys, resid[sel]), client_chunk,
                    d=d, prefer_block=cfg.use_kernel_agg,
                    shards=ctx.stream_shards, pods=ctx.stream_pods,
                    block_extra=True)
                resid = resid.at[sel].set(new_resid)
            else:
                args = (xb, yb, byz, sel, keys)
                if async_mode:
                    args = args + (live, fault_rows)
                delta, agg_logs, client_logs = stream_aggregate(
                    rule, block_fn, args, client_chunk,
                    d=d, prefer_block=cfg.use_kernel_agg,
                    shards=ctx.stream_shards, pods=ctx.stream_pods,
                    extra_state=extra_state)
            logs.update(client_logs)
            logs.update(agg_logs)

            # ---- buffer refill: this round's stragglers -------------
            if async_mode:
                N = cfg.n_clients
                stale_buffered = jnp.zeros((), jnp.int32)
                stale_expired = jnp.zeros((), jnp.int32)
                new_astate = {"r": astate["r"] + 1}
                if expire_all:
                    stale_expired = jnp.sum(strag.astype(jnp.int32))
                if B > 0:
                    on2 = astate["on"] & ~landed
                    ttl_keep = jnp.maximum(ttl1, 0)
                    if straggler and not expire_all:
                        # rank-assign stragglers (in client order) to
                        # free slots; the overflow expires.  O(B·model)
                        # recompute keeps the slab O(buffer·D): slots
                        # store only the FLAT update, rebuilt from the
                        # round's own batch at the round's own params.
                        ns = jnp.sum(strag.astype(jnp.int32))
                        order = jnp.argsort(
                            jnp.where(strag, jnp.arange(N),
                                      N + jnp.arange(N)))
                        # per-slot rank among FREE slots: slot with free
                        # rank j takes the j-th straggler in client
                        # order; ranks >= ns (or occupied slots) don't
                        free = ~on2
                        free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
                        take = free & (free_rank < ns)
                        src = order[jnp.clip(free_rank, 0, N - 1)]
                        upd_s = jax.vmap(
                            lambda i: client_update(params, xb[i], yb[i],
                                                    lr))(src)
                        if model_shard_count() > 1:
                            U_s, _ = flatten_updates_sharded(upd_s)
                        else:
                            U_s, _ = agg.flatten_updates(upd_s)
                        keys_s = keys[src] if keys is not None else None
                        U_s = _apply_update_attacks(
                            U_s, scen["byz"][src], keys_s, ka, acfg, scen)
                        tsel = take.reshape(take.shape
                                            + (1,) * (U_s.ndim - 1))
                        new_astate.update(
                            u=jnp.where(tsel, U_s.astype(jnp.float32),
                                        astate["u"]),
                            cid=jnp.where(take, src, astate["cid"]),
                            ttl=jnp.where(take, jnp.int32(fcfg.delay),
                                          ttl_keep),
                            on=on2 | take)
                        stale_buffered = jnp.sum(take.astype(jnp.int32))
                        stale_expired = ns - stale_buffered
                    else:
                        new_astate.update(u=astate["u"],
                                          cid=astate["cid"],
                                          ttl=ttl_keep, on=on2)
                astate = new_astate

                # ---- async accounting: per-client rows + counts -----
                # landed slot rows join the per-client log plane so the
                # tag/TPR/FPR accounting covers them at their landing
                # round; `cand` marks which rows actually participated
                cand = live
                if B > 0 and stale_logs is not None:
                    for k in list(logs):
                        if k in stale_logs:
                            logs[k] = jnp.concatenate(
                                [logs[k], stale_logs[k]])
                    logs["byz"] = jnp.concatenate(
                        [byz, scen["byz"][land_cid]])
                    logs["sel"] = jnp.concatenate([sel, land_cid])
                    cand = jnp.concatenate([live, landed])
                logs["cand"] = cand
                logs["cohort"] = jnp.sum(live.astype(jnp.int32))
                logs["stale_buffered"] = stale_buffered
                logs["stale_folded"] = stale_folded
                logs["stale_expired"] = stale_expired
        else:
            # ---- Step 2: client local training (chunked federation) ----
            updates = chunked_vmap(
                lambda x, y: client_update(params, x, y, lr), (xb, yb),
                client_chunk)
            U, unravel = agg.flatten_updates(updates)
            U = shard_updates(U)

            # ---- update-level attacks ----
            if acfg.kind in UPDATE_ATTACKS or acfg.kind == "backdoor":
                keys = jax.random.split(ka, C) \
                    if acfg.kind == "gaussian" else None
                U = _apply_update_attacks(U, byz, keys, ka, acfg, scen)
                U = shard_updates(U)

            if lossy:
                # client→server boundary: the registry rules receive the
                # *decoded* updates — the exact bits the shared reference
                # decoder recovers from the wire payload, so dense and
                # streaming agree on what the server saw (DESIGN.md §10)
                _, U, new_resid = encode_with_feedback(codec, U, resid[sel])
                resid = resid.at[sel].set(new_resid)
                U = shard_updates(U)

            # ---- Steps 3-5: SecureServer (enclave guides -> registry) ----
            G = None
            if entry.needs_guides:
                G = fed.server.compute_guides(
                    params, grad_fn, lr, E, select=sel,
                    client_chunk=client_chunk,
                    codec=codec if lossy else None, flat=True)
            ctx = AggregationContext(
                key=kr, f=cfg.f, dfl=cfg.dfl, byz_mask=byz, guides=G,
                root_update=root, resample_s=cfg.resample_s,
                use_kernel_stats=cfg.use_kernel_stats,
                use_kernel_agg=cfg.use_kernel_agg,
                codec=None)   # dense rules already hold decoded values
            delta, agg_logs = fed.server.aggregate(cfg.aggregator, U, ctx)
            logs.update(agg_logs)

        # the per-leaf constraints pin the updated parameters back to the
        # MODEL_AXIS partition-table layout, so the scan carry keeps its
        # tensor-parallel placement round over round (no-op off a
        # model-sharded mesh — the pre-zoo jaxpr is unchanged)
        new_params = shard_params(jax.tree.map(
            lambda p, d: (p - d).astype(p.dtype), params, unravel(delta)))
        if lossy:
            return (new_params, resid), logs
        if async_mode:
            return (new_params, astate), logs
        return new_params, logs

    body.streaming = stream_entry is not None
    body.streaming_fallback = streaming_fallback
    body.lossy = lossy
    body.codec = codec
    body.async_mode = async_mode
    return body


# each round's batch subkey, exactly as the body derives it:
# kb = split(sub, 4)[0] (jitted once; eager vmap would retrace per call)
_batch_keys = jax.jit(jax.vmap(lambda s: jax.random.split(s, 4)[0]))


# ----------------------------------------------------------------------
# RoundEngine
# ----------------------------------------------------------------------

class RoundEngine:
    """Compile federated rounds into donated scans — per segment or for
    the whole training run.

    ``run_segment(params, key, lrs)`` executes ``len(lrs)`` rounds in a
    single dispatch, advancing the caller's RNG chain exactly as the
    legacy per-round loop would (``key, sub = split(key)`` per round),
    and returns ``(params, key, last_logs)`` where ``last_logs`` is the
    final round's log dict — the one the eval point reads.

    ``run_training(params, key, lrs)`` goes one level further: the whole
    multi-segment run compiles into **one outer ``lax.scan`` over eval
    segments** whose body is the segment scan followed by the device
    eval tail (fl/metrics.make_eval_fn) — main-task/backdoor accuracy
    and detection TPR/FPR accumulate into a per-eval-point metric buffer
    on device, and the host syncs exactly once when the caller fetches
    it (DESIGN.md §7).  ``run_training_sweep`` vmaps that program over
    a stacked scenario axis — a whole structural group of runs in one
    compile and one dispatch (fl/sweep.py, DESIGN.md §8).

    ``batch_mode``:
      * ``"inline"``  — minibatches are sampled inside the traced body
        (memory-light; the default off-mesh);
      * ``"segment"`` — the data pipeline serves a per-segment
        minibatch stack (data/pipeline.segment_minibatches) placed with
        client-axis NamedShardings (the default when a mesh is active,
        so batch data lives distributed from the start).
    Both derive batches from the same ``kb`` subkeys — bit-identical.
    ``run_segment`` honors the mode; ``run_training`` always samples
    inline (a whole run's batch stacks would scale the batch working
    set by the segment count).

    ``donate``: tri-state scan-carry donation knob.  ``None`` resolves
    to ``cfg.donate``, and a ``None`` there means *auto* — donate
    wherever the backend supports it (XLA:CPU does not, so auto skips
    the warning-spamming request there).  ``True``/``False`` force the
    request on or off regardless of backend, which is what lets
    benchmarks/dispatch_bench measure the donation working-set delta.
    """

    def __init__(self, model, fed, cfg, *, eval_every: Optional[int] = None,
                 client_chunk: Optional[int] = None,
                 batch_mode: Optional[str] = None, mesh=None,
                 donate: Optional[bool] = None):
        self.model, self.fed, self.cfg = model, fed, cfg
        self.eval_every = eval_every if eval_every is not None \
            else cfg.eval_every
        self.client_chunk = client_chunk if client_chunk is not None \
            else getattr(cfg, "client_chunk", None)
        self.mesh = mesh if mesh is not None else get_mesh()
        if batch_mode is None:
            batch_mode = "segment" if self.mesh is not None else "inline"
        if batch_mode not in ("inline", "segment"):
            raise ValueError(f"unknown batch_mode {batch_mode!r}")
        self.batch_mode = batch_mode
        # tensor parallelism: >1 iff the mesh carries a non-trivial
        # ``model`` axis.  The knob-compatibility check needs the flat
        # model dim, which only exists once params are seen — deferred
        # to the first run_* call (cached; see _check_model_sharding)
        self.model_shards = model_shard_count(self.mesh)
        self._model_sharding_checked = False
        self._body = make_round_body(model, fed, cfg,
                                     client_chunk=self.client_chunk)
        # observability: did the body take the streaming path, and if not
        # (streaming requested but rule not associative), why not
        self.streaming = self._body.streaming
        self.streaming_fallback = self._body.streaming_fallback
        # lossy compression threads an (N, d) error-feedback residual
        # through every carry: the engine's params slot becomes
        # (params, resid) and callers go through init_carry/carry_params
        self.lossy = self._body.lossy
        self.codec = self._body.codec
        # async rounds wrap the carry as (params, async state): a round
        # counter indexing the cohort chain plus, with staleness_buffer
        # > 0, the O(buffer·D) pending slab (DESIGN.md §13)
        self.async_mode = self._body.async_mode
        # on-device round telemetry (DESIGN.md §11): a per-round block of
        # device scalars accumulated inside the scan and drained at the
        # caller's one host sync — never a new round-trip.  Off by
        # default; off means the empty pytree, i.e. the exact
        # pre-telemetry program.
        self.telemetry = bool(getattr(cfg, "telemetry", False))
        self._tel_fn = telemetry.make_round_telemetry_fn(cfg) \
            if self.telemetry else None
        if donate is None:
            donate = getattr(cfg, "donate", None)
        if donate is None:                   # auto: backend support only
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        self.default_scenario = make_scenario(cfg, fed)
        jit_kwargs = {"static_argnums": (3,)}
        donate_kw = {"donate_argnums": (0,)} if self.donate else {}
        self._segment = jax.jit(_counted("segment", self._segment_fn),
                                **jit_kwargs, **donate_kw)
        self._training = jax.jit(_counted("training", self._training_fn),
                                 **donate_kw)
        # the sweep twins: one extra leading scenario axis on every
        # operand, one compile + one dispatch for a whole structural
        # group of runs (fl/sweep.py, DESIGN.md §8).  Wrapping the same
        # Python bodies keeps the compile counters shared: a sweep
        # group's compile counts exactly like a solo run's.
        self._training_sweep = jax.jit(
            jax.vmap(_counted("training", self._training_fn)), **donate_kw)
        self._segment_sweep = jax.jit(
            jax.vmap(_counted("segment", self._segment_sweep_fn)),
            **donate_kw)
        self._eval_fn = make_eval_fn(model, fed, cfg)
        self._eval_jit = jax.jit(_counted("eval", self._eval_fn))
        self._eval_sweep = jax.jit(jax.vmap(_counted("eval", self._eval_fn)))

    def eval_metrics(self, params, logs):
        """Device metric dict for one eval point — the jitted form of the
        same eval the one-dispatch scan tail traces (bitwise equal)."""
        return self._eval_jit(params, logs)

    # --- the error-feedback carry (lossy compression) -----------------

    def _flat_shape(self, params):
        """The flat-update shape one client produces under the active
        layout: ``(d,)`` classic, blocked ``(ms, L)`` model-sharded.
        Abstract (eval_shape) — no device allocation."""
        if self.model_shards > 1:
            f0 = jax.eval_shape(
                lambda p: flatten_updates_sharded(
                    jax.tree.map(lambda q: q[None], p))[0], params)
            return tuple(f0.shape[1:])
        return (sum(p.size for p in jax.tree.leaves(params)),)

    def init_carry(self, params):
        """The round-scan carry for ``params``: bare params for lossless
        codecs (every pre-compression jaxpr unchanged), ``(params,
        zeros(N, d))`` — fresh residuals — under lossy compression,
        ``(params, async state)`` under async rounds (the two wrapped
        forms are mutually exclusive — FLConfig.__post_init__)."""
        if self.lossy:
            d = sum(p.size for p in jax.tree.leaves(params))
            return params, jnp.zeros((self.cfg.n_clients, d), jnp.float32)
        if self.async_mode:
            return params, init_async_state(self.cfg,
                                            self._flat_shape(params))
        return params

    def carry_params(self, carry):
        """The params inside a carry (identity for lossless codecs)."""
        return carry[0] if (self.lossy or self.async_mode) else carry

    def _ensure_carry(self, carry):
        """Accept bare params where a carry is expected — existing call
        sites that never heard of residuals or async state keep working
        (their runs start from zero residual / round zero, which is what
        a fresh run means)."""
        if self.lossy:
            if (isinstance(carry, tuple) and len(carry) == 2
                    and getattr(carry[1], "ndim", None) == 2):
                return carry
            return self.init_carry(carry)
        if self.async_mode:
            if (isinstance(carry, tuple) and len(carry) == 2
                    and isinstance(carry[1], dict) and "r" in carry[1]):
                return carry
            return self.init_carry(carry)
        return carry

    def _prepare_carry(self, carry):
        """Model-sharded runs only: validate the cfg against the actual
        flat dim (named errors, once) and eagerly place the params with
        the MODEL_AXIS partition table — the one host->device scatter
        before the compiled segments take over.  Identity off a
        model-sharded mesh."""
        carry = self._ensure_carry(carry)
        if self.model_shards <= 1:
            return carry
        params = self.carry_params(carry)
        if not self._model_sharding_checked:
            leaves = jax.tree.leaves(params)
            self.cfg.validate_model_sharding(
                sum(p.size for p in leaves), self.model_shards,
                streaming_fallback=self.streaming_fallback,
                leaf_sizes=tuple(p.size for p in leaves))
            self._model_sharding_checked = True
        params = place_params(params, self.mesh)
        if self.lossy or self.async_mode:
            return (params, carry[1])
        return params

    def _scan_rounds(self, params, subs, lrs, with_batches, batches, scen):
        """One segment: scan ``len(lrs)`` round bodies, return the final
        round's logs (the only logs an eval point reads) plus the
        per-round telemetry block (``{}`` with telemetry off — the extra
        ys slot is structurally empty, so the pre-telemetry jaxpr is
        unchanged).  ``scen`` is scan-invariant — the same operand every
        round reads."""
        def step(p, xs):
            if with_batches:
                sub, lr, batch = xs
            else:
                (sub, lr), batch = xs, None
            p, logs = self._body(p, sub, lr, batch, scen)
            tel = self._tel_fn(logs) if self._tel_fn is not None else {}
            return p, (logs, tel)
        xs = (subs, lrs, batches) if with_batches else (subs, lrs)
        params, (logs, tel) = jax.lax.scan(step, params, xs)
        # only the final round's logs leave the device: that is what the
        # eval point reads, and slicing inside the compiled segment keeps
        # the host side to one dispatch (T eager slices would dwarf the
        # scan itself on CPU).  The telemetry block is the exception —
        # per-round device scalars are exactly what it exists to keep —
        # so its (T,)-stacked leaves ride the same dispatch.
        return params, jax.tree.map(lambda x: x[-1], logs), tel

    def _segment_fn(self, params, subs, lrs, with_batches, batches, scen):
        return self._scan_rounds(params, subs, lrs, with_batches, batches,
                                 scen)

    def _segment_sweep_fn(self, params, subs, lrs, scen):
        """The vmappable segment program (no precomputed batch stacks —
        sweeps always sample in-body, like ``run_training``)."""
        return self._scan_rounds(params, subs, lrs, False, None, scen)

    def _training_fn(self, params, subs, lrs, scen):
        """The one-dispatch program: outer scan over (S, T)-shaped
        segment stacks; each step runs the segment scan then the device
        eval tail, so the stacked ys are the (num_evals, k) metric
        buffer — plus the (S, T)-stacked per-round telemetry block when
        telemetry is on — and nothing but the final carry + buffers
        leaves XLA.  Minibatches are always sampled inside the traced
        body (bit-identical to the per-segment batch stacks — same
        ``kb`` subkeys): a whole-run (S, T, N, m, ...) stack would scale
        the batch working set by S, the opposite of the constant-memory
        story the engine exists for."""
        def seg(p, xs):
            sub, lr = xs
            p, logs, tel = self._scan_rounds(p, sub, lr, False, None, scen)
            return p, (self._eval_fn(self.carry_params(p), logs), tel)
        return jax.lax.scan(seg, params, (subs, lrs))

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(1,))
    def _segment_keys(key, n_rounds: int):
        """The legacy loop's exact per-round subkey chain (``key, sub =
        split(key)`` n times), staged as one scan so precomputing a
        segment's keys costs one dispatch, not n."""
        def step(k, _):
            k, sub = jax.random.split(k)
            return k, sub
        return jax.lax.scan(step, key, None, length=n_rounds)

    def run_segment(self, params, key, lrs, scen=None):
        """Run ``len(lrs)`` rounds; returns (params, advanced key, last logs).

        ``scen`` (default: the engine's own federation/config values)
        carries the traced per-run operands — see :func:`make_scenario`;
        passing a different scenario reuses the compiled program.

        Under lossy compression the params slot is the ``(params,
        resid)`` carry — bare params are accepted (zero residual) and
        the advanced *carry* is returned, so chained ``run_segment``
        calls (the host-eval loop) keep the error feedback flowing;
        ``carry_params`` unwraps.  Lossless codecs: params in, params
        out, exactly as before."""
        if scen is None:
            scen = self.default_scenario
        lrs = jnp.asarray(lrs, jnp.float32)
        n = int(lrs.shape[0])
        key, subs = self._segment_keys(key, n)
        carry = self._prepare_carry(params)
        with use_mesh(self.mesh):
            if self.batch_mode == "segment":
                kbs = _batch_keys(subs)
                batches = self.fed.data.segment_minibatches(
                    kbs, self.cfg.local_steps * self.cfg.batch_size)
                carry, logs, _ = self._segment(carry, subs, lrs, True,
                                               batches, scen)
            else:
                carry, logs, _ = self._segment(carry, subs, lrs, False, None,
                                               scen)
        return carry, key, logs

    def _training_args(self, params, key, lrs, scen):
        """``run_training``'s operands of the one-dispatch program —
        ``(carry, (S, T) subkeys, (S, T) lrs, scen)`` for its S full
        eval segments — plus what a partial tail segment reads: the
        advanced key and the whole (R,) subkey and lr vectors."""
        if scen is None:
            scen = self.default_scenario
        lrs = jnp.asarray(lrs, jnp.float32)
        R = int(lrs.shape[0])
        T = self.eval_every
        S = R // T
        key, subs = self._segment_keys(key, R)
        # (R, *key) -> (S, T, *key): agnostic to the PRNG key
        # representation (raw uint32 pairs today, typed keys tomorrow)
        args = (self._prepare_carry(params),
                subs[:S * T].reshape((S, T) + subs.shape[1:]),
                lrs[:S * T].reshape(S, T), scen)
        return args, key, subs, lrs

    def lower_training(self, params, key, lrs, scen=None):
        """Lower the one-dispatch program exactly as ``run_training``
        with these arguments calls it (its S full eval segments); a
        partial tail segment is a separate program.  Lowering reads
        shapes only, so donated params stay usable.  ``.compile()`` on
        the result gives the executable's HLO text and memory analysis."""
        args = self._training_args(params, key, lrs, scen)[0]
        with use_mesh(self.mesh):
            return self._training.lower(*args)

    def run_training(self, params, key, lrs, scen=None):
        """Run ``len(lrs)`` rounds as one device-resident program.

        Segments of ``eval_every`` rounds compile into a single outer
        scan with the eval tail inside (one dispatch, zero host syncs —
        the caller fetches the returned metric buffer whenever it wants
        the one sync).  The RNG chain, segmentation, and eval points are
        exactly ``run_segment`` in a loop: a non-divisible ``rounds``
        leaves a shorter final segment, which runs as one extra dispatch
        with its eval row concatenated on device.  Minibatches are
        sampled inside the scan regardless of ``batch_mode`` — the
        modes are bit-identical, and staging a whole run's batch stacks
        would multiply the batch working set by the segment count.

        Returns ``(params, advanced key, metrics, eval_rounds)`` where
        ``metrics`` is a dict of device arrays with leading dim = number
        of eval points and ``eval_rounds`` the (host) round index each
        metric row was evaluated at — the one definition of the eval
        points, so callers cannot drift from the segmentation that
        actually ran.
        """
        args, key, subs, lrs = self._training_args(params, key, lrs, scen)
        carry, scen = args[0], args[3]
        R = int(lrs.shape[0])
        T = self.eval_every
        S, rem = divmod(R, T)
        with use_mesh(self.mesh):
            metrics, tel = None, None
            if S:
                carry, (metrics, tel) = self._training(*args)
                # (S, T, ...) segment-stacked telemetry -> (R', ...)
                tel = jax.tree.map(
                    lambda x: x.reshape((S * T,) + x.shape[2:]), tel)
            if rem:
                # the carry — residual included — flows into the tail
                # segment: error feedback does not reset at eval points
                carry, logs, tel_tail = self._segment(
                    carry, subs[S * T:], lrs[S * T:], False, None, scen)
                row = jax.tree.map(
                    lambda x: jnp.asarray(x)[None],
                    self._eval_jit(self.carry_params(carry), logs))
                metrics = row if metrics is None else jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b]), metrics, row)
                tel = tel_tail if tel is None else jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b]), tel, tel_tail)
            if self.telemetry and tel:
                # reserved key: drained (popped) by the caller right
                # after its one host sync — never part of the history,
                # so telemetry-on histories stay bitwise-identical to
                # telemetry-off ones
                metrics = dict(metrics)
                metrics["_tel"] = tel
        eval_rounds = [T * (s + 1) for s in range(S)] + ([R] if rem else [])
        return self.carry_params(carry), key, metrics, eval_rounds

    # --- the batched scenario axis (fl/sweep.py) ----------------------

    @staticmethod
    @functools.partial(jax.jit, static_argnums=(1,))
    def _sweep_segment_keys(keys, n_rounds: int):
        """Per-cell RNG chains: ``_segment_keys`` vmapped over a (G, ...)
        stack of run keys — each cell advances exactly the chain its
        solo run would."""
        return jax.vmap(
            lambda k: RoundEngine._segment_keys(k, n_rounds))(keys)

    def run_training_sweep(self, params, keys, lrs, scen):
        """Run a whole *structural group* of training runs in one
        compile and (per eval-divisible round count) one dispatch.

        Every operand carries a leading scenario axis G — ``params`` a
        stacked init pytree, ``keys`` (G, *key) run keys, ``lrs``
        (G, R) per-cell learning-rate vectors, ``scen`` a stacked
        :func:`make_scenario` pytree — and the one-dispatch program of
        :meth:`run_training` is vmapped over it, so the G runs execute
        as one batched device program: same segmentation, same RNG
        chains, same eval points, cell g bitwise-equal to the solo run
        (DESIGN.md §8).  With a mesh active the scenario axis is placed
        over the data axes (``sharding.sweep_put``), running cells in
        parallel across devices.  Returns ``(params, keys, metrics,
        eval_rounds)`` with metrics leaves shaped (G, num_evals, ...).
        """
        lrs = jnp.asarray(lrs, jnp.float32)
        G, R = int(lrs.shape[0]), int(lrs.shape[1])
        T = self.eval_every
        S, rem = divmod(R, T)
        keys, subs = self._sweep_segment_keys(keys, R)
        carry = params
        if self.lossy:
            # stacked carry: one (N, d) residual plane per sweep cell
            d = sum(l.size // l.shape[0] for l in jax.tree.leaves(params))
            carry = (params,
                     jnp.zeros((G, self.cfg.n_clients, d), jnp.float32))
        elif self.async_mode:
            # stacked async state: one round counter (+ pending slab)
            # per sweep cell — all zeros, like each cell's solo init
            ast = init_async_state(
                self.cfg,
                self._flat_shape(jax.tree.map(lambda l: l[0], params)))
            carry = (params, jax.tree.map(
                lambda x: jnp.zeros((G,) + x.shape, x.dtype), ast))
        with use_mesh(self.mesh):
            carry, lrs, scen, subs = sweep_put((carry, lrs, scen, subs))
            metrics, tel = None, None
            if S:
                carry, (metrics, tel) = self._training_sweep(
                    carry,
                    subs[:, :S * T].reshape((G, S, T) + subs.shape[2:]),
                    lrs[:, :S * T].reshape(G, S, T), scen)
                # (G, S, T, ...) -> (G, R', ...): per-cell round axis
                tel = jax.tree.map(
                    lambda x: x.reshape((G, S * T) + x.shape[3:]), tel)
            if rem:
                carry, logs, tel_tail = self._segment_sweep(
                    carry, subs[:, S * T:], lrs[:, S * T:], scen)
                row = jax.tree.map(
                    lambda x: jnp.asarray(x)[:, None],
                    self._eval_sweep(self.carry_params(carry), logs))
                metrics = row if metrics is None else jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], axis=1),
                    metrics, row)
                tel = tel_tail if tel is None else jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b], axis=1),
                    tel, tel_tail)
            if self.telemetry and tel:
                metrics = dict(metrics)
                metrics["_tel"] = tel   # (G, R, ...) — popped per cell
        eval_rounds = [T * (s + 1) for s in range(S)] + ([R] if rem else [])
        return self.carry_params(carry), keys, metrics, eval_rounds
