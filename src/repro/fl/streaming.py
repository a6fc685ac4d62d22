"""Streaming aggregation — constant-memory partial aggregation (DESIGN.md §6).

After the scan/chunk/shard engine (PR 2), the dense ``(N, D)`` update
matrix (plus its ``(N, D)`` guide twin) was the last O(N) memory term in
a federated round — exactly the term that caps how many clients an
enclave-faithful simulation fits, since TEE memory is the scarce
resource the paper's server lives inside.  This module removes it for
every *associative* aggregation rule:

  * **AggState monoid** — each streaming rule is a
    :class:`StreamingAggregator` with

        init(d)                -> state            (the identity)
        update(state, u_i, ctx_i) -> (state, logs_i)
        merge(a, b)            -> state             (associative)
        finalize(state)        -> (delta, logs)

    ``state`` is a fixed-size pytree — O(D), never O(N·D).  ``update``
    folds ONE client's flattened update ``u_i`` (with its per-client
    context: guide row, Byzantine bit, validity) into the state;
    ``merge`` combines partial states from disjoint client sets (the
    cross-chunk / cross-shard / multi-pod combiner); ``finalize`` turns
    the state into the round delta.  ``update(s, u, c)`` must equal
    ``merge(s, update(init, u, c))`` up to fp rounding — that is the
    associativity contract tests/test_streaming.py property-checks.
  * **Registry alongside the AggregatorRegistry** — streaming rules are
    registered by decorator under the *same* names as fl/server.py's
    dense rules (registering a name the dense registry does not know is
    an error, so the two registries cannot drift).  ``mean``, ``oracle``,
    ``diversefl`` and ``fltrust`` stream — they are all weighted means
    with per-client weights, the DiverseFL C1/C2 criterion being
    *per-client* against the guiding update, so it streams exactly.
    ``median``/``trimmed_mean``/``krum``/``bulyan``/``resampling`` are
    not associative (``NON_STREAMING`` records why) and fall back to the
    dense path with an explicitly logged reason.
  * **The sweep** — ``stream_aggregate`` drives the fold over the same
    padded ``(k, chunk, ...)`` blocks ``chunked_vmap`` uses
    (fl/chunking.pad_to_blocks — one partition definition), but with a
    ``lax.scan`` carrying the AggState: each block's client updates are
    computed, folded, and *freed* before the next block starts, so a
    round peaks at O(chunk·D) instead of O(N·D).

**Bitwise contract.**  The default fold applies ``update`` row by row —
a strict left fold in client order, the exact association
``core.diversefl.masked_sum_fold`` fixes for the dense rules — so
streaming and dense paths agree *bit for bit* (delta and criterion
logs) for the masked-mean family, at any chunk size, with any
participation.  Padding rows contribute exact ±0.0 (weight 0) and a
trailing ``x + 0.0`` cannot change a float's magnitude.  With
``use_kernel_agg`` the fold instead accumulates per *block* through the
streaming Pallas kernel (kernels/masked_agg.masked_agg_fold_kernel) —
one HBM pass per block into an accumulator that keeps, for the whole
sweep, the kernel's own 2-D layout (``fold_layout``: the
lane-dense (D/128, 128) view, else the (1, D) row); block-level
association trades the bitwise guarantee for fp-tolerance parity.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.diversefl import criterion_logs, diversefl_mask
from ..sharding import (data_shard_count, model_shard_count,
                        pod_data_counts, shard_clients,
                        shard_flat, shard_lanes)
from . import telemetry
from .chunking import (block_valid, group_blocks, group_blocks_2d,
                       pad_to_blocks, resolve_pods, resolve_shards, unblock)
from .server import _REGISTRY as _DENSE_REGISTRY
from .server import AggregationContext

logger = logging.getLogger(__name__)

AggState = Any          # fixed-size pytree of arrays — O(D), never O(N·D)
ClientCtx = Dict[str, jnp.ndarray]   # per-client arrays: guide/byz/valid


@dataclasses.dataclass(frozen=True)
class StreamingAggregator:
    """A bound streaming rule: an AggState monoid over client updates.

    ``weights``/``update_block`` are optional vectorized forms for the
    weighted-mean family: ``weights(U_blk, ctx_blk)`` maps a whole
    (c, D) block to per-client (numerator coeff, denominator coeff,
    logs); ``update_block`` folds a block in one step (through the
    streaming Pallas kernel when the rule was bound with
    ``use_kernel_agg``).  ``unroll`` is the sweep's row-fold unroll
    factor: 8 (matching ``masked_sum_fold``) is only layout-stable for
    rules whose weights are exact 0/1 — real-weight rules (fltrust)
    set 1, keeping the fold body a single mul + add that XLA lowers
    identically solo and vmapped (no FMA latitude, DESIGN.md §8)."""
    init: Callable[[int], AggState]
    update: Callable[[AggState, jnp.ndarray, ClientCtx],
                     Tuple[AggState, Dict]]
    merge: Callable[[AggState, AggState], AggState]
    finalize: Callable[[AggState], Tuple[jnp.ndarray, Dict]]
    weights: Optional[Callable] = None
    update_block: Optional[Callable] = None
    unroll: int = 8


@dataclasses.dataclass(frozen=True)
class StreamingEntry:
    """Registry row: ``bind(ctx)`` closes a rule over the round's static
    context (DiverseFL thresholds, root update, kernel flags) and returns
    the pure monoid."""
    name: str
    bind: Callable[[AggregationContext], StreamingAggregator]


_STREAMING: Dict[str, StreamingEntry] = {}

# Why each dense-only rule cannot fold into an O(D) state: the logged
# fallback reason when FLConfig.streaming=True requests one of these.
NON_STREAMING: Dict[str, str] = {
    "median": "coordinate-wise median needs every client's value per "
              "dimension — order statistics do not form a bounded monoid",
    "trimmed_mean": "per-dimension trimming needs the full sorted column "
                    "of client values",
    "krum": "Krum scores couple every pair of clients (pairwise "
            "distances), so no per-client fold exists",
    "bulyan": "recursive Krum selection couples every pair of clients",
    "resampling": "resampled groups average arbitrary client subsets "
                  "before the median — group membership is not a fold",
}


def register_streaming(name: str):
    """Decorator: register ``bind(ctx) -> StreamingAggregator`` under a
    name the dense AggregatorRegistry already knows."""
    def deco(bind_fn):
        if name in _STREAMING:
            raise ValueError(f"streaming rule {name!r} already registered")
        if name not in _DENSE_REGISTRY:
            raise ValueError(
                f"streaming rule {name!r} has no dense AggregatorRegistry "
                f"counterpart — register the dense rule first so the two "
                f"registries cannot drift")
        _STREAMING[name] = StreamingEntry(name, bind_fn)
        return bind_fn
    return deco


def get_streaming(name: str) -> Optional[StreamingEntry]:
    """The streaming entry for ``name``, or None if the rule only exists
    densely (callers fall back with ``fallback_reason``)."""
    return _STREAMING.get(name)


def streaming_rules() -> Tuple[str, ...]:
    """Registered streaming rule names, in registration order."""
    return tuple(_STREAMING)


def fallback_reason(name: str) -> Optional[str]:
    """Why ``name`` cannot stream (None when it can)."""
    if name in _STREAMING:
        return None
    return NON_STREAMING.get(
        name, "no streaming AggState registered for this rule")


# ----------------------------------------------------------------------
# The weighted-mean family
# ----------------------------------------------------------------------

def flat_ndim() -> int:
    """Rank of ONE client's flattened update under the active layout:
    1 for the classic ``(D,)`` vector, 2 for the model-sharded blocked
    ``(ms, L)`` matrix (:func:`sharding.flatten_updates_sharded`).  A
    trace-time constant — the layout is fixed by the mesh the round is
    traced under."""
    return 2 if model_shard_count() > 1 else 1


def stat_sum(x):
    """Per-client sum over the flat model dims — ``axis=-1`` on the
    classic layout (jaxpr-identical to the historical reductions), the
    last TWO axes on the blocked ``(…, ms, L)`` layout.  There GSPMD
    lowers the row-dim reduce to per-shard partials + a psum over
    ``model`` — the one cross-model-axis collective in the Eq. 6
    criterion statistics (DESIGN.md §12: bounded-ULP, not bitwise)."""
    k = flat_ndim()
    return jnp.sum(x, axis=tuple(range(x.ndim - k, x.ndim)))


def weighted_mean_rule(weight_fn: Callable, *, floor: float = 1.0,
                       use_kernel: bool = False,
                       unroll: int = 8, codec=None) -> StreamingAggregator:
    """Build the AggState monoid for a weighted-mean rule.

    ``weight_fn(u, ctx) -> (a, b, logs)``: client ``i`` contributes
    ``a_i · u_i`` to the numerator and ``b_i`` to the denominator; the
    state is the pair ``(Σ a_i u_i, Σ b_i)`` and ``finalize`` divides
    once (``s / max(n, floor)``).  ``weight_fn`` must be written with
    ``axis=-1`` reductions so the same body serves one (D,) row inside
    ``update`` and a whole (c, D) block inside ``weights`` — under
    vmap/batching both lower to the identical last-axis reduction the
    dense ``similarity_stats_matrix`` performs, which is what keeps the
    criterion statistics bitwise equal across execution layouts.

    ``codec`` (an fl/compression.Codec, threaded from
    ``AggregationContext.codec``) marks the update stream as
    lossy-encoded: ``u`` arrives as the codec's encoded pytree and is
    decoded before the weights and the fold — per-client statistics are
    computed on the *decoded* values, the same bits the dense fallback
    rules see through the shared reference decoder, which is what keeps
    streaming == dense bitwise under every codec (DESIGN.md §10).  On
    the kernel block path the dequantization instead fuses into the
    fold pass itself: dense payloads (bf16) go straight through
    ``masked_agg_update`` (its in-kernel f32 cast IS the decode), int8
    payloads through the fused dequantize-and-fold kernel
    (kernels/dequant_fold.py).  ``codec=None`` is the raw-f32 status
    quo — jaxpr-identical to every pre-compression path.

    init is the monoid identity (zeros); merge adds componentwise —
    associative, and commutative up to fp rounding.  Rows flagged
    invalid (padding) get weight exactly 0.0.

    **The numerator's layout.**  On the kernel block fold of the flat
    layout (``use_kernel``, a raw or dense-payload stream) the numerator
    is, from ``init`` to ``finalize``, the 2-D accumulator the Pallas
    fold reads and writes: ``kernels/masked_agg.fold_layout``'s
    lane-dense (D/128, 128) view where 128 divides D, else the (1, D)
    row.  ``update`` and ``merge`` work in it, and ``finalize`` hands a
    flat (D,) delta on, once a round.  Each trace of ``init`` records a
    ``fold_layout`` event (rows, lanes, ``view``) on the flight
    recorder.  The row fold, the int8 fold and the model-sharded layout
    keep their flat (D,) or blocked (ms, L) numerator.

    **Model-sharded D** (DESIGN.md §12): on a client x model mesh the
    (D,) numerator is constrained over the ``model`` axis at ``init``
    and ``finalize``, so the fold's ``s + u_i * a_i`` is a *per-shard
    partial fold* — every multiply-add stays shard-local, the merge
    tree adds co-located shards, and the ONLY cross-model-axis
    collective in Steps 4-5 is the psum GSPMD inserts at the
    ``weight_fn`` dot/norm reductions (the Eq. 6 criterion statistics,
    which are per-client *scalars*).  With a trivial model axis the
    constraints no-op and the fold keeps the §6/§9 bitwise merge-order
    contracts verbatim; across a non-trivial model axis the scalar
    stats reassociate into per-shard partials + psum — bounded-ULP,
    not bitwise (exactly where DESIGN.md §12 relaxes the contract).
    """
    decode = (lambda u: u) if codec is None else codec.decode
    # Non-finite guard (ISSUE 10 satellite): on the raw-f32 stream a
    # client emitting NaN/Inf would poison the AggState numerator
    # irreversibly (NaN · 0 = NaN, so zeroing the *weight* alone is not
    # enough — the value itself must be sanitized before it multiplies
    # anything).  Lossy codecs skip the guard: their wire formats cannot
    # encode non-finite payloads, and the decode path is pinned
    # bitwise against the dense reference decoder.
    guard = codec is None
    # the Pallas fold's own accumulator layout (see the docstring); the
    # int8 fold (kernels/dequant_fold.py) keeps a flat (D,) numerator
    kernel_layout = use_kernel and (codec is None or codec.qblock is None)

    def _screen(ud):
        """(sanitized update, finite-row bits or None).

        ``stat_sum(ud * 0.0)`` is 0.0 iff every element is finite
        (0·Inf = 0·NaN = NaN), giving one O(D) reduce per row instead
        of a full isfinite mask reduction.  On finite data the
        sanitizer is bitwise-inert: ``where(True, x, 0) == x`` and the
        weight multiply by 1.0 is exact."""
        if not guard:
            return ud, None
        fin = jnp.isfinite(stat_sum(ud * 0.0))
        mask = fin.reshape(jnp.shape(fin) + (1,) * flat_ndim()) \
            if jnp.ndim(fin) else fin
        return jnp.where(mask, ud, jnp.zeros_like(ud)), fin

    def _valid(a, b, ctx):
        # two multiplicative weight channels: "valid" (padding rows —
        # set by stream_aggregate) and "live" (async cohort membership
        # minus dropouts — set by the engine's round body); both are
        # exact 0/1 floats, so ×1.0 keeps finite weights bitwise
        for key in ("valid", "live"):
            v = ctx.get(key)
            if v is not None:
                vf = v.astype(jnp.float32)
                a, b = a * vf, b * vf
        return a, b

    def init(d) -> AggState:
        # the O(D) numerator lives model-sharded when the mesh says so:
        # the identity's placement is what keeps every fold step's
        # multiply-add shard-local (no-op on a trivial model axis).
        # ``d`` is the flat length (classic layout) or the blocked
        # (ms, L) shape tuple (model-sharded layout).
        if isinstance(d, tuple):
            shape = d
        elif kernel_layout:
            from ..kernels.masked_agg import fold_layout
            shape = fold_layout(d)
            telemetry.event("fold_layout", rows=shape[0], lanes=shape[1],
                            view=shape[1] % 128 == 0)
        else:
            shape = (d,)
        return (shard_flat(jnp.zeros(shape, jnp.float32)),
                jnp.zeros((), jnp.float32))

    # the criterion statistics are Step 4; everything else the state
    # sees is the Step 5 fold (DESIGN.md §11)
    weight_fn = jax.named_scope("step4_filter")(weight_fn)

    @jax.named_scope("step5_fold")
    def update(state, u, ctx):
        s, n = state
        ud, fin = _screen(decode(u))
        a, b, logs = weight_fn(ud, ctx)
        if fin is not None:
            ff = fin.astype(jnp.float32)
            a, b = a * ff, b * ff
            logs = dict(logs, nonfinite=~fin)
        a, b = _valid(a, b, ctx)
        # one row into the numerator's layout (a no-op but on the kernel
        # fold's 2-D accumulator)
        return (s + ud.astype(jnp.float32).reshape(s.shape) * a,
                n + b), logs

    def merge(x, y):
        return jax.tree.map(jnp.add, x, y)

    @jax.named_scope("step5_fold")
    def finalize(state):
        s, n = state
        if flat_ndim() == 1:
            # the kernel fold's 2-D accumulator as the flat vector it
            # tiles (a bitcast on the TPU), divided once
            s = s.reshape(-1)
        # the round delta inherits the numerator's model sharding — the
        # division is elementwise, so no gather happens here either
        return shard_flat(s / jnp.maximum(n, jnp.float32(floor))), {}

    def _block(U, ctx_blk):
        """Shared block form: (sanitized decoded block, a, b, logs) —
        the guard must sanitize the VALUES the fold multiplies, not just
        the weights, so both `weights` and `update_block` route here."""
        ud, fin = _screen(decode(U))
        a, b, logs = weight_fn(ud, ctx_blk)
        if fin is not None:
            ff = fin.astype(jnp.float32)
            a, b = a * ff, b * ff
            logs = dict(logs, nonfinite=~fin)
        a, b = _valid(a, b, ctx_blk)
        return ud, a, b, logs

    def weights(U, ctx_blk):
        _, a, b, logs = _block(U, ctx_blk)
        return a, b, logs

    @jax.named_scope("step5_fold")
    def update_block(state, U, ctx_blk):
        s, n = state
        ud, a, b, logs = _block(U, ctx_blk)
        if use_kernel:
            from ..kernels import ops as kops
            if codec is None:
                s = kops.masked_agg_update(ud, a, s)
            elif codec.qblock is not None:
                # int8 per-block scales: dequantization fused into the
                # fold's single HBM pass over the 1-byte payload
                s = kops.dequant_fold_update(U["q"], U["scale"], a, s,
                                             qblock=codec.qblock)
            else:
                # dense payload (bf16/f32): the masked-agg kernel's
                # in-kernel f32 cast is the whole dequantization
                s = kops.masked_agg_update(U["q"], a, s)
        else:
            # a: (c,) broadcast against (c, D) or blocked (c, ms, L) —
            # reshape((c, 1)) is a[:, None] verbatim on the classic
            # layout, so the historical jaxpr is unchanged
            ax = a.reshape(a.shape + (1,) * flat_ndim())
            s = s + jnp.sum(ud.astype(jnp.float32) * ax, axis=0)
        return (s, n + jnp.sum(b)), logs

    return StreamingAggregator(init, update, merge, finalize,
                               weights=weights, update_block=update_block,
                               unroll=unroll)


@register_streaming("mean")
def _mean_stream(ctx: AggregationContext) -> StreamingAggregator:
    def weight(u, ci):
        one = jnp.ones(jnp.shape(u)[:u.ndim - flat_ndim()], jnp.float32)
        return one, one, {}
    return weighted_mean_rule(weight, use_kernel=ctx.use_kernel_agg,
                              codec=ctx.codec)


@register_streaming("oracle")
def _oracle_stream(ctx: AggregationContext) -> StreamingAggregator:
    def weight(u, ci):
        keep = ~ci["byz"]
        w = keep.astype(jnp.float32)
        return w, w, {"mask": keep}
    return weighted_mean_rule(weight, use_kernel=ctx.use_kernel_agg,
                              codec=ctx.codec)


@register_streaming("diversefl")
def _diversefl_stream(ctx: AggregationContext) -> StreamingAggregator:
    dfl = ctx.dfl
    kernel_stats = ctx.use_kernel_stats

    def weight(u, ci):
        # Per-client C1/C2 against the guiding update, computed on the
        # fly: multiply + last-axis reduce (NOT vdot/dot_general) so one
        # row here and a row of the dense similarity_stats_matrix are the
        # same reduction — bitwise-equal statistics either way.
        g = ci["guide"].astype(jnp.float32)
        uf = u.astype(jnp.float32)
        if kernel_stats and uf.ndim == 2 and flat_ndim() == 1:
            # block form (update_block / use_kernel_agg): the fused Pallas
            # similarity kernel — one HBM pass over the block pair
            # (model-sharded layouts never reach it: FLConfig validation
            # rejects kernels on a non-trivial model axis)
            from ..kernels import ops as kops
            stats = kops.similarity_stats(uf, g)
            dot, zz, gg = stats[:, 0], stats[:, 1], stats[:, 2]
        else:
            dot = stat_sum(uf * g)
            zz = stat_sum(uf * uf)
            gg = stat_sum(g * g)
        keep = diversefl_mask(dot, zz, gg, dfl)
        w = keep.astype(jnp.float32)
        # z_sq/g_sq mirror the dense rule's log keys exactly (bitwise per
        # client — identical elementwise form), feeding the telemetry
        # block's norm summaries on the streaming path too
        return w, w, {"mask": keep, "z_sq": zz, "g_sq": gg,
                      **criterion_logs(dot, zz, gg)}
    return weighted_mean_rule(weight, use_kernel=ctx.use_kernel_agg,
                              codec=ctx.codec)


@register_streaming("fltrust")
def _fltrust_stream(ctx: AggregationContext) -> StreamingAggregator:
    root = ctx.root_update.astype(jnp.float32)
    rn = jnp.sqrt(jnp.sum(root * root)) + 1e-12

    def weight(u, ci):
        uf = u.astype(jnp.float32)
        un = jnp.sqrt(stat_sum(uf * uf)) + 1e-12
        ts = jax.nn.relu(stat_sum(uf * root) / (un * rn))
        return ts * (rn / un), ts, {}
    # real-valued weights: the 8-way-unrolled fold's multiply-add chain
    # is FMA-latitude XLA resolves differently solo vs vmapped; one
    # iteration per row keeps the streaming fltrust fold layout-stable
    return weighted_mean_rule(weight, floor=1e-12,
                              use_kernel=ctx.use_kernel_agg, unroll=1,
                              codec=ctx.codec)


# ----------------------------------------------------------------------
# The streaming sweep
# ----------------------------------------------------------------------

def tree_merge(merge: Callable, states, n: int):
    """Canonical fixed-association tree-reduce of ``n`` stacked partial
    AggStates (leading axis ``n`` on every leaf).

    The merge order is part of the bitwise contract (DESIGN.md §7): a
    balanced binary tree over the shard index — round 1 merges
    ``(s0, s1), (s2, s3), ...``, an odd tail passes through untouched,
    and rounds repeat until one state remains — so the association is a
    pure function of ``n``, never of device layout or scheduling.
    ``n == 1`` returns the single state unchanged (no merge at all),
    which is what keeps the one-shard path bitwise-identical to the
    sequential sweep."""
    parts = [jax.tree.map(lambda x, i=i: x[i], states) for i in range(n)]
    while len(parts) > 1:
        parts = [merge(parts[i], parts[i + 1])
                 if i + 1 < len(parts) else parts[i]
                 for i in range(0, len(parts), 2)]
    return parts[0]


def stream_aggregate(rule: StreamingAggregator, block_fn: Callable,
                     args: tuple, chunk: Optional[int], *, d: int,
                     prefer_block: bool = False,
                     shards: Optional[int] = None,
                     pods: Optional[int] = None,
                     block_extra: bool = False,
                     extra_state=None):
    """Fold per-client updates into ``rule``'s AggState, one chunk-sized
    block at a time — the (N, D) update matrix never materializes.

    ``args`` is a tuple of pytrees sharing leading client axis C (the
    minibatch stacks plus any O(C) per-client scalars); ``block_fn(blk,
    valid) -> (U_blk (c, D), ctx_blk)`` computes one block's flattened
    updates and per-client context (guide rows, Byzantine bits) from the
    sliced block arguments.  The sweep scans the same padded blocks
    ``chunked_vmap`` would map over, carrying the state; per-client logs
    come back stacked (k, chunk), are unblocked to (C,) and the padding
    rows dropped — exactly chunked_vmap's output contract.

    ``prefer_block=True`` uses ``rule.update_block`` when available (the
    Pallas-kernel block fold); the default folds ``rule.update`` row by
    row, the left-fold association the bitwise contract relies on.

    ``shards`` selects the shard-parallel sweep (``None`` = auto from
    the active mesh's data axes; 1 off-mesh): the ``k`` blocks split
    into S *contiguous* groups, each group folded independently with
    the identical left fold (a vmapped scan whose group axis carries
    the client-axis sharding constraint, so an active mesh runs the
    groups in parallel — ``N/(chunk·S)`` sequential fold steps instead
    of ``N/chunk``), and the S partial states combine via
    :func:`tree_merge`'s canonical ``log2(S)``-deep order.  The result
    is a pure function of (client order, chunk, S) — device layout
    cannot change the bits, ``S == 1`` *is* the sequential sweep, and
    per-client criterion logs are bitwise-identical at every S (the
    fold association never touches per-row statistics).  A shard count
    that does not divide the block count is clamped to the largest
    divisor (fl/chunking.resolve_shards).

    ``pods`` selects the **hierarchical two-tier fold** (DESIGN.md §9):
    the ``k`` blocks split into P *contiguous* pod groups (pod-major —
    the same client ranges the ``("pod", "data")`` sharding places on
    each pod's devices); **tier 1** folds every pod's clients with the
    identical left fold, ``shards``-way shard-parallel *within* the pod
    (``shards`` is per-pod here; auto = the mesh's non-pod data axes),
    its S partials combined by :func:`tree_merge`; **tier 2** combines
    the P per-pod partial AggStates — O(pods·D), the only cross-pod
    traffic — by the same canonical balanced-binary association.  The
    result is a pure function of (client order, chunk, S, pods);
    ``pods=1`` takes the single-tier path above *verbatim* (bitwise);
    per-client logs are bitwise at every (S, pods).  ``pods=None``
    derives P from the mesh's pod axis (1 off-mesh, clamped to a
    divisor of ``k``); an explicit non-dividing ``pods`` raises the
    named ``ShardMismatchError`` (fl/chunking.resolve_pods).

    ``extra_state`` (an AggState, or None) is a pre-folded partial state
    merged into the sweep's result just before ``finalize`` — the async
    engine's landed-straggler channel (DESIGN.md §13): stale updates
    folded outside the block sweep (they belong to no current block)
    join the round mean through the same monoid merge.  ``None`` (every
    pre-async caller) leaves the fold bitwise-untouched — no merge op
    is traced at all.

    ``block_extra=True`` gives the fold a per-block *output* channel:
    ``block_fn`` returns a triple ``(U_blk, ctx_blk, extra)`` whose
    third element is an arbitrary (chunk, ...) pytree riding the scan ys
    alongside the per-client logs (error-feedback residual rows in
    fl/engine.py — values the round must carry out of the fold but that
    never touch the AggState).  The extras are unblocked to (C, ...)
    exactly like client logs and returned as a fourth element:
    ``(delta, agg_logs, client_logs, extra)``.

    Returns ``(delta, agg_logs, client_logs)`` (plus ``extra`` with
    ``block_extra=True``).
    """
    C = jax.tree.leaves(args)[0].shape[0]
    chunk = C if chunk is None or chunk >= C else chunk
    blocks, k, _ = pad_to_blocks(args, chunk)
    valid = block_valid(k, chunk, C)
    use_block = prefer_block and rule.update_block is not None
    mesh_pods, mesh_data = pod_data_counts()
    P = resolve_pods(pods, k, auto=mesh_pods)

    def sweep(state, xs):
        blk, valid_b = xs
        if block_extra:
            U_blk, ctx_blk, extra = block_fn(blk, valid_b)
        else:
            U_blk, ctx_blk = block_fn(blk, valid_b)
            extra = ()
        ctx_blk = dict(ctx_blk, valid=valid_b)
        if use_block:
            state, logs = rule.update_block(state, U_blk, ctx_blk)
        else:
            # unroll matches masked_sum_fold's (same adds in the same
            # order) except where the rule folds real-valued weights and
            # pins unroll=1 for layout stability (StreamingAggregator.
            # unroll)
            state, logs = jax.lax.scan(
                lambda st, uc: rule.update(st, uc[0], uc[1]),
                state, (U_blk, ctx_blk), unroll=rule.unroll)
        return state, (logs, extra)

    fold = lambda g: jax.lax.scan(sweep, rule.init(d), g)   # noqa: E731

    if P > 1:
        # ---- two-tier: pod-local folds, cross-pod partial merge ----
        S = resolve_shards(shards if shards is not None else mesh_data,
                           k // P)
        gxs = group_blocks_2d((blocks, valid), k, P, S)
        gxs = jax.tree.map(shard_lanes, gxs)    # (pod, shard) -> mesh axes
        states, ys = jax.vmap(jax.vmap(fold))(gxs)
        ys = jax.tree.map(
            lambda x: x.reshape((k,) + x.shape[3:]), ys)
        # tier 1 finishes inside the pod: S partials -> one per-pod state
        pod_states = jax.vmap(
            lambda st: tree_merge(rule.merge, st, S))(states)
        # tier 2: only the (P, D)-sized partial states cross pods
        state = tree_merge(rule.merge, pod_states, P)
    else:
        S = resolve_shards(
            shards if shards is not None else data_shard_count(), k)
        if S == 1:
            state, ys = jax.lax.scan(sweep, rule.init(d), (blocks, valid))
        else:
            gxs = group_blocks((blocks, valid), k, S)
            gxs = jax.tree.map(shard_clients, gxs)  # group axis -> data axes
            states, ys = jax.vmap(fold)(gxs)
            ys = jax.tree.map(
                lambda x: x.reshape((k,) + x.shape[2:]), ys)
            state = tree_merge(rule.merge, states, S)
    if extra_state is not None:
        # landed stale updates join as one canonical trailing merge —
        # part of the fixed association (DESIGN.md §13)
        state = rule.merge(state, extra_state)
    delta, agg_logs = rule.finalize(state)
    logs, extras = ys
    if block_extra:
        return (delta, agg_logs, unblock(logs, k, chunk, C),
                unblock(extras, k, chunk, C))
    return delta, agg_logs, unblock(logs, k, chunk, C)
