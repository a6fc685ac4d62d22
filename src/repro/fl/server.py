"""SecureServer — Algorithm 1's trust boundary, plus the aggregator registry.

Every aggregation path in the repo routes through this module
(DESIGN.md §3):

  * ``SecureServer`` owns the TEE ``Enclave``.  At setup it performs the
    attestation handshake (Step 0) and ingests each client's once-shared
    sample as a *sealed* blob (Step 1).  Guiding-update data is only ever
    obtained by unsealing those blobs — there is no raw-sample side
    channel — and the unsealed guide batches are cached device-side
    (keyed on the enclave's seal version) so the jitted round step pays
    the unseal cost once, not per round.
  * ``AggregatorRegistry`` (module-level, decorator-registered) maps each
    aggregation rule name to a strategy with the uniform signature
    ``fn(U, ctx) -> (delta, logs)`` where ``U`` is the stacked (N, D)
    update matrix and ``ctx`` is an :class:`AggregationContext`.  This
    replaces the per-call-site if/elif dispatch the seed carried in
    fl/simulator.py and benchmarks/.

The DiverseFL rule itself imports its mask/statistics/aggregation math
from core/diversefl.py (one source of truth) and can route Step 4+5
through the fused Pallas kernels (kernels/similarity.py +
kernels/masked_agg.py) via the ``use_kernel_stats``/``use_kernel_agg``
context flags.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import aggregators as agg
from ..core.diversefl import (DiverseFLConfig, criterion_logs, diversefl_mask,
                              guiding_update, masked_mean_flat,
                              similarity_stats_matrix)
from ..core.tee import Enclave
from .chunking import chunked_vmap
from .telemetry import AuditLog

DEFAULT_IDENTITY = "diversefl-enclave-v1"

# The masked/weighted-mean family: rules whose delta is a per-client-
# weighted mean, so the fused Pallas masked-agg kernels (use_kernel_agg)
# apply — 0/1 masks for diversefl/oracle/mean, trust-score weights for
# fltrust.  Any other rule never reaches the kernel —
# FLConfig.__post_init__ rejects the combination instead of silently
# ignoring the flag.
KERNEL_AGG_RULES = ("diversefl", "oracle", "mean", "fltrust")


# ----------------------------------------------------------------------
# Aggregator registry
# ----------------------------------------------------------------------

@dataclasses.dataclass
class AggregationContext:
    """Everything a registered rule may need beyond the update matrix.

    All array members are traced values inside the jitted round step;
    the scalars/configs are compile-time constants.  ``byz_mask`` in
    particular is *scenario data*, never a baked constant: the round
    body slices it from the run's scenario operands
    (fl/engine.make_scenario), which is what lets a batched sweep vary
    Byzantine identities per cell without retracing (DESIGN.md §8) —
    whereas ``f`` is a static int, so rules that consume it as a shape
    (trimmed_mean/krum/bulyan) force a new structural group per value
    (fl/sweep.F_STATIC_RULES)."""
    key: Optional[jax.Array] = None          # rng (resampling)
    f: int = 0                               # Byzantine budget
    dfl: DiverseFLConfig = DiverseFLConfig()
    byz_mask: Optional[jnp.ndarray] = None   # ground truth (oracle only)
    guides: Optional[jnp.ndarray] = None     # G (N, D) — enclave Step 3
    root_update: Optional[jnp.ndarray] = None  # FLTrust root direction
    resample_s: int = 2
    use_kernel_stats: bool = False           # Pallas similarity kernel
    use_kernel_agg: bool = False             # Pallas fused masked mean
    stream_shards: Optional[int] = None      # streaming fold groups: None =
    #                                          auto from the active mesh's
    #                                          data axes (fl/streaming.py);
    #                                          per-pod when stream_pods > 1
    stream_pods: Optional[int] = None        # two-tier fold pod count: None =
    #                                          auto from the mesh's pod axis
    #                                          (1 off-mesh); an explicit count
    #                                          must divide the block count
    #                                          (DESIGN.md §9)
    codec: Optional[object] = None           # fl/compression.Codec when the
    #                                          update stream is LOSSY-encoded:
    #                                          streaming rules decode blocks
    #                                          through it (or fold the int8
    #                                          payload via the fused dequant
    #                                          kernel).  None == raw f32
    #                                          arrays — the uncompressed and
    #                                          f32-passthrough paths, whose
    #                                          jaxprs stay identical
    #                                          (DESIGN.md §10)


@dataclasses.dataclass(frozen=True)
class AggregatorEntry:
    name: str
    fn: Callable[[jnp.ndarray, AggregationContext],
                 Tuple[jnp.ndarray, Dict]]
    needs_guides: bool = False               # requires ctx.guides
    needs_root: bool = False                 # requires ctx.root_update


_REGISTRY: Dict[str, AggregatorEntry] = {}


def register_aggregator(name: str, *, needs_guides: bool = False,
                        needs_root: bool = False):
    """Decorator: register ``fn(U, ctx) -> (delta, logs)`` under ``name``."""
    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"aggregator {name!r} already registered")
        _REGISTRY[name] = AggregatorEntry(name, fn, needs_guides, needs_root)
        return fn
    return deco


def get_aggregator(name: str) -> AggregatorEntry:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown aggregator {name!r}; "
                         f"available: {available_aggregators()}") from None


def available_aggregators() -> Tuple[str, ...]:
    """Registered rule names, in registration order."""
    return tuple(_REGISTRY)


def aggregate(name: str, U, ctx: AggregationContext):
    """Dispatch one aggregation: (N, D) updates -> ((D,) delta, logs)."""
    return get_aggregator(name).fn(U, ctx)


# ----------------------------------------------------------------------
# Registered rules (paper Sec. IV + Appendix A)
# ----------------------------------------------------------------------

@register_aggregator("diversefl", needs_guides=True)
def _diversefl(U, ctx):
    """Per-client C1/C2 criteria + masked mean (Eq. 2-6)."""
    if ctx.use_kernel_agg:
        from ..kernels import ops as kops
        delta, mask, (dot, zz, gg) = kops.diversefl_step45(U, ctx.guides,
                                                           ctx.dfl)
    else:
        if ctx.use_kernel_stats:
            from ..kernels import ops as kops
            stats = kops.similarity_stats(U, ctx.guides)
            dot, zz, gg = stats[:, 0], stats[:, 1], stats[:, 2]
        else:
            dot, zz, gg = similarity_stats_matrix(U, ctx.guides)
        mask = diversefl_mask(dot, zz, gg, ctx.dfl)
        delta = masked_mean_flat(U, mask)
    # z_sq/g_sq feed the per-round norm summaries in the telemetry block
    # (fl/telemetry.make_round_telemetry_fn); like every log key they are
    # filtered out of the history by make_eval_fn's key selection
    return delta, {"mask": mask, "z_sq": zz, "g_sq": gg,
                   **criterion_logs(dot, zz, gg)}


@register_aggregator("oracle")
def _oracle(U, ctx):
    mask = ~ctx.byz_mask
    if ctx.use_kernel_agg:
        from ..kernels import ops as kops
        return kops.masked_aggregate(U, mask), {"mask": mask}
    return masked_mean_flat(U, mask), {"mask": mask}


@register_aggregator("mean")
def _mean(U, ctx):
    ones = jnp.ones((U.shape[0],), jnp.float32)
    if ctx.use_kernel_agg:
        from ..kernels import ops as kops
        return kops.masked_aggregate(U, ones), {}
    # masked_mean_flat with an all-ones mask == the plain mean, reduced in
    # the canonical fold order the streaming path reproduces bitwise.
    return masked_mean_flat(U, ones), {}


@register_aggregator("median")
def _median(U, ctx):
    return agg.median(U), {}


@register_aggregator("trimmed_mean")
def _trimmed_mean(U, ctx):
    return agg.trimmed_mean(U, ctx.f), {}


@register_aggregator("krum")
def _krum(U, ctx):
    return agg.krum(U, ctx.f), {}


@register_aggregator("bulyan")
def _bulyan(U, ctx):
    return agg.bulyan(U, ctx.f), {}


@register_aggregator("resampling")
def _resampling(U, ctx):
    return agg.resampling(U, ctx.key, ctx.resample_s), {}


@register_aggregator("fltrust", needs_root=True)
def _fltrust(U, ctx):
    if ctx.use_kernel_agg:
        # weighted-mean form: a_i = TS_i · ‖root‖/‖z_i‖ folds the rescale
        # into the per-client weight, one kernel pass over U accumulates
        # Σ a_i·z_i, one division by Σ TS_i finalizes [26]
        from ..kernels import ops as kops
        r = ctx.root_update.astype(jnp.float32)
        rn = jnp.sqrt(jnp.sum(r * r)) + 1e-12
        Uf = U.astype(jnp.float32)
        un = jnp.sqrt(jnp.sum(Uf * Uf, axis=1)) + 1e-12
        ts = jax.nn.relu((Uf @ r) / (un * rn))
        s = kops.masked_agg_update(
            Uf, ts * (rn / un), jnp.zeros((U.shape[1],), jnp.float32))
        return s / jnp.maximum(ts.sum(), 1e-12), {}
    return agg.fltrust(U, ctx.root_update), {}


# ----------------------------------------------------------------------
# SecureServer
# ----------------------------------------------------------------------

class SecureServer:
    """The FL server's enclave-backed aggregation choke point.

    Setup (Steps 0-1): construct -> attestation handshake; then
    ``ingest_samples`` seals each client's once-shared sample into the
    enclave.  Training (Steps 3-5): ``guide_batches`` exposes the
    *unsealed* samples (cached device-side, invalidated whenever the
    sealed store changes), ``compute_guides`` runs the enclave-side
    guiding updates, and ``aggregate`` dispatches through the registry.
    """

    def __init__(self, enclave: Optional[Enclave] = None,
                 identity: str = DEFAULT_IDENTITY, nonce: int = 0x5ecf1):
        self.enclave = enclave if enclave is not None else Enclave(identity)
        # append-only, hash-chained record of every enclave-side decision
        # (fl/telemetry.AuditLog, DESIGN.md §11): attestation, seals/
        # drops, guide-cache rebuilds, per-round tag counts.  Entries
        # commit to the previous digest, so the server cannot silently
        # rewrite what it did — the simulation analogue of SecFL's
        # attested aggregation log.  Only ids/counts/versions are logged,
        # never samples or updates.
        self.audit = AuditLog()
        quote = self.enclave.attest(nonce)
        if not Enclave.verify_quote(quote, identity, nonce):
            raise RuntimeError(
                f"attestation failed: enclave does not measure as {identity!r}")
        self.audit.append("attestation", identity=identity, nonce=nonce,
                          measurement=quote.measurement)
        self._guide_cache = None             # (seal_version, gx, gy)

    # --- Step 1: sealed-sample ingestion ------------------------------
    def ingest_samples(self, client_id: int, x, y) -> None:
        """Seal one client's shared sample M_j^0 into the enclave."""
        self.enclave.seal_samples(client_id, x, y)
        self.audit.append("seal", client=int(client_id),
                          version=self.enclave.seal_version)

    def drop_client(self, client_id: int) -> None:
        self.enclave.drop_client(client_id)
        self.audit.append("drop", client=int(client_id),
                          version=self.enclave.seal_version)

    # --- unsealed guide batches (cached device-side) ------------------
    def guide_batches(self, refresh: bool = False):
        """Guide batches stacked BY CLIENT ID: row j is client j's sample,
        obtained ONLY by unsealing — callers index the stack with client
        ids, so the alignment must survive ``drop_client``.  A dropped
        (or never-ingested) id gets an all-zero row: a zero guiding
        update fails both C1 (dot = 0) and C2 (‖Δ̃‖ = 0), so such a
        client can never pass the criterion — the paper's semantics for
        clients removed from the enclave (Sec. IV-C).

        The unseal runs once per seal_version and the result lives on
        device, so jitted round steps close over stable arrays; any
        mutation of the sealed store (ingest/drop/tamper via re-seal)
        invalidates the cache."""
        version = self.enclave.seal_version
        if refresh or self._guide_cache is None \
                or self._guide_cache[0] != version:
            ids = self.enclave.client_ids()
            if not ids:
                raise RuntimeError(
                    "SecureServer has no sealed samples — ingest_samples "
                    "must run before guide_batches")
            unsealed = {j: self.enclave.unseal_samples(j) for j in ids}
            zx, zy = jax.tree.map(jnp.zeros_like, unsealed[ids[0]])
            rows = [unsealed.get(j, (zx, zy)) for j in range(max(ids) + 1)]
            gx = jnp.stack([r[0] for r in rows])
            gy = jnp.stack([r[1] for r in rows])
            if isinstance(gx, jax.core.Tracer) \
                    or isinstance(gy, jax.core.Tracer):
                raise RuntimeError(
                    "guide_batches cache rebuild attempted under an active "
                    "JAX trace — the unsealed arrays would be cached as "
                    "tracers and leak.  Warm the cache eagerly first "
                    "(fl/engine.make_round_body does this).")
            self._guide_cache = (version, gx, gy)
            self.audit.append("guide_cache_rebuild", version=version,
                              clients=len(ids))
        return self._guide_cache[1], self._guide_cache[2]

    # --- audit: per-round tag decisions -------------------------------
    def record_round_tags(self, round_index: int, **counts) -> None:
        """Commit one round's tag decision counts (kept/tagged clients,
        C1/C2 pass counts) to the hash-chained audit log.  Called by the
        simulator's telemetry drain after the run's one host sync — the
        counts come from the on-device telemetry block, so committing
        them costs no extra device round-trip."""
        self.audit.append(
            "round_tags", round=int(round_index),
            **{k: (v.item() if hasattr(v, "item") else v)
               for k, v in counts.items()})

    def record_cohort_resample(self, round_index: int, cohort: int,
                               **extra) -> None:
        """Commit one round's resampled cohort size (live participants
        after dropout faults) to the audit chain — the async control
        path's answer to "which clients did the enclave even hear from
        this round" (DESIGN.md §13)."""
        self.audit.append("cohort_resample", round=int(round_index),
                          cohort=int(cohort), **extra)

    def record_stale(self, round_index: int, decision: str,
                     count: int, **extra) -> None:
        """Commit one round's staleness decision count to the audit
        chain.  ``decision`` is one of ``buffered`` (straggler update
        entered the pending slab), ``folded`` (a buffered update landed
        and went through Eq. 6 at the landing round) or ``expired``
        (dropped: no free slot, buffer=0, or over the staleness cap)."""
        if decision not in ("buffered", "folded", "expired"):
            raise ValueError(
                f"unknown staleness decision {decision!r}; expected "
                f"'buffered', 'folded' or 'expired'")
        self.audit.append(f"stale_{decision}", round=int(round_index),
                          count=int(count), **extra)

    # --- Step 3: guiding updates --------------------------------------
    def compute_guides(self, params, grad_fn, lr, E: int = 1, select=None,
                       client_chunk: Optional[int] = None, codec=None,
                       flat: bool = False):
        """Δ̃_j from unsealed samples only — the sole guide-data path.

        ``select`` restricts to the round's participating subset S^i
        (client-id index array, traced or concrete); ``client_chunk``
        bounds how many guiding updates are in flight at once
        (fl/chunking.chunked_vmap), so the enclave-side Step 3 scales
        with the chunk, not the federation.  ``client_chunk=None`` is
        exactly the seed vmap.

        ``codec`` (an fl/compression.Codec) quantize-dequantizes the
        guides per tensor before they leave this method — the enclave
        computing its side of the C1/C2 criterion at the wire precision,
        so compressed runs compare quantized updates against equally
        quantized guides (the paper-adjacent science question DESIGN.md
        §10 records).  Lossless codecs (and None) change nothing.

        ``flat=True`` returns the flattened f32 ``(c, D)`` guide matrix
        directly — each client's guide pytree is raveled (and, under a
        lossy codec, quantize-dequantized per tensor first — the exact
        bits ``flatten_updates(quantize_tree(...))`` would produce)
        *inside* the chunked map, so at zoo scale the enclave's working
        set is O(chunk x model): the stacked guide pytree and its flat
        copy never coexist, which is the 100M+-param guide memory model
        (DESIGN.md §12).  The matrix carries the client x model update
        sharding; ``flat=False`` is the legacy pytree contract,
        unchanged."""
        gx, gy = self.guide_batches()
        if select is not None:
            gx, gy = gx[select], gy[select]
        if flat:
            from .compression import quantize_tree
            from ..sharding import (model_shard_count, ravel_sharded,
                                    shard_updates)
            sharded = model_shard_count() > 1

            def one_flat(x, y):
                g = guiding_update(params, (x, y), grad_fn, lr, E)
                if codec is not None and not codec.lossless:
                    # per-tensor quantization BEFORE the ravel: the wire
                    # blocks (int8 qblock) align with tensor boundaries
                    # exactly as on the pytree path — bitwise-identical
                    # guides either way
                    g = quantize_tree(codec, g)
                if sharded:
                    # blocked (ms, L) layout, concatenated along the
                    # unsharded column dim: same element values, none of
                    # the flat build's unsharded full-D temp — and the
                    # same column offsets as the update blocks, so the
                    # Eq. 6 dots align (sharding.ravel_sharded, §12)
                    return ravel_sharded(g)
                return jnp.concatenate(
                    [jnp.ravel(l).astype(jnp.float32)
                     for l in jax.tree.leaves(g)])
            return shard_updates(chunked_vmap(one_flat, (gx, gy),
                                              client_chunk))
        guides = chunked_vmap(
            lambda x, y: guiding_update(params, (x, y), grad_fn, lr, E),
            (gx, gy), client_chunk)
        if codec is not None and not codec.lossless:
            from .compression import quantize_tree   # deferred: no cycle, but
            guides = quantize_tree(codec, guides)    # keep server import-light
        return guides

    def compute_root_update(self, params, grad_fn, lr, E, root_x, root_y):
        """FLTrust's server-side root direction: the same Step-3 SGD on
        the server's root dataset (one pseudo-client, never chunked)."""
        return guiding_update(params, (root_x, root_y), grad_fn, lr, E)

    # --- Steps 4-5: criterion + aggregation ---------------------------
    @staticmethod
    def aggregate(name: str, U, ctx: AggregationContext):
        return aggregate(name, U, ctx)

    @staticmethod
    def streaming_aggregator(name: str, ctx: AggregationContext):
        """The bound streaming AggState monoid for ``name`` — the
        constant-memory counterpart of :meth:`aggregate` (fl/streaming.py,
        DESIGN.md §6) — or None when the rule only exists densely and the
        caller must fall back to the (N, D) path."""
        from .streaming import get_streaming    # deferred: streaming imports
        entry = get_streaming(name)             # this module's registry
        return None if entry is None else entry.bind(ctx)
