"""Evaluation metrics — jittable, device-resident (DESIGN.md §7).

The seed metrics used dynamic-shape boolean indexing (``test_x[sel]``)
and ``float()`` casts, so every eval forced a host round-trip and could
never compile into the round engine's scan.  Every metric here is a
**where-masked reduction over a static-shape test set**:

  * selections are boolean masks, never gathers — shapes stay static, so
    the same function runs eagerly, under ``jax.jit``, or in the scan
    tail of :class:`~repro.fl.engine.RoundEngine`;
  * counts are integer sums (exact under any reduction association —
    what makes the in-scan eval bitwise-equal to an eval on the host)
    with a single fp32 division at the end;
  * results are **device scalars** — nothing here syncs the host.

The trigger-stamped backdoor test set is precomputed once per
federation (:func:`make_backdoor_eval`, cached by
``Federation.backdoor_eval``) instead of re-stamping
``x.at[:, :3, :3].set(1.0)`` on every eval call; the loose
``backdoor_accuracy(model, params, test_x, test_y, acfg)`` signature is
kept for the fig-7 benchmark and stamps inline (still jittable).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..core.attacks import AttackConfig


def _ratio(num, den, empty):
    """Exact integer counts -> fp32 ratio; ``empty`` when ``den == 0``."""
    return jnp.where(den > 0,
                     num.astype(jnp.float32)
                     / jnp.maximum(den, 1).astype(jnp.float32),
                     jnp.float32(empty))


def masked_accuracy(model, params, x, y, mask=None):
    """Fraction of ``mask``-selected rows classified correctly.

    ``mask=None`` scores the whole set.  Correctness is counted with an
    integer sum, so the value is bitwise identical whether this runs
    eagerly, jitted, or inside a scan."""
    preds = jnp.argmax(model.apply(params, x), -1)
    hit = preds == y
    if mask is None:
        return _ratio(jnp.sum(hit), jnp.asarray(y.shape[0]), 0.0)
    keep = mask.astype(bool)
    return _ratio(jnp.sum(hit & keep), jnp.sum(keep), 0.0)


def accuracy(model, params, x, y):
    """Whole-test-set accuracy as a device scalar (jittable twin of
    ``SmallModel.accuracy``; same integer count, fp32 division)."""
    return masked_accuracy(model, params, x, y)


def mask_rates(mask, byz, valid=None):
    """Byzantine-detection TPR/FPR from a round's keep-mask.

    ``mask`` is the aggregator's keep decision (True = kept), ``byz`` the
    ground-truth Byzantine bits for the same client rows.  Flagged means
    *not* kept.  Degenerate cohorts keep the legacy conventions: TPR is
    1.0 with no Byzantine client, FPR 0.0 with no benign client.  Both
    come back as device scalars from exact integer counts.

    ``valid`` (async rounds, DESIGN.md §13) restricts the accounting to
    rows that actually participated — the live cohort plus landed stale
    updates: a Byzantine straggler is scored at its LANDING round, never
    silently dropped, and empty buffer slots/dropped-out clients count
    toward neither rate.  ``valid=None`` (every pre-async call) is the
    all-rows accounting, bit for bit."""
    flagged = ~mask.astype(bool)
    byz = byz.astype(bool)
    if valid is not None:
        v = valid.astype(bool)
        flagged = flagged & v
        tpr = _ratio(jnp.sum(flagged & byz), jnp.sum(byz & v), 1.0)
        fpr = _ratio(jnp.sum(flagged & ~byz), jnp.sum(~byz & v), 0.0)
        return tpr, fpr
    tpr = _ratio(jnp.sum(flagged & byz), jnp.sum(byz), 1.0)
    fpr = _ratio(jnp.sum(flagged & ~byz), jnp.sum(~byz), 0.0)
    return tpr, fpr


# ----------------------------------------------------------------------
# Backdoor eval set — stamped once, reused every eval
# ----------------------------------------------------------------------

def stamp_trigger(x):
    """Apply the paper's pixel-pattern trigger to a batch (3x3 top-left
    patch for image inputs, first 3 features for flat inputs)."""
    if x.ndim >= 3:
        return x.at[:, :3, :3].set(1.0)
    return x.at[:, :3].set(1.0)


@dataclasses.dataclass(frozen=True)
class BackdoorEval:
    """The precomputed backdoor evaluation set for one federation.

    ``x`` is the full test set with the trigger stamped on *every* row;
    ``src`` masks the rows whose true label is the attack's source class
    — the only rows the backdoor metric scores.  Keeping the full
    (static) shape plus a mask is what lets the metric compile: the
    seed's ``test_x[test_y == src]`` gather had a data-dependent shape.
    """
    x: jnp.ndarray
    src: jnp.ndarray
    source_class: int
    target_class: int


def make_backdoor_eval(test_x, test_y, acfg: AttackConfig) -> BackdoorEval:
    """Stamp the trigger once; every later eval is a masked reduction."""
    return BackdoorEval(x=stamp_trigger(test_x),
                        src=test_y == acfg.source_class,
                        source_class=acfg.source_class,
                        target_class=acfg.target_class)


def backdoor_accuracy_on(model, params, ev: BackdoorEval):
    """Fraction of trigger-stamped source-class inputs classified as the
    attacker's target class (lower = better defence); device scalar."""
    preds = jnp.argmax(model.apply(params, ev.x), -1)
    return _ratio(jnp.sum((preds == ev.target_class) & ev.src),
                  jnp.sum(ev.src), 0.0)


def backdoor_accuracy(model, params, test_x, test_y, acfg: AttackConfig):
    """One-shot form (stamps inline, jittable).  Prefer
    ``Federation.backdoor_eval`` + :func:`backdoor_accuracy_on` on any
    path that evaluates more than once."""
    return backdoor_accuracy_on(model, params,
                                make_backdoor_eval(test_x, test_y, acfg))


def main_task_accuracy(model, params, test_x, test_y, acfg: AttackConfig):
    """Accuracy on all classes except the backdoor source class."""
    return masked_accuracy(model, params, test_x, test_y,
                           test_y != acfg.source_class)


# ----------------------------------------------------------------------
# Communication cost — a first-class, recorded quantity
# ----------------------------------------------------------------------

def comm_stats(cfg, d: int, model_shards: int = 1):
    """Per-round wire traffic of one federated round, in bytes.

    ``d`` is the flattened model dimension.  Uplink is what the
    ``cfg.n_selected`` participating clients send — the codec's encoded
    wire size per client (``fl/compression.wire_bytes``: payload plus
    any scale sidecar), NOT the dense f32 size; downlink is the server
    broadcasting the f32 model to the same clients (the paper's server
    sends plain parameters — only the client→server direction is
    compressed).  Keys are flat host ints/floats so run histories stay
    elementwise-comparable across the solo and sweep paths
    (tests/test_sweep.py compares every history key by value).

    ``model_shards`` (> 1 on a tensor-sharded mesh —
    sharding.model_shard_count) prices the wire format each model shard
    actually emits: every shard encodes its **local D/model_shards
    slice independently** (per-shard qblock padding and scale sidecar
    included), and the per-client cost is the sum over shards.  This is
    the whole satellite contract: the stats are pure host arithmetic on
    metadata — ``d`` comes from aval sizes, never from a device gather
    of the sharded params — so a 100M-param sharded run prices its
    uplink without a single extra host sync.  ``model_shards=1``
    (every existing call) is bit-for-bit the old arithmetic."""
    from .compression import get_codec, wire_bytes
    codec = get_codec(getattr(cfg, "compression", "f32"))
    c = cfg.n_selected
    if model_shards > 1:
        base, extra = divmod(d, model_shards)
        # uneven split: `extra` shards hold one more element (how XLA
        # tiles a non-dividing dim is degrade-to-replicated in our
        # constraints, but the priced contract is the even-ish split)
        per_client = ((model_shards - extra) * wire_bytes(codec, base)
                      + extra * wire_bytes(codec, base + 1))
    else:
        per_client = wire_bytes(codec, d)
    dense = d * 4
    return {
        "uplink_bytes_per_client": int(per_client),
        "uplink_bytes_per_round": int(c * per_client),
        "downlink_bytes_per_round": int(c * dense),
        "dense_uplink_bytes_per_round": int(c * dense),
        "uplink_reduction": float(dense / per_client),
    }


def round_telemetry_bytes(cfg) -> int:
    """On-device bytes one round's telemetry block adds to the scan's
    stacked ys — the §11 memory model, as code.

    The block is *summaries, not vectors*: counts (kept/tagged and, for
    DiverseFL, C1/C2 pass counts — int32) plus mean/max norm scalars
    (f32), all reduced from the per-client logs inside the scan.  So the
    per-round cost is O(#fields)·4 bytes — **independent of N** — and a
    whole R-round run's drained block is ``R · round_telemetry_bytes``
    riding the one host sync.  Mirrors the key logic of
    ``fl/telemetry.make_round_telemetry_fn`` field for field (the unit
    test pins the two against each other)."""
    fields = 0
    entry = None
    try:
        from .server import get_aggregator
        entry = get_aggregator(cfg.aggregator)
    except ValueError:
        pass
    # "mask" is logged by every masked rule (diversefl/oracle) -> kept +
    # tagged; the DiverseFL criterion adds c1/c2 pass counts and the
    # z_sq/g_sq norm mean/max pairs
    if cfg.aggregator in ("oracle",) or (entry is not None
                                         and entry.needs_guides):
        fields += 2                           # kept, tagged (int32)
    if entry is not None and entry.needs_guides:
        fields += 2                           # c1_pass, c2_pass (int32)
        fields += 4                           # upd/guide norm mean+max (f32)
    # streaming fold's non-finite guard (active on the raw-f32 stream —
    # lossy codecs skip it) logs a per-client bit the block popcounts
    from .compression import get_codec
    from .streaming import get_streaming
    if (getattr(cfg, "streaming", False)
            and get_streaming(cfg.aggregator) is not None
            and get_codec(getattr(cfg, "compression", "f32")).lossless):
        fields += 1                           # nonfinite (int32)
    # async rounds: cohort size + the three staleness decision counts
    if getattr(cfg, "async_rounds", False):
        fields += 4                           # cohort, stale_* (int32)
    return fields * 4


# ----------------------------------------------------------------------
# The round engine's eval tail
# ----------------------------------------------------------------------

def make_eval_fn(model, fed, cfg):
    """Build ``eval_fn(params, logs) -> {metric: device array}`` — the
    one eval definition every execution mode shares.

    ``RoundEngine.eval_metrics`` jits it for one eval point; the
    one-dispatch path traces the *same function* into the scan tail of
    ``RoundEngine.run_training``, which is why the two agree bitwise
    (integer-count metrics are association-free).  The metric
    set is static per config: main-task + backdoor accuracy appear under
    a backdoor attack, detection TPR/FPR and the C1·C2 criterion logs
    whenever the aggregator emits a keep-mask, and a model with a
    dropless expert share adds its routing counters from the same eval
    forward (``ZooModel.apply_with_routing``).
    """
    acfg = cfg.attack
    bd = fed.backdoor_eval(acfg) if acfg.kind == "backdoor" else None
    main_mask = None if bd is None else ~bd.src

    share = getattr(model, "expert_share", None)
    routing = model.apply_with_routing if share and share["dropless"] \
        else None

    @jax.named_scope("eval")
    def eval_fn(params, logs):
        if routing is None:
            m = {"acc": accuracy(model, params, fed.test_x, fed.test_y)}
        else:
            # one forward gives the accuracy and the routing counters
            lg, counters = routing(params, fed.test_x)
            m = {"acc": _ratio(jnp.sum(jnp.argmax(lg, -1) == fed.test_y),
                               jnp.asarray(fed.test_y.shape[0]), 0.0),
                 **counters}
        if bd is not None:
            m["main_acc"] = masked_accuracy(model, params, fed.test_x,
                                            fed.test_y, main_mask)
            m["backdoor_acc"] = backdoor_accuracy_on(model, params, bd)
        if "mask" in logs:
            m["mask_tpr"], m["mask_fpr"] = mask_rates(logs["mask"],
                                                      logs["byz"],
                                                      logs.get("cand"))
        if "c1c2" in logs:
            m["c1c2"] = logs["c1c2"]
        return m

    return eval_fn
