"""Federated-learning simulator — Algorithm 1 plus every baseline server.

The round math (paper Steps 2-5) is defined once in
fl/engine.make_round_body: clients run E local-SGD iterations on fresh
minibatches, Byzantine clients corrupt data (label flip / backdoor) or
updates (gaussian / sign flip / same value / x5 scaling), then the round
is handed to the SecureServer (fl/server.py) and the aggregator
registry.

Training runs through the :class:`~repro.fl.engine.RoundEngine`: the
whole run compiles into one donated outer ``jax.lax.scan`` over
``eval_every`` segments of rounds with the eval inside (one dispatch,
one host sync), client local training and guiding updates are bounded
to ``client_chunk``-sized blocks, and the client axis is sharded over
the mesh's data axes when one is active.  ``FLConfig(streaming=True)``
additionally folds the aggregation into the chunked sweep
(fl/streaming.py): associative rules never materialize the (N, D)
update/guide matrices, bit-identically to the dense path (DESIGN.md
§6).  The per-round reference loop the engine is tested against lives
in the tests (``tests/conftest.py``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..core import DiverseFLConfig
from ..core.attacks import AttackConfig, make_byzantine_mask
from ..data.pipeline import FederatedData
from . import telemetry
from .compression import available_codecs, get_codec
from .engine import RoundEngine, make_scenario
from .faults import FaultConfig
from .metrics import BackdoorEval, comm_stats, make_backdoor_eval
from .server import KERNEL_AGG_RULES, SecureServer, available_aggregators
from .small_models import SmallModel
from .streaming import fallback_reason, get_streaming


# names come from the registry now; the tuple stays for back-compat
AGGREGATORS = available_aggregators()


@dataclasses.dataclass(frozen=True)
class FLConfig:
    n_clients: int = 23
    f: int = 5
    rounds: int = 100
    local_steps: int = 1                 # E
    batch_size: int = 30                 # m
    l2: float = 0.0067
    aggregator: str = "diversefl"
    attack: AttackConfig = AttackConfig()
    dfl: DiverseFLConfig = DiverseFLConfig()
    sample_frac: float = 0.01            # enclave sample s / n_j
    root_frac: float = 0.01              # FLTrust root dataset fraction
    resample_s: int = 2                  # Resampling s_R
    participation: float = 1.0           # C = ceil(participation * N) <= N
    use_kernel_stats: bool = False       # Pallas fused similarity kernel
    use_kernel_agg: bool = False         # Pallas fused Step 4+5 (masked mean)
    client_chunk: Optional[int] = None   # engine: clients in flight at once
    streaming: bool = False              # fold aggregation into the chunked
    #                                      sweep (O(chunk·D) memory); non-
    #                                      associative rules fall back dense
    stream_shards: Optional[int] = None  # streaming fold groups: None = auto
    #                                      from the mesh's data axes (1 off-
    #                                      mesh), int forces an S-way fold +
    #                                      canonical tree-merge (DESIGN.md §7);
    #                                      per-pod groups when pods > 1
    pods: Optional[int] = None           # two-tier streaming fold: None =
    #                                      auto from the mesh's pod axis (1
    #                                      off-mesh), int forces P pod-local
    #                                      folds tree-merged across pods —
    #                                      pods=1 IS the single-tier fold,
    #                                      bitwise (DESIGN.md §9)
    compression: str = "f32"             # client→server update codec
    #                                      (fl/compression.py): "f32" is the
    #                                      lossless wire format (bitwise the
    #                                      pre-compression paths), "bf16"/
    #                                      "int8" quantize at the client
    #                                      boundary with error feedback
    telemetry: bool = False              # per-round on-device telemetry
    #                                      block (fl/telemetry.py): C1/C2
    #                                      pass counts, tag popcounts, norm
    #                                      summaries accumulated in the scan
    #                                      and drained at the one host sync;
    #                                      histories stay bitwise-identical
    #                                      to telemetry=False (DESIGN.md §11)
    fault: FaultConfig = FaultConfig()   # device-malfunction model
    #                                      (fl/faults.py): straggler delay,
    #                                      dropout, intermittent corruption —
    #                                      drawn per round from the RNG
    #                                      chain, composing with the attack
    #                                      axis (DESIGN.md §13)
    cohort_participation: Optional[float] = None
    #                                      per-round cohort RESAMPLING: a
    #                                      fresh ceil(p*N)-client cohort per
    #                                      scanned round via the (R, N)
    #                                      cohort-chain scenario operand.
    #                                      None = off (the static
    #                                      `participation` selection — the
    #                                      PR-9 path, jaxpr-identical)
    staleness_buffer: int = 0            # bounded-staleness slots in the
    #                                      scan carry (O(buffer·D) pending
    #                                      slab); 0 = stragglers' updates
    #                                      expire instead of landing
    staleness_cap: int = 0               # hard staleness cap in rounds:
    #                                      updates older than the cap expire
    #                                      instead of buffering (0 = no cap)
    staleness_discount: float = 1.0      # landing weight multiplier per
    #                                      round of staleness (discount**age
    #                                      rides the fold's valid channel)
    eval_every: int = 10
    seed: int = 0

    def __post_init__(self):
        # shape knobs fail here, with names, instead of deep inside the
        # chunked fold as an inscrutable reshape/shape error
        if self.client_chunk is not None and (
                not isinstance(self.client_chunk, int)
                or isinstance(self.client_chunk, bool)
                or self.client_chunk < 1):
            raise ValueError(
                f"client_chunk must be None or a positive int (clients in "
                f"flight at once), got {self.client_chunk!r}")
        if self.stream_shards is not None and (
                not isinstance(self.stream_shards, int)
                or isinstance(self.stream_shards, bool)
                or self.stream_shards < 1):
            raise ValueError(
                f"stream_shards must be None (auto from the mesh) or a "
                f"positive int (forced fold groups), got "
                f"{self.stream_shards!r}")
        if self.pods is not None and (
                not isinstance(self.pods, int)
                or isinstance(self.pods, bool)
                or self.pods < 1):
            raise ValueError(
                f"pods must be None (auto from the mesh's pod axis) or a "
                f"positive int (forced two-tier pod count), got "
                f"{self.pods!r}")
        if self.pods is not None and self.pods > 1 and not self.streaming:
            raise ValueError(
                f"pods={self.pods} requires streaming=True: the two-tier "
                f"aggregation is an association of the streaming AggState "
                f"fold (DESIGN.md §9) — the dense (N, D) path has no pod "
                f"tiers and would silently ignore the knob")
        if self.pods is not None and self.pods > 1:
            if self.client_chunk is None:
                raise ValueError(
                    f"pods={self.pods} requires client_chunk: without "
                    f"chunking the round is a single block and there is "
                    f"nothing to partition across pods")
            k = -(-self.n_selected // min(self.client_chunk,
                                          self.n_selected))
            if self.pods > k or k % self.pods:
                raise ValueError(
                    f"pods={self.pods} cannot tile the padded block count "
                    f"{k} (= ceil(n_selected {self.n_selected} / "
                    f"client_chunk {self.client_chunk})); pick a "
                    f"client_chunk so the blocks divide evenly across pods")
        if self.use_kernel_agg and self.aggregator not in KERNEL_AGG_RULES:
            raise ValueError(
                f"use_kernel_agg=True requires a masked/weighted-mean "
                f"family aggregator {KERNEL_AGG_RULES}; {self.aggregator!r} "
                f"never routes through the fused masked-agg kernel, so the "
                f"flag would be silently ignored")
        if (self.streaming and self.use_kernel_stats
                and not self.use_kernel_agg
                and self.aggregator == "diversefl"):
            raise ValueError(
                "use_kernel_stats=True is unreachable on the streaming "
                "row-fold path (per-client statistics are computed inline "
                "during the fold); combine it with use_kernel_agg=True for "
                "the fused per-block kernel path, or drop the flag")
        if self.compression not in available_codecs():
            raise ValueError(
                f"compression={self.compression!r} is not a registered "
                f"codec; available: {available_codecs()} "
                f"(fl/compression.py)")
        if (not get_codec(self.compression).lossless
                and self.use_kernel_agg and not self.streaming):
            raise ValueError(
                f"compression={self.compression!r} with use_kernel_agg=True "
                f"requires streaming=True: the fused dequantize-and-fold "
                f"kernel IS the streaming block fold — the dense path "
                f"decodes updates before aggregation, so the kernel flag "
                f"would silently buy no fusion (DESIGN.md §10)")
        # --- async knobs (DESIGN.md §13) -------------------------------
        if not isinstance(self.fault, FaultConfig):
            raise ValueError(
                f"fault must be a fl.faults.FaultConfig, got "
                f"{type(self.fault).__name__}")
        if self.cohort_participation is not None:
            p = self.cohort_participation
            if isinstance(p, bool) or not isinstance(p, (int, float)) \
                    or not (0.0 < float(p) <= 1.0):
                raise ValueError(
                    f"cohort_participation must be None (static cohort) or "
                    f"a fraction in (0, 1] — a cohort that selects zero "
                    f"clients every round is degenerate (0/0 weighted "
                    f"mean); got {p!r}")
        for name in ("staleness_buffer", "staleness_cap"):
            v = getattr(self, name)
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                raise ValueError(
                    f"{name} must be a non-negative int (rounds/slots), "
                    f"got {v!r}")
        if not (0.0 < float(self.staleness_discount) <= 1.0):
            raise ValueError(
                f"staleness_discount must be in (0, 1] (landing weight "
                f"multiplier per round of staleness), got "
                f"{self.staleness_discount!r}")
        if self.async_rounds:
            if self.participation != 1.0:
                raise ValueError(
                    f"async rounds (fault/cohort/staleness knobs) replace "
                    f"the static participation selection with the per-round "
                    f"cohort chain — set participation=1.0 and use "
                    f"cohort_participation={self.participation} for "
                    f"resampled partial participation (DESIGN.md §13)")
            if not self.streaming or get_streaming(self.aggregator) is None:
                why = ("streaming=False" if not self.streaming else
                       f"aggregator {self.aggregator!r} has no streaming "
                       f"rule ({fallback_reason(self.aggregator)})")
                raise ValueError(
                    f"async rounds fold per-round cohorts, faulty clients "
                    f"and landed stale updates through the streaming "
                    f"AggState monoid's weight channel, but {why}: the "
                    f"dense path has no per-client weight channel to carry "
                    f"the cohort/staleness masks (DESIGN.md §13)")
            if not get_codec(self.compression).lossless:
                raise ValueError(
                    f"async rounds cannot compose with the lossy "
                    f"compression={self.compression!r}: error-feedback "
                    f"residuals assume every client transmits every round, "
                    f"but cohort resampling/dropout makes transmission "
                    f"intermittent — the residual would silently go stale "
                    f"(DESIGN.md §13).  Use compression='f32'")

    @property
    def async_rounds(self) -> bool:
        """True when any async knob engages the per-round cohort / fault
        / staleness machinery.  False means the round body traces the
        exact PR-9 jaxpr — the structural half of the §13 bitwise
        contract."""
        return (self.fault.kind != "none"
                or self.cohort_participation is not None
                or self.staleness_buffer > 0)

    @property
    def n_selected(self) -> int:
        return max(1, min(self.n_clients,
                          math.ceil(self.participation * self.n_clients)))

    def validate_model_sharding(self, d: int, model_shards: int,
                                streaming_fallback: Optional[str] = None,
                                leaf_sizes: Optional[tuple] = None):
        """Named errors for knobs that cannot compose with a tensor-
        (model-axis-)sharded run — checked by the engine once the flat
        model dim ``d`` is known (it needs the params, so it cannot live
        in ``__post_init__``).  ``model_shards`` is the mesh's model-axis
        size (sharding.model_shard_count); ``streaming_fallback`` the
        engine's resolved fallback reason, so a streaming=True config
        whose rule silently fell back dense still fails loudly here.
        No-op when ``model_shards <= 1`` — every existing config is
        untouched (DESIGN.md §12)."""
        if model_shards <= 1:
            return
        if not self.streaming or streaming_fallback is not None:
            why = (f"aggregator {self.aggregator!r} cannot stream "
                   f"({streaming_fallback})" if streaming_fallback
                   else "streaming=False")
            raise ValueError(
                f"model-sharded run (model_shards={model_shards}) requires "
                f"the streaming fold, but {why}: the dense fallback "
                f"materializes the full (n_selected={self.n_selected}, "
                f"D={d}) update matrix — at tensor-parallel model sizes "
                f"that is exactly the O(N·D) term the streaming AggState "
                f"exists to remove (DESIGN.md §6, §12).  Use streaming=True "
                f"with a streaming-capable aggregator "
                f"(fl/streaming.streaming_rules())")
        if self.use_kernel_agg or self.use_kernel_stats:
            flag = "use_kernel_agg" if self.use_kernel_agg \
                else "use_kernel_stats"
            raise ValueError(
                f"{flag}=True cannot compose with a model-sharded run "
                f"(model_shards={model_shards}): the Pallas fold/stats "
                f"kernels are single-device programs over an unsharded "
                f"(chunk, D) block — under GSPMD they would force a "
                f"cross-model-axis gather of the very matrix the sharding "
                f"splits.  Drop the kernel flags (the in-fold axis=-1 "
                f"reductions shard for free)")
        codec = get_codec(self.compression)
        if not codec.lossless and leaf_sizes is not None:
            bad = [s for s in leaf_sizes if s % model_shards]
            if bad:
                raise ValueError(
                    f"compression={self.compression!r} (lossy) on a "
                    f"model-sharded run needs every parameter tensor to "
                    f"tile the model axis — the blocked (ms, L) layout "
                    f"must be pad-free so the (N, D) error-feedback "
                    f"residual plane reshapes losslessly onto the update "
                    f"blocks — but {len(bad)} leaf(s) (e.g. size "
                    f"{bad[0]}) are not multiples of model_shards="
                    f"{model_shards} (DESIGN.md §12)")
        if codec.qblock is not None:
            if d % model_shards:
                raise ValueError(
                    f"compression={self.compression!r} on a model-sharded "
                    f"run needs the flat dim to tile the model axis: "
                    f"D={d} % model_shards={model_shards} != 0, so the "
                    f"per-block scale groups would straddle shard "
                    f"boundaries")
            local = d // model_shards
            if local % codec.qblock:
                raise ValueError(
                    f"compression={self.compression!r} quantizes in "
                    f"QBLOCK={codec.qblock} groups along the flat dim, but "
                    f"the local model shard D/model_shards = {d}/"
                    f"{model_shards} = {local} is not a multiple of "
                    f"{codec.qblock}: wire blocks would straddle shard "
                    f"boundaries and every encode/decode would pay a "
                    f"cross-model-axis reshard.  Pick a model_shards (or "
                    f"model size) with QBLOCK | D/model_shards")


@dataclasses.dataclass
class Federation:
    model: SmallModel
    data: FederatedData
    test_x: jnp.ndarray
    test_y: jnp.ndarray
    byz_mask: jnp.ndarray                   # (N,) bool — ground truth
    server: SecureServer                    # owns the enclave + registry
    root_x: Optional[jnp.ndarray] = None    # FLTrust root dataset
    root_y: Optional[jnp.ndarray] = None
    _bd_eval: Optional[BackdoorEval] = dataclasses.field(
        default=None, repr=False)           # cached trigger-stamped test set

    @property
    def enclave(self):
        return self.server.enclave

    def backdoor_eval(self, acfg: AttackConfig) -> BackdoorEval:
        """The trigger-stamped backdoor test set, built once per
        federation (per source/target pair) — every eval after the first
        is a masked reduction over the cached stamp, not a re-stamp."""
        bd = self._bd_eval
        if bd is None or (bd.source_class, bd.target_class) != \
                (acfg.source_class, acfg.target_class):
            bd = make_backdoor_eval(self.test_x, self.test_y, acfg)
            self._bd_eval = bd
        return bd

    @classmethod
    @telemetry.span("fl.federation")
    def create(cls, model: SmallModel, data: FederatedData, test_x, test_y,
               cfg: FLConfig, key):
        k1, k2 = jax.random.split(key)
        byz = make_byzantine_mask(data.n_clients, cfg.f)
        # Steps 0-1: attested server, clients seal their shared samples.
        # No plaintext copy is kept — guide batches are only reachable by
        # unsealing through the SecureServer.
        server = SecureServer()
        gx, gy = data.enclave_samples(k1, cfg.sample_frac)
        for j in range(data.n_clients):
            server.ingest_samples(j, gx[j], gy[j])
        del gx, gy
        # FLTrust root dataset: random subset of the union of client data
        flat_x = data.x.reshape((-1,) + data.x.shape[2:])
        flat_y = data.y.reshape(-1)
        n_root = max(1, int(cfg.root_frac * flat_y.shape[0]))
        idx = jax.random.choice(k2, flat_y.shape[0], (n_root,), replace=False)
        return cls(model=model, data=data, test_x=test_x, test_y=test_y,
                   byz_mask=byz, server=server,
                   root_x=flat_x[idx], root_y=flat_y[idx])


# ----------------------------------------------------------------------

def host_sync(tree):
    """The simulator's single device→host materialization point.

    Every value ``run_federated_training`` moves off the device flows
    through here, exactly once per training run.  Keeping one choke
    point makes the sync count *measurable*: tests/test_dispatch_eval.py
    wraps this function with a counter and runs training under
    ``jax.transfer_guard_device_to_host("disallow_explicit")``, so on
    accelerator backends a host read that bypasses it raises instead of
    hiding (on CPU, where arrays are host-resident, the guard is inert
    and the counter is the whole measurement).

    When the flight recorder is on, each sync emits a ``sync`` event
    carrying the bytes moved (sum of leaf ``nbytes``) and the fetch wall
    time — the one-sync contract becomes *visible* in a recorded run,
    not just counted in a test."""
    rec = telemetry.get_recorder()
    if not rec.enabled:
        with jax.transfer_guard_device_to_host("allow"):
            return jax.device_get(tree)
    leaves = jax.tree.leaves(tree)
    nbytes = int(sum(getattr(x, "nbytes", 0) for x in leaves))
    t0 = rec.now()
    with jax.transfer_guard_device_to_host("allow"):
        out = jax.device_get(tree)
    rec.event("sync", bytes=nbytes, leaves=len(leaves),
              dur=round(rec.now() - t0, 6))
    return out


def drain_round_telemetry(server, tel_host, *, uplink_bytes=None, cell=None):
    """Host-side drain of the engine's per-round telemetry block.

    ``tel_host`` is the already-synced ``"_tel"`` dict (leaves shaped
    (R,)) popped off the metric buffer *after* the run's one host sync —
    this function only reformats host data, it never touches the device.
    Each round becomes (a) a ``round`` event on the flight recorder
    (C1/C2 pass counts, tag popcounts, norm summaries, uplink bytes) and
    (b) a ``round_tags`` entry in the SecureServer's hash-chained audit
    log — the enclave's committed record of *which counts it tagged*,
    the thing SecFL-style deployments must be able to prove they did not
    rewrite."""
    if not tel_host:
        return
    n = len(next(iter(tel_host.values())))
    rec = telemetry.get_recorder()
    for r in range(n):
        row = {}
        for k, v in tel_host.items():
            x = v[r]
            row[k] = x.item() if hasattr(x, "item") else x
        if uplink_bytes is not None:
            row["uplink_bytes"] = uplink_bytes
        if cell is not None:
            row["cell"] = cell
        if rec.enabled:
            rec.event("round", index=r + 1, **row)
        tags = {k: row[k] for k in ("kept", "tagged", "c1_pass", "c2_pass")
                if k in row}
        if tags:
            if cell is not None:
                tags["cell"] = cell
            server.record_round_tags(r + 1, **tags)
        # async control path: the hash chain commits the per-round cohort
        # size and every staleness decision (ISSUE 10 satellite)
        extra = {} if cell is None else {"cell": cell}
        if "cohort" in row:
            server.record_cohort_resample(r + 1, int(row["cohort"]), **extra)
        for decision in ("buffered", "folded", "expired"):
            k = f"stale_{decision}"
            if k in row and int(row[k]) > 0:
                server.record_stale(r + 1, decision, int(row[k]), **extra)


def _record_eval(history, i, metrics, log_every):
    """Append one eval point's host-side metric dict to the history.

    The dict is make_eval_fn's output verbatim — every key it computes
    is recorded, so adding a metric there needs no change here."""
    history["round"].append(i)
    for k, v in metrics.items():
        history.setdefault(k, []).append(v)
    if log_every and i % log_every == 0:
        print(f"  round {i:5d} acc={metrics['acc']:.4f}")


def _lr_vector(lr_schedule: Callable, rounds: int) -> jnp.ndarray:
    """Evaluate the schedule for rounds 1..R as one device (R,) vector.

    The seed loop called ``float(lr_schedule(i))`` per round — R tiny
    device→host transfers before training even dispatched (and a
    transfer-guard violation on accelerator backends).  One vmap keeps
    the values on device, bit-identical per element for the repo's
    elementwise-jnp schedules (repro/optim/schedules.py).  A schedule
    with host control flow (``0.1 if i < 100 else 0.01``) cannot trace;
    it keeps working through the legacy eager per-round evaluation —
    slower, but the pre-existing public contract."""
    ix = jnp.arange(1, rounds + 1)
    try:
        return jax.vmap(lr_schedule)(ix).astype(jnp.float32)
    except (jax.errors.JAXTypeError, TypeError):
        return jnp.asarray([float(lr_schedule(i))
                            for i in range(1, rounds + 1)], jnp.float32)


def run_federated_training(model: SmallModel, fed: Federation, cfg: FLConfig,
                           lr_schedule: Callable, log_every: int = 0,
                           engine: Optional[RoundEngine] = None) -> Dict:
    """Run ``cfg.rounds`` federated rounds; returns the metric history.

    The run is **one-dispatch**: it compiles into a single outer scan
    over eval segments with the eval metrics accumulated on device
    (`RoundEngine.run_training`), and the host syncs exactly once at the
    end.  ``engine`` reuses a prebuilt (already-compiled)
    ``RoundEngine`` instead of constructing one per call.

    ``log_every`` prints eval lines as they reach the host: everything
    is on device until the single final sync, so the lines appear
    together at the end.
    """
    key = jax.random.PRNGKey(cfg.seed)
    params = model.init(jax.random.PRNGKey(cfg.seed + 1))
    history = {"round": [], "acc": [], "mask_tpr": [], "mask_fpr": [],
               "c1c2": []}
    if engine is None:
        engine = RoundEngine(model, fed, cfg)

    lrs_all = _lr_vector(lr_schedule, cfg.rounds)
    # the run's traced operands (attack magnitudes, Byzantine mask):
    # derived from *this call's* cfg/fed, not the engine's, so reusing a
    # prebuilt engine with a magnitude-only cfg change is a cache hit,
    # never a stale constant (tests/test_sweep.py pins the no-retrace)
    scen = make_scenario(cfg, fed)

    # d from aval metadata (p.size is the GLOBAL size of a sharded
    # array — no device gather, no host sync); the wire stats price the
    # per-shard encoding when the engine runs tensor-sharded
    d_model = sum(p.size for p in jax.tree.leaves(params))
    cstats = comm_stats(cfg, d_model, model_shards=engine.model_shards)
    with telemetry.span(
            "run_training", n_clients=cfg.n_clients, rounds=cfg.rounds,
            aggregator=cfg.aggregator, attack=cfg.attack.kind,
            d=int(d_model), chunk=cfg.client_chunk, pods=cfg.pods,
            codec=cfg.compression, streaming=bool(cfg.streaming)):
        params, key, metrics, eval_rounds = engine.run_training(
            params, key, lrs_all, scen)
        if metrics is not None:                    # rounds >= 1
            host = host_sync(metrics)              # THE host sync
            # the reserved telemetry block rides the same sync and is
            # drained here — it never enters the history
            drain_round_telemetry(
                fed.server, host.pop("_tel", None),
                uplink_bytes=cstats["uplink_bytes_per_round"])
            for s, i in enumerate(eval_rounds):
                _record_eval(history, i,
                             {k: v[s] for k, v in host.items()},
                             log_every)

    history["final_acc"] = history["acc"][-1] if history["acc"] else float("nan")
    history["params"] = params
    # why a run fell off the streaming path (None when it did not) — on
    # the history, not just the engine instance, so sweep cells and saved
    # histories keep the reason (ISSUE 8 satellite)
    history["streaming_fallback"] = engine.streaming_fallback
    history.update(cstats)
    return history


def run_federated_sweep(model: SmallModel, fed: Federation, spec,
                        lr_schedule: Optional[Callable] = None,
                        log_every: int = 0) -> list:
    """Run a whole experiment grid batched: the sweep counterpart of
    :func:`run_federated_training`.

    ``spec`` is a :class:`~repro.fl.sweep.SweepSpec` — a grid of seeds,
    Byzantine counts/masks, attack magnitudes, learning-rate schedules
    and participation levels over a base config.  Cells are partitioned
    into *structural groups* (same trace → same compiled program) and
    each group executes as one ``jax.vmap`` of the one-dispatch training
    program over a stacked scenario axis: one compile and one
    ``host_sync`` per group instead of per cell (fl/sweep.py,
    DESIGN.md §8).  Returns one history dict per cell, in ``spec.cells()``
    order, each bitwise-equal to running that cell solo through
    :func:`run_federated_training` against a federation created with the
    cell's config and the same federation key as ``fed``."""
    from .sweep import execute_sweep    # deferred: sweep imports this module
    return execute_sweep(model, fed, spec, lr_schedule=lr_schedule,
                         log_every=log_every)
