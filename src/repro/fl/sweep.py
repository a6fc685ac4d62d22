"""Batched experiment sweeps — vmap whole federated runs (DESIGN.md §8).

The paper's results are grids: every table sweeps attack kind x fault
count x aggregator x seed.  After the one-dispatch engine (§7) each
cell still paid its own trace/compile and ran strictly sequentially —
a 60-cell grid cost 60 compiles and 60 dispatches of a program that
individually underfills the device.  This module batches them:

  * **SweepSpec** — a grid of per-cell values over a base ``FLConfig``:
    seeds, Byzantine counts (or explicit masks), attack configs (whose
    sigma/scale magnitudes batch), learning-rate schedules,
    participation levels.  ``cells()`` is the cartesian product, seeds
    innermost, so same-structure cells sit adjacent.
  * **Structural groups** — cells are partitioned by
    :func:`structural_key`: everything that shapes the *trace*
    (aggregator, attack kind and its class targets, participation — it
    sets the selection shape — rounds/eval cadence, chunking, DiverseFL
    thresholds, ...) splits groups; everything that is *data* (seed,
    attack sigma/scale, the Byzantine mask — and therefore ``f`` for
    every rule that does not consume it as a static shape) batches.
    One group == one compiled program.
  * **The batched axis** — each group runs as a single
    ``RoundEngine.run_training_sweep``: the §7 one-dispatch training
    program ``jax.vmap``-ed over a stacked scenario axis (per-cell init
    params, RNG chains, lr vectors and :func:`~repro.fl.engine.
    make_scenario` operands), one compile and one final ``host_sync``
    per group, with the scenario axis placed over an active mesh's data
    axes (``sharding.sweep_put``) so cells run in parallel across
    devices.

**Bitwise contract.**  vmap is a program transform, not a numeric one:
every per-cell slice of the batched program performs the same
elementwise ops, last-axis reductions and canonical client-order folds
(core/diversefl.masked_sum_fold) the solo program performs, so each
cell's metric history and final params are *bitwise equal* to running
that cell alone through ``run_federated_training`` (tests/test_sweep.py
pins this across attacks x aggregators x seeds, partial participation
included).  The price is memory, not bits: a group's working set is
~group_size x the per-run working set, traded against ``client_chunk``
(DESIGN.md §8 records the model).
"""
from __future__ import annotations

import dataclasses
import numbers
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.attacks import AttackConfig
from . import simulator as _sim
from . import telemetry
from .engine import RoundEngine, make_scenario, trace_counter
from .faults import FaultConfig
from .simulator import FLConfig, _lr_vector, _record_eval

# Rules that consume the Byzantine budget ``f`` as a *static shape*
# (sorted-column trims, neighbour counts) — for them ``f`` is structure
# and splits groups.  Every other rule sees Byzantine identity only as
# the scenario mask, so ``f`` is data and batches.
F_STATIC_RULES = ("trimmed_mean", "krum", "bulyan")


@dataclasses.dataclass(frozen=True, eq=False)
class SweepCell:
    """One grid point: a full config plus its non-config operands."""
    cfg: FLConfig
    lr_schedule: Optional[Callable] = None    # None -> the sweep default
    byz_mask: Optional[jnp.ndarray] = None    # None -> derive from cfg.f


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A grid of federated runs over a base config.

    Each axis is optional; ``None`` keeps the base value.  ``fs``
    entries may be ints (Byzantine counts — the mask derives via the
    deterministic ``make_byzantine_mask``, exactly what
    ``Federation.create`` would build) or explicit (N,) masks (count
    and identities both per-cell).  ``attacks`` entries are whole
    ``AttackConfig``s: kinds/class targets are structural, sigma/scale
    magnitudes batch.  ``pods`` entries are two-tier fold pod counts
    (``FLConfig.pods``): a **structural** axis — different pod counts
    are different fold associations, hence different traces, so each
    value lands in its own structural group and is never batched with
    another (``structural_key`` erases only data fields, pinned by
    tests/test_sweep.py).  ``compressions`` entries are codec names
    (``FLConfig.compression``) — likewise **structural**: codecs change
    the wire pytree, the fold's decode graph and (lossy) the carry
    itself, so two codecs never share a compiled program; the axis
    exists so one spec can sweep f32 vs bf16 vs int8 side by side (the
    accuracy-vs-bytes trade the compression PR gates on).  ``faults``
    entries are whole ``fl.faults.FaultConfig``s and ``stalenesses``
    staleness-buffer sizes (``FLConfig.staleness_buffer``) — both
    **structural by default** (``structural_key`` erases only data
    fields, so a fault kind or buffer size lands in its own compiled
    group): the robustness-vs-staleness grids the async PR gates on run
    as one dispatch per (fault, buffer) point (DESIGN.md §13).  The
    product order is the declaration order below with ``seeds``
    innermost, so cells of one structural group are adjacent and
    ``cells()[i]`` maps 1:1 to the result list of
    ``run_federated_sweep``."""
    base: FLConfig
    seeds: Sequence[int] = (0,)
    aggregators: Optional[Sequence[str]] = None
    attacks: Optional[Sequence[AttackConfig]] = None
    fs: Optional[Sequence] = None             # ints or explicit (N,) masks
    participations: Optional[Sequence[float]] = None
    pods: Optional[Sequence[Optional[int]]] = None   # two-tier pod counts
    compressions: Optional[Sequence[str]] = None     # codec names (structural)
    faults: Optional[Sequence[FaultConfig]] = None   # fault models (structural)
    stalenesses: Optional[Sequence[int]] = None      # buffer sizes (structural)
    lr_schedules: Optional[Sequence[Callable]] = None

    def cells(self) -> list:
        # every axis: None keeps the base value; an explicitly empty
        # sequence yields zero cells (no silent base fallback — a
        # programmatically filtered-to-empty axis must not resurrect
        # the base value)
        def axis(values, default):
            return values if values is not None else (default,)

        out = []
        for agg in axis(self.aggregators, self.base.aggregator):
            for atk in axis(self.attacks, self.base.attack):
                for f in axis(self.fs, self.base.f):
                    for part in axis(self.participations,
                                     self.base.participation):
                        for pod in axis(self.pods, self.base.pods):
                            for comp in axis(self.compressions,
                                             self.base.compression):
                                for flt in axis(self.faults,
                                                self.base.fault):
                                    for stal in axis(
                                            self.stalenesses,
                                            self.base.staleness_buffer):
                                        for sched in axis(
                                                self.lr_schedules, None):
                                            for seed in self.seeds:
                                                out.append(self._cell(
                                                    agg, atk, f, part, pod,
                                                    comp, flt, stal, sched,
                                                    seed))
        return out

    def _cell(self, agg, atk, f, part, pod, comp, flt, stal, sched, seed):
        mask = None
        if isinstance(f, numbers.Integral):
            fi = int(f)                        # plain/numpy int
        else:
            mask = jnp.asarray(f, bool)
            if mask.shape != (self.base.n_clients,):
                raise ValueError(
                    f"explicit Byzantine mask must be "
                    f"({self.base.n_clients},), got {mask.shape}")
            fi = int(mask.sum())
        cfg = dataclasses.replace(
            self.base, aggregator=agg, attack=atk, f=fi,
            participation=part, pods=pod, compression=comp,
            fault=flt, staleness_buffer=stal, seed=seed)
        return SweepCell(cfg, sched, mask)


def structural_key(cfg: FLConfig):
    """The trace identity of a config: two cells share a compiled
    program iff their keys are equal.

    Implemented by *erasing the batchable fields* — seed, the attack
    magnitudes, and ``f`` for every rule outside ``F_STATIC_RULES`` —
    and comparing the rest of the (frozen, hashable) config wholesale,
    so a new FLConfig knob is structural by default: the conservative
    failure mode is an extra group (a redundant compile), never a wrong
    batch."""
    return dataclasses.replace(
        cfg, seed=0,
        f=cfg.f if cfg.aggregator in F_STATIC_RULES else 0,
        attack=dataclasses.replace(cfg.attack, sigma=0.0, scale=0.0))


def group_cells(cells: Sequence[SweepCell]):
    """Partition cells into structural groups, preserving cell order:
    ``{structural_key: [(cell_index, cell), ...]}``."""
    groups = {}
    for i, cell in enumerate(cells):
        groups.setdefault(structural_key(cell.cfg), []).append((i, cell))
    return groups


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def execute_sweep(model, fed, spec: SweepSpec,
                  lr_schedule: Optional[Callable] = None,
                  log_every: int = 0) -> list:
    """Run every cell of ``spec``, one batched program per structural
    group; returns per-cell histories in ``spec.cells()`` order.

    The implementation behind ``fl.simulator.run_federated_sweep`` (the
    public entry — see its docstring for the contract)."""
    cells = spec.cells()
    if not cells:
        return []
    for cell in cells:
        if cell.cfg.n_clients != fed.data.n_clients:
            raise ValueError(
                f"sweep cell has n_clients={cell.cfg.n_clients} but the "
                f"federation holds {fed.data.n_clients} clients")
        if cell.cfg.rounds < 1:
            raise ValueError("sweep cells need rounds >= 1")
        if cell.lr_schedule is None and lr_schedule is None:
            raise ValueError(
                "no learning-rate schedule: pass lr_schedule= or give "
                "the spec an lr_schedules axis")

    results = [None] * len(cells)
    for gi, members in enumerate(group_cells(cells).values()):
        rep = members[0][1].cfg                # structural representative
        with telemetry.span("sweep_group", group=gi, cells=len(members),
                            aggregator=rep.aggregator,
                            attack=rep.attack.kind, rounds=rep.rounds,
                            pods=rep.pods, codec=rep.compression,
                            streaming=bool(rep.streaming)):
            with telemetry.span("compile+dispatch"), \
                    trace_counter() as compiles:
                engine = RoundEngine(model, fed, rep)
                R = rep.rounds
                params0 = _stack(
                    [model.init(jax.random.PRNGKey(c.cfg.seed + 1))
                     for _, c in members])
                keys = jnp.stack([jax.random.PRNGKey(c.cfg.seed)
                                  for _, c in members])
                lrs = jnp.stack([_lr_vector(c.lr_schedule or lr_schedule, R)
                                 for _, c in members])
                scen = _stack([make_scenario(c.cfg, byz_mask=c.byz_mask)
                               for _, c in members])
                params, _keys, metrics, eval_rounds = \
                    engine.run_training_sweep(params0, keys, lrs, scen)
            telemetry.event("sweep_group_compiles", group=gi,
                            **compiles.snapshot())
            # THE host sync, one per group — looked up through the module
            # so a counter wrapped around simulator.host_sync
            # (tests/test_dispatch_eval.py style) sees sweep syncs too
            host = _sim.host_sync(metrics)
            tel_host = host.pop("_tel", None)     # (G, R, ...) leaves
            for g, (idx, _cell) in enumerate(members):
                hist = {"round": [], "acc": [], "mask_tpr": [], "mask_fpr": [],
                        "c1c2": []}
                for s, r in enumerate(eval_rounds):
                    _record_eval(hist, r,
                                 {k: v[g][s] for k, v in host.items()},
                                 log_every)
                hist["final_acc"] = hist["acc"][-1] if hist["acc"] \
                    else float("nan")
                hist["params"] = jax.tree.map(lambda x, g=g: x[g], params)
                # same flat comm keys as run_federated_training — cell
                # histories stay key- and value-identical to their solo twin
                d_model = sum(l.size // l.shape[0]
                              for l in jax.tree.leaves(params))
                cstats = _sim.comm_stats(_cell.cfg, d_model)
                hist.update(cstats)
                # the solo path records the fallback reason on the
                # history; cells must not lose it (ISSUE 8 satellite)
                hist["streaming_fallback"] = engine.streaming_fallback
                if tel_host is not None:
                    _sim.drain_round_telemetry(
                        fed.server,
                        {k: v[g] for k, v in tel_host.items()},
                        uplink_bytes=cstats["uplink_bytes_per_round"],
                        cell=idx)
                results[idx] = hist
    return results
