"""DiverseFL — the paper's contribution (Sec. III).

Per-client Byzantine mitigation: the server (inside the TEE enclave)
computes, for every participating client j, a *guiding update* Δ̃_j by
running the same E local-SGD steps on the small sample M_j^0 the client
shared once before training.  The client's uploaded update z_j is kept
iff both similarity conditions hold:

    C1 = sign(Δ̃_j · z_j)            C1 > ε1            (direction, Eq. 2/4)
    C2 = ‖z_j‖₂ / ‖Δ̃_j‖₂            ε2 < C2 < ε3        (length,   Eq. 3/5)

and the global model is updated with the plain mean of surviving updates
(Eq. 6).  Paper defaults: (ε1, ε2, ε3) = (0, 0.5, 2).

This module is the single source of truth for the criterion: the mask
(`diversefl_mask`), the similarity statistics (pytree / stacked-matrix)
and the masked aggregation (Eq. 6) are defined once here and imported by
every execution layer:
  * fl/server.py — the SecureServer + aggregator registry every
    simulator round routes through (DESIGN.md §3);
  * kernels/similarity.py + kernels/masked_agg.py — fused Pallas
    twins of the same math (one HBM pass each), used on TPU;

At pod scale the same criterion runs inside the sharded FL round step
(launch/train.py): each client's (dot, ‖z‖², ‖Δ̃‖²) is reduced
shard-locally and psum'd over the ``model`` axis, so per-client updates
are never materialized N-fold.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DiverseFLConfig:
    eps1: float = 0.0     # direction threshold: require dot > eps1 (sign test)
    eps2: float = 0.5     # length ratio lower bound
    eps3: float = 2.0     # length ratio upper bound
    local_steps: int = 1  # E
    sample_frac: float = 0.01


# ----------------------------------------------------------------------
# Similarity statistics
# ----------------------------------------------------------------------

@jax.named_scope("step4_filter")
def similarity_stats(z: jnp.ndarray, g: jnp.ndarray):
    """Flat-vector stats: (z·g, ‖z‖², ‖g‖²) in fp32."""
    z = z.astype(jnp.float32)
    g = g.astype(jnp.float32)
    return jnp.vdot(z, g), jnp.vdot(z, z), jnp.vdot(g, g)


def _tree_vdot(a_tree, b_tree):
    """Elementwise-multiply + per-leaf reduce, summed across leaves (fp32).

    Deliberately NOT jnp.vdot: vdot flattens its operands to 1-D, which
    defeats GSPMD sharding propagation when the leaves are sharded over a
    ``model`` axis and forces a full all-gather of every update leaf.
    Per-leaf elementwise products keep the partial sums shard-local, so
    the same function serves the simulator and the pod-scale round step
    (launch/train.py, §Perf A2)."""
    parts = jax.tree.map(
        lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)),
        a_tree, b_tree)
    return jnp.sum(jnp.stack(jax.tree.leaves(parts)))


@jax.named_scope("step4_filter")
def similarity_stats_tree(z_tree, g_tree):
    """Pytree stats: (z·g, ‖z‖², ‖g‖²), exact fp32, shard-local partials."""
    return (_tree_vdot(z_tree, g_tree), _tree_vdot(z_tree, z_tree),
            _tree_vdot(g_tree, g_tree))


@jax.named_scope("step4_filter")
def similarity_stats_matrix(U, G):
    """Stacked-matrix stats: U, G (N, D) -> per-client (dot, ‖z‖², ‖g‖²)."""
    U = U.astype(jnp.float32)
    G = G.astype(jnp.float32)
    return jnp.sum(U * G, axis=1), jnp.sum(U * U, axis=1), jnp.sum(G * G, axis=1)


@jax.named_scope("step4_filter")
def diversefl_mask(dot, z_sq, g_sq, cfg: DiverseFLConfig):
    """Boolean keep-mask from per-client stats (any shape, elementwise).

    Condition 1: C1 = sign(Δ̃·z): kept iff dot > eps1 (eps1=0 reproduces the
    paper's sign test).  Condition 2: eps2 < ‖z‖/‖Δ̃‖ < eps3, evaluated in
    squared form to avoid sqrt of tiny values.
    """
    c1 = dot > cfg.eps1
    ratio_sq = z_sq / jnp.maximum(g_sq, 1e-30)
    c2 = (ratio_sq > cfg.eps2 ** 2) & (ratio_sq < cfg.eps3 ** 2)
    return c1 & c2


def c2_ratio(z_sq, g_sq):
    """C2 = ‖z‖/‖Δ̃‖ from the squared norms (Eq. 3/5)."""
    return jnp.sqrt(z_sq / jnp.maximum(g_sq, 1e-30))


@jax.named_scope("step4_filter")
def criterion_logs(dot, z_sq, g_sq):
    """Per-client criterion diagnostics shared by every round-step layer:
    C1 = sign(Δ̃·z), C2 = ‖z‖/‖Δ̃‖, and their product (Fig. 2's y-axis)."""
    c1 = jnp.sign(dot)
    c2 = c2_ratio(z_sq, g_sq)
    return {"c1": c1, "c2": c2, "c1c2": c1 * c2}


# ----------------------------------------------------------------------
# Guiding update (enclave Step 3)
# ----------------------------------------------------------------------

def guiding_update(params, guide_batch, grad_fn: Callable, lr, E: int = 1):
    """Δ̃ = θ - SGD_E(θ; M^0): E gradient-descent steps on the enclave sample.

    grad_fn(params, batch) -> grad pytree.  Mirrors the client's local
    optimizer exactly (plain SGD, same lr, same E) per Algorithm 1.
    """
    theta = params

    def step(theta, _):
        g = grad_fn(theta, guide_batch)
        # trailing astype: dtype-stable scan carry for bf16 zoo params
        # (f32 lr promotes the product); identity for f32 small models
        theta = jax.tree.map(
            lambda t, gg: (t - lr * gg.astype(t.dtype)).astype(t.dtype),
            theta, g)
        return theta, None

    theta, _ = jax.lax.scan(step, theta, None, length=E)
    return jax.tree.map(lambda a, b: (a - b).astype(jnp.float32), params, theta)


# ----------------------------------------------------------------------
# Aggregation (Eq. 6)
# ----------------------------------------------------------------------

def masked_sum_fold(U, w):
    """Ordered weighted sum over the client axis: a strict left fold
    (client 0 first, one ``s + u_i * w_i`` per client via ``lax.scan``).

    XLA's native axis-0 reduction associates however the backend
    vectorizes, so its bits change with the memory layout; the fold fixes
    one canonical association, making Eq. 6 *bitwise independent of how
    the client axis is executed* — unchunked, chunked, or streamed one
    block at a time (fl/streaming.py folds its AggState in exactly this
    order).  ``unroll`` cuts the while-loop overhead without touching
    the operation order — same adds, same bits, *for the 0/1 mask
    weights this fold is used with*: their products are exact, so the
    FMA an unrolled multiply-add chain may or may not compile to cannot
    change a bit.  Real-valued weights lose that immunity (solo and
    vmapped lowerings pick FMA differently) — rules folding real
    weights must unroll=1 instead (core/aggregators.fltrust,
    DESIGN.md §8).  Cost profile: at model-scale
    D (~34k, fp32) the single streamed pass over U beats the
    ``(U * m[:, None]).sum(0)`` materialize-then-reduce it replaced
    (~14.9 ms vs ~150 ms at N=1024 on this CPU), while at toy dimensions
    the loop trip count adds per-round overhead — determinism across
    execution layouts, not speed, is what this function buys.  Returns
    ``(sum (D,), total weight)`` in fp32.
    """
    U = U.astype(jnp.float32)
    w = w.astype(jnp.float32)

    def step(carry, uw):
        u, wi = uw
        s, n = carry
        return (s + u * wi, n + wi), None

    init = (jnp.zeros(U.shape[1:], jnp.float32), jnp.float32(0.0))
    (s, n), _ = jax.lax.scan(step, init, (U, w), unroll=8)
    return s, n


def masked_mean_flat(U, mask):
    """Stacked-matrix Eq. 6: U (N, D), mask (N,) -> (D,) fp32 masked mean.

    The single source of truth for the masked aggregation the simulator,
    the registry's ``oracle``/``diversefl`` rules and the kernel oracle
    all share; kernels/masked_agg.py is its one-HBM-pass Pallas twin.
    Reduces via ``masked_sum_fold``, so the result matches the streaming
    AggState path bit-for-bit (DESIGN.md §6)."""
    s, n = masked_sum_fold(U, mask)
    return s / jnp.maximum(n, 1.0)


def masked_mean(updates, mask):
    """updates: pytree with leading client dim N; mask: (N,) bool/float."""
    m = mask.astype(jnp.float32)
    denom = jnp.maximum(m.sum(), 1.0)

    def agg(u):
        mm = m.reshape((-1,) + (1,) * (u.ndim - 1))
        return (u.astype(jnp.float32) * mm).sum(0) / denom
    return jax.tree.map(agg, updates)


def diversefl_aggregate(updates, guides, cfg: DiverseFLConfig):
    """Full Step 4+5 at simulator scale.

    updates/guides: pytrees whose leaves have leading client dim N.
    Returns (aggregated update pytree, keep mask (N,), stats dict)."""
    def stats_one(z, g):
        return similarity_stats_tree(z, g)
    n = jax.tree.leaves(updates)[0].shape[0]
    dot, zz, gg = jax.vmap(
        lambda i: stats_one(jax.tree.map(lambda u: u[i], updates),
                            jax.tree.map(lambda u: u[i], guides)))(jnp.arange(n))
    mask = diversefl_mask(dot, zz, gg, cfg)
    agg = masked_mean(updates, mask)
    return agg, mask, {"dot": dot, "z_norm_sq": zz, "g_norm_sq": gg,
                       "c2": c2_ratio(zz, gg)}
