"""Byzantine-robust aggregation baselines the paper compares against
(Sec. IV + Appendix A).  All operate on a stacked update matrix
``U: (N, D)`` (clients × flattened model dim), fp32.

  - oracle_sgd : mean over the (oracle-known) benign set
  - median     : coordinate-wise median [Yin et al., 9]
  - trimmed_mean: coordinate-wise trimmed mean (beta / closest-to-median)
  - krum       : update of the client closest to its N-f-2 neighbours [8]
  - bulyan     : recursive Krum selection + per-dim trimmed mean [12]
  - resampling : s_R-fold resample-and-average then Median [24]
  - fltrust    : root-update projection + ReLU cosine weighting [26]

RSA [23] maintains per-client model copies and is a *training rule*, not
a one-shot aggregator — it lives in fl/rsa.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .diversefl import masked_mean_flat


def flatten_updates(updates):
    """pytree with leading client dim N -> (N, D) fp32 matrix + unravel fn."""
    leaves = jax.tree.leaves(updates)
    n = leaves[0].shape[0]
    with jax.named_scope("flatten"):
        flat = jnp.concatenate(
            [u.reshape(n, -1).astype(jnp.float32) for u in leaves], axis=1)

    treedef = jax.tree.structure(updates)
    shapes = [u.shape[1:] for u in leaves]
    sizes = [int(math.prod(s)) for s in shapes]

    def unravel(vec):
        outs, off = [], 0
        for s, sz in zip(shapes, sizes):
            outs.append(vec[off:off + sz].reshape(s))
            off += sz
        return jax.tree.unflatten(treedef, outs)
    return flat, unravel


# ----------------------------------------------------------------------

def oracle_sgd(U, benign_mask):
    return masked_mean_flat(U, benign_mask)


def median(U):
    return jnp.median(U, axis=0)


def trimmed_mean(U, f: int, mode: str = "beta"):
    """mode='beta': drop largest/smallest f per dim [9].
    mode='near_median': keep N-2f values closest to the median per dim [12]."""
    N = U.shape[0]
    if mode == "beta":
        s = jnp.sort(U, axis=0)
        kept = s[f:N - f] if N - 2 * f > 0 else s
        return kept.mean(0)
    med = jnp.median(U, axis=0)
    d = jnp.abs(U - med[None, :])
    keep_n = max(N - 2 * f, 1)
    idx = jnp.argsort(d, axis=0)[:keep_n]                    # (keep_n, D)
    vals = jnp.take_along_axis(U, idx, axis=0)
    return vals.mean(0)


def _pairwise_sq_dists(U):
    sq = jnp.sum(U * U, axis=1)
    return sq[:, None] + sq[None, :] - 2.0 * (U @ U.T)


def krum_scores(U, f: int, active=None):
    """Sum of distances to the nearest N-f-2 other clients (lower = better).

    ``active``: optional bool mask of clients still in play (Bulyan)."""
    N = U.shape[0]
    d = _pairwise_sq_dists(U)
    big = jnp.float32(1e30)
    d = d + jnp.eye(N, dtype=U.dtype) * big                  # exclude self
    if active is not None:
        inact = ~active
        d = jnp.where(inact[None, :], big, d)
        n_active = active.sum()
    else:
        n_active = N
    k = jnp.clip(n_active - f - 2, 1, N - 1)
    s = jnp.sort(d, axis=1)
    ar = jnp.arange(N - 0)
    # sum of the k smallest distances per row (k is dynamic under masking)
    cums = jnp.cumsum(s, axis=1)
    scores = jnp.take_along_axis(
        cums, jnp.broadcast_to(k - 1, (N, 1)).astype(jnp.int32), axis=1)[:, 0]
    if active is not None:
        scores = jnp.where(active, scores, big)
    return scores


def krum(U, f: int):
    return U[jnp.argmin(krum_scores(U, f))]


def bulyan(U, f: int):
    """Recursive Krum to select N-2f candidates, then the [12] trimmed mean
    (per dim: mean of the N'-2f values closest to the median)."""
    N = U.shape[0]
    n_sel = max(N - 2 * f, 1)

    def pick(carry, _):
        active = carry
        scores = krum_scores(U, f, active)
        j = jnp.argmin(scores)
        return active.at[j].set(False), j

    active0 = jnp.ones((N,), bool)
    _, sel = jax.lax.scan(pick, active0, None, length=n_sel)
    V = U[sel]                                               # (n_sel, D)
    f2 = max(min(f, (n_sel - 1) // 2), 0)
    if n_sel - 2 * f2 <= 0:
        f2 = max((n_sel - 1) // 2, 0)
    return trimmed_mean(V, f2, mode="near_median")


def resampling(U, key, s_r: int = 2, robust=median):
    """[24]: build N averaged groups with each client used <= s_r times."""
    N = U.shape[0]
    # sample without exceeding s_r uses: shuffle s_r copies of client ids
    ids = jnp.tile(jnp.arange(N), s_r)
    ids = jax.random.permutation(key, ids)[: N * s_r].reshape(N, s_r)
    V = U[ids].mean(axis=1)                                  # (N, D)
    return robust(V)


def fltrust(U, root_update):
    """[26]: TS_j = ReLU(cos(root, z_j)); rescale z_j to ‖root‖; weighted avg.

    Written layout-stably, so the same bits come out whether the rule
    runs solo or as one cell of a vmapped sweep (fl/sweep.py's bitwise
    contract): per-client statistics are multiply + last-axis
    reductions (never a matvec, whose contraction order shifts under
    batching), and both client-axis reductions — the weighted sum AND
    the trust-score denominator — go through one canonical left fold in
    client order, exactly the ``(s + u·a_i, n + ts_i)`` association the
    streaming fltrust rule folds (fl/streaming.weighted_mean_rule).
    Unlike ``masked_sum_fold`` this fold runs **unrolled=1**: fltrust's
    weights are real-valued, and an unrolled fold body gives XLA:CPU a
    multiply-add chain it may emit as FMA — solo and vmapped lowerings
    choose differently, so the same fold produces different bits across
    layouts whenever the products ``u·a_i`` round (the 0/1 mask weights
    of the other rules have exact products, which is why their unrolled
    fold is immune).  One iteration per client keeps the body a single
    mul + add that lowers identically everywhere — determinism over
    speed, the same trade ``masked_sum_fold`` documents."""
    r = root_update.astype(jnp.float32)
    rn = jnp.sqrt(jnp.sum(r * r)) + 1e-12
    Uf = U.astype(jnp.float32)
    un = jnp.sqrt(jnp.sum(Uf * Uf, axis=-1)) + 1e-12
    ts = jax.nn.relu(jnp.sum(Uf * r, axis=-1) / (un * rn))
    a = ts * (rn / un)

    def step(carry, xs):
        u, ai, ti = xs
        s, n = carry
        return (s + u * ai, n + ti), None

    init = (jnp.zeros(Uf.shape[1:], jnp.float32), jnp.float32(0.0))
    (s, n), _ = jax.lax.scan(step, init, (Uf, a, ts))
    return s / jnp.maximum(n, 1e-12)
