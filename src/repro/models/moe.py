"""Fine-grained Mixture-of-Experts with shared experts (DeepSeek-MoE style).

Two dispatches share the router:

* :func:`apply_moe`, sort-based with a capacity limit (GShard-style
  dropping, MaxText-style implementation): no (tokens × experts ×
  capacity) one-hot tensors are ever materialized, so it scales to
  384-expert / 1T-param configurations.  Expert weights carry an explicit
  leading expert dim that the sharding rules map onto the ``model`` mesh
  axis (expert parallelism).
* :func:`apply_expert_share`, dropless, for a layer that holds a share
  of the experts (``cfg.n_held_experts`` from ``cfg.held_share *
  n_held``): it routes over all experts, sorts the (token, slot) pairs
  by held expert, and runs grouped products (megablox ``gmm``) whose
  device work follows the rows routed to the held experts; the absent
  experts' part of the result is left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.sharding import PartitionSpec as P

from ..sharding import shard
from .config import ModelConfig
from .layers import activation, dense_init, gated, make_mlp_params, apply_mlp


def make_moe_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    E, D, F = cfg.n_held_experts, cfg.d_model, cfg.d_expert
    p = {"router": dense_init(ks[0], (D, cfg.n_experts), cfg.router_dtype),
         "routed_up": dense_init(ks[1], (E, D, F), cfg.param_dtype, fan_in=D),
         "routed_down": dense_init(ks[2], (E, F, D), cfg.param_dtype, fan_in=F)}
    if gated(cfg.activation):
        p["routed_gate"] = dense_init(ks[3], (E, D, F), cfg.param_dtype, fan_in=D)
    if cfg.n_shared_experts > 0:
        p["shared"] = make_mlp_params(ks[4], cfg,
                                      d_ff=cfg.n_shared_experts * cfg.d_expert)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _route(xf, p, cfg: ModelConfig):
    """The router over all ``cfg.n_experts``, in f32: (probs (t, E), gates
    (t, K), expert ids (t, K)); gates renormalised over the top k where
    ``cfg.norm_topk_prob``, then scaled by ``cfg.routed_scale``."""
    logits = jnp.matmul(xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)  # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)              # (t, K)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:
        gates = gates * cfg.routed_scale
    return probs, gates, idx


def _balance_loss(probs, idx, cfg: ModelConfig):
    """Load-balance auxiliary loss (Switch/GShard form); 0 without a
    coefficient."""
    if not cfg.router_aux_coef:
        return jnp.zeros((), jnp.float32)
    t, E = probs.shape
    ones = jnp.zeros((t, E), probs.dtype).at[
        jnp.arange(t)[:, None], idx].set(1.0)
    frac_tokens = ones.mean(0)                                # f_e
    frac_probs = probs.mean(0)                                # p_e
    return cfg.router_aux_coef * E * jnp.sum(frac_tokens * frac_probs)


def apply_moe(x, p, cfg: ModelConfig):
    """x: (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    E, K, F = cfg.n_experts, cfg.top_k, cfg.d_expert
    t = B * S
    xf = x.reshape(t, D)

    probs, gates, idx = _route(xf, p, cfg)
    aux = _balance_loss(probs, idx, cfg)

    # ---- sort-based dispatch with capacity dropping ----
    # Index-inversion formulation: the only scatters are into small int32/
    # fp32 *index/gate* slot tables; token rows move via a gather whose
    # output is expert-sharded (each shard pulls its own rows from the
    # replicated activations — no (E,C,D)-sized collective), and the
    # combine is a shard-local scatter-add followed by one psum-sized
    # all-reduce of the (t, D) output.  The naive row-scatter variant
    # replicated (E*C, D) fp32 buffers across the mesh (see EXPERIMENTS.md
    # §Perf, kimi iteration A1).
    C = _capacity(t, cfg)
    eids = idx.reshape(-1)                                    # (t*K,)
    order = jnp.argsort(eids)                                 # stable
    sorted_eids = eids[order]
    counts = jax.ops.segment_sum(jnp.ones_like(eids), eids, num_segments=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * K) - starts[sorted_eids]
    keep = pos < C
    dest = jnp.where(keep, sorted_eids * C + jnp.clip(pos, 0, C - 1), E * C)
    tok = order // K                                          # source token

    # slot tables: slot -> source token, slot -> gate (sentinel slot E*C)
    slot_tok = jnp.full((E * C + 1,), t, jnp.int32).at[dest].set(tok)
    slot_gate = jnp.zeros((E * C + 1,), jnp.float32).at[dest].set(
        keep * gates.reshape(-1)[order])

    xf_pad = jnp.concatenate([xf, jnp.zeros((1, D), x.dtype)], axis=0)
    h = xf_pad[slot_tok[:E * C]].reshape(E, C, D)             # gather
    h = shard(h, P("model", None, None))

    up = jnp.einsum("ecd,edf->ecf", h, p["routed_up"])
    if "routed_gate" in p:
        g = activation(jnp.einsum("ecd,edf->ecf", h, p["routed_gate"]),
                       cfg.activation)
        hidden = g * up
    else:
        hidden = activation(up, cfg.activation)
    y = jnp.einsum("ecf,efd->ecd", hidden, p["routed_down"])
    y = shard(y, P("model", None, None))

    contrib = y.reshape(E * C, D) * slot_gate[:E * C, None].astype(y.dtype)
    out = jnp.zeros((t + 1, D), y.dtype).at[slot_tok[:E * C]].add(contrib)[:t]

    if "shared" in p:
        out = out + apply_mlp(xf[:, None, :], p["shared"], cfg)[:, 0, :]
    return out.reshape(B, S, D), aux


# ----------------------------------------------------------------------
# Dropless expert share
# ----------------------------------------------------------------------

GMM_ROWS = 128          # megablox row tile: groups start inside at most one


def _gmm_tile(d: int) -> int:
    """A tile of a contracted or output width: 512 where it divides, the
    whole width up to 1536 (e.g. an expert width of 1408), else 128
    (or the whole width, below one lane tile)."""
    if d % 512 == 0:
        return 512
    if d % 128 == 0:
        return d if d <= 1536 else 128
    return d


def _gmm_tiling(m: int, k: int, n: int):
    return (GMM_ROWS, _gmm_tile(k), _gmm_tile(n))


def _gmm(lhs, rhs, sizes):
    """Grouped product: rows of group g times ``rhs[g]``; ``sizes`` has
    one more group than ``rhs`` (the rows held elsewhere), whose rows
    come out 0 and cost no device work."""
    return megablox.gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiling, None, None,
                        False, jax.default_backend() != "tpu")


def apply_expert_share(x, p, cfg: ModelConfig):
    """Dropless DeepSeekMoE layer that holds experts ``[j*n, (j+1)*n)`` of
    ``E``.  x: (B, S, D) -> (out, aux_loss, load (n,) int32): the routed
    part of the held experts plus the shared MLP, and the slots each held
    expert served.

    The (token, slot) pairs are sorted by held expert, those of absent
    experts last; the grouped products compute the held groups only, so
    their work follows the routed rows (``t*K*n/E`` expected), not the
    static ``t*K`` bound; each token sums its slots' rows, weighted by
    their gates, in f32."""
    B, S, D = x.shape
    K, n = cfg.top_k, cfg.n_held_experts
    t = B * S
    xf = x.reshape(t, D)
    with jax.named_scope("routed_experts"):
        probs, gates, idx = _route(xf, p, cfg)
        aux = _balance_loss(probs, idx, cfg)
        local = idx.reshape(-1) - cfg.held_share * n            # (t*K,)
        held = (local >= 0) & (local < n)
        gid = jnp.where(held, local, n)
        order = jnp.argsort(gid, stable=True)
        m = -(-t * K // GMM_ROWS) * GMM_ROWS
        sizes = jnp.zeros((n + 1,), jnp.int32).at[gid].add(1)
        sizes = sizes.at[n].add(m - t * K)                    # padding rows
        rows = xf[jnp.pad(order // K, (0, m - t * K))]
        up = _gmm(rows, p["routed_up"], sizes)
        if "routed_gate" in p:
            hidden = activation(_gmm(rows, p["routed_gate"], sizes),
                                cfg.activation) * up
        else:
            hidden = activation(up, cfg.activation)
        y = _gmm(hidden, p["routed_down"], sizes)             # (m, D)
        slot = jnp.zeros((t * K,), jnp.int32).at[order].set(
            jnp.arange(t * K, dtype=jnp.int32))
        w = jnp.where(held, gates.reshape(-1), 0.0).reshape(t, K)
        out = jnp.einsum("tkd,tk->td", y[slot].reshape(t, K, D), w,
                         preferred_element_type=jnp.float32)
        out = out.astype(x.dtype)
    if "shared" in p:
        out = out + apply_mlp(xf[:, None, :], p["shared"], cfg)[:, 0, :]
    return out.reshape(B, S, D), aux, sizes[:n]
