"""DeepSeek-V2-Lite (arXiv:2405.04434), one pipeline stage of an
eight-way expert-parallel deployment, through fl/zoo.

Multi-head latent attention (a low-rank latent KV with its own RMSNorm,
a rotary key shared by all heads, YaRN frequencies), one dense layer,
then DeepSeekMoE layers whose router spans all 64 experts while this
chip computes the 8 it holds, plus two shared experts.  The plain
reference below computes the full-sequence language-model loss of one
batch in float32 from the configuration file alone, its expert layer
dense and masked: every held expert on every token, times a gate that is
0 where the token was not routed to it.  ``q`` rounds every stored
activation and every operand of a matrix product, so the same code gives
the reference (``q`` the identity) and its lower-precision control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the reference's attention takes its queries in blocks of this many, so
# that it fits beside its float32 copies of the weights
QUERY_BLOCK = 256


def _model_config(conf: dict):
    from repro.models import ModelConfig
    from repro.models.config import Yarn
    rs = conf["rope_scaling"]
    return ModelConfig(
        name=conf["name"], n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        layout=(("mla", "moe"),), first_k_dense=conf["first_k_dense_replace"],
        activation="swiglu", rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"], embed_scale=1.0,
        yarn=Yarn(factor=float(rs["factor"]),
                  original_max_position=rs["original_max_position_embeddings"],
                  beta_fast=float(rs["beta_fast"]),
                  beta_slow=float(rs["beta_slow"]),
                  mscale=float(rs["mscale"]),
                  mscale_all_dim=float(rs["mscale_all_dim"])),
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        n_experts=conf["published_n_routed_experts"],
        n_held_experts=conf["n_routed_experts"],
        held_share=conf["held_share"], top_k=conf["num_experts_per_tok"],
        n_shared_experts=conf["n_shared_experts"],
        d_expert=conf["moe_intermediate_size"], capacity_factor=None,
        router_aux_coef=conf["router_aux_coef"],
        router_dtype=conf["param_dtype"],
        norm_topk_prob=conf["norm_topk_prob"],
        routed_scale=float(conf["routed_scaling_factor"]),
        dtype=conf["torch_dtype"], param_dtype=conf["param_dtype"])


def build(conf: dict, traffic: dict):
    from repro.fl.zoo import zoo_model
    return zoo_model(_model_config(conf), seq_len=traffic["seq_tokens"] - 1)


def _leaves(conf: dict):
    """(path, shape, fan_in, dtype) of every weight in the program's
    layout; fan_in None marks a norm weight; a path under ``prelude`` is
    the dense layer, under ``block`` the stacked MoE layers."""
    L = conf["num_hidden_layers"] - conf["first_k_dense_replace"]
    D, H, r = (conf["hidden_size"], conf["num_attention_heads"],
               conf["kv_lora_rank"])
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    F, Fe, V = (conf["intermediate_size"], conf["moe_intermediate_size"],
                conf["vocab_size"])
    n, E = conf["n_routed_experts"], conf["published_n_routed_experts"]
    Fs = conf["n_shared_experts"] * Fe
    wdt = conf["param_dtype"]

    def mla(lead):
        return [(("ln1", "scale"), lead + (D,), None, wdt),
                (("mla", "wq"), lead + (D, H * (dn + dr)), D, wdt),
                (("mla", "wkv_a"), lead + (D, r + dr), D, wdt),
                (("mla", "kv_norm", "scale"), lead + (r,), None, wdt),
                (("mla", "wkv_b"), lead + (r, H * (dn + dv)), r, wdt),
                (("mla", "wo"), lead + (H * dv, D), H * dv, wdt),
                (("ln2", "scale"), lead + (D,), None, wdt)]

    out = [(("embed",), (V, D), D, wdt), (("final_norm", "scale"), (D,),
                                          None, wdt)]
    if not conf["tie_word_embeddings"]:
        out.append((("lm_head",), (D, V), D, wdt))
    for k in range(conf["first_k_dense_replace"]):
        out += [(("prelude", k) + p, s, fi, dt) for p, s, fi, dt in
                mla(()) + [(("mlp", "w_up"), (D, F), D, wdt),
                           (("mlp", "w_down"), (F, D), F, wdt),
                           (("mlp", "w_gate"), (D, F), D, wdt)]]
    moe = [(("moe", "router"), (L, D, E), D, wdt),
           (("moe", "routed_up"), (L, n, D, Fe), D, wdt),
           (("moe", "routed_down"), (L, n, Fe, D), Fe, wdt),
           (("moe", "routed_gate"), (L, n, D, Fe), D, wdt),
           (("moe", "shared", "w_up"), (L, D, Fs), D, wdt),
           (("moe", "shared", "w_down"), (L, Fs, D), Fs, wdt),
           (("moe", "shared", "w_gate"), (L, D, Fs), D, wdt)]
    out += [(("block",) + p, s, fi, dt) for p, s, fi, dt in mla((L,)) + moe]
    return out


def init_params(conf: dict, traffic: dict, key):
    """Normal weights over sqrt(fan in) in the stored dtype, from
    ``key``, one jitted call on device."""
    leaves = _leaves(conf)

    @jax.jit
    def make(key):
        top, block = {}, {}
        prelude = [{} for _ in range(conf["first_k_dense_replace"])]
        for i, (path, shape, fan_in, dt) in enumerate(leaves):
            if fan_in is None:
                v = jnp.zeros(shape, dt)
            else:
                v = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) / fan_in ** 0.5).astype(dt)
            if path[0] == "prelude":
                node, path = prelude[path[1]], path[2:]
            elif path[0] == "block":
                node, path = block, path[1:]
            else:
                node = top
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = v
        top["prelude"] = prelude
        top["groups"] = (block,)
        return top
    return make(key)


def yarn_freqs(conf: dict) -> np.ndarray:
    """The rotary frequencies in closed form (HF ``modeling_deepseek``'s
    YaRN): base f_i = theta^(-2i/d); correction range low = floor(c(32)),
    high = ceil(c(1)) with c(r) = d ln(L0/(2 pi r)) / (2 ln theta); ramp
    clip((i - low)/(high - low), 0, 1); f_i/factor where the ramp is 1."""
    rs, d = conf["rope_scaling"], conf["qk_rope_head_dim"]
    theta, L0 = float(conf["rope_theta"]), rs["original_max_position_embeddings"]

    def c(rot):
        return d * math.log(L0 / (2 * math.pi * rot)) / (2 * math.log(theta))
    low = max(math.floor(c(rs["beta_fast"])), 0)
    high = min(math.ceil(c(rs["beta_slow"])), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    base = theta ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (base / rs["factor"] * ramp + base * (1.0 - ramp)).astype(np.float32)


def softmax_scale(conf: dict) -> float:
    """(qk head dim)^-0.5 times mscale^2, mscale = 0.1 m ln(factor) + 1."""
    rs = conf["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5 * m * m


def reference_loss(conf: dict, traffic: dict):
    """Plain ``loss(params, x, y, q)``: the mean next-token cross entropy
    over every position of ``concat(x, y)``, in float32."""
    H, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    K, n = conf["num_experts_per_tok"], conf["n_routed_experts"]
    lo = conf["held_share"] * n
    eps, gscale = conf["rms_norm_eps"], float(conf["routed_scaling_factor"])
    freq, scale = jnp.asarray(yarn_freqs(conf)), softmax_scale(conf)
    f32 = jnp.float32

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
            1.0 + w.astype(f32))

    def rotary(x, pos):
        half = x.shape[-1] // 2
        ang = pos[:, None] * freq
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def mm(q, a, w):
        return q(a) @ q(w.astype(f32))

    def swiglu(q, m, up, gate, down):
        g = jax.nn.silu(mm(q, m, gate))
        return mm(q, q(g * mm(q, m, up)), down)

    def mla(q, pos, h, lp):
        B, S, _ = h.shape
        a = q(rms(h, lp["ln1"]["scale"]))
        p = lp["mla"]
        qh = mm(q, a, p["wq"]).reshape(B, S, H, dn + dr)
        ckv = mm(q, a, p["wkv_a"])
        c = q(rms(ckv[..., :r], p["kv_norm"]["scale"]))
        kv = mm(q, c, p["wkv_b"]).reshape(B, S, H, dn + dv)
        q_nope, q_pe = q(qh[..., :dn]), q(rotary(qh[..., dn:], pos))
        k_nope, k_pe = q(kv[..., :dn]), q(rotary(ckv[..., None, r:], pos))
        v = q(kv[..., dn:])

        def block(_, xs):
            """The queries of one block against every key: blocks keep
            the float32 scores to a fraction of the sequence's."""
            qn, qp, i = xs
            s = (jnp.einsum("bqhd,bshd->bhqs", qn, k_nope)
                 + jnp.einsum("bqhd,bsxd->bhqs", qp, k_pe)) * scale
            s = jnp.where(pos[None, :] <= i[:, None], s, -jnp.inf)
            pr = q(jax.nn.softmax(s, axis=-1))
            return None, jnp.einsum("bhqs,bshd->bqhd", pr, v)
        nb = S // QUERY_BLOCK if S % QUERY_BLOCK == 0 else 1

        def blocks(a):
            return a.reshape((B, nb, S // nb) + a.shape[2:]).swapaxes(0, 1)
        _, o = jax.lax.scan(jax.checkpoint(block), None, (
            blocks(q_nope), blocks(q_pe), pos.reshape(nb, S // nb)))
        o = o.swapaxes(0, 1).reshape(B, S, H * dv)
        return q(h + mm(q, o, p["wo"]))

    def experts(q, m, mp):
        """Dense and masked: every held expert on every token, weighted
        by its gate, 0 where the token did not route to it."""
        probs = jax.nn.softmax(mm(q, m, mp["router"]), axis=-1)
        top, idx = jax.lax.top_k(probs, K)
        t = m.shape[0]
        gate = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], idx].set(top)
        gate = gate[:, lo:lo + n] * gscale                      # (t, n)

        def one(acc, e):
            w_up, w_gate, w_down, g = e
            return acc + g[:, None] * q(swiglu(q, m, w_up, w_gate, w_down)), None
        routed, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(m), (
            mp["routed_up"], mp["routed_gate"], mp["routed_down"], gate.T))
        sh = mp["shared"]
        return q(routed) + q(swiglu(q, m, sh["w_up"], sh["w_gate"],
                                    sh["w_down"]))

    def dense_layer(q, pos, h, lp):
        h = mla(q, pos, h, lp)
        m = q(rms(h, lp["ln2"]["scale"]))
        mp = lp["mlp"]
        return q(h + swiglu(q, m, mp["w_up"], mp["w_gate"], mp["w_down"]))

    def moe_layer(q, pos, h, lp):
        h = mla(q, pos, h, lp)
        B, S, D = h.shape
        m = q(rms(h, lp["ln2"]["scale"])).reshape(B * S, D)
        return q(h + experts(q, m, lp["moe"]).reshape(B, S, D))

    def loss(p, x, y, q):
        tok = jnp.concatenate([x.astype(jnp.int32),
                               y.astype(jnp.int32)[:, None]], axis=1)
        S = tok.shape[1]
        pos = jnp.arange(S, dtype=f32)
        h = q(q(p["embed"].astype(f32))[tok])
        for lp in p["prelude"]:
            h = jax.checkpoint(lambda h, lp: dense_layer(q, pos, h, lp))(h, lp)
        body = jax.checkpoint(lambda h, lp: (moe_layer(q, pos, h, lp), None))
        h, _ = jax.lax.scan(body, h, p["groups"][0])
        h = q(rms(h, p["final_norm"]["scale"]))
        w = p["lm_head"] if "lm_head" in p else p["embed"].T
        logits = mm(q, h[:, :-1], w)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, tok[:, 1:, None], -1)[..., 0]
        return nll.mean()
    return loss


def _moe_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def routed_rows(conf: dict, tokens: int) -> float:
    """Expected (token, slot) rows routed to the held experts: every
    token picks ``num_experts_per_tok`` of the published experts, n of
    which are held here."""
    return tokens * conf["num_experts_per_tok"] * conf["n_routed_experts"] \
        / conf["published_n_routed_experts"]


def forward_flops(conf: dict, traffic: dict) -> float:
    """Model FLOPs of one sequence's forward pass at the expected
    routing: 2 per multiply-add of every weight matrix (the routed
    experts at their expected rows), the causal MLA scores (q.k over the
    qk head dim, p.v over the v head dim, for the keys each query sees)
    and the output head."""
    D, H, r = (conf["hidden_size"], conf["num_attention_heads"],
               conf["kv_lora_rank"])
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    S, V, L = traffic["seq_tokens"], conf["vocab_size"], conf["num_hidden_layers"]
    Fe, E = conf["moe_intermediate_size"], conf["published_n_routed_experts"]
    mla = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D
    seen = S * (S + 1) // 2
    scores = 2 * H * (dn + dr + dv) * seen
    dense = 3 * D * conf["intermediate_size"]
    moe = D * E + 3 * D * Fe * conf["n_shared_experts"]
    per_seq = 2 * S * (L * mla + conf["first_k_dense_replace"] * dense
                       + _moe_layers(conf) * moe + D * V) + L * scores
    return float(per_seq + expert_work(conf, traffic, 1)[0])


def expert_work(conf: dict, traffic: dict, seqs: int):
    """(FLOPs, bytes) of the forward grouped products of every MoE layer
    on a batch of ``seqs`` sequences, at the expected routed rows: three
    products of (rows, D) by (D, F) each; the bytes read each held
    expert's weights once and each product's rows in and out once, in
    the stored dtype."""
    D, Fe = conf["hidden_size"], conf["moe_intermediate_size"]
    R = routed_rows(conf, seqs * traffic["seq_tokens"])
    b = np.dtype(jnp.dtype(conf["param_dtype"])).itemsize
    flops = 3 * 2 * R * D * Fe
    weights = 3 * conf["n_routed_experts"] * D * Fe * b
    rows = (2 * (R * D + R * Fe) + (R * Fe + R * D)) * b
    L = _moe_layers(conf)
    return float(L * flops), float(L * (weights + rows))
