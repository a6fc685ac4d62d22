"""h2o-danube-1.8b (arXiv:2401.16818), cut in depth, through fl/zoo.

A Mistral-style decoder: RMSNorm, rotary embeddings, grouped-query
attention over a sliding window, a SwiGLU MLP and an untied output
head, with the program's departures that the configuration file lists.
The plain reference below computes the full-sequence language-model
loss of one batch in float32 from the configuration file alone; ``q``
rounds every stored activation and every operand of a matrix product,
so the same code gives the reference (``q`` the identity) and its
lower-precision control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _model_config(conf: dict):
    from repro.models import ModelConfig
    return ModelConfig(
        name=conf["name"], n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        layout=(("swa", "mlp"),), window=conf["sliding_window"],
        activation="swiglu", rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"], param_dtype=conf["param_dtype"])


def build(conf: dict, traffic: dict):
    from repro.fl.zoo import zoo_model
    return zoo_model(_model_config(conf), seq_len=traffic["seq_tokens"] - 1)


def _leaves(conf: dict):
    """(path, shape, fan_in) of every weight in the program's layout;
    fan_in None marks a norm weight."""
    L, D = conf["num_hidden_layers"], conf["hidden_size"]
    H, K, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                conf["head_dim"])
    F, V = conf["intermediate_size"], conf["vocab_size"]
    out = [(("embed",), (V, D), D), (("final_norm", "scale"), (D,), None)]
    if not conf["tie_word_embeddings"]:
        out.append((("lm_head",), (D, V), D))
    out += [(("ln1", "scale"), (L, D), None),
            (("attn", "wq"), (L, D, H * hd), D),
            (("attn", "wk"), (L, D, K * hd), D),
            (("attn", "wv"), (L, D, K * hd), D),
            (("attn", "wo"), (L, H * hd, D), H * hd),
            (("ln2", "scale"), (L, D), None),
            (("mlp", "w_up"), (L, D, F), D),
            (("mlp", "w_down"), (L, F, D), F),
            (("mlp", "w_gate"), (L, D, F), D)]
    return out


def init_params(conf: dict, traffic: dict, key):
    """Normal weights over sqrt(fan in) in the stored dtype, from
    ``key``, one jitted call on device."""
    leaves = _leaves(conf)
    dt = jnp.dtype(conf["param_dtype"])

    @jax.jit
    def make(key):
        top, block = {}, {}
        for i, (path, shape, fan_in) in enumerate(leaves):
            if fan_in is None:
                v = jnp.zeros(shape, dt)
            else:
                v = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) / fan_in ** 0.5).astype(dt)
            node = top if path[0] in ("embed", "lm_head",
                                      "final_norm") else block
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = v
        top["groups"] = (block,)
        return top
    return make(key)


def reference_loss(conf: dict, traffic: dict):
    """Plain ``loss(params, x, y, q)``: the mean next-token cross entropy
    over every position of ``concat(x, y)``, in float32."""
    D, H, K, hd = (conf["hidden_size"], conf["num_attention_heads"],
                   conf["num_key_value_heads"], conf["head_dim"])
    window, theta = conf["sliding_window"], conf["rope_theta"]
    eps, mult = conf["rms_norm_eps"], conf["embedding_multiplier"]
    f32 = jnp.float32

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
            1.0 + w.astype(f32))

    def rotary(x, pos):
        half = hd // 2
        freq = theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = pos[:, None] * freq
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def mm(q, a, w):
        return q(a) @ q(w.astype(f32))

    def layer(q, pos, h, lp):
        B, S, _ = h.shape
        a = q(rms(h, lp["ln1"]["scale"]))
        qh = rotary(mm(q, a, lp["attn"]["wq"]).reshape(B, S, H, hd), pos)
        kh = rotary(mm(q, a, lp["attn"]["wk"]).reshape(B, S, K, hd), pos)
        vh = mm(q, a, lp["attn"]["wv"]).reshape(B, S, K, hd)
        qh = q(qh).reshape(B, S, K, H // K, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qh, q(kh)) / hd ** 0.5
        i, j = pos[:, None], pos[None, :]
        s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
        pr = q(jax.nn.softmax(s, axis=-1))
        o = jnp.einsum("bkgqs,bskd->bqkgd", pr, q(vh)).reshape(B, S, H * hd)
        h = q(h + mm(q, o, lp["attn"]["wo"]))
        m = q(rms(h, lp["ln2"]["scale"]))
        g = jax.nn.silu(mm(q, m, lp["mlp"]["w_gate"]))
        f = q(g * mm(q, m, lp["mlp"]["w_up"]))
        return q(h + mm(q, f, lp["mlp"]["w_down"]))

    def loss(p, x, y, q):
        tok = jnp.concatenate([x.astype(jnp.int32),
                               y.astype(jnp.int32)[:, None]], axis=1)
        S = tok.shape[1]
        pos = jnp.arange(S, dtype=f32)
        h = q(q(p["embed"].astype(f32))[tok] * mult)
        body = jax.checkpoint(lambda h, lp: (layer(q, pos, h, lp), None))
        h, _ = jax.lax.scan(body, h, p["groups"][0])
        h = q(rms(h, p["final_norm"]["scale"]))
        w = p["lm_head"] if "lm_head" in p else p["embed"].T
        logits = mm(q, h[:, :-1], w)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, tok[:, 1:, None], -1)[..., 0]
        return nll.mean()
    return loss


def forward_flops(conf: dict, traffic: dict) -> float:
    """Model FLOPs of one sequence's forward pass: 2 per multiply-add of
    every weight matrix and of the output head, plus the causal,
    windowed attention (q.k and p.v over the keys each query sees)."""
    L, D = conf["num_hidden_layers"], conf["hidden_size"]
    H, K, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                conf["head_dim"])
    F, V = conf["intermediate_size"], conf["vocab_size"]
    S, W = traffic["seq_tokens"], conf["sliding_window"]
    per_layer = D * H * hd * 2 + D * K * hd * 2 + 3 * D * F
    dense = 2 * S * (L * per_layer + D * V)
    seen = sum(min(t, W) for t in range(1, S + 1))
    attn = L * 4 * H * hd * seen
    return float(dense + attn)
