"""A cell small enough for the CPU: the DeepSeek-V2-Lite configuration's
code at toy widths in float32 (latent attention with YaRN, a dropless
share of 4 of 16 experts, top 3, two shared experts), with
``silo-moe-s2048``'s federation shrunk as ``tiny.py`` shrinks
``silo-s2048``'s."""
from __future__ import annotations

import copy

from bench import spec
from bench.tests import tiny

CHANGES = {
    "num_hidden_layers": 3, "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "moe_intermediate_size": 24, "n_routed_experts": 4,
    "published_n_routed_experts": 16, "num_experts_per_tok": 3,
    "vocab_size": 256, "torch_dtype": "float32", "param_dtype": "float32"}


def conf():
    c = spec.config("deepseek-v2-lite-cut")
    c.update(copy.deepcopy(CHANGES))
    # the YaRN correction range inside the toy rotary width
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=16)
    return c


def traffic():
    return tiny.traffic()


def cfgmod():
    return spec.config_module("deepseek-v2-lite-cut")
