"""A cell small enough for the CPU: the danube configuration's code at
toy widths in float32, four silos, streaming fold through the Pallas
kernel (interpret mode off the TPU)."""
from __future__ import annotations

import copy

from bench import spec

CONF = {
    "name": "danube-tiny", "num_hidden_layers": 1, "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "sliding_window": 4, "hidden_act": "silu", "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "embedding_multiplier": 8.0,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "param_dtype": "float32", "reduced": []}

TRAFFIC = {
    "data": "tokens", "data_seed": 0, "seq_tokens": 8, "n_clients": 4,
    "f": 1, "attack": "sign_flip", "per_client": 4, "n_test": 2,
    "sample_frac": 0.25, "local_steps": 1, "batch_size": 2, "l2": 0.0,
    "lr": 0.5, "eps": [0.0, 0.5, 2.0], "streaming": True,
    "client_chunk": 1, "use_kernel_stats": False, "use_kernel_agg": True,
    "rounds_per_call": 2,
    # float32 on both sides: the program and the reference differ by
    # the order of float32 sums alone
    "limits": {"grad1_gap": 1e-3, "change3_gap": 1e-3, "c1c2_gap": 1e-3,
               "keep_mismatch": 0}}


def conf():
    return copy.deepcopy(CONF)


def traffic():
    return copy.deepcopy(TRAFFIC)


def cfgmod():
    return spec.config_module("danube-1.8b-cut")
