"""Model configuration for the repro transformer zoo.

A single ``ModelConfig`` describes every architecture family we support:
dense decoders (MHA/GQA/MQA, optional sliding window), fine-grained MoE,
Mamba-1 SSMs, hybrid (Jamba-style) stacks, encoder-decoder (Whisper
backbone) and VLM decoders with interleaved cross-attention.

Layers are described by a repeating ``layout`` *group*: a tuple of
``(mixer, ffn)`` pairs.  ``n_layers`` must be ``first_k_dense +
n_groups * len(layout)``.  Mixers:

  - ``attn``    causal self attention (GQA; ``window`` applies if set)
  - ``mla``     multi-head latent attention (DeepSeek-V2): a low-rank
                latent KV and a decoupled rotary key shared by all heads
  - ``swa``     sliding-window causal self attention (forces ``window``)
  - ``mamba``   Mamba-1 selective-scan block
  - ``xattn``   cross-attention block (VLM image layers, attends to
                precomputed patch/frame embeddings)
  - ``attn_x``  self attention followed by cross attention in the same
                block (classic transformer-decoder layer, Whisper)

FFN kinds: ``mlp`` (gated or plain), ``moe`` (fine-grained, optional
shared experts) or ``none`` (block has no separate FFN, e.g. Mamba-only
stacks).  An expert layer may hold a share of the experts
(``n_held_experts`` of ``n_experts``, expert parallelism): it routes over
all of them and computes its own experts' part.  ``capacity_factor=None``
makes the layer dropless (grouped products over the rows routed to the
held experts) instead of dropping by capacity.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

Mixer = str
Ffn = str
LayoutEntry = Tuple[Mixer, Ffn]


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 sets it."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # default: d_model // n_heads
    layout: Tuple[LayoutEntry, ...] = (("attn", "mlp"),)
    first_k_dense: int = 0                  # leading unscanned dense-MLP attn layers (DeepSeek/Kimi)
    activation: str = "swiglu"              # swiglu | geglu | gelu | relu
    norm: str = "rmsnorm"                   # rmsnorm | layernorm
    rope_theta: float = 10_000.0
    window: Optional[int] = None            # sliding-window size for swa mixers
    logit_softcap: Optional[float] = None
    embed_scale: Optional[float] = None     # None: sqrt(d_model) for rmsnorm models
    yarn: Optional[Yarn] = None             # rotary scaling of the mla mixer

    # --- MLA (mixer "mla") ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_expert: Optional[int] = None          # fine-grained expert hidden dim (defaults d_ff)
    capacity_factor: Optional[float] = 1.25  # None: dropless
    router_aux_coef: float = 0.01
    router_dtype: str = "float32"           # stored router; its logits are f32
    norm_topk_prob: bool = True             # renormalise the top-k gates
    routed_scale: float = 1.0               # routed gates' scaling factor
    n_held_experts: Optional[int] = None    # experts this layer holds (default all)
    held_share: int = 0                     # holds experts [j*n_held, (j+1)*n_held)

    # --- SSM (Mamba-1) ---
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: Optional[int] = None           # default ceil(d_model / 16)

    # --- encoder (enc-dec archs; None => decoder-only) ---
    n_enc_layers: int = 0
    enc_seq: int = 1500                     # precomputed frame-embedding length (Whisper 30s)

    # --- modality frontend stub ---
    frontend: Optional[str] = None          # None | "audio" | "vision"
    n_patches: int = 1600                   # VLM precomputed patch embeddings per example

    # --- numerics / implementation ---
    dtype: str = "bfloat16"                 # activation / param compute dtype
    param_dtype: str = "bfloat16"
    attn_chunk: int = 1024                  # q-chunk for blockwise attention when seq is long
    attn_direct_max: int = 2048             # use direct attention for seq <= this
    loss_chunk: int = 2048                  # token chunk for vocab-sharded chunked xent
    tie_embeddings: bool = True
    remat: bool = True                      # activation checkpointing per block group
    use_kernels: bool = False               # route hot ops through Pallas kernels (TPU)
    scan_layers: bool = True                # stack layout groups with jax.lax.scan

    # ------------------------------------------------------------------
    def __post_init__(self):
        hd = self.head_dim or (self.d_model // max(self.n_heads, 1))
        object.__setattr__(self, "head_dim", hd)
        if self.dt_rank is None:
            object.__setattr__(self, "dt_rank", max(1, math.ceil(self.d_model / 16)))
        if self.d_expert is None:
            object.__setattr__(self, "d_expert", self.d_ff)
        body = self.n_layers - self.first_k_dense
        if self.layout and body % len(self.layout) != 0:
            raise ValueError(
                f"{self.name}: n_layers-first_k_dense={body} not divisible by "
                f"layout length {len(self.layout)}")
        if any(m == "swa" for m, _ in self.layout) and self.window is None:
            raise ValueError(f"{self.name}: swa mixer requires window")
        if any(f == "moe" for _, f in self.layout) and self.n_experts <= 0:
            raise ValueError(f"{self.name}: moe layout requires n_experts > 0")
        if self.n_held_experts is None:
            object.__setattr__(self, "n_held_experts", self.n_experts)
        if self.n_held_experts < self.n_experts and self.capacity_factor \
                is not None:
            raise ValueError(f"{self.name}: an expert share is dropless "
                             f"(capacity_factor=None)")
        if (self.held_share + 1) * self.n_held_experts > self.n_experts:
            raise ValueError(f"{self.name}: expert share {self.held_share} "
                             f"lies outside the {self.n_experts} experts")
        if any(m == "mla" for m, _ in self.layout) and not (
                self.kv_lora_rank and self.qk_rope_head_dim
                and self.v_head_dim):
            raise ValueError(f"{self.name}: mla mixer requires kv_lora_rank, "
                             f"qk_rope_head_dim and v_head_dim")

    # ------------------------------------------------------------------
    @property
    def n_groups(self) -> int:
        return (self.n_layers - self.first_k_dense) // len(self.layout)

    @property
    def dropless(self) -> bool:
        return self.n_experts > 0 and self.capacity_factor is None

    @property
    def prelude_entry(self) -> LayoutEntry:
        """The leading dense layers: the layout's first mixer, a dense MLP."""
        return (self.layout[0][0], "mlp")

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def is_enc_dec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def has_cross(self) -> bool:
        return any(m in ("xattn", "attn_x") for m, _ in self.layout)

    @property
    def cross_len(self) -> int:
        """Length of the cross-attended embedding sequence."""
        return self.enc_seq if self.is_enc_dec else self.n_patches

    @property
    def attn_free(self) -> bool:
        return all(m == "mamba" for m, _ in self.layout) and self.first_k_dense == 0

    @property
    def padded_vocab(self) -> int:
        """Embedding rows padded to a multiple of 256 so the vocab dim
        shards evenly on any mesh (Megatron-style vocab padding); pad
        logits are masked out in the loss and at decode."""
        return -(-self.vocab_size // 256) * 256

    @property
    def sub_quadratic(self) -> bool:
        """True when the arch can serve very long contexts (long_500k):
        attention-free (SSM), sliding-window, or hybrid stacks whose full-
        attention layers are a small minority (Jamba 1:7 — decode cost is
        dominated by the recurrent mixers and the few KV caches fit when
        seq-sharded).  ``xattn`` attends to a fixed-length embedding
        sequence; ``attn_x`` contains full causal self attention."""
        def is_full_attn(m):
            return (m in ("attn", "attn_x", "mla")) and self.window is None

        full = sum(is_full_attn(m) for m, _ in self.layout)
        mamba = sum(m == "mamba" for m, _ in self.layout)
        if full == 0 and self.first_k_dense == 0:
            return True
        n_full = full * max(self.n_groups, 1) + self.first_k_dense
        return mamba > 0 and n_full / max(self.n_layers, 1) <= 0.25

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Exact parameter count of the constructed model (see model.init)."""
        from . import model as _model  # lazy; avoids cycle at import time
        import jax

        shapes = jax.eval_shape(lambda: _model.init(jax.random.PRNGKey(0), self))
        return sum(int(math.prod(x.shape)) for x in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k + shared experts only)."""
        from . import model as _model
        import jax
        import numpy as np

        shapes = jax.eval_shape(lambda: _model.init(jax.random.PRNGKey(0), self))
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            n = int(np.prod(leaf.shape))
            keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            if "routed" in keys and self.n_experts > 0:
                n = n * self.top_k // self.n_experts
            total += n
        return total
