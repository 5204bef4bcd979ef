"""Unified model configuration schema covering all assigned architectures.

One dataclass describes every family (dense / moe / hybrid / audio / vlm /
ssm); family-specific fields are ignored by families that don't use them.
The layer stack is described by *segments* — homogeneous runs of a repeating
block pattern — so big dense stacks compile as one ``lax.scan`` while hybrid
patterns (RG-LRU 2:1, xLSTM m:s) scan over their pattern unit.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

__all__ = ["ModelConfig", "InputShape", "SHAPES", "Segment"]


@dataclasses.dataclass(frozen=True)
class Segment:
    """A run of ``reps`` repetitions of ``pattern`` (tuple of block kinds).

    Block kinds: 'attn' (global attention + FFN), 'local_attn' (windowed
    attention + FFN), 'moe' (attention + MoE FFN), 'rec' (RG-LRU recurrent
    block + FFN), 'mlstm', 'slstm'.
    """
    pattern: tuple[str, ...]
    reps: int

    @property
    def n_layers(self) -> int:
        return len(self.pattern) * self.reps


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One dry-run cell's input geometry."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # ---- identity ----------------------------------------------------------
    arch_id: str
    family: str                      # dense | moe | hybrid | audio | vlm | ssm
    source: str = ""                 # provenance note ([hf:...] / [arXiv:...])

    # ---- core transformer dims ---------------------------------------------
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0                # 0 -> d_model // n_heads
    d_ff: int = 256                  # 0 -> family provides its own expansion
    vocab_size: int = 1000

    # ---- attention / position ----------------------------------------------
    causal: bool = True              # False for encoder-only (audio)
    qkv_bias: bool = False           # qwen2 family: True
    rope_theta: float = 10_000.0
    rope_pct: float = 1.0            # stablelm-2: 0.25 partial rotary
    m_rope_sections: tuple[int, ...] = ()   # qwen2-vl M-RoPE ((16,24,24))
    local_window: int = 0            # >0: sliding-window attention size
    # latent attention (MLA, DeepSeek-V2/V3; arXiv:2405.04434). With
    # kv_lora_rank > 0 every attention block caches one latent of
    # kv_lora_rank + qk_rope_dim values per position (the normed c_kv and
    # the shared roped key part) instead of K/V heads; the queries carry
    # qk_nope_dim + qk_rope_dim per head, the values v_head_dim.
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # ---- norms / activations / embeddings ----------------------------------
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    norm_eps: float = 1e-6
    activation: str = "silu"         # silu(SwiGLU) | gelu(GeGLU) | gelu_mlp
    tie_embeddings: bool = False
    embeds_input: bool = False       # audio/vlm prefill: frontend stub feeds
                                     # precomputed embeddings, not token ids

    # ---- MoE ---------------------------------------------------------------
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 2.0
    moe_group_size: int = 512        # tokens per dispatch group (GShard-style)
    moe_local_groups: bool = False   # under seq_shard: groups nest inside
                                     # sequence shards (no pre-MoE gather;
                                     # dispatch becomes a model-axis a2a)
    moe_dense_residual: bool = False # arctic: dense FFN in parallel with MoE
    moe_d_ff: int = 0                # routed expert width (0 -> d_ff)
    n_shared_experts: int = 0        # always-on experts: one FFN of
                                     # n_shared_experts * moe_d_ff
    first_dense_layers: int = 0      # leading dense-FFN layers
                                     # (first_k_dense_replace)
    router: str = "softmax"          # softmax | sigmoid_bias (DeepSeek-V3
                                     # noaux_tc: sigmoid scores, a bias
                                     # added for selection only)
    norm_topk_prob: bool = False     # renormalise the chosen gate weights
    routed_scaling: float = 1.0      # routed_scaling_factor
    moe_dropless: bool = False       # sorted grouped dispatch (no capacity,
                                     # no token dropped) instead of GShard

    # ---- hybrid (RG-LRU) ----------------------------------------------------
    lru_width: int = 0               # 0 -> d_model
    conv_width: int = 4

    # ---- ssm (xLSTM) --------------------------------------------------------
    xlstm_pf: float = 2.0            # block expansion (projection factor)
    slstm_every: int = 4             # every k-th block is sLSTM (rest mLSTM)
    chunk_size: int = 256            # mLSTM chunkwise-parallel chunk

    # ---- the paper's technique (Masksembles uncertainty) --------------------
    mask_samples: int = 0            # N=0 -> technique off (baseline DNN)
    mask_scale: float = 2.0
    mask_seed: int = 0
    # serving form: store per-sample PACKED FFN weights (mask-zero skipping,
    # paper §V-C) instead of multiplying by masks. FLOPs shrink by the keep
    # rate; weight bytes grow x(N*keep) — wins when compute-bound (prefill),
    # loses when weight-read-bound (decode). Measured in EXPERIMENTS §Perf.
    packed_ffn_serving: bool = False

    # ---- numerics / execution ----------------------------------------------
    # sequence parallelism: keep the residual stream sharded over
    # ("model", seq) between blocks — norms/FFN/projections are token-
    # parallel, attention gathers only the (small, GQA) K/V heads, and the
    # wo/wd partial-sum all-reduces become reduce-scatters (Korthikanti'22).
    # Beyond-paper optimization; validated per-cell in EXPERIMENTS §Perf.
    seq_shard: bool = False
    # keep the materialized attention score matrix in f32 (True) or bf16
    # (False). bf16 halves the dominant HBM-traffic term of the XLA
    # attention path; softmax statistics still reduce in f32.
    attn_scores_f32: bool = True
    # explicit segment structure ((pattern, reps), ...) — used by the
    # dry-run's cost-probe configs; empty -> derived from n_layers/family
    segments_override: tuple = ()
    # unroll time-loops (xLSTM chunk/step scans) so XLA cost analysis sees
    # every iteration — probe configs only (cost_analysis counts a while
    # body once regardless of trip count)
    analysis_unroll: bool = False
    dtype: Any = jnp.bfloat16        # activation/param compute dtype
    # KV cache storage dtype tag: "" = cache in `dtype`; "bfloat16" keeps
    # the cache in bf16 (fused decode supported — attention upcasts cache
    # reads to f32); "int8" adds per-(row, head, slot) scale leaves and
    # serves through the per-op decode path only.
    kv_dtype: str = ""
    remat: str = "full"              # none | full | dots
    attn_chunk: int = 1024           # q-chunk for the XLA chunked-attn path
    use_pallas: bool = False         # real-TPU flag: route hot ops to kernels
    scan_layers: bool = True         # lax.scan over segment reps

    # ------------------------------------------------------------------------
    def __post_init__(self):
        if self.family not in ("dense", "moe", "hybrid", "audio", "vlm",
                               "ssm"):
            raise ValueError(f"unknown family {self.family}")
        if self.kv_dtype not in ("", "bfloat16", "int8"):
            raise ValueError(f"unknown kv_dtype {self.kv_dtype!r}")
        if self.router not in ("softmax", "sigmoid_bias"):
            raise ValueError(f"unknown router {self.router!r}")

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def bayesian(self) -> bool:
        return self.mask_samples > 0

    @property
    def sub_quadratic(self) -> bool:
        """Supports the long_500k cell (no O(S^2) full attention)."""
        return self.family in ("hybrid", "ssm")

    @property
    def has_decode(self) -> bool:
        return self.causal  # encoder-only archs have no decode step

    def segments(self) -> tuple[Segment, ...]:
        """The layer stack as homogeneous scan segments."""
        if self.segments_override:
            return tuple(Segment(tuple(p), r)
                         for p, r in self.segments_override)
        L = self.n_layers
        if self.family in ("dense", "vlm"):
            return (Segment(("attn",), L),)
        if self.family == "audio":
            return (Segment(("attn",), L),)     # causal=False handles encoder
        if self.family == "moe":
            k = min(self.first_dense_layers, L)
            return tuple(Segment(p, r) for p, r in
                         ((("attn",), k), (("moe",), L - k)) if r)
        if self.family == "hybrid":
            # RecurrentGemma: repeating (rec, rec, attn); remainder rec-only.
            reps, rem = divmod(L, 3)
            segs = []
            if reps:
                segs.append(Segment(("rec", "rec", "local_attn"), reps))
            if rem:
                segs.append(Segment(("rec",) * rem, 1))
            return tuple(segs)
        if self.family == "ssm":
            # xLSTM: every `slstm_every`-th block is sLSTM.
            k = self.slstm_every
            reps, rem = divmod(L, k)
            segs = []
            if reps:
                segs.append(Segment(("mlstm",) * (k - 1) + ("slstm",), reps))
            if rem:
                segs.append(Segment(("mlstm",) * rem, 1))
            return tuple(segs)
        raise AssertionError(self.family)

    def attn_param_count(self) -> int:
        """Weights of one attention sub-layer (projections only)."""
        d, h = self.d_model, self.n_heads
        if self.mla:
            r, rope = self.kv_lora_rank, self.qk_rope_dim
            return (d * h * (self.qk_nope_dim + rope) + d * (r + rope) + r
                    + r * h * (self.qk_nope_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)
        dh = self.resolved_head_dim
        return d * dh * (h + 2 * self.n_kv_heads) + dh * h * d

    def param_count(self) -> int:
        """Parameter count, embedding and head included (for roofline
        MODEL_FLOPS and the configurations' published sizes)."""
        d = self.d_model
        qkv = self.attn_param_count()
        if self.activation in ("silu", "gelu"):
            ffn = 3 * d * self.d_ff
        else:
            ffn = 2 * d * self.d_ff
        per_layer = 0
        for seg in self.segments():
            for kind in seg.pattern:
                if kind in ("attn", "local_attn"):
                    per_layer += (qkv + ffn) * seg.reps
                elif kind == "moe":
                    expert = 3 * d * self.expert_d_ff
                    layer = qkv + self.n_experts * expert + d * self.n_experts
                    layer += self.n_shared_experts * expert
                    if self.moe_dense_residual:
                        layer += ffn
                    per_layer += layer * seg.reps
                elif kind == "rec":
                    w = self.lru_width or d
                    per_layer += (2 * d * w + w * d + 3 * w
                                  + self.conv_width * w + ffn) * seg.reps
                elif kind in ("mlstm", "slstm"):
                    pd = int(self.xlstm_pf * d)
                    per_layer += (2 * d * pd + pd * d + 4 * pd) * seg.reps
        embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return per_layer + embed

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k routed experts only)."""
        if self.family != "moe":
            return self.param_count()
        expert = 3 * self.d_model * self.expert_d_ff
        moe_layers = sum(seg.reps * seg.pattern.count("moe")
                         for seg in self.segments())
        return self.param_count() \
            - moe_layers * (self.n_experts - self.top_k) * expert
