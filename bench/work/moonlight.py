"""Work a Bayesian Moonlight-16B-A3B (DeepSeek-V3 block: latent attention,
routed and shared experts) requires, whatever implements it.

Every request runs as N rows, one per mask. Decode, per step: FLOPs of
the projections, of absorbed latent attention over the positions each row
really attends to (scores against the 512-wide latent and the 64 rope
dims, the output in latent space, then W_uv), of the dense FFN, the shared
experts and the top-k routed experts at the kept widths (mask-zero
skipping), of the router, and of the head; bytes of every non-expert
weight once (the embedding only for the rows it looks up), of the routed
experts that the step's tokens hit (``experts_hit``, summed over the MoE
layers), of the latent cache at the attended positions and of the latents
written. Prefill of one admission: expanded attention at the prompt's true
length under each mask, every weight once (at a prompt of hundreds of
tokens every expert is hit), the latents written, logits at the last
position only. Padding, empty cache positions and dead rows are waste that
a roofline share exposes.
"""

from __future__ import annotations

from bench.reference import masks as masks_ref

BF16 = 2


def _keep(c: dict, width: int) -> int:
    return masks_ref.keep_count(width, c["mask_samples"], c["mask_scale"])


def _shape(c: dict):
    return (c["hidden_size"], c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])


def layers(c: dict) -> tuple[int, int]:
    """(dense layers, MoE layers) held."""
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def latent_width(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def attn_params(c: dict) -> int:
    d, h, nope, rope, dv, r = _shape(c)
    return (d * h * (nope + rope) + d * (r + rope) + r
            + r * h * (nope + dv) + h * dv * d + 2 * d)       # + 2 norms


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def non_expert_bytes(c: dict) -> float:
    """Every weight but the routed experts and the embedding table."""
    d, e = c["hidden_size"], c["n_routed_experts"]
    dense, moe = layers(c)
    shared = 3 * d * c["n_shared_experts"] * c["moe_intermediate_size"]
    per_moe = attn_params(c) + shared + d * e + e
    per_dense = attn_params(c) + 3 * d * c["intermediate_size"]
    head = d * c["vocab_size"] + d
    return float(BF16 * (dense * per_dense + moe * per_moe + head))


def _token_flops(c: dict) -> float:
    """Per row and token, every layer: projections, FFN and experts at the
    kept widths, router; attention's per-position terms are apart."""
    d, h, nope, rope, dv, r = _shape(c)
    dense, moe = layers(c)
    proj = 2 * d * h * (nope + rope) + 2 * d * (r + rope) + 2 * h * dv * d
    ffn = 3 * 2 * d * _keep(c, c["intermediate_size"])
    routed = c["num_experts_per_tok"] * 3 * 2 * d * _keep(
        c, c["moe_intermediate_size"])
    shared = 3 * 2 * d * _keep(c, c["n_shared_experts"]
                               * c["moe_intermediate_size"])
    router = 2 * d * c["n_routed_experts"]
    return float(dense * (proj + ffn) + moe * (proj + routed + shared
                                               + router))


def decode_step(c: dict, rows: int, attended: int, experts_hit: float
                ) -> tuple[float, float]:
    """(flops, bytes) of one absorbed decode step over ``rows`` live rows
    that attend to ``attended`` positions in all (new token included),
    whose tokens hit ``experts_hit`` routed experts over the MoE layers."""
    d, h, nope, rope, dv, r = _shape(c)
    n_layers = c["num_hidden_layers"]
    absorb = 2 * h * nope * r + 2 * h * r * dv            # per row, layer
    per_pos = 2 * h * (r + rope) + 2 * h * r              # scores, o_lat
    flops = rows * (_token_flops(c) + n_layers * absorb
                    + 2 * d * c["vocab_size"]) \
        + n_layers * per_pos * attended
    lat = n_layers * latent_width(c) * BF16
    nbytes = non_expert_bytes(c) + BF16 * rows * d \
        + experts_hit * expert_params(c) * BF16 + lat * (attended + rows)
    return float(flops), float(nbytes)


def prefill(c: dict, length: int) -> tuple[float, float]:
    """(flops, bytes) of one admission: a ``length``-token prompt under
    each of the N masks, expanded attention, logits at its last position
    only."""
    d, h, nope, rope, dv, r = _shape(c)
    n = c["mask_samples"]
    n_layers = c["num_hidden_layers"]
    _, moe = layers(c)
    causal = length * (length + 1) // 2
    expand = 2 * r * h * (nope + dv)                      # per token, layer
    attn = 2 * h * (nope + rope) * causal + 2 * h * dv * causal
    flops = n * (length * (_token_flops(c) + n_layers * expand)
                 + n_layers * attn + 2 * d * c["vocab_size"])
    weights = non_expert_bytes(c) \
        + moe * c["n_routed_experts"] * expert_params(c) * BF16
    nbytes = weights + BF16 * n * length * (d + n_layers * latent_width(c))
    return float(flops), float(nbytes)


def seconds(work: tuple[float, float], peaks: dict) -> float:
    """Least time on the chip for (flops, bytes)."""
    return max(work[0] / peaks["flops_bf16"],
               work[1] / peaks["hbm_bytes_per_s"])
