"""Work a Bayesian qwen2-style decoder requires, whatever implements it.

Every request runs as N rows, one per mask. FLOPs count the projections,
attention over the positions each row really attends to, the FFN at the
kept width (mask-zero skipping) and the vocabulary head where the step
needs logits. Bytes count every weight once per step, the live KV
positions of the live rows, and the KV written. Padding, empty cache
positions and dead rows are waste that a roofline share exposes.
"""

from __future__ import annotations

from bench.reference import masks as masks_ref

BF16 = 2


def _dims(c: dict):
    d, h, hkv, dh = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    return d, h, hkv, dh, c["num_hidden_layers"], c["vocab_size"]


def kept_width(c: dict) -> int:
    return masks_ref.keep_count(c["intermediate_size"], c["mask_samples"],
                                c["mask_scale"])


def weight_bytes(c: dict) -> float:
    d, h, hkv, dh, layers, vocab = _dims(c)
    f = c["intermediate_size"]
    per_layer = (d * (h + 2 * hkv) * dh + (h + 2 * hkv) * dh   # qkv + bias
                 + h * dh * d + 3 * d * f + 2 * d)              # o, ffn, norms
    return float(BF16 * (layers * per_layer + vocab * d + d))


def _token_flops(c: dict) -> float:
    """Per row and token: projections and the kept-width FFN, all layers."""
    d, h, hkv, dh, layers, _ = _dims(c)
    return float(layers * (2 * d * (h + 2 * hkv) * dh + 2 * h * dh * d
                           + 3 * 2 * d * kept_width(c)))


def kv_bytes_per_position(c: dict) -> float:
    d, h, hkv, dh, layers, _ = _dims(c)
    return float(layers * 2 * hkv * dh * BF16)


def decode_step(c: dict, rows: int, attended: int) -> tuple[float, float]:
    """(flops, bytes) of one decode step over ``rows`` live rows that
    attend to ``attended`` positions in all (new token included)."""
    d, h, hkv, dh, layers, vocab = _dims(c)
    flops = rows * (_token_flops(c) + 2 * d * vocab) \
        + layers * 4 * h * dh * attended
    nbytes = weight_bytes(c) + kv_bytes_per_position(c) * (attended + rows)
    return float(flops), float(nbytes)


def prefill(c: dict, length: int) -> tuple[float, float]:
    """(flops, bytes) of one admission: a ``length``-token prompt under
    each of the N masks, logits at its last position only."""
    d, h, hkv, dh, layers, vocab = _dims(c)
    n = c["mask_samples"]
    causal = length * (length + 1) // 2
    flops = n * (length * _token_flops(c) + layers * 4 * h * dh * causal
                 + 2 * d * vocab)
    nbytes = weight_bytes(c) + kv_bytes_per_position(c) * n * length
    return float(flops), float(nbytes)


def seconds(work: tuple[float, float], peaks: dict) -> float:
    """Least time on the chip for (flops, bytes)."""
    return max(work[0] / peaks["flops_bf16"],
               work[1] / peaks["hbm_bytes_per_s"])
