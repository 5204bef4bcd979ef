"""Public wrapper for the masked_ffn Pallas kernel.

Handles: backend select once per process on first call (Pallas-TPU →
Pallas-interpret → pure-XLA reference, via ``repro.compat.kernel_backend``,
lazy so importing never initializes jax devices), MXU-alignment
padding (exact — see kernel.py docstring), the hidden-unit tile that keeps
the kernel inside the scoped-VMEM budget, and a convenience entry point
that takes unpacked weights + masks and does the offline packing
(mask-zero skipping) itself.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from repro import compat
from repro.kernels.masked_ffn import ref as _ref
from repro.kernels.pad import VMEM_LIMIT
from repro.kernels.pad import pad_to as _pad_to

# None iff Pallas is absent (the xla tier); backend probing stays lazy so
# importing this module never initializes jax device state.
_kernel = compat.import_pallas_kernel("repro.kernels.masked_ffn.kernel")

__all__ = ["masked_ffn", "masked_ffn_all_samples", "on_tpu",
           "masked_ffn_vmem_bytes", "pick_block_k", "KERNEL_BACKEND"]


def __getattr__(name: str) -> str:
    if name == "KERNEL_BACKEND":    # public, resolved on first access
        return compat.kernel_backend_for(_kernel)
    raise AttributeError(name)


def on_tpu() -> bool:
    return compat.on_tpu()


def masked_ffn_vmem_bytes(d: int, k: int, d2: int, block_b: int,
                          block_k: int, *, x_bytes: int = 4,
                          w_bytes: int = 4) -> int:
    """Modeled scoped-VMEM footprint of one kernel launch at padded widths:
    double-buffered x / w1 / w2 / output tiles and bias (+ int8 scale)
    rows, the f32 accumulator and hidden scratch, the weight, x and output
    tiles once more as f32 values (dequantized, or re-laid when K is
    tiled), and 1 MiB of compiler slack. On a described v5e the compiler
    never needed more than this (tests/test_tpu_compile.py compiles at the
    chosen tile)."""
    io = (block_b * d * x_bytes + d * block_k * w_bytes
          + block_k * d2 * w_bytes + block_b * d2 * x_bytes
          + (block_k + d2) * (4 + (2 if w_bytes == 1 else 0)))
    scratch = block_b * (d2 + block_k) * 4
    values = (d * block_k + block_k * d2 + block_b * (d + d2)) * 4
    return 2 * io + scratch + values + 2 ** 20


def pick_block_k(d: int, k: int, d2: int, block_b: int, *, x_bytes: int = 4,
                 w_bytes: int = 4, limit: int = VMEM_LIMIT) -> int:
    """Widest 128-multiple hidden tile dividing K whose modeled footprint
    fits ``limit`` — K itself when one sample's weights fit, which keeps
    the batch-level scheme's one weight load per sample. Raises ValueError
    when not even a 128-unit tile fits (the widths are then too large for
    this kernel's whole-D / whole-D2 tiles)."""
    for bk in range(k, 0, -128):
        if k % bk == 0 and masked_ffn_vmem_bytes(
                d, k, d2, block_b, bk, x_bytes=x_bytes,
                w_bytes=w_bytes) <= limit:
            return bk
    need = masked_ffn_vmem_bytes(d, k, d2, block_b, 128, x_bytes=x_bytes,
                                 w_bytes=w_bytes)
    raise ValueError(
        f"masked_ffn: D={d}, D2={d2} need {need} bytes of scoped VMEM even "
        f"at a 128-unit hidden tile (> {limit})")


@functools.partial(jax.jit, static_argnames=("block_b", "sample_major",
                                             "interpret"))
def masked_ffn(x: jax.Array, w1p: jax.Array, b1p: jax.Array,
               w2p: jax.Array, b2: jax.Array,
               w1s: jax.Array | None = None,
               w2s: jax.Array | None = None, *,
               block_b: int = 128, sample_major: bool = True,
               interpret: bool | None = None) -> jax.Array:
    """Packed N-sample masked FFN, MXU-aligned and batch-tiled.

    x [B, D], w1p [N, D, K], b1p [N, K], w2p [N, K, D2], b2 [D2] -> [N, B, D2].
    w1s/w2s (optional, [N, 1, K] / [N, 1, D2] bf16): per-output-channel
    dequant scales of int8 w1p/w2p — the quantized serving form; dequant
    happens in VMEM next to the matmul (or in the oracle on the xla tier).
    Zero-padding D/K/D2 to 128 and B to block_b is exact (relu(0)=0 and the
    padded w2p rows are zero; padded scale columns pair with zero weight
    columns). The hidden units are tiled per :func:`pick_block_k`.
    interpret=None -> auto (True off-TPU).
    """
    if (w1s is None) != (w2s is None):
        raise ValueError("w1s and w2s must be passed together")
    if compat.kernel_backend_for(_kernel) == "xla":
        return _ref.masked_ffn_ref(x, w1p, b1p, w2p, b2, w1s, w2s)
    if interpret is None:
        interpret = compat.pallas_interpret_default()
    b, d2 = x.shape[0], w2p.shape[-1]
    block_b = min(block_b, max(8, 1 << (b - 1).bit_length()))
    xp = _pad_to(_pad_to(x, 1, 128), 0, block_b)
    w1p_ = _pad_to(_pad_to(w1p, 1, 128), 2, 128)
    b1p_ = _pad_to(b1p, 1, 128)
    w2p_ = _pad_to(_pad_to(w2p, 1, 128), 2, 128)
    b2_ = _pad_to(b2, 0, 128)
    scales = {}
    if w1s is not None:
        scales["w1s"] = _pad_to(w1s, 2, 128)
        scales["w2s"] = _pad_to(w2s, 2, 128)
    block_k = pick_block_k(xp.shape[1], w1p_.shape[2], w2p_.shape[2],
                           block_b, x_bytes=xp.dtype.itemsize,
                           w_bytes=w1p_.dtype.itemsize)
    y = _kernel.masked_ffn_pallas(xp, w1p_, b1p_, w2p_, b2_, **scales,
                                  block_b=block_b, block_k=block_k,
                                  sample_major=sample_major,
                                  vmem_limit=VMEM_LIMIT,
                                  interpret=interpret)
    return y[:, :b, :d2]


def masked_ffn_all_samples(x: jax.Array, w1: jax.Array, b1: jax.Array,
                           w2: jax.Array, b2: jax.Array,
                           masks: np.ndarray | jax.Array, **kw) -> jax.Array:
    """Unpacked entry: compiles a one-pair PackedPlan (mask-zero skipping,
    core/plan.py) and executes it through this kernel's dispatch stack.
    Matches ref.unpacked_masked_ffn_ref numerics exactly."""
    from repro.core import plan as plan_lib  # lazy: plan dispatches back here
    plan = plan_lib.compile_masked_ffn(w1, b1, w2, b2, masks)
    return plan_lib.execute(plan, x, **kw)


# Re-export the oracle so callers can A/B without importing ref directly.
masked_ffn_ref = _ref.masked_ffn_ref
