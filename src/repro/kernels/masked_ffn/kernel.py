"""Pallas TPU kernel: packed N-sample masked FFN (the paper's §V hot-spot).

Computes, for every mask-sample n and batch tile b:

    h = relu(x[b] @ w1p[n] + b1p[n])      # hidden stays in VMEM (the paper's
    y[n, b] = h @ w2p[n] + b2             # "intermediate layer cache")

Hardware mapping of the paper's two optimizations:

* **Mask-zero skipping** happens *before* this kernel: w1p/w2p are the packed
  dense per-sample weights (core/packing.py) — the kernel never sees a mask,
  exactly like the FPGA PEs never see dropped weights.

* **Batch-level scheme** is the grid order: ``grid = (N, B/bB)`` with the
  sample index outermost and weight BlockSpecs that depend only on ``n``.
  Pallas fetches a block from HBM only when its index changes between
  consecutive grid steps, so each sample's weights cross HBM->VMEM **once**
  while the whole batch streams through — N weight loads per batch instead of
  N x (B/bB) (paper Fig. 5). The sampling-level order would be
  ``grid=(B/bB, N)``; ops.py exposes it for the traffic A/B benchmark.

VMEM tiling: the hidden activation [bB, K] lives in a VMEM scratch tile and
never round-trips to HBM — the FPGA's "intermediate layer cache" (§V-B).
When one sample's weights do not fit the scoped-VMEM budget, ops.py picks a
hidden tile bK < K: a third, innermost grid axis walks the hidden units and
an f32 accumulator sums their contributions (exact up to summation order;
weights are then re-fetched per batch tile). All matmul operands are
zero-padded to MXU-aligned shapes by ops.py; padding is exact because
relu(0)=0 and padded rows of w2p are zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["masked_ffn_pallas"]


def _ffn_step(x, w1, b1, w2, o_ref, b2_ref, acc_ref, h_ref):
    """Accumulate one hidden-unit tile's contribution; write the output on
    the last tile. relu acts per hidden unit, so splitting K is exact."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h_ref[...] = jnp.maximum(
        jnp.dot(x, w1, preferred_element_type=jnp.float32)
        + b1.astype(jnp.float32), 0.0)
    acc_ref[...] += jnp.dot(h_ref[...].astype(x.dtype), w2,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] + b2_ref[...].astype(jnp.float32)
                    ).astype(o_ref.dtype)


def _ffn_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, acc_ref,
                h_ref):
    """One (sample, batch-tile, hidden-tile) grid step.

    x_ref   [bB, D]    — batch tile
    w1_ref  [1, D, bK] — sample n's packed first-layer weights, hidden tile
    b1_ref  [1, 1, bK]
    w2_ref  [1, bK, D2]
    b2_ref  [1, D2]
    o_ref   [1, bB, D2] — written once, on the last hidden tile
    acc_ref [bB, D2]   — VMEM scratch: f32 output accumulator
    h_ref   [bB, bK]   — VMEM scratch: the intermediate layer cache
    """
    _ffn_step(x_ref[...], w1_ref[0], b1_ref[0], w2_ref[0], o_ref, b2_ref,
              acc_ref, h_ref)


def _ffn_kernel_q(x_ref, w1_ref, s1_ref, b1_ref, w2_ref, s2_ref, b2_ref,
                  o_ref, acc_ref, h_ref):
    """Quantized-weight variant: w1/w2 cross HBM→VMEM as int8 and are
    dequantized here, next to the matmul, by the per-output-channel bf16
    scales s1 [1, 1, bK] / s2 [1, 1, D2] (lane-padded like the weights;
    padded columns are zero, matching the zero weight columns)."""
    w1 = w1_ref[0].astype(jnp.float32) * s1_ref[0].astype(jnp.float32)
    w2 = w2_ref[0].astype(jnp.float32) * s2_ref[0].astype(jnp.float32)
    _ffn_step(x_ref[...].astype(jnp.float32), w1, b1_ref[0], w2, o_ref,
              b2_ref, acc_ref, h_ref)


@functools.partial(jax.jit, static_argnames=("block_b", "block_k",
                                             "sample_major", "vmem_limit",
                                             "interpret"))
def masked_ffn_pallas(x: jax.Array, w1p: jax.Array, b1p: jax.Array,
                      w2p: jax.Array, b2: jax.Array,
                      w1s: jax.Array | None = None,
                      w2s: jax.Array | None = None, *,
                      block_b: int = 128, block_k: int | None = None,
                      sample_major: bool = True,
                      vmem_limit: int | None = None,
                      interpret: bool = False) -> jax.Array:
    """x [B, D], w1p [N, D, K], b1p [N, K], w2p [N, K, D2], b2 [D2]
    -> y [N, B, D2].

    sample_major=True  -> batch-level scheme (paper's optimization).
    sample_major=False -> sampling-level baseline (weights re-fetched per
                          batch tile); numerics identical.
    w1s/w2s (both or neither, [N, 1, K] / [N, 1, D2] bf16): lane-padded
    per-output-channel dequant scales of int8 w1p/w2p — dispatches the
    quantized kernel variant.
    block_k (None -> K) tiles the hidden units; ``vmem_limit`` is the
    scoped-VMEM budget handed to the compiler (None -> its default).
    Shapes must already be MXU-aligned (ops.py pads).
    """
    n, d, k = w1p.shape
    b = x.shape[0]
    d2 = w2p.shape[-1]
    bk = k if block_k is None else block_k
    if (w1s is None) != (w2s is None):
        raise ValueError("w1s and w2s must be passed together")
    if b % block_b:
        raise ValueError(f"batch {b} not divisible by block_b {block_b}")
    if k % bk:
        raise ValueError(f"hidden {k} not divisible by block_k {bk}")
    nb, nk = b // block_b, k // bk

    # grid (sample, batch tile, hidden tile) or (batch tile, sample, ...);
    # the hidden tile is innermost so each output block is finished before
    # its index moves on.
    if sample_major:
        grid = (n, nb, nk)

        def sb(i, j):
            return i, j
    else:
        grid = (nb, n, nk)

        def sb(i, j):
            return j, i

    # Biases ride as [N, 1, K] / [1, D2]: Mosaic wants the last two block
    # dims (8, 128)-divisible or equal to the array's, so a per-sample row
    # of a 2-D [N, K] array is not a legal block.
    b1p = b1p.reshape(n, 1, k)
    b2 = b2.reshape(1, d2)
    x_spec = pl.BlockSpec((block_b, d), lambda i, j, h: (sb(i, j)[1], 0))
    w1_spec = pl.BlockSpec((1, d, bk), lambda i, j, h: (sb(i, j)[0], 0, h))
    b1_spec = pl.BlockSpec((1, 1, bk), lambda i, j, h: (sb(i, j)[0], 0, h))
    w2_spec = pl.BlockSpec((1, bk, d2), lambda i, j, h: (sb(i, j)[0], h, 0))
    b2_spec = pl.BlockSpec((1, d2), lambda i, j, h: (0, 0))
    if w1s is None:
        kernel = _ffn_kernel
        in_specs = [x_spec, w1_spec, b1_spec, w2_spec, b2_spec]
        args = (x, w1p, b1p, w2p, b2)
    else:
        kernel = _ffn_kernel_q
        s1_spec = pl.BlockSpec((1, 1, bk),
                               lambda i, j, h: (sb(i, j)[0], 0, h))
        s2_spec = pl.BlockSpec((1, 1, d2),
                               lambda i, j, h: (sb(i, j)[0], 0, 0))
        in_specs = [x_spec, w1_spec, s1_spec, b1_spec, w2_spec, s2_spec,
                    b2_spec]
        args = (x, w1p, w1s, b1p, w2p, w2s, b2)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_b, d2),
                               lambda i, j, h: (*sb(i, j), 0)),
        out_shape=jax.ShapeDtypeStruct((n, b, d2), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, d2), jnp.float32),
                        pltpu.VMEM((block_b, bk), jnp.float32)],
        interpret=interpret,
        compiler_params=(None if vmem_limit is None else
                         pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)),
    )(*args)
