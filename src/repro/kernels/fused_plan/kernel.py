"""Pallas TPU megakernel: an entire PackedPlan op chain in one pallas_call.

The per-op executor (``core/plan.execute``) launches one masked_ffn kernel
per PackedPair and runs SharedDense/OutputHead as separate XLA ops, so every
inter-layer activation ``[N·G, B, K]`` round-trips HBM. This kernel streams
the *whole* compiled chain instead — the TPU realization of the paper's FPGA
pipeline, which keeps each mask-sample's packed weights on-chip and pushes
the full network through them (§V-B "intermediate layer cache" + §V-D
operation reordering). Two modes:

* **samples mode** — ``grid = (n_rows, B/bB)`` with the sample row outermost
  (the batch-level scheme of kernels/masked_ffn, extended from one pair to
  the whole chain): every per-sample weight BlockSpec depends only on the
  row index, so each row's packed weights for *all* layers cross HBM→VMEM
  once while the entire batch streams through. Inter-layer activations live
  in two ping-pong VMEM scratch tiles ``[bB, Wmax]`` and never touch HBM.
  Output: ``[n_rows, B, d_out]``.

* **moments mode** — ``grid = (B/bB,)`` with *all* packed weights passed as
  whole-array blocks (constant index maps: one HBM→VMEM crossing per weight
  set for the entire batch — the FPGA's weights-resident regime, which is
  what makes an in-kernel sample reduction legal: no output block is ever
  revisited across grid steps). The sample loop is unrolled inside the
  kernel; a running Welford (mean, M2) epilogue — the ``kernels/moments``
  scheme, streamed — reduces over the ``n_masks`` rows of each group, so
  the ``[n_rows, B, d_out]`` sample tensor is never materialized anywhere,
  VMEM included. Steps before the first per-sample op are hoisted out of
  the sample loop (computed once per batch tile). Output:
  ``(mean, std) [B, groups·d_out]``, group-major columns.

Padding contract (ops.py): every width is zero-padded to the 128 lane; this
is exact because padded *rows* of the next weight are zero, so whatever a
non-zero-preserving activation (sigmoid) writes into padded columns is
annihilated by the following matmul, and final padded columns/rows are
sliced off by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_plan import ref as _spec_lib

__all__ = ["fused_plan_pallas", "fused_decode_pallas"]


def _dense(h, w, ws, b, bp, activation):
    """One fused dense step on f32 hidden state (operands in weight dtype).

    ``ws`` (present iff the step's weight is quantized) holds the
    per-output-channel bf16 dequant scales [1, d_out_pad]; the dequant
    happens here — in VMEM, right next to the matmul — so the int8 tensor
    is what crossed HBM. Biases arrive as [1, d_out_pad] rows."""
    if ws is not None:
        w = w.astype(jnp.float32) * ws.astype(jnp.float32)
    y = jnp.dot(h.astype(w.dtype), w, preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    if bp is not None:
        y = y + bp.astype(jnp.float32)
    if activation:
        y = _spec_lib.act_fn(activation)(y)
    return y


def _run_chain(steps, read, h, sbufs):
    """Run (index, step) pairs over ping-pong VMEM scratch.

    ``read(i, slot)`` yields the step's weight/bias block for the current
    sample row. After every dense step the activation is stored to a scratch
    tile and read back, so the inter-layer state provably lives in VMEM and
    the footprint is bounded by 2×[bB, Wmax] regardless of chain depth.
    """
    buf = 0
    for i, st in steps:
        if st.kind == "act":
            h = _spec_lib.act_fn(st.activation)(h)
            continue
        y = _dense(h, read(i, "w"),
                   read(i, "ws") if st.w_dtype else None,
                   read(i, "b") if st.shared_bias else None,
                   read(i, "bp") if st.sample_bias else None,
                   st.activation)
        sbufs[buf][:, : y.shape[1]] = y
        h = sbufs[buf][:, : y.shape[1]]
        buf ^= 1
    return h


def _split_prefix(spec):
    """(shared prefix, per-sample body) as (index, step) lists."""
    steps = list(enumerate(spec.steps))
    for cut, (_, st) in enumerate(steps):
        if st.per_sample or st.sample_bias:
            return steps[:cut], steps[cut:]
    return steps, []


@functools.partial(jax.jit,
                   static_argnames=("spec", "block_b", "moments", "interpret",
                                    "vmem_limit"))
def fused_plan_pallas(x: jax.Array, params: tuple[jax.Array, ...], *,
                      spec: _spec_lib.FusedSpec, vmem_limit: int,
                      block_b: int = 128, moments: bool = False,
                      interpret: bool = False):
    """x [B, d_in_pad], params padded per the ops.py contract.

    moments=False -> samples [n_rows, B, d_out_pad]
    moments=True  -> (mean, std) [B, groups * d_out_pad]
    B must be divisible by block_b; widths must be lane-aligned (ops pads).
    ``vmem_limit`` is the scoped-VMEM budget handed to the compiler.
    """
    compiler_params = pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
    b, d0 = x.shape
    if b % block_b:
        raise ValueError(f"batch {b} not divisible by block_b {block_b}")
    nb = b // block_b
    slots = _spec_lib.param_slots(spec)
    # Biases ride as [1, d] rows and per-sample biases as [n_rows, 1, d]:
    # Mosaic wants the last two block dims (8, 128)-divisible or equal to
    # the array's, so one sample's row of a 2-D [n_rows, d] is not a legal
    # block (the 'ws' scales already carry that unit axis).
    params = tuple(
        a.reshape(a.shape[0], 1, a.shape[1]) if slot == "bp"
        else a.reshape(1, -1) if slot == "b" else a
        for (_, slot), a in zip(slots, params))
    table = dict(zip(slots, params))
    n_rows, groups, n_masks = spec.n_rows, spec.groups, spec.n_masks

    # padded widths along the chain (spec widths are unpadded; the arrays
    # are authoritative): final dense output + the scratch width cap
    widths = [d0]
    for (i, slot) in slots:
        if slot == "w":
            widths.append(table[(i, "w")].shape[-1])
    wmax = max(widths)
    d_last = widths[-1]

    scratch = [pltpu.VMEM((block_b, wmax), jnp.float32),
               pltpu.VMEM((block_b, wmax), jnp.float32)]

    if not moments:
        # ------- samples mode: grid (n_rows, B/bB), sample-major ----------
        in_specs = [pl.BlockSpec((block_b, d0), lambda n, j: (j, 0))]
        for (i, slot) in slots:
            arr = table[(i, slot)]
            st = spec.steps[i]
            per = st.per_sample if slot in ("w", "ws") else (slot == "bp")
            if per:
                blk = (1,) + arr.shape[1:]
                in_specs.append(pl.BlockSpec(
                    blk, lambda n, j, nd=arr.ndim: (n,) + (0,) * (nd - 1)))
            else:
                in_specs.append(pl.BlockSpec(
                    arr.shape, lambda n, j, nd=arr.ndim: (0,) * nd))

        def kernel(x_ref, *refs):
            p_refs = dict(zip(slots, refs[: len(slots)]))
            o_ref = refs[len(slots)]
            sbufs = refs[len(slots) + 1:]

            def read(i, slot):
                st = spec.steps[i]
                r = p_refs[(i, slot)]
                per = st.per_sample if slot in ("w", "ws") else (slot == "bp")
                return r[0] if per else r[...]

            h = _run_chain(list(enumerate(spec.steps)), read,
                           x_ref[...].astype(jnp.float32), sbufs)
            o_ref[0] = h.astype(o_ref.dtype)

        return pl.pallas_call(
            kernel,
            grid=(n_rows, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_b, d_last),
                                   lambda n, j: (n, j, 0)),
            out_shape=jax.ShapeDtypeStruct((n_rows, b, d_last), x.dtype),
            scratch_shapes=scratch,
            interpret=interpret,
            compiler_params=compiler_params,
        )(x, *params)

    # ------- moments mode: grid (B/bB,), weights resident ----------------
    in_specs = [pl.BlockSpec((block_b, d0), lambda i: (i, 0))]
    for (i, slot) in slots:
        arr = table[(i, slot)]
        in_specs.append(pl.BlockSpec(
            arr.shape, lambda i, nd=arr.ndim: (0,) * nd))
    prefix, body = _split_prefix(spec)

    def kernel(x_ref, *refs):
        p_refs = dict(zip(slots, refs[: len(slots)]))
        mean_ref, std_ref = refs[len(slots)], refs[len(slots) + 1]
        sbufs = refs[len(slots) + 2: len(slots) + 4]
        pfx_ref = refs[len(slots) + 4]

        def read_shared(i, slot):
            return p_refs[(i, slot)][...]

        # shared prefix: once per batch tile, parked in its own scratch
        h0 = _run_chain(prefix, read_shared, x_ref[...].astype(jnp.float32),
                        sbufs)
        w0 = h0.shape[1]
        pfx_ref[:, :w0] = h0

        for g in range(groups):
            mean = m2 = None
            for k in range(n_masks):
                r = g * n_masks + k

                def read(i, slot, r=r):
                    st = spec.steps[i]
                    ref = p_refs[(i, slot)]
                    per = st.per_sample if slot in ("w", "ws") else (slot == "bp")
                    return ref[r] if per else ref[...]

                y = _run_chain(body, read, pfx_ref[:, :w0], sbufs)
                if k == 0:                          # Welford running moments
                    mean, m2 = y, jnp.zeros_like(y)
                else:
                    delta = y - mean
                    mean = mean + delta / (k + 1)
                    m2 = m2 + delta * (y - mean)
            cols = slice(g * d_last, (g + 1) * d_last)
            mean_ref[:, cols] = mean.astype(mean_ref.dtype)
            std_ref[:, cols] = jnp.sqrt(m2 / n_masks).astype(std_ref.dtype)

    out_blk = pl.BlockSpec((block_b, groups * d_last), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=(out_blk, out_blk),
        out_shape=(jax.ShapeDtypeStruct((b, groups * d_last), x.dtype),
                   jax.ShapeDtypeStruct((b, groups * d_last), x.dtype)),
        scratch_shapes=scratch + [pltpu.VMEM((block_b, wmax), jnp.float32)],
        interpret=interpret,
        compiler_params=compiler_params,
    )(x, *params)


# ---------------------------------------------------------------------------
# fused serving-decode megakernel (FusedDecodeSpec)
# ---------------------------------------------------------------------------
#
# One decode step of the whole mask-expanded slot pool in ONE pallas_call:
# the per-op serving path launches KV gather + attention, the (packed)
# Bayesian FFN and the posterior reduction as separate kernels per layer per
# token, so every inter-stage activation [R, D] and the [R, V] log-prob
# tensor round-trip HBM at exactly the batch sizes where launch overhead
# dominates. Here the pool is small by construction (R = n_masks x
# max_slots rows, one token each), so the whole working set — every
# layer's weights, every layer's KV cache rows, and the running residual —
# fits VMEM at once: the kernel is a single program (no grid) over
# whole-array VMEM blocks, the decode twin of the moments-mode
# weights-resident regime. The chain math (norms, RoPE'd KV-gather
# attention with the fresh k/v appended, gated/packed FFN, in-kernel
# Welford posterior over the mask axis) is shared with the oracle tier by
# construction: the kernel reads its refs into VMEM values and runs the
# exact `ref.py` sub-layer contract, so xla/interpret tiers cannot drift.
# Fresh per-layer k/v are emitted as outputs and committed to the cache by
# the caller (one XLA scatter per layer outside the launch) — the kernel
# itself never mutates the pool, which keeps every ref read-only and the
# launch trivially idempotent. Lane-alignment gating lives in ops.py.


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def fused_decode_pallas(x: jax.Array, params: tuple[jax.Array, ...],
                        caches: tuple[jax.Array, ...], pos: jax.Array,
                        cos: jax.Array, sin: jax.Array, *,
                        spec: _spec_lib.FusedDecodeSpec,
                        interpret: bool = False):
    """x [R, d_model], params per ``ref.decode_param_slots`` order, caches
    flattened ``(k, v, kpos)`` per 'attn' step, pos [R] i32, cos/sin
    [R, rot/2] -> (mean_logp [b, V], rel_unc [b], k_new, v_new) with
    k_new/v_new [n_attn, R, hkv, dh]."""
    r = x.shape[0]
    b = r // spec.n_samples
    if b * spec.n_samples != r:
        raise ValueError(f"rows {r} not divisible by n_samples "
                         f"{spec.n_samples}")
    slots = _spec_lib.decode_param_slots(spec)
    if len(caches) != 3 * spec.n_attn:
        raise ValueError(f"expected {3 * spec.n_attn} cache arrays, "
                         f"got {len(caches)}")
    a = spec.n_attn
    attn_step = next(s for s in spec.steps if s.kind == "attn")
    hkv, dh = attn_step.n_kv_heads, attn_step.head_dim

    def kernel(x_ref, pos_ref, cos_ref, sin_ref, *refs):
        p_refs = dict(zip(slots, refs[: len(slots)]))
        c_refs = refs[len(slots): len(slots) + 3 * a]
        mean_ref, rel_ref, knew_ref, vnew_ref = refs[len(slots) + 3 * a:]
        pos_v = pos_ref[...]
        cos_v, sin_v = cos_ref[...], sin_ref[...]
        resid = x_ref[...].astype(jnp.float32)
        h = resid
        ai = 0
        for i, st in enumerate(spec.steps):
            p = {name: p_refs[(j, name)][...]
                 for (j, name) in slots if j == i}
            if st.kind == "norm":
                h = _spec_lib.norm_fn(resid, p["scale"], p.get("bias"),
                                      st.norm)
            elif st.kind == "attn":
                cache = tuple(cr[...] for cr in c_refs[3 * ai: 3 * ai + 3])
                y, kn, vn = _spec_lib.decode_attn_ref(st, h, p, cache,
                                                      pos_v, cos_v, sin_v)
                resid = resid + y
                h = resid
                knew_ref[ai] = kn.astype(knew_ref.dtype)
                vnew_ref[ai] = vn.astype(vnew_ref.dtype)
                ai += 1
            elif st.kind == "ffn":
                resid = resid + _spec_lib.decode_ffn_ref(st, h, p)
                h = resid
            elif st.kind == "dense":
                h = h @ p["w"]
                if st.shared_bias:
                    h = h + p["b"]
                if st.activation:
                    h = _spec_lib.act_fn(st.activation)(h)
            else:                       # 'act'
                h = _spec_lib.act_fn(st.activation)(h)
        logp = jax.nn.log_softmax(h.astype(jnp.float32), -1)
        mean, rel = _spec_lib.welford_posterior(logp, spec.n_samples)
        mean_ref[...] = mean
        rel_ref[...] = rel[:, None]

    # single program, whole-array blocks (default specs): the entire pool
    # working set is VMEM-resident for the launch — no grid, no revisits
    out = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, spec.vocab), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1), jnp.float32),
                   jax.ShapeDtypeStruct((a, r, hkv, dh), x.dtype),
                   jax.ShapeDtypeStruct((a, r, hkv, dh), x.dtype)),
        interpret=interpret,
    )(x, pos, cos, sin, *params, *caches)
    mean, rel, knew, vnew = out
    return mean, rel[:, 0], knew, vnew
