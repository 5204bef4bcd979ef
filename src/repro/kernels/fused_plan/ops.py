"""Public wrapper for the fused whole-plan megakernel.

Backend select once per process on first call (Pallas-TPU → Pallas-interpret
→ pure-XLA reference via ``repro.compat.kernel_backend``, lazy so importing
never initializes jax devices), lane/batch padding (exact — padded weight
rows are zero, see kernel.py), output unpadding, and the VMEM-residency
guard for the weights-resident moments mode.
"""

from __future__ import annotations

import functools
import math

import jax

from repro import compat
from repro.kernels.fused_plan import ref as _ref
from repro.kernels.fused_plan.ref import (FusedDecodeSpec,
                                          FusedPlanUnsupported, FusedSpec,
                                          param_slots)
from repro.kernels.pad import VMEM_LIMIT
from repro.kernels.pad import pad_to as _pad_to

# None iff Pallas is absent (the xla tier); backend probing stays lazy so
# importing this module never initializes jax device state.
_kernel = compat.import_pallas_kernel("repro.kernels.fused_plan.kernel")

__all__ = ["fused_plan", "fused_vmem_bytes", "check_vmem",
           "FusedPlanUnsupported",
           "VMEM_MOMENTS_LIMIT", "KERNEL_BACKEND",
           "fused_decode", "fused_decode_vmem_bytes"]

#: Scoped-VMEM budget of the fused kernels (``kernels.pad.VMEM_LIMIT``). It
#: is passed to Mosaic as ``vmem_limit_bytes``, and a kernel whose modeled
#: footprint exceeds it raises
#: FusedPlanUnsupported before lowering, so the per-op fallbacks in
#: serving/engine and serving/server catch it. The moments mode needs all
#: packed weights + scratch resident at once (the paper's on-chip-weights
#: regime); the v5e compiler's scoped allocation tracks
#: :func:`fused_vmem_bytes` to within 2% (tests/test_tpu_compile.py).
VMEM_MOMENTS_LIMIT = VMEM_LIMIT


def __getattr__(name: str) -> str:
    if name == "KERNEL_BACKEND":    # public, resolved on first access
        return compat.kernel_backend_for(_kernel)
    raise AttributeError(name)


def _pad_params(spec: FusedSpec, params: tuple[jax.Array, ...]
                ) -> tuple[jax.Array, ...]:
    out = []
    for (i, slot), arr in zip(param_slots(spec), params):
        st = spec.steps[i]
        per = st.per_sample if slot in ("w", "ws") else (slot == "bp")
        if per and arr.shape[0] != spec.n_rows:
            raise ValueError(f"step {i} {slot}: leading dim {arr.shape[0]} "
                             f"!= n_rows {spec.n_rows}")
        # 'ws' scales [.., 1, d_out] lane-pad with their weight's d_out axis
        # only (the broadcast axis stays 1); zero scales on padded columns
        # are exact — the padded w columns are zero too.
        a = _pad_to(arr, arr.ndim - 1, 128)
        if slot == "w":
            a = _pad_to(a, arr.ndim - 2, 128)
        out.append(a)
    return tuple(out)


def fused_vmem_bytes(spec: FusedSpec, block_b: int = 128,
                     bytes_per_el: int = 4, *, moments: bool = True) -> int:
    """Modeled resident VMEM footprint of the fused kernel: padded weights
    + 3 scratch tiles + the double-buffered batch tile and output tiles.
    Moments mode holds every row's weights once (constant index maps);
    samples mode holds one row's per-sample blocks, double-buffered
    because their index changes along the grid."""
    def pad(d: int) -> int:
        return -(-d // 128) * 128

    w_bytes = 0
    widths = [spec.d_in]
    for st in spec.steps:
        if st.kind != "dense":
            continue
        rows = (spec.n_rows if moments else 2) if st.per_sample else 1
        wb = 1 if st.w_dtype == "int8" else bytes_per_el
        w_bytes += rows * pad(st.d_in) * pad(st.d_out) * wb
        if st.w_dtype:                  # bf16 per-channel scales, lane-padded
            w_bytes += rows * pad(st.d_out) * 2
        if st.shared_bias:
            w_bytes += pad(st.d_out) * bytes_per_el
        if st.sample_bias:
            w_bytes += (spec.n_rows if moments else 2) * pad(st.d_out) \
                * bytes_per_el
        widths.append(st.d_out)
    wmax = max(pad(d) for d in widths)
    scratch_el = 3 * block_b * wmax
    outs = 2 * spec.groups if moments else 1          # (mean, std) | samples
    io_el = 2 * block_b * (pad(widths[0]) + outs * pad(widths[-1]))
    return w_bytes + (scratch_el + io_el) * bytes_per_el


def check_vmem(spec: FusedSpec, block_b: int, *, moments: bool) -> int:
    """Modeled footprint of the compiled kernel, or FusedPlanUnsupported
    when it exceeds the scoped-VMEM budget it would be compiled against."""
    need = fused_vmem_bytes(spec, block_b, moments=moments)
    if need > VMEM_MOMENTS_LIMIT:
        raise FusedPlanUnsupported(
            f"{'moments' if moments else 'samples'}-mode fused plan needs "
            f"{need} resident bytes (> {VMEM_MOMENTS_LIMIT}); use the "
            f"per-op executor")
    return need


@functools.partial(jax.jit,
                   static_argnames=("spec", "moments", "block_b", "interpret"))
def fused_plan(spec: FusedSpec, x: jax.Array, params: tuple[jax.Array, ...],
               *, moments: bool = False, block_b: int = 128,
               interpret: bool | None = None):
    """Execute a lowered PackedPlan chain in one kernel launch.

    x [B, d_in], params per ``ref.param_slots`` order (unpadded) ->
    samples [n_rows, B, d_out], or (mean, std) [B, groups·d_out] with
    ``moments=True``. interpret=None -> auto (True off-TPU).
    """
    if compat.kernel_backend_for(_kernel) == "xla":
        fn = _ref.fused_moments_ref if moments else _ref.fused_plan_ref
        return fn(spec, x, tuple(params))
    if interpret is None:
        interpret = compat.pallas_interpret_default()
    b = x.shape[0]
    block_b = min(block_b, max(8, 1 << (b - 1).bit_length()))
    check_vmem(spec, block_b, moments=moments)
    xp = _pad_to(_pad_to(x, 1, 128), 0, block_b)
    pp = _pad_params(spec, tuple(params))
    out = _kernel.fused_plan_pallas(xp, pp, spec=spec, block_b=block_b,
                                    moments=moments, interpret=interpret,
                                    vmem_limit=VMEM_MOMENTS_LIMIT)
    do = spec.d_out
    if not moments:
        return out[:, :b, :do]
    mean, std = out
    g = spec.groups
    dlp = mean.shape[1] // g
    mean = mean[:b].reshape(b, g, dlp)[:, :, :do].reshape(b, g * do)
    std = std[:b].reshape(b, g, dlp)[:, :, :do].reshape(b, g * do)
    return mean, std


# ---------------------------------------------------------------------------
# fused serving-decode step
# ---------------------------------------------------------------------------


def fused_decode_vmem_bytes(spec: FusedDecodeSpec,
                            arrays: tuple[jax.Array, ...],
                            bytes_per_el: int = 4) -> int:
    """Modeled resident footprint of the single-program decode kernel: every
    input/output array plus a 3-tile working-state slack (residual, normed
    hidden, widest sub-layer intermediate) — all f32 in-kernel."""
    rows = arrays[0].shape[0]
    wmax = max((st.d_hidden for st in spec.steps if st.kind == "ffn"),
               default=spec.d_model)
    wmax = max(wmax, spec.vocab, spec.d_model)
    slack = 3 * rows * wmax
    total = sum(math.prod(a.shape) for a in arrays) + slack
    return total * bytes_per_el


def fused_decode(spec: FusedDecodeSpec, x: jax.Array,
                 params: tuple[jax.Array, ...],
                 caches: tuple[jax.Array, ...], pos: jax.Array,
                 cos: jax.Array, sin: jax.Array, *,
                 interpret: bool | None = None):
    """Execute one lowered serving decode step in one kernel launch.

    x [R, d_model] (embedded pool tokens), params per
    ``ref.decode_param_slots``, caches flattened ``(k, v, kpos)`` per 'attn'
    step, pos [R], cos/sin [R, rot/2] ->
    ``(mean_logp [b, V], rel_unc [b], k_new, v_new)``. interpret=None ->
    auto (True off-TPU). Raises :class:`FusedPlanUnsupported` when the
    resident footprint exceeds the VMEM guard, and always on the compiled
    pallas-tpu tier: Mosaic cannot lower the kernel's per-row KV gather
    (``ref.decode_attn_ref``; tests/test_tpu_compile.py pins the refusal),
    so on the chip callers fall back to the per-op decode path.
    """
    if compat.kernel_backend_for(_kernel) == "xla":
        return _ref.fused_decode_ref(spec, x, params, caches, pos, cos, sin)
    if interpret is None:
        interpret = compat.pallas_interpret_default()
    arrays = (x,) + tuple(params) + tuple(caches)
    need = fused_decode_vmem_bytes(spec, arrays)
    if need > VMEM_MOMENTS_LIMIT:
        raise FusedPlanUnsupported(
            f"fused decode step needs {need} resident bytes "
            f"(> {VMEM_MOMENTS_LIMIT}); use the per-op decode path")
    if not interpret:
        raise FusedPlanUnsupported(
            "the fused decode kernel has no Mosaic lowering (per-row KV "
            "gather); use the per-op decode path")
    return _kernel.fused_decode_pallas(x, tuple(params), tuple(caches), pos,
                                       cos, sin, spec=spec,
                                       interpret=interpret)


# Re-export the oracle pair so callers can A/B without importing ref directly.
fused_plan_ref = _ref.fused_plan_ref
fused_moments_ref = _ref.fused_moments_ref
fused_decode_ref = _ref.fused_decode_ref
