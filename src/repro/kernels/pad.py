"""Shared zero-padding helper and scoped-VMEM budget of the kernel ops
wrappers.

Every Pallas wrapper pads operands to the 128 lane / batch-tile multiple
before the ``pallas_call`` and slices the result back; the padding is exact
for the mask pipeline because padded weight rows are zero (see the kernel
docstrings). One implementation so the kernel stacks cannot silently
diverge on padding behavior.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["pad_to", "VMEM_LIMIT"]

#: Scoped-VMEM budget the Pallas wrappers hand to Mosaic as
#: ``vmem_limit_bytes`` (the v5e default scoped limit is 16 MiB of the
#: chip's 128 MiB). Each wrapper sizes its tiles, or refuses, from its own
#: modeled footprint against this budget.
VMEM_LIMIT = 100 * 2 ** 20


def pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple of ``mult`` (no-op when
    already aligned)."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
