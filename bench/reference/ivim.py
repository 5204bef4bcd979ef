"""Plain uIVIM-NET (arXiv:2407.05521 section IV): the unpacked network
evaluated under every mask, in straightforward ``jax.numpy``.

Each of the four sub-networks (one per IVIM parameter) is
``linear -> batchnorm -> relu -> mask1 -> linear -> batchnorm -> relu ->
mask2 -> linear -> sigmoid -> C(.)``, with batchnorm at its running
statistics and C(.) mapping the sigmoid onto the parameter's range. Every
voxel runs under each of the N masks; the answer is the mean and the
population std over the N samples. Nothing is folded or packed here, so
batchnorm folding, mask-zero packing, chunk padding and the fused moments
kernel of the program are all checked against it.

``weights``: fc1/fc2/enc ``{"w", "b"}`` stacked over the 4 sub-networks,
bn1/bn2 ``{"gamma", "beta", "mean", "var"}``, and ``mask1``/``mask2``
[N, width]. ``dtype`` float32 runs at "highest" matmul precision;
``bfloat16`` is the control, with every weight, activation and output in
bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _forward(weights, x, ranges, eps, dtype):
    c = lambda a: jnp.asarray(a).astype(dtype)  # noqa: E731
    x = c(x)

    def bn(h, p):
        shape = (h.shape[0],) + (1,) * (h.ndim - 2) + (h.shape[-1],)
        r = lambda k: c(p[k]).reshape(shape)  # noqa: E731
        return (h - r("mean")) * (r("gamma") / jnp.sqrt(r("var") + eps)) \
            + r("beta")

    # layer 1 per sub-network [G, B, W]; the masks open an N axis
    h = jnp.einsum("bi,gij->gbj", x, c(weights["fc1"]["w"])) \
        + c(weights["fc1"]["b"])[:, None]
    h = jax.nn.relu(bn(h, weights["bn1"]))
    h = h[:, None] * c(weights["mask1"])[None, :, None]      # [G, N, B, W]
    h = jnp.einsum("gnbi,gij->gnbj", h, c(weights["fc2"]["w"])) \
        + c(weights["fc2"]["b"])[:, None, None]
    h = jax.nn.relu(bn(h, weights["bn2"])) * c(weights["mask2"])[None, :, None]
    z = jnp.einsum("gnbi,gi->gnb", h, c(weights["enc"]["w"])[..., 0]) \
        + c(weights["enc"]["b"])[:, :1, None]
    lo, hi = c(ranges[:, 0]), c(ranges[:, 1])
    p = lo[:, None, None] + jax.nn.sigmoid(z) * (hi - lo)[:, None, None]
    mean = jnp.mean(p, axis=1)
    std = jnp.sqrt(jnp.mean(jnp.square(p - mean[:, None]), axis=1))
    return mean.T.astype(jnp.float32), std.T.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted(eps: float, dtype: str):
    dt = jnp.dtype(dtype)

    def fn(weights, x, ranges):
        if dt == jnp.float32:
            with jax.default_matmul_precision("highest"):
                return _forward(weights, x, ranges, eps, dt)
        return _forward(weights, x, ranges, eps, dt)

    return jax.jit(fn)


def moments(weights, x, ranges, *, eps: float = 1e-5, dtype="float32",
            block: int = 65536):
    """x [B, n_b] -> (mean [B, 4], std [B, 4]) as host float32 arrays,
    computed ``block`` voxels at a time."""
    fn = _jitted(float(eps), str(jnp.dtype(dtype)))
    r = jnp.asarray(np.asarray(ranges, np.float32))
    x = np.asarray(x, np.float32)
    means, stds = [], []
    for lo in range(0, x.shape[0], block):
        xb = x[lo:lo + block]
        n = xb.shape[0]
        if n < block and x.shape[0] > block:
            xb = np.concatenate([xb, np.zeros((block - n, xb.shape[1]),
                                              xb.dtype)])
        m, s = fn(weights, jnp.asarray(xb), r)
        means.append(np.asarray(m)[:n])
        stds.append(np.asarray(s)[:n])
    return np.concatenate(means), np.concatenate(stds)
