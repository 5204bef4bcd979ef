"""One-shot serving engine: batched generation + mask-based Bayesian serving.

``generate`` is the plain path (prefill -> greedy decode loop).

``serve_uncertain`` is the paper's technique at LM scale: every request is
evaluated under all N fixed Masksembles masks; the per-token prediction is
the sample-mean distribution and the per-token uncertainty is the std of the
sample log-probabilities. Two schedules exist, mirroring paper Fig. 5:

  * sampling-level — expand the batch x N (each row pinned to one mask) and
    decode the expanded batch: N x the KV cache, N x the weight traffic per
    token *relative to batch* (the naive BayesNN baseline);
  * batch-level    — decode the expanded batch but with the mask-sample as
    the *outer* grid of the masked-FFN computation, weights touched once per
    sample (the paper's scheme; realized in the packed Pallas kernel and,
    in the XLA path, by the sample-major einsum in core/packing.py).

The uncertainty signal gates generation: tokens whose relative uncertainty
exceeds a threshold can be flagged for escalation (the paper's clinical
"adopt more comprehensive examinations" pathway, §VI-B).

Both entry points are thin wrappers over the jitted fixed-shape step
functions of :mod:`repro.serving.server` — the hot loop runs exactly the
graphs the continuous-batching server runs, it just drives one fixed batch
to completion instead of a request stream. Identical request batches
therefore produce identical tokens and per-token uncertainties through
either path (tests/test_serving_server.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import plan as plan_lib
from repro.core import scheduler as scheduler_lib
from repro.core import uncertainty as unc_lib
from repro.models.model import Model
from repro.obs import trace as obs_trace
from repro.serving import server as server_lib
from repro.serving.server import mesh_scope

Params = dict[str, Any]

__all__ = ["ServeConfig", "generate", "uncertainty_decode_step",
           "serve_uncertain", "plan_chunk_runner", "predict_packed",
           "predict_volume"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 16
    greedy: bool = True
    uncertainty_threshold: float = 0.5   # flag tokens above this rel-unc
    fused: bool | None = None            # decode executor (True = require
                                         # fused, False = per-op, None =
                                         # auto with per-op fallback)


def generate(model: Model, params: Params, tokens: jax.Array,
             cfg: ServeConfig = ServeConfig(), *, mesh=None) -> jax.Array:
    """Greedy generation: tokens [B, S] -> [B, S + max_new_tokens]."""
    b, s = tokens.shape
    fns = server_lib.step_fns(model, expand_masks=False, fused=cfg.fused)
    with mesh_scope(mesh):
        mean, _, cache = fns.prefill(params, tokens,
                                     max_seq=s + cfg.max_new_tokens)
        out = [jnp.argmax(mean, -1).astype(jnp.int32)]
        for i in range(cfg.max_new_tokens - 1):
            mean, _, cache = fns.decode(params, cache, out[-1][:, None],
                                        jnp.int32(s + i))
            out.append(jnp.argmax(mean, -1).astype(jnp.int32))
    return jnp.concatenate([tokens, jnp.stack(out, 1)], axis=1)


def _expand_for_masks(x: jax.Array, n: int) -> jax.Array:
    return jnp.tile(x, (n,) + (1,) * (x.ndim - 1))


def plan_chunk_runner(plan: plan_lib.PackedPlan, *,
                      backend: str | None = None,
                      fused: bool | None = None):
    """Build the per-chunk moments executor for one compiled PackedPlan:
    a callable ``xc [chunk, D] -> (mean [chunk, d_out], std)``.

    This is the ONE runner both voxel-serving paths share — the direct
    :func:`predict_packed`/:func:`predict_volume` stream and the server's
    pooled :class:`repro.serving.server.VoxelScanRequest` work items
    (``server.submit_scan``). Sharing the callable composition (same fused
    executor, same per-op fallback, same chunk padding rule upstream) is
    what makes pooled scan results bitwise-identical to the direct path.

    ``fused`` selects the executor exactly like ``predict_packed(fused=)``:
    ``True`` requires the whole-plan megakernel with the in-kernel moments
    epilogue — one device program per chunk, the range conversion C(.)
    included — and surfaces :class:`plan_lib.FusedPlanUnsupported`; ``False``
    forces the per-op path (one masked-FFN launch per PackedPair, then
    ``uncertainty.predictive_moments``); ``None`` (default) tries fused and
    falls back per-op — at build when the plan has no fused lowering, or at
    the first apply when the moments-mode VMEM-residency guard fires (trace
    time; every chunk shares one shape, so the choice is made once and is
    deterministic across chunks)."""
    def per_op(xc):
        return unc_lib.predictive_moments(
            plan_lib.execute(plan, xc, backend=backend))

    if fused is False:
        return per_op
    try:
        run = plan_lib.fused_executor(plan, moments=True, backend=backend)
    except plan_lib.FusedPlanUnsupported:
        if fused:
            raise
        server_lib._note_fallback("build", "plan")
        return per_op
    if fused:
        return run

    state: dict[str, Callable] = {}

    def runner(xc):
        fn = state.get("fn")
        if fn is not None:
            return fn(xc)
        try:
            out = run(xc)          # VMEM guard fires here, at trace time
        except plan_lib.FusedPlanUnsupported:
            server_lib._note_fallback("trace", "plan")
            state["fn"] = per_op
            return per_op(xc)
        state["fn"] = run
        return out

    return runner


def predict_packed(plan: plan_lib.PackedPlan, x: jax.Array, *,
                   chunk: int | None = None, backend: str | None = None,
                   fused: bool | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Serve a compiled PackedPlan on a voxel batch: x [B, D] ->
    (mean [B, d_out], std [B, d_out]).

    The feed-forward analogue of :func:`serve_uncertain`: the engine consumes
    the Phase-3 artifact directly and reduces the mask samples to predictive
    moments.

    ``fused`` selects the executor: ``True`` runs the whole-plan megakernel
    with the in-kernel moments epilogue (``plan.execute_fused(moments=True)``
    — one launch per chunk, the ``[N, B, d_out]`` sample tensor is never
    materialized); ``False`` runs the per-op path (one kernels/masked_ffn
    launch per PackedPair, then ``uncertainty.predictive_moments``);
    ``None`` (default) tries fused and falls back per-op when the plan has
    no fused lowering or its moments-mode footprint trips the VMEM guard.
    ``chunk`` bounds the resident batch: a volume is streamed through the
    shared :func:`plan_chunk_runner` executor in ``chunk``-row slices
    (``core.scheduler.chunk_bounds`` partition, the last slice zero-padded
    to the chunk shape, pad rows dropped), so the kernel traces once and
    each chunk is exactly one fused launch. ``backend`` forwards to the
    executor (None -> the process-wide probe).
    """
    b = x.shape[0]
    if chunk is None or chunk >= b:
        if fused is not False:
            try:
                run = plan_lib.fused_executor(plan, moments=True,
                                              backend=backend)
                return run(x)
            except plan_lib.FusedPlanUnsupported:
                if fused:
                    raise
        return unc_lib.predictive_moments(
            plan_lib.execute(plan, x, backend=backend))

    # Streamed: the SAME runner + chunk partition + padding rule the pooled
    # VoxelScanRequest path runs (server._advance_scan) — chunk for chunk,
    # so the two paths agree bitwise.
    runner = plan_chunk_runner(plan, backend=backend, fused=fused)
    tr = obs_trace.TRACER
    host_input = not isinstance(x, jax.Array)
    moments = []
    for lo, hi in scheduler_lib.chunk_bounds(b, chunk):
        with tr.span("engine.chunk", valid=hi - lo, padded=chunk - (hi - lo),
                     host_input=host_input):
            xc = x[lo:hi]
            if hi - lo < chunk:
                pad = jnp.zeros((chunk - (hi - lo),) + x.shape[1:], x.dtype)
                xc = jnp.concatenate([xc, pad])
            moments.append(runner(xc))
    with tr.span("engine.assemble", chunks=len(moments)):
        mean = jnp.concatenate([m for m, _ in moments])[:b]
        std = jnp.concatenate([s for _, s in moments])[:b]
    return mean, std


def predict_volume(plan: plan_lib.PackedPlan, volume: jax.Array, *,
                   chunk: int = 4096, backend: str | None = None,
                   fused: bool | None = None, server=None,
                   priority: int = 0) -> tuple[jax.Array, jax.Array]:
    """Stream a clinical scan through the fused executor.

    volume [..., D] (e.g. ``[X, Y, Z, n_bvalues]``) -> (mean, std), each
    ``[..., d_out]``. The voxel grid is flattened, streamed through
    :func:`predict_packed` in fixed ``chunk``-voxel slices (zero-padded to
    the chunk shape so every slice reuses the one cached fused executor,
    pad voxels unpadded on the way out), and reshaped back to the scan's
    spatial layout — the ROADMAP's volume-serving follow-on at engine level.

    With ``server=`` (a :class:`repro.serving.server.BayesianLMServer`)
    this becomes a thin pool client: the scan is submitted as one
    voxel-chunk work item (``server.submit_scan`` — sharing the LM
    requests' admission queue, backpressure and escalation policy at
    ``priority``), the server drains, and the reassembled moments come back
    bitwise-identical to the direct path (both paths run the one
    :func:`plan_chunk_runner` executor over the same
    ``core.scheduler.chunk_bounds`` partition)."""
    if volume.ndim < 2:
        raise ValueError(f"volume must be [..., D], got {volume.shape}")
    lead = volume.shape[:-1]
    x = volume.reshape(-1, volume.shape[-1])
    with obs_trace.TRACER.span("engine.predict_volume",
                               n_voxels=int(x.shape[0]), chunk=chunk,
                               pooled=server is not None):
        if server is not None:
            rid = server.submit_scan(plan, x, chunk=chunk,
                                     priority=priority, backend=backend,
                                     fused=fused)
            server.run()
            mean, std = server.result(rid).scan_moments()
        else:
            mean, std = predict_packed(plan, x, chunk=chunk,
                                       backend=backend, fused=fused)
    return (mean.reshape(lead + (mean.shape[-1],)),
            std.reshape(lead + (std.shape[-1],)))


def uncertainty_decode_step(model: Model, params: Params, caches,
                            tokens: jax.Array, pos: jax.Array):
    """One Bayesian decode step on a mask-expanded batch [N*B, 1].

    Row j uses mask j // B (contiguous groups). Returns
    (mean_logprobs [B, V], rel_uncertainty [B], new caches). Unjitted
    reference form of the server's decode step (same math via
    server.posterior)."""
    n = model.cfg.mask_samples
    nb = tokens.shape[0]
    b = nb // n
    mask_ids = jnp.repeat(jnp.arange(n), b)
    logits, caches = model.decode_step(params, caches, tokens, pos) \
        if not model.cfg.bayesian else \
        _decode_with_ids(model, params, caches, tokens, pos, mask_ids)
    mean, rel_unc = server_lib.posterior(logits, n)
    return mean, rel_unc, caches


def _decode_with_ids(model, params, caches, tokens, pos, mask_ids):
    from repro.models import transformer
    return transformer.decode_step(model.cfg, params, caches, tokens, pos,
                                   mask_ids=mask_ids)


def serve_uncertain(model: Model, params: Params, tokens: jax.Array,
                    cfg: ServeConfig = ServeConfig(), *, mesh=None):
    """Bayesian generation with per-token uncertainty.

    Returns (generated [B, S+T], rel_uncertainty [B, T], flags [B, T]).
    The whole request batch is expanded x N ONCE (prefill included) — the
    batch-level weight-traffic argument then applies to every decode step.
    """
    if not model.cfg.bayesian:
        raise ValueError("serve_uncertain requires mask_samples > 0")
    n = model.cfg.mask_samples
    b, s = tokens.shape
    fns = server_lib.step_fns(model, fused=cfg.fused)
    xt = _expand_for_masks(tokens, n)                    # [N*B, S]
    outs, uncs = [], []
    with mesh_scope(mesh):
        # Each step's rel-uncertainty describes the argmax of the dist that
        # step produced, i.e. the NEXT emitted token — so token i pairs with
        # the uncertainty from the step that chose it (prefill for token 0),
        # and the last decode's uncertainty (an un-emitted token) is dropped.
        mean, unc_next, caches = fns.prefill(params, xt,
                                             max_seq=s + cfg.max_new_tokens)
        cur = jnp.argmax(mean, -1).astype(jnp.int32)
        for i in range(cfg.max_new_tokens):
            outs.append(cur)
            uncs.append(unc_next)
            mean, unc_next, caches = fns.decode(
                params, caches, _expand_for_masks(cur, n)[:, None],
                jnp.int32(s + i))
            cur = jnp.argmax(mean, -1).astype(jnp.int32)
    gen = jnp.concatenate([tokens, jnp.stack(outs, 1)], 1)
    unc = jnp.stack(uncs, 1)
    flags = unc > cfg.uncertainty_threshold
    return gen, unc, flags
