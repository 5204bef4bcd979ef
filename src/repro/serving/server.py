"""Continuous-batching Bayesian LM server — the paper's uncertainty pathway
as a *service*, not a function call.

The one-shot engine (serving/engine.py) evaluates a fixed request batch to
completion; real traffic arrives as a stream. This module adds the request
layer that lets the batch-level mask schedule (paper Fig. 5) amortize across
that stream:

* **admission queue** — ``submit()`` enqueues a :class:`Request` under a
  priority heap with ``max_queue`` backpressure (:class:`QueueFullError`);
* **slot pool** — one KV/state cache of ``n_masks x max_slots`` batch rows,
  laid out by :class:`repro.core.scheduler.SlotSchedule` (mask-major: a
  request owns the ``n_masks`` rows of one slot). Finished requests free
  their slot group; waiting requests are prefilled into free slots while
  in-flight requests keep decoding — continuous batching;
* **jitted fixed-shape steps** — :func:`step_fns` builds ``prefill``/
  ``decode`` closures padded to the pool shape with donated caches, so the
  hot decode loop traces exactly once (asserted in
  tests/test_serving_server.py). The decode step runs the *fused*
  single-launch executor (``core.plan.compile_decode_step`` — KV gather,
  attention over the slot pool, the Bayesian FFN and the Welford posterior
  in ONE ``kernels/fused_plan`` launch) on the pallas-interpret tier
  whenever the config has a fused lowering, with the per-op
  ``transformer.decode_step`` path as the ``FusedPlanUnsupported``
  fallback and the only path on the xla and compiled pallas-tpu tiers;
* **first-class uncertainty** — every decode step returns the per-request
  relative uncertainty; consecutive flagged tokens drive per-request
  escalation state, and the policy can early-terminate (``"terminate"``) or
  preempt + down-prioritize (``"deprioritize"``) flagged requests — the
  paper's §VI-B clinical escalation pathway applied to scheduling.

Prompt lengths may vary: each admission prefills at the request's true
length, so the prefill function retraces once per *distinct* prompt length
(bucket prompts upstream if that matters); the decode step shape never
changes. Decode positions are per-row — the continuous-batching form of
``transformer.decode_step``.

Pool rows are computed batch-independently, so resident requests cannot
perturb each other — with one caveat: GShard-capacity MoE blocks route all
rows through shared expert capacity, so per-request results are
batch-composition-independent only when capacity cannot drop
(``capacity_factor >= n_experts / top_k``, as in the smoke configs). The
dropless grouped dispatch (``moe_dropless``, what Moonlight serves with)
has no capacity: every row routes on its own. For such configs the decode
and prefill steps also return each MoE layer's per-expert pair counts,
which the server folds into its span attrs (``experts_hit``,
``expert_load_max``) and the ``serving_moe_routed_pairs_total`` counter.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import heapq
import itertools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import plan as plan_lib
from repro.core import scheduler as scheduler_lib, uncertainty as unc_lib
from repro.models import transformer
from repro.models.model import Model
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.serving.metrics import MetricsCollector, ServingSummary

Params = dict[str, Any]

# -- serving telemetry (process registry; see repro/obs/registry.py) --------
_REJECTS = obs_registry.REGISTRY.counter(
    "serving_queue_rejections_total",
    "admissions refused by max_queue backpressure", labels=("modality",))
_PREEMPTS = obs_registry.REGISTRY.counter(
    "serving_preemptions_total",
    "running work items bounced back to the queue", labels=("policy",))
_FALLBACKS = obs_registry.REGISTRY.counter(
    "fused_fallback_total",
    "fused-executor demotions to the per-op path, by stage (build = no "
    "fused lowering for the config; trace = a kernel guard fired on a "
    "concrete pool shape) and key", labels=("stage", "key"))


_ROUTED = obs_registry.REGISTRY.counter(
    "serving_moe_routed_pairs_total",
    "(token, expert) pairs the dropless MoE layers routed for live rows, "
    "decode and prefill", labels=("layer",))


def _note_fallback(stage: str, key: str) -> None:
    """Record one fused->per-op demotion (counter + trace event); shared
    with engine.plan_chunk_runner."""
    _FALLBACKS.inc(stage=stage, key=key)
    obs_trace.TRACER.event("fused_fallback", stage=stage, key=key)

__all__ = ["mesh_scope", "QueueFullError", "Request", "VoxelScanRequest",
           "WorkItem", "RequestState", "ServerConfig",
           "BayesianLMServer", "StepFns", "step_fns"]


def mesh_scope(mesh):
    """Scope serving math to a device mesh via the portability layer
    (no-op when single-device)."""
    return compat.use_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()


def _donate_argnums(*argnums: int) -> tuple[int, ...]:
    """Buffer-donation argnums for jit — () on CPU, which has no donation
    support and warns on every call."""
    return argnums if jax.default_backend() != "cpu" else ()


# ---------------------------------------------------------------------------
# jitted step functions (shared with the legacy engine API)
# ---------------------------------------------------------------------------


def posterior(logits: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """Mask-sample posterior of one step: logits [n*b, V] (mask-major rows)
    -> (mean log-probs [b, V], relative uncertainty of the argmax token [b]).

    n=1 degenerates to plain log-probs with zero uncertainty. (Delegates to
    ``core.uncertainty.token_posterior`` — the same math the bucketed
    prefill runner jits in ``core.plan.compile_prefill_step``, so both
    prefill forms emit bitwise-identical posteriors.)"""
    return unc_lib.token_posterior(logits, n)


@dataclasses.dataclass(frozen=True)
class StepFns:
    """Jitted serving steps. ``prefill(params, tokens [n*b, P], max_seq=M)``
    and ``decode(params, caches, tokens [n*b, 1], pos)`` both return
    ``(mean_logp [b, V], rel_unc [b], caches)``; ``pos`` is scalar or
    per-row [n*b]. ``trace_counts`` increments at *trace* time — the
    retrace-count observable the tests pin down (the fused decode's traces
    live in ``core.plan.fused_trace_counts``, keyed on ``fused_spec``).
    ``fused_spec`` is the decode chain's static shape-key when the fused
    single-launch executor is selected, None when the per-op path is;
    ``fused_state["blocked"]`` records the pool-shape keys whose first call
    tripped a kernel guard into the per-op fallback.

    ``prefill_spec`` is the bucketed prefill's static shape-key when the
    config admits padded length-bucket prefill (``core.plan.
    prefill_spec``), None when every admission takes the per-length
    exact path. With a spec, ``prefill`` dispatches each call to the
    smallest covering bucket (``core.plan.compile_prefill_step`` — one
    trace per bucket, counted in ``core.plan.fused_trace_counts`` under
    ``(spec, backend, "prefill", bucket, max_seq)``), zero-padding the
    prompt and passing its true length as a traced scalar; lengths no
    bucket covers fall back to the exact path.

    For a config with dropless MoE layers both steps return a fourth
    value, the per-expert pair counts [n_moe, E] of the live rows (decode)
    or the prompt's true positions (prefill)."""
    n_samples: int
    prefill: Callable
    decode: Callable
    trace_counts: dict[str, int]
    fused_spec: object | None = None
    fused_state: dict | None = None
    prefill_spec: object | None = None
    prefill_buckets: tuple[int, ...] | None = None

    def prefill_bucket(self, length: int, max_seq: int) -> int | None:
        """The bucket ``prefill`` pads a ``length``-token prompt to; None
        where it takes the exact per-length path."""
        if self.prefill_spec is None:
            return None
        return plan_lib.prefill_bucket(length, max_seq, self.prefill_buckets)

    def fused_live(self) -> bool:
        """True iff the decode hot loop is running the fused executor and
        no pool shape has fallen back to the per-op path — what a benchmark
        must check *after* its run to claim the fused numbers are real."""
        return self.fused_spec is not None and \
            not (self.fused_state or {}).get("blocked")


def step_fns(model: Model, expand_masks: bool = True,
             fused: bool | None = None,
             prefill_buckets: tuple[int, ...] | None = None) -> StepFns:
    """Build (and cache per *config*) the jitted serving steps.

    expand_masks=True is the Bayesian serving form: rows are the mask
    expansion (mask-major groups, row j uses mask ``j // b``). With
    expand_masks=False (or a non-Bayesian config) rows are plain requests
    and the posterior is the single-sample degenerate case — the legacy
    ``generate`` path.

    ``fused`` selects the decode executor the same way
    ``engine.predict_packed(fused=)`` does: ``True`` requires the fused
    single-launch decode step (``core.plan.compile_decode_step``) and
    surfaces ``FusedPlanUnsupported``; ``False`` forces the per-op
    ``transformer.decode_step`` path; ``None`` (default) tries fused on the
    pallas-interpret tier and falls back per-op when the config has no
    fused lowering or the kernel's VMEM-residency guard fires at first
    call; on the xla and compiled pallas-tpu tiers (the decode kernel has
    no Mosaic lowering) it decodes per-op from the start.

    ``prefill_buckets`` selects the admission prefill's length-bucket set:
    ``None`` (default) resolves to the power-of-two set per ``max_seq``
    (``core.plan.prefill_buckets``), an explicit tuple is validated loudly,
    and ``()`` disables bucketing — every admission then takes the
    per-length exact prefill (the pre-bucketing behaviour). Configs whose
    caches do not pad exactly (``core.plan.prefill_spec``: capacity MoE,
    recurrent state, M-RoPE, local-attention rolling caches) fall back to
    the exact path regardless.

    The cache key is the hashable ``ModelConfig`` (plus ``expand_masks`` /
    ``fused`` / ``prefill_buckets``), never the ``Model`` instance —
    building steps must not pin model objects for the life of the process.
    A bare config is accepted in place of a model."""
    cfg = getattr(model, "cfg", model)
    if prefill_buckets is not None:
        prefill_buckets = tuple(int(b) for b in prefill_buckets)
        if prefill_buckets and any(b < 1 for b in prefill_buckets):
            raise ValueError(
                f"non-positive prefill bucket in {prefill_buckets}")
    return _step_fns(cfg, bool(expand_masks), fused, prefill_buckets)


@functools.lru_cache(maxsize=None)
def _step_fns(cfg, expand_masks: bool, fused: bool | None,
              buckets: tuple[int, ...] | None = None) -> StepFns:
    bayes = cfg.bayesian and expand_masks
    n = cfg.mask_samples if bayes else 1
    counts = {"prefill": 0, "decode": 0}
    # donating the decode caches keeps the pool memory flat
    donate = _donate_argnums(1)

    def _mask_ids(rows: int):
        # Non-expanded rows keep the transformer's default (training
        # batch-group) assignment.
        return jnp.repeat(jnp.arange(n), rows // n) if bayes else None

    def prefill_impl(params, tokens, max_seq):
        counts["prefill"] += 1
        logits, caches, routes = transformer.prefill(
            cfg, params, {"tokens": tokens}, max_seq=max_seq,
            mask_ids=_mask_ids(tokens.shape[0]), return_counts=True)
        mean, rel = posterior(logits, n)
        return (mean, rel, caches) + ((routes,) if routes is not None else ())

    exact_prefill = jax.jit(prefill_impl, static_argnames=("max_seq",))

    # Bucketed prefill: bounded retraces — one trace per (bucket, max_seq)
    # instead of one per distinct prompt length. Gated on the cache layout
    # (core.plan.prefill_spec); () disables.
    prefill_spec = None
    if buckets is None or buckets:
        try:
            prefill_spec = plan_lib.prefill_spec(
                cfg, expand_masks=expand_masks)
        except plan_lib.FusedPlanUnsupported:
            prefill_spec = None

    if prefill_spec is None:
        def prefill(params, tokens, max_seq):
            return exact_prefill(params, tokens, max_seq=max_seq)
    else:
        def prefill(params, tokens, max_seq):
            toks = jnp.asarray(tokens)
            length = toks.shape[1]
            bucket = plan_lib.prefill_bucket(length, max_seq, buckets)
            if bucket is None:                 # custom set doesn't cover it
                return exact_prefill(params, toks, max_seq=max_seq)
            if bucket > length:
                pad = jnp.zeros((toks.shape[0], bucket - length),
                                toks.dtype)
                toks = jnp.concatenate([toks, pad], axis=1)
            step = plan_lib.compile_prefill_step(
                cfg, bucket, max_seq, expand_masks=expand_masks)
            return step(params, toks, jnp.int32(length))

    def decode_impl(params, caches, tokens, pos):
        counts["decode"] += 1
        logits, caches, routes = transformer.decode_step(
            cfg, params, caches, tokens, pos,
            mask_ids=_mask_ids(tokens.shape[0]), return_counts=True)
        mean, rel = posterior(logits, n)
        return (mean, rel, caches) + ((routes,) if routes is not None else ())

    perop_decode = jax.jit(decode_impl, donate_argnums=donate)

    fused_step = fspec = None
    if fused is not False:
        # Auto-select tries fused only on the pallas-interpret tier. On the
        # xla tier there is no launch to fuse — the "fused" executor would
        # just be the fully unrolled reference graph (L layers × H heads in
        # Python), which traces/compiles far slower than the per-op scanned
        # decode for identical math. On the compiled pallas-tpu tier the
        # decode kernel has no Mosaic lowering (its per-row KV gather), so
        # every trace would fail. fused=True still forces it (in-process
        # A/B and the forced-xla CI leg rely on it; on the chip it raises).
        from repro.kernels.fused_plan import ops as fp_ops
        if fused or fp_ops.KERNEL_BACKEND == "pallas-interpret":
            try:
                fspec = plan_lib.decode_fused_spec(
                    cfg, expand_masks=expand_masks)
                fused_step = plan_lib.compile_decode_step(
                    cfg, expand_masks=expand_masks)
            except plan_lib.FusedPlanUnsupported:
                if fused:
                    raise
                _note_fallback("build", "decode")

    fused_state = None
    if fused_step is None:
        decode = perop_decode
    else:
        fused_state = {"blocked": set()}

        def _shape_key(caches, tokens):
            # What the kernel guards actually scale with: pool rows and the
            # cache sequence capacities (kpos leaves are [reps, R, smax]).
            return (tokens.shape[0],) + tuple(sorted(
                {leaf.shape[-1] for leaf in jax.tree.leaves(caches)
                 if leaf.ndim == 3}))

        def decode(params, caches, tokens, pos):
            # Fused-first with a per-POOL-SHAPE per-op fallback: the kernel
            # tier's VMEM-residency / compiled-tier guards fire at trace
            # time, from the first call with each pool shape, and depend on
            # that shape — one oversized pool must not silently demote
            # every other server on the same config.
            key = _shape_key(caches, tokens)
            if key not in fused_state["blocked"]:
                try:
                    return fused_step(params, caches, tokens, pos)
                except plan_lib.FusedPlanUnsupported:
                    if fused:
                        raise
                    fused_state["blocked"].add(key)
                    _note_fallback("trace", str(key))
            return perop_decode(params, caches, tokens, pos)

    return StepFns(
        n_samples=n,
        prefill=prefill,
        decode=decode,
        trace_counts=counts,
        fused_spec=fspec if fused_step is not None else None,
        fused_state=fused_state,
        prefill_spec=prefill_spec,
        prefill_buckets=buckets)


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------


class QueueFullError(RuntimeError):
    """Admission queue at ``max_queue`` — backpressure; caller retries or
    sheds load."""


@dataclasses.dataclass(frozen=True)
class Request:
    """One LM generation request (work-item kind ``"lm"``).
    ``priority``: lower value = served first."""
    req_id: int
    tokens: tuple[int, ...]
    max_new_tokens: int
    priority: int = 0

    kind = "lm"


@dataclasses.dataclass(frozen=True)
class VoxelScanRequest:
    """One clinical-scan request (work-item kind ``"voxel"``): a flattened
    voxel batch served through the pool one fixed-size chunk per engine
    step.

    ``x`` is the scan's ``[n_voxels, D]`` signal matrix; ``bounds`` the
    ``core.scheduler.chunk_bounds`` partition; ``runner`` the per-chunk
    moments executor (``engine.plan_chunk_runner`` — the SAME callable
    composition the direct ``engine.predict_volume`` path runs, which is
    what makes pooled results bitwise-identical to the direct path). A
    resident scan occupies one slot and advances one chunk per ``step()``;
    preemption (deprioritize) re-queues it and it resumes at its next
    unprocessed chunk, so chunks of one scan never complete out of order.
    """
    req_id: int
    x: Any
    chunk: int
    bounds: tuple[tuple[int, int], ...]
    runner: Callable
    priority: int = 0

    kind = "voxel"

    @property
    def n_voxels(self) -> int:
        return self.x.shape[0]


#: A pool work item — both kinds share the priority queue, the
#: ``max_queue`` backpressure, the escalation-policy surface and the
#: metrics stream (per-modality labels).
WorkItem = Request | VoxelScanRequest


@dataclasses.dataclass
class RequestState:
    """Mutable serving state + final result of one work item.

    status: queued -> running -> done (or "escalated" when the uncertainty
    policy terminated it early; "deprioritize" preemption bounces it back
    to queued).

    LM items fill ``generated``/``pending``; voxel items fill
    ``chunk_results`` (per-chunk ``(mean, std)`` device arrays, strictly in
    chunk order — the resume cursor is ``len(chunk_results)``).
    ``uncertainty``/``flags`` hold per-token rel-unc for LM items and
    per-chunk max voxel rel-unc for scans; the escalation policy reads them
    identically."""
    request: WorkItem
    status: str = "queued"
    slot: int | None = None
    effective_priority: int = 0
    generated: list[int] = dataclasses.field(default_factory=list)
    uncertainty: list[float] = dataclasses.field(default_factory=list)
    flags: list[bool] = dataclasses.field(default_factory=list)
    flag_streak: int = 0
    escalated: bool = False
    preempts: int = 0
    pending: int | None = None    # next token to feed through decode
    pending_unc: float = 0.0      # rel-unc of pending (from the step that
                                  # chose it; recorded when it is emitted)
    chunk_results: list = dataclasses.field(default_factory=list)

    @property
    def kind(self) -> str:
        return self.request.kind

    @property
    def next_pos(self) -> int:
        """Decode position of the pending token: prompt + emitted so far
        (invariant across preemption — re-prefill re-encodes exactly the
        first ``next_pos`` positions)."""
        return len(self.request.tokens) + len(self.generated)

    def scan_moments(self):
        """Reassemble a finished scan: concatenate the per-chunk moments,
        strip the zero-pad tail -> (mean [n_voxels, d_out], std)."""
        if self.kind != "voxel":
            raise ValueError(f"work item {self.request.req_id} is "
                             f"{self.kind}, not a voxel scan")
        if self.status != "done":
            raise ValueError(
                f"scan {self.request.req_id} is {self.status}; only "
                f"completed scans reassemble (escalation policy "
                f"'terminate' leaves partial results in chunk_results)")
        b = self.request.n_voxels
        mean = jnp.concatenate([m for m, _ in self.chunk_results])[:b]
        std = jnp.concatenate([s for _, s in self.chunk_results])[:b]
        return mean, std


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    max_slots: int = 4
    max_queue: int = 64
    max_prompt_len: int = 32
    max_new_tokens: int = 16          # per-request cap; requests may ask less
    uncertainty_threshold: float = 0.5
    escalation_patience: int = 2      # consecutive flagged tokens to escalate
    escalation_policy: str = "flag"   # flag | terminate | deprioritize
    deprioritize_penalty: int = 10    # priority added on escalation preempt
    fused: bool | None = None         # decode executor: True = require the
                                      # fused single-launch step, False =
                                      # per-op, None = auto w/ fallback
    prefill_buckets: tuple[int, ...] | None = None
                                      # admission prefill length buckets:
                                      # None = power-of-two auto set,
                                      # () = exact per-length prefill
    kv_dtype: str = ""                # pool KV storage: "" = inherit the
                                      # model config's kv_dtype, "bfloat16"
                                      # (fused-decode supported), "int8"
                                      # (+ per-vector scales; decode runs
                                      # the per-op path)
    trace: bool = False               # enable span tracing on the process
                                      # tracer (obs.trace.TRACER) — one
                                      # record per lifecycle event; off by
                                      # default (zero hot-path appends)

    def __post_init__(self) -> None:
        if self.escalation_policy not in ("flag", "terminate",
                                          "deprioritize"):
            raise ValueError(
                f"unknown escalation policy {self.escalation_policy!r}")
        if self.max_slots < 1:
            raise ValueError(f"max_slots {self.max_slots} < 1")
        if self.max_queue < self.max_slots:
            # fewer queue seats than slots means backpressure rejects
            # traffic the pool could already hold — a misconfiguration
            # that starves admission, caught here rather than at runtime.
            raise ValueError(
                f"max_queue {self.max_queue} < max_slots {self.max_slots}: "
                f"the admission queue must at least cover the pool")
        if self.max_prompt_len < 1 or self.max_new_tokens < 1:
            raise ValueError(
                f"max_prompt_len {self.max_prompt_len} and max_new_tokens "
                f"{self.max_new_tokens} must be >= 1")
        if self.kv_dtype not in ("", "bfloat16", "int8"):
            raise ValueError(f"unknown kv_dtype {self.kv_dtype!r}")
        if self.prefill_buckets is not None:
            # normalize (frozen dataclass: bypass immutability once) and
            # validate loudly — a non-positive bucket would otherwise
            # surface as a shape error deep inside the first admission
            vals = tuple(int(b) for b in self.prefill_buckets)
            object.__setattr__(self, "prefill_buckets", vals)
            if vals:      # () = bucketing disabled, valid
                plan_lib.prefill_buckets(self.max_seq, vals)

    @property
    def max_seq(self) -> int:
        return self.max_prompt_len + self.max_new_tokens


class BayesianLMServer:
    """Continuous-batching server over one Bayesian model.

        server = BayesianLMServer(model, params, ServerConfig(max_slots=4))
        rid = server.submit(prompt_tokens, max_new_tokens=12)
        summary = server.run()            # drain queue + slots
        state = server.result(rid)        # tokens, per-token uncertainty

    ``step()`` is one engine iteration — admit waiting requests into free
    slots (prefill + scatter into the pool), then one jitted decode over the
    whole pool — so a driver can also interleave ``submit``/``step`` to
    replay a live arrival trace (benchmarks/bench_serving.py).
    """

    def __init__(self, model: Model, params: Params,
                 cfg: ServerConfig = ServerConfig(), *, mesh=None,
                 device=None,
                 clock: Callable[[], float] | None = None,
                 tracer: obs_trace.Tracer | None = None) -> None:
        """``device`` pins the pool to one device: the params, the KV pool
        and every voxel scan's input and plan weights are placed there, and
        every jitted step follows its committed operands (the multi-host
        router gives each host its own chip)."""
        if not model.cfg.bayesian:
            raise ValueError("BayesianLMServer requires mask_samples > 0")
        if device is not None:
            params = jax.device_put(params, device)
        # The jit-cached step closures are process-global, so the default
        # tracer is the process TRACER; cfg.trace=True switches it on.
        self._tracer = obs_trace.TRACER if tracer is None else tracer
        if cfg.trace:
            self._tracer.enable()
        self.model, self.params, self.cfg, self.mesh = model, params, cfg, \
            mesh
        self.device = device
        self.schedule = scheduler_lib.SlotSchedule(model.cfg.mask_samples,
                                                   cfg.max_slots)
        # cfg.kv_dtype rewrites the MODEL config the steps/caches build
        # against — one knob on the server, no model surgery at call sites
        # ("" inherits whatever the model config already says)
        mcfg = model.cfg
        if cfg.kv_dtype and cfg.kv_dtype != mcfg.kv_dtype:
            mcfg = dataclasses.replace(mcfg, kv_dtype=cfg.kv_dtype)
        self.model_cfg = mcfg
        self.steps = step_fns(mcfg, fused=cfg.fused,
                              prefill_buckets=cfg.prefill_buckets)
        # donate the pool on scatter (admission overwrites rows in place);
        # CPU has no donation support and warns, so only donate off-CPU
        self._scatter = jax.jit(transformer.cache_scatter_rows,
                                donate_argnums=_donate_argnums(0))
        self._reset = jax.jit(transformer.cache_reset_rows,
                              donate_argnums=_donate_argnums(0))
        self._caches = transformer.init_cache(mcfg, self.schedule.rows,
                                              cfg.max_seq)
        # model layer index of each dropless MoE layer, in counts order
        self._moe_layers = [i for i, kind in enumerate(
            k for seg in mcfg.segments() for _ in range(seg.reps)
            for k in seg.pattern) if kind == "moe"]
        if device is not None:
            self._caches = jax.device_put(self._caches, device)
        self._slots: list[int | None] = [None] * cfg.max_slots
        self._queue: list[tuple[int, int, int]] = []   # (prio, seq, req_id)
        self._seq = itertools.count()
        self._ids = itertools.count()
        self._cancelled: set[int] = set()   # heap tombstones (cancel())
        self.states: dict[int, RequestState] = {}
        self.metrics = MetricsCollector(cfg.max_slots, clock)

    # ---- admission ---------------------------------------------------------
    def _claim_id(self, req_id: int | None) -> int:
        """Next id from the server counter, or the caller-pinned one (the
        multi-host router keeps ONE global id space across per-host
        servers by pinning, so a failover resubmission keeps its id)."""
        if req_id is None:
            return next(self._ids)
        rid = int(req_id)
        if rid in self.states:
            raise ValueError(f"req_id {rid} is already tracked by this "
                             f"server ({self.states[rid].status})")
        return rid

    def submit(self, tokens, *, max_new_tokens: int | None = None,
               priority: int = 0, req_id: int | None = None) -> int:
        """Enqueue ONE prompt (a 1-D token sequence — submit a batch as
        separate requests); returns the request id. Raises QueueFullError
        when the admission queue is at max_queue (backpressure).
        ``req_id`` pins the id instead of drawing from the server counter
        (router failover resubmits under the original global id)."""
        arr = np.asarray(tokens)
        if arr.ndim > 1:
            raise ValueError(f"submit takes one prompt, got shape "
                             f"{arr.shape}; submit batch rows separately")
        toks = tuple(int(t) for t in arr.reshape(-1))
        if not 1 <= len(toks) <= self.cfg.max_prompt_len:
            raise ValueError(f"prompt length {len(toks)} outside "
                             f"[1, {self.cfg.max_prompt_len}]")
        if self.queue_depth >= self.cfg.max_queue:
            _REJECTS.inc(modality="lm")
            self._tracer.event("reject", kind="lm")
            raise QueueFullError(
                f"admission queue full ({self.cfg.max_queue})")
        mnt = self.cfg.max_new_tokens if max_new_tokens is None \
            else max_new_tokens
        if not 1 <= mnt <= self.cfg.max_new_tokens:
            raise ValueError(f"max_new_tokens {mnt} outside "
                             f"[1, {self.cfg.max_new_tokens}]")
        rid = self._claim_id(req_id)
        st = RequestState(Request(rid, toks, mnt, priority),
                          effective_priority=priority)
        self.states[rid] = st
        heapq.heappush(self._queue, (priority, next(self._seq), rid))
        self.metrics.on_enqueue(rid)
        self._tracer.event("enqueue", req_id=rid, kind="lm",
                           prompt_len=len(toks), priority=priority,
                           queue_depth=self.queue_depth)
        return rid

    def submit_scan(self, plan, x, *, chunk: int = 4096, priority: int = 0,
                    backend: str | None = None,
                    fused: bool | None = None, req_id: int | None = None,
                    resume_results: list | None = None) -> int:
        """Enqueue ONE clinical scan (a compiled ``core.plan.PackedPlan``
        plus its flattened ``[n_voxels, D]`` voxel batch) as a voxel-chunk
        work item; returns the request id.

        The scan shares the LM requests' priority queue and ``max_queue``
        backpressure; resident, it occupies one slot and advances one
        zero-padded ``chunk``-voxel fused-moments launch per engine step —
        the same per-chunk executor the direct ``engine.predict_volume``
        path runs, so a completed scan's ``scan_moments()`` is
        bitwise-identical to the direct path. Admission requires the plan's
        sample axis to map onto the pool layout
        (``plan.slot_schedule == pool schedule``, i.e. matching n_masks).

        ``req_id`` pins the id (see :meth:`submit`); ``resume_results``
        seeds the chunk cursor with moments already computed elsewhere —
        router failover resubmits a scan from a dead host this way, and it
        resumes at ``len(chunk_results)`` exactly like ``_preempt``
        re-admission does on a single host (chunks never recompute and
        never complete out of order)."""
        # lazy import: engine imports this module at its top level
        from repro.serving import engine as engine_lib
        self.schedule.admits(plan.slot_schedule(self.cfg.max_slots))
        x = jnp.asarray(x)
        if x.ndim != 2:
            raise ValueError(f"scan must be [n_voxels, D], got {x.shape}")
        if self.queue_depth >= self.cfg.max_queue:
            _REJECTS.inc(modality="voxel")
            self._tracer.event("reject", kind="voxel")
            raise QueueFullError(
                f"admission queue full ({self.cfg.max_queue})")
        bounds = scheduler_lib.chunk_bounds(x.shape[0], chunk)
        if resume_results is not None and \
                len(resume_results) >= len(bounds):
            raise ValueError(
                f"resume_results carries {len(resume_results)} chunks but "
                f"the scan only has {len(bounds)}: nothing left to run")
        if self.device is not None:
            x, params = jax.device_put((x, plan.params), self.device)
            plan = dataclasses.replace(plan, params=params)
            # chunks a failed-over scan brings from another host's device
            resume_results = [
                r if r[0].devices() == {self.device}
                else jax.device_put(r, self.device)
                for r in resume_results or ()]
        runner = engine_lib.plan_chunk_runner(plan, backend=backend,
                                              fused=fused)
        rid = self._claim_id(req_id)
        st = RequestState(VoxelScanRequest(rid, x, chunk, bounds, runner,
                                           priority),
                          effective_priority=priority)
        if resume_results:
            st.chunk_results = list(resume_results)
        self.states[rid] = st
        heapq.heappush(self._queue, (priority, next(self._seq), rid))
        self.metrics.on_enqueue(rid, modality="voxel")
        self._tracer.event("enqueue", req_id=rid, kind="voxel",
                           n_voxels=int(x.shape[0]), priority=priority,
                           resumed_chunks=len(resume_results or ()),
                           queue_depth=self.queue_depth)
        return rid

    def cancel(self, req_id: int) -> None:
        """Withdraw a QUEUED work item (the router's drain/rebalance hook):
        its state is evicted and its heap entry becomes a tombstone the
        admission loop skips. Running or finished items cannot be cancelled
        — preemption is the policy surface for resident work."""
        st = self.states.get(req_id)
        if st is None or st.status != "queued":
            raise ValueError(
                f"request {req_id} is "
                f"{'unknown' if st is None else st.status}, not queued")
        kind = st.kind
        del self.states[req_id]
        self._cancelled.add(req_id)
        self._tracer.event("cancel", req_id=req_id, kind=kind)

    @property
    def queue_depth(self) -> int:
        # cancelled entries linger in the heap as tombstones until popped
        return len(self._queue) - len(self._cancelled)

    @property
    def occupied_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    def result(self, req_id: int) -> RequestState:
        return self.states[req_id]

    def pop_result(self, req_id: int) -> RequestState:
        """Return and evict a finished request's state — long-running
        servers call this per completion to keep memory bounded (``result``
        keeps states resident forever). The metrics timeline (a few floats)
        stays so ``summary()`` still covers the whole run; rotate the
        collector between runs if even that matters."""
        st = self.states[req_id]
        if st.status not in ("done", "escalated"):
            raise ValueError(f"request {req_id} is still {st.status}")
        del self.states[req_id]
        return st

    # ---- slot lifecycle ----------------------------------------------------
    def _admit(self, req_id: int, slot: int) -> None:
        """Bind one queued work item to a free slot. LM requests prefill and
        scatter their cache rows into the slot group — in-flight slots are
        untouched and keep decoding. Voxel scans touch no pool cache (their
        state is the chunk cursor); the slot is pure scheduling capacity."""
        st = self.states[req_id]
        tr = self._tracer
        if st.kind == "voxel":
            with tr.span("serving.admit", req_id=req_id, slot=slot,
                         kind=st.kind, resumed=st.preempts > 0):
                st.status, st.slot = "running", slot
                self._slots[slot] = req_id
                if st.preempts == 0:
                    self.metrics.on_admit(req_id)
            return
        ctx = list(st.request.tokens) + st.generated  # re-entry after preempt
        max_seq = self.cfg.max_seq
        bucket = self.steps.prefill_bucket(len(ctx), max_seq)
        with tr.span("serving.admit", req_id=req_id, slot=slot,
                     kind=st.kind, resumed=st.preempts > 0,
                     prompt_len=len(ctx), bucket=bucket), \
                mesh_scope(self.mesh):
            with tr.span("serving.prefill",
                         path="exact" if bucket is None else "bucketed",
                         bucket=bucket, length=len(ctx)) as prefill_span:
                xt = jnp.tile(jnp.asarray(ctx, jnp.int32)[None],
                              (self.schedule.n_masks, 1))
                mean, rel, fresh, *routes = self.steps.prefill(
                    self.params, xt, max_seq=max_seq)
                self._caches = self._scatter(
                    self._caches, fresh, self.schedule.rows_for_slot(slot))
            with tr.span("serving.sync"):
                first, unc, routes = jax.device_get(
                    (jnp.argmax(mean[0]), rel[0], routes))
                st.pending, st.pending_unc = int(first), float(unc)
            if routes:
                self._note_routes(prefill_span, routes[0])
            st.status, st.slot = "running", slot
            self._slots[slot] = req_id
            if st.preempts == 0:
                self.metrics.on_admit(req_id)
                self.metrics.on_first_token(req_id)  # computed by prefill

    def _note_routes(self, span, routes: np.ndarray) -> None:
        """Fold one step's per-expert pair counts [n_moe, E] into ``span``:
        ``experts_hit`` (experts with a pair, summed over the MoE layers)
        and ``expert_load_max`` (the busiest expert's pairs over the mean
        per expert, in the worst layer); and into the routed-pairs counter
        by model layer."""
        total = routes.sum(-1)
        busy = routes.max(-1) * routes.shape[-1] / np.maximum(total, 1)
        span.set(experts_hit=int((routes > 0).sum()),
                 expert_load_max=float(busy.max(initial=0.0)))
        for layer, n in zip(self._moe_layers, total):
            if n:
                _ROUTED.inc(float(n), layer=str(layer))

    def _release_slot(self, slot: int) -> None:
        """Free a slot group: clear host state and reset its cache rows
        (K/V zero, kpos -1) so unoccupied groups stay observably empty."""
        self._slots[slot] = None
        mask = np.zeros(self.schedule.rows, bool)
        mask[np.asarray(self.schedule.rows_for_slot(slot))] = True
        self._caches = self._reset(self._caches, jnp.asarray(mask))

    def _finish(self, st: RequestState, *, terminated: bool) -> None:
        st.status = "escalated" if terminated else "done"
        self._release_slot(st.slot)
        st.slot, st.pending = None, None
        self.metrics.on_finish(st.request.req_id, escalated=st.escalated)
        self._tracer.event("finish", req_id=st.request.req_id,
                           status=st.status, kind=st.kind)

    def _preempt(self, st: RequestState) -> None:
        """Deprioritize policy: bounce an escalated request back to the queue
        (its slot goes to calmer traffic); it resumes later by re-prefilling
        prompt + generated-so-far at a worse priority."""
        self._release_slot(st.slot)
        st.slot, st.status = None, "queued"
        st.preempts += 1
        st.effective_priority += self.cfg.deprioritize_penalty
        heapq.heappush(self._queue, (st.effective_priority, next(self._seq),
                                     st.request.req_id))
        _PREEMPTS.inc(policy=self.cfg.escalation_policy)
        self._tracer.event("preempt", req_id=st.request.req_id,
                           priority=st.effective_priority)

    # ---- the engine iteration ----------------------------------------------
    def step(self) -> bool:
        """Admit waiting work items into free slots, then run one engine
        iteration across the pool: one jitted decode step over every
        resident LM slot (voxel/empty slots ride along at pos -1) plus one
        fused-moments chunk launch per resident voxel scan. Returns False
        once fully idle."""
        tr = self._tracer
        with tr.span("serving.step") as step_span:
            while self._queue and None in self._slots:
                _, _, rid = heapq.heappop(self._queue)
                if rid in self._cancelled:    # tombstone left by cancel()
                    self._cancelled.discard(rid)
                    continue
                self._admit(rid, self._slots.index(None))
            occupied = [(slot, rid) for slot, rid in enumerate(self._slots)
                        if rid is not None]
            lm = [(s, r) for s, r in occupied
                  if self.states[r].kind == "lm"]
            voxel = [(s, r) for s, r in occupied
                     if self.states[r].kind == "voxel"]
            step_span.set(lm=len(lm), voxel=len(voxel),
                          queue_depth=self.queue_depth)
            if not occupied:
                return False
            self.metrics.on_step(len(occupied), self.queue_depth,
                                 voxel_occupied=len(voxel))
            if lm:
                # Inactive slots decode at pos -1: their (garbage) K/V write
                # lands on a kpos=-1 slot, so unoccupied rows stay observably
                # empty — voxel-occupied slots never touch the pool cache and
                # ride along exactly like empty ones.
                with tr.span("serving.decode"):
                    tok = np.zeros(self.cfg.max_slots, np.int32)
                    pos = np.full(self.cfg.max_slots, -1, np.int32)
                    for slot, rid in lm:
                        st = self.states[rid]
                        tok[slot] = st.pending
                        pos[slot] = st.next_pos
                    rows_tok = self.schedule.row_values(
                        jnp.asarray(tok))[:, None]
                    rows_pos = self.schedule.row_values(jnp.asarray(pos))
                    if tr.enabled:
                        tr.event("decode", rows=self.schedule.rows,
                                 slots=len(lm),
                                 fused=self.steps.fused_live())
                    with mesh_scope(self.mesh):
                        mean, rel, self._caches, *routes = self.steps.decode(
                            self.params, self._caches, rows_tok, rows_pos)
                        best = jnp.argmax(mean, -1)
                with tr.span("serving.sync"):
                    nxt, rel, routes = jax.device_get((best, rel, routes))
                if routes:
                    self._note_routes(step_span, routes[0])
                with tr.span("serving.absorb"):
                    for slot, rid in lm:
                        self._absorb(self.states[rid], int(nxt[slot]),
                                     float(rel[slot]))
            for _, rid in voxel:
                self._advance_scan(self.states[rid])
        return True

    def _advance_scan(self, st: RequestState) -> None:
        """Run one chunk of a resident scan through its per-chunk moments
        executor and fold the result into scan state. The chunk slice is
        zero-padded to exactly ``chunk`` rows — the same padding rule as
        the direct ``engine.predict_volume`` path (``core.scheduler.
        chunk_bounds``), so pooled and direct moments are bitwise equal."""
        req = st.request
        lo, hi = req.bounds[len(st.chunk_results)]
        with self._tracer.span("engine.chunk", valid=hi - lo,
                               padded=req.chunk - (hi - lo),
                               host_input=not isinstance(req.x, jax.Array)):
            xc = req.x[lo:hi]
            if hi - lo < req.chunk:
                pad = jnp.zeros((req.chunk - (hi - lo),) + xc.shape[1:],
                                xc.dtype)
                xc = jnp.concatenate([xc, pad])
            with mesh_scope(self.mesh):
                mean, std = req.runner(xc)
        # Chunk-level uncertainty signal for the shared escalation policy:
        # the worst per-voxel relative uncertainty (max over valid voxels
        # and output columns) — "any voxel uncertain => flag the chunk".
        valid = hi - lo
        rel = np.asarray(std[:valid]) / np.maximum(
            np.abs(np.asarray(mean[:valid])), unc_lib.REL_UNC_EPS)
        st.chunk_results.append((mean, std))
        if self._tracer.enabled:
            self._tracer.event("chunk", req_id=req.req_id,
                               index=len(st.chunk_results) - 1,
                               voxels=valid, rel=float(rel.max()))
        self._absorb_chunk(st, float(rel.max()), n_voxels=valid)

    def _absorb(self, st: RequestState, next_tok: int, rel: float) -> None:
        """Fold one decode result into request state: the pending token is
        now emitted with the uncertainty of the step that *chose* it; this
        step's ``rel`` describes ``next_tok`` and travels with it. The
        escalation policy therefore acts on the emitted token's own
        uncertainty."""
        cfg = self.cfg
        st.generated.append(st.pending)
        st.uncertainty.append(st.pending_unc)
        flagged = st.pending_unc > cfg.uncertainty_threshold
        st.flags.append(flagged)
        st.flag_streak = st.flag_streak + 1 if flagged else 0
        st.pending = next_tok
        st.pending_unc = rel
        self.metrics.on_token(st.request.req_id)
        if self._tracer.enabled:
            self._tracer.event("token", req_id=st.request.req_id,
                               token=st.generated[-1],
                               rel=st.uncertainty[-1], flagged=flagged)
        newly = not st.escalated and \
            st.flag_streak >= cfg.escalation_patience
        if newly:
            st.escalated = True
            self._tracer.event("escalate", req_id=st.request.req_id,
                               policy=cfg.escalation_policy)
        if st.escalated and cfg.escalation_policy == "terminate":
            self._finish(st, terminated=True)
        elif len(st.generated) >= st.request.max_new_tokens:
            self._finish(st, terminated=False)
        elif newly and cfg.escalation_policy == "deprioritize" and \
                self._queue:
            self._preempt(st)

    def _absorb_chunk(self, st: RequestState, rel: float,
                      n_voxels: int) -> None:
        """Fold one completed scan chunk into work-item state — the voxel
        twin of :meth:`_absorb`, driving the SAME escalation surface:
        chunk-level flags feed the streak counter, ``terminate`` stops the
        scan early (partial ``chunk_results``), ``deprioritize`` preempts
        it between chunks (it resumes in order at ``len(chunk_results)``)."""
        cfg = self.cfg
        flagged = rel > cfg.uncertainty_threshold
        st.uncertainty.append(rel)
        st.flags.append(flagged)
        st.flag_streak = st.flag_streak + 1 if flagged else 0
        self.metrics.on_token(st.request.req_id, units=n_voxels)
        newly = not st.escalated and \
            st.flag_streak >= cfg.escalation_patience
        if newly:
            st.escalated = True
            self._tracer.event("escalate", req_id=st.request.req_id,
                               policy=cfg.escalation_policy)
        if st.escalated and cfg.escalation_policy == "terminate":
            self._finish(st, terminated=True)
        elif len(st.chunk_results) >= len(st.request.bounds):
            self._finish(st, terminated=False)
        elif newly and cfg.escalation_policy == "deprioritize" and \
                self._queue:
            self._preempt(st)

    def run(self, max_steps: int | None = None) -> ServingSummary:
        """Drive step() until queue and slots drain (or max_steps)."""
        steps = 0
        while self._queue or self.occupied_slots:
            if max_steps is not None and steps >= max_steps:
                break
            self.step()
            steps += 1
        return self.metrics.summary()
