"""PackedPlan — the single mask-compilation pipeline (paper Fig. 1, Phase 3).

The paper's transformation design flow lowers *any* dropout-equipped network
to a mask-based BayesNN served with its two hardware optimizations:
mask-zero skipping (packed per-sample dense weights, §V-C) and operation
reordering (the batch-level sample schedule, §V-D). This module is the one
place that lowering happens. It owns

  * BN folding (inference-mode batchnorm folded into the preceding dense),
  * ``kept_indices`` gathering (mask → packed per-sample weight slices),
  * the sample schedule (batch-level by default; ``SlotSchedule``-compatible
    for the serving pool), and
  * kernel dispatch: every :class:`PackedPair` runs through
    ``kernels/masked_ffn`` (Pallas-TPU → Pallas-interpret → pure-XLA ref via
    the ``compat.kernel_backend`` probe), so the IVIM sub-networks hit the
    same kernel the transformer FFN does.

IR shape: a :class:`PackedPlan` is an ordered list of ops over a running
hidden state ``h`` (``[B, D]`` until the first packed op introduces the
sample axis, ``[G·N, B, D]`` after it):

  ========================  =================================================
  op                        semantics
  ========================  =================================================
  :class:`SharedDense`      ``h @ w + b`` with weights shared across samples
  :class:`PackedPair`       fused 2-layer FFN on per-mask gathered weights:
                            ``act(h @ w1p[n] + b1p[n]) @ w2p[n] + b2`` — the
                            masked_ffn kernel shape (act='relu' dispatches to
                            the kernel; other activations and per-sample
                            inputs take the sample-major einsum form)
  :class:`Activation`       elementwise nonlinearity
  :class:`OutputHead`       final (optionally per-mask in-gathered) dense +
                            output activation
  ========================  =================================================

Stacked sub-networks (IVIM's 4 identical chains) ride the kernel's sample
axis: ``groups=G`` flattens subnet × mask into ``G·N`` independent weight
sets applied to one shared batch — exactly what the batch-level grid
amortizes. The executor un-flattens at the end and applies the clinical
range conversion C(.) when ``out_ranges`` is set (the fused executor does
both inside its one jitted program).

Compile entry points (one per model family):
  * :func:`compile_ivim`        — uIVIM-NET (owns the BN folding)
  * :func:`compile_mlp`         — any ``transform.MaskedMlp`` chain
  * :func:`compile_masked_ffn`  — a bare masked relu-FFN (kernels entry)
  * :func:`pack_ffn_leaves`     — transformer FFN serving leaves (wgp/wup/wdp)

Exactness relies on the two invariants the rest of the repo property-tests:
masks keep exactly K units (masks.py I2, so gathers are rectangular) and
activations are zero-preserving (relu(z)·m == relu(z·m) for binary m).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import latency_model, packing
from repro.core import scheduler as sched_lib
from repro.core import uncertainty as unc_lib
from repro.kernels.fused_plan import ref as fused_ref
from repro.kernels.fused_plan.ref import FusedPlanUnsupported
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace

Params = dict[str, Any]

__all__ = [
    "SharedDense", "PackedPair", "Activation", "OutputHead", "PackedPlan",
    "Precision", "DTYPE_BYTES",
    "fold_bn_dense", "fold_bn_ivim", "compile_ivim", "compile_mlp",
    "compile_masked_ffn", "pack_ffn_leaves", "ffn_leaves_apply", "execute",
    "lower_fused", "execute_fused", "fused_executor",
    "FusedPlanUnsupported", "fused_trace_counts",
    "lower_fused_decode", "compile_decode_step", "decode_fused_spec",
    "prefill_buckets", "prefill_bucket", "PrefillSpec", "prefill_spec",
    "compile_prefill_step",
    "decode_traffic", "decode_stage_traffic", "decode_modeled_latency",
]

#: The one activation-name table for the mask pipeline and the model specs
#: that compile through it (transform.MaskedMlp resolves against this too —
#: a name that trains must also compile).
ACTIVATIONS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "relu": jax.nn.relu, "gelu": jax.nn.gelu, "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh, "identity": lambda x: x,
}


def activation_fn(name: str) -> Callable[[jax.Array], jax.Array]:
    """Resolve an activation name ('gelu_mlp' is the plain-MLP gelu)."""
    return ACTIVATIONS["gelu" if name == "gelu_mlp" else name]


#: Storage bytes per element by dtype tag — the per-tensor pricing table the
#: traffic models consult ("" = defer to the call's ``bytes_per_el``).
DTYPE_BYTES: dict[str, int] = {
    "float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def _dtype_bytes(tag: str, default: int) -> int:
    return DTYPE_BYTES.get(tag, default) if tag else default


@dataclasses.dataclass(frozen=True)
class Precision:
    """Serving precision policy of a :class:`PackedPlan`.

    ``weights``: storage dtype of the packed dense weights as they cross
    HBM→VMEM — "fp32" (native, the bitwise-gated default) or "int8"
    (per-output-channel symmetric quantization applied ONCE at
    ``lower_fused`` time, scales carried as bf16 param slots, dequant
    in-kernel next to the matmul; biases store as bf16 too). The KV-cache
    dtype is a *model/server* knob (``ModelConfig.kv_dtype`` /
    ``ServerConfig.kv_dtype``), not a plan property, so it lives there.
    """
    weights: str = "fp32"

    def __post_init__(self) -> None:
        if self.weights not in ("fp32", "int8"):
            raise ValueError(f"unknown weight precision {self.weights!r}")


# ---------------------------------------------------------------------------
# ops (static metadata; weights live in plan.params[op.name])
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SharedDense:
    """Sample-independent dense: params {w [D, D2], b [D2]?}."""
    name: str
    d_in: int
    d_out: int
    activation: str | None = None


@dataclasses.dataclass(frozen=True)
class PackedPair:
    """Fused 2-matrix packed FFN over per-mask gathered weights.

    params: w1p [Ne, d_in, keep], b1p [Ne, keep], w2p [Ne, keep, d_out] and
    either b2 [d_out] (shared) or b2p [Ne, d_out] (the pair's output units
    are themselves mask-gathered). The gated transformer FFN keeps its own
    leaf layout (:func:`pack_ffn_leaves` / :func:`ffn_leaves_apply`).

    ``d_in``/``d_out`` are the *packed* operand widths; ``d_in_full``/
    ``d_out_full``/``hidden`` record the unpacked widths so the latency and
    traffic models can price the pre-optimization baseline without
    re-deriving anything from the weights.
    """
    name: str
    d_in: int
    hidden: int
    keep: int
    d_out: int
    d_in_full: int = 0
    d_out_full: int = 0
    activation: str = "relu"

    def __post_init__(self) -> None:
        if self.d_in_full == 0:
            object.__setattr__(self, "d_in_full", self.d_in)
        if self.d_out_full == 0:
            object.__setattr__(self, "d_out_full", self.d_out)


@dataclasses.dataclass(frozen=True)
class Activation:
    """Elementwise nonlinearity between packed ops (no params)."""
    fn: str
    name: str = ""


@dataclasses.dataclass(frozen=True)
class OutputHead:
    """Terminal dense + output activation. per_mask=True → params
    {wp [Ne, d_in, d_out], bp [Ne, d_out] | b [d_out]} (input units are
    mask-gathered); else {w [d_in, d_out], b [d_out]?}."""
    name: str
    d_in: int
    d_out: int
    d_in_full: int = 0
    activation: str | None = None
    per_mask: bool = True

    def __post_init__(self) -> None:
        if self.d_in_full == 0:
            object.__setattr__(self, "d_in_full", self.d_in)


Op = SharedDense | PackedPair | Activation | OutputHead


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class PackedPlan:
    """Compiled serving program: ops + packed weights + sample schedule.

    ``groups`` stacked sub-networks share the kernel sample axis (row order
    group-major: row ``g * n_masks + n``); ``out_ranges`` is the optional
    clinical conversion C(.) applied per output column.
    """
    ops: tuple[Op, ...]
    params: Params
    n_masks: int
    groups: int = 1
    schedule: sched_lib.Schedule = sched_lib.Schedule("batch")
    out_ranges: tuple[tuple[float, float], ...] | None = None
    precision: Precision = Precision()

    @property
    def sample_axis(self) -> int:
        """Rows of the kernel's sample axis (groups × masks)."""
        return self.groups * self.n_masks

    def with_precision(self, precision: Precision) -> "PackedPlan":
        """Same plan (same fp32 master params), different serving precision.
        Quantization happens at ``lower_fused`` time, so distinct precisions
        lower to distinct (cached) fused specs."""
        return dataclasses.replace(self, precision=precision)

    @property
    def pairs(self) -> tuple[PackedPair, ...]:
        return tuple(op for op in self.ops if isinstance(op, PackedPair))

    def slot_schedule(self, max_slots: int) -> sched_lib.SlotSchedule:
        """The serving-pool row layout this plan's sample axis maps onto."""
        return sched_lib.SlotSchedule(n_masks=self.n_masks,
                                      max_slots=max_slots)

    def traffic(self, batch: int, bytes_per_el: int = 2,
                schedule: sched_lib.Schedule | None = None, *,
                fused: bool = False, moments: bool = False
                ) -> sched_lib.TrafficModel:
        """Modeled HBM traffic of one batch, fed straight from op metadata.

        Default (``fused=False``): summed pair traffic under a schedule
        (defaults to the plan's own) — the quantity the batch-level reorder
        optimizes. Each per-op kernel launch reads its input activations
        from HBM and writes its output back.

        ``fused=True`` prices the whole-plan megakernel
        (:func:`execute_fused`): every packed weight set — *all layers
        together* — crosses HBM→VMEM once per sample row
        (``weight_loads = sample_axis``), and inter-layer activations stay
        in VMEM scratch. With ``moments=True`` (weights-resident grid, the
        serving fast path) the input batch crosses once and only the
        predictive (mean, std) come back out; in samples mode the
        ``(n_rows, B/bB)`` grid re-fetches each input tile per sample row
        and writes the full ``[N, B, d_out]`` tensor. Shared prefix FLOPs
        are priced once (the moments kernel hoists them out of the sample
        loop).
        """
        n = self.sample_axis
        quant = self.precision.weights == "int8"
        wb = 1 if quant else bytes_per_el
        # The int8 bundle ships bf16 per-output-channel dequant scales (one
        # per output unit) and bf16 biases next to the int8 matrices — price
        # every tensor family at its own width.
        sb = 2 if quant else 0                    # scale bytes per d_out unit
        bb = 2 if quant else bytes_per_el         # bias bytes per element

        def wcost(rows: int, d_in: int, d_out: int) -> int:
            """HBM bytes of one weight matrix set [rows, d_in, d_out] at the
            plan's weight precision (+ its scale tensors when quantized)."""
            return rows * d_in * d_out * wb + rows * d_out * sb

        if not fused:
            schedule = schedule or self.schedule
            w = a = f = loads = 0
            for op in self.pairs:
                tm = sched_lib.traffic_model(schedule, batch, n, op.d_in,
                                             op.keep, op.d_out, bytes_per_el,
                                             weight_bytes_per_el=wb)
                w += tm.weight_bytes
                # per load set: scale tensors of the two packed matrices
                # (keep + d_out output units) and the bias repricing delta
                # (traffic_model prices biases at bytes_per_el)
                w += tm.weight_loads * (op.keep + op.d_out) \
                    * (sb + bb - bytes_per_el)
                a += tm.act_bytes
                f += tm.flops
                loads += tm.weight_loads
            return sched_lib.TrafficModel(weight_bytes=w, act_bytes=a,
                                          flops=f, weight_loads=loads)
        w_bytes = flops = 0
        d_first = d_last = None
        for op in self.ops:
            if isinstance(op, SharedDense):
                w_bytes += wcost(1, op.d_in, op.d_out) + op.d_out * bb
                flops += 2 * batch * op.d_in * op.d_out
            elif isinstance(op, PackedPair):
                w_bytes += wcost(n, op.d_in, op.keep) \
                    + wcost(n, op.keep, op.d_out) \
                    + n * (op.keep + op.d_out) * bb
                flops += 2 * n * batch * (op.d_in * op.keep
                                          + op.keep * op.d_out)
            elif isinstance(op, OutputHead):
                rows = n if op.per_mask else 1
                w_bytes += wcost(rows, op.d_in, op.d_out) \
                    + rows * op.d_out * bb
                flops += 2 * rows * batch * op.d_in * op.d_out
            else:
                continue
            if d_first is None:
                d_first = op.d_in
            d_last = op.d_out
        in_el = batch * d_first * (1 if moments else n)
        out_el = (2 * batch * self.groups * d_last if moments
                  else n * batch * d_last)
        act_bytes = (in_el + out_el) * bytes_per_el
        return sched_lib.TrafficModel(weight_bytes=w_bytes,
                                      act_bytes=act_bytes, flops=flops,
                                      weight_loads=n)

    def fused_spec(self) -> fused_ref.FusedSpec:
        """Static kernel spec of this plan's fused lowering (shape-key of
        the cached executor; raises FusedPlanUnsupported when the op chain
        has no fused form)."""
        return lower_fused(self)[0]

    def modeled_latency(self, batch: int, *,
                        spec: latency_model.TpuSpec = latency_model.V5E,
                        packed: bool = True, batch_level: bool = True,
                        bytes_per_el: int = 2, fused: bool = False,
                        moments: bool = True) -> float:
        """Eq.-2-analogue latency of one batch, summed over ops. With
        ``packed=False, batch_level=False`` this prices the conventional
        BayesNN baseline (full hidden widths, weights re-streamed per voxel
        chunk) on the same op list. ``fused=True`` prices the whole-plan
        megakernel instead: a single launch (one fill term) at the roofline
        of the fused traffic model — per-op kernel fills and inter-layer
        HBM round-trips disappear. ``moments`` (fused only) selects the
        in-kernel-moments variant (the serving fast path, default) vs the
        samples-mode grid that writes the full sample tensor."""
        n = self.sample_axis
        if fused:
            tm = self.traffic(batch, bytes_per_el, fused=True,
                              moments=moments)
            return max(tm.flops / spec.peak_flops_bf16,
                       tm.total_bytes / spec.hbm_bw) \
                + spec.kernel_fill_us * 1e-6
        t = 0.0
        for op in self.ops:
            if isinstance(op, PackedPair):
                t += latency_model.masked_ffn_latency(
                    batch, n, op.d_in if packed else op.d_in_full, op.hidden,
                    op.keep, op.d_out if packed else op.d_out_full,
                    packed=packed, batch_level=batch_level, spec=spec,
                    bytes_per_el=bytes_per_el)
            elif isinstance(op, SharedDense):
                t += latency_model.matmul_time(batch, op.d_in, op.d_out,
                                               spec, bytes_per_el)
            elif isinstance(op, OutputHead):
                d_in = op.d_in if packed else op.d_in_full
                per = latency_model.matmul_time(batch, d_in, op.d_out, spec,
                                                bytes_per_el)
                t += per * (n if op.per_mask else 1)
        return t


# ---------------------------------------------------------------------------
# BN folding (owned here — the compiler's one folding implementation)
# ---------------------------------------------------------------------------


def fold_bn_dense(fc: Params, bn: Params, st: Params,
                  eps: float = 1e-5) -> Params:
    """Fold inference-mode batchnorm into the preceding dense — exact at
    eval time: returns {w', b'} with w' = w·γ/√(σ²+ε)."""
    inv = bn["gamma"] * jax.lax.rsqrt(st["var"] + eps)
    return {"w": fc["w"] * inv[None, :],
            "b": (fc["b"] - st["mean"]) * inv + bn["beta"]}


def fold_bn_ivim(params: Params, state: Params) -> Params:
    """IVIM-shaped folding: fc1/fc2 carry bn1/bn2, all leaves stacked [G, ...]
    over sub-networks. Returns params with plain fc1/fc2 and no bn."""
    out = {k: v for k, v in params.items() if k not in ("bn1", "bn2")}
    fold = jax.vmap(fold_bn_dense)
    out["fc1"] = fold(params["fc1"], params["bn1"], state["bn1"])
    out["fc2"] = fold(params["fc2"], params["bn2"], state["bn2"])
    return out


# ---------------------------------------------------------------------------
# compilers
# ---------------------------------------------------------------------------


def _host_masks(masks) -> np.ndarray:
    return np.asarray(jax.device_get(masks)).astype(bool)


def compile_masked_ffn(w1: jax.Array, b1: jax.Array, w2: jax.Array,
                       b2: jax.Array, masks) -> PackedPlan:
    """A bare masked relu-FFN (the masked_ffn kernel's own shape):
    relu(x @ w1 + b1) · mask[n] @ w2 + b2 → one PackedPair."""
    idx = packing.kept_indices(_host_masks(masks))
    params = {"pair": {"w1p": packing.pack_out_dim(w1, idx),
                       "b1p": packing.pack_out_dim(b1, idx),
                       "w2p": packing.pack_in_dim(w2, idx),
                       "b2": b2}}
    op = PackedPair("pair", d_in=w1.shape[0], hidden=w1.shape[1],
                    keep=idx.shape[1], d_out=w2.shape[1])
    return PackedPlan(ops=(op,), params=params, n_masks=idx.shape[0])


def compile_ivim(cfg, params: Params, state: Params) -> PackedPlan:
    """uIVIM-NET → PackedPlan (cfg: repro.ivim.model.IvimConfig, duck-typed).

    Folds BN, gathers the fc1→fc2→enc chain (mask1 on fc1's outputs, mask2
    on fc2's), and flattens the 4 sub-networks onto the kernel sample axis:
    w1p [4N, Nb, K1], w2p [4N, K1, K2], w3p [4N, K2, 1]. One shared voxel
    batch streams through 4N independent weight sets — the batch-level
    schedule, with sub-network parallelism for free (deviation §8.4).
    """
    if not cfg.bayesian:
        raise ValueError("packing requires a Masksembles model")
    p = fold_bn_ivim(params, state) if cfg.use_batchnorm else params
    idx1 = packing.kept_indices(_host_masks(p["mask1"]))
    idx2 = packing.kept_indices(_host_masks(p["mask2"]))
    k1, k2 = idx1.shape[1], idx2.shape[1]
    groups = p["fc1"]["w"].shape[0]
    width = cfg.width

    def flat(x: jax.Array) -> jax.Array:            # [G, N, ...] -> [G·N, ...]
        return x.reshape((-1,) + x.shape[2:])

    out1 = jax.vmap(lambda leaf: packing.pack_out_dim(leaf, idx1))
    out2 = jax.vmap(lambda leaf: packing.pack_out_dim(leaf, idx2))
    body = {"w1p": flat(out1(p["fc1"]["w"])),       # [G·N, Nb, K1]
            "b1p": flat(out1(p["fc1"]["b"])),       # [G·N, K1]
            "w2p": flat(jax.vmap(
                lambda leaf: packing.pack_pair_dims(leaf, idx1, idx2))(
                    p["fc2"]["w"])),                # [G·N, K1, K2]
            "b2p": flat(out2(p["fc2"]["b"]))}       # [G·N, K2]
    head = {"wp": flat(jax.vmap(
                lambda leaf: packing.pack_in_dim(leaf, idx2))(
                    p["enc"]["w"])),                # [G·N, K2, 1]
            "bp": jnp.repeat(p["enc"]["b"], idx1.shape[0], axis=0)}
    ops = (
        PackedPair("body", d_in=width, hidden=width, keep=k1, d_out=k2,
                   d_out_full=width, activation="relu"),
        Activation("relu"),
        OutputHead("head", d_in=k2, d_in_full=width, d_out=1,
                   activation="sigmoid", per_mask=True),
    )
    return PackedPlan(ops=ops, params={"body": body, "head": head},
                      n_masks=cfg.n_masks, groups=groups,
                      out_ranges=tuple(cfg.out_ranges))


def compile_mlp(model) -> PackedPlan:
    """Any ``transform.MaskedMlp`` chain → PackedPlan.

    Grammar: leading unmasked hidden layers become :class:`SharedDense`; a
    run of consecutive masked hidden layers packs pairwise with its
    successor (out-gather + paired in/out-gather); the final layer becomes
    an :class:`OutputHead` (in-gathered when the last hidden was masked) or
    is absorbed into the trailing pair. Chains that interleave unmasked
    hidden layers *inside* a masked run are not expressible with packed
    gathers alone and raise NotImplementedError.
    """
    spec, params = model.spec, model.params
    widths = spec.widths
    n_layers = len(widths) - 1
    ops: list[Op] = []
    plan_params: Params = {}
    cur_idx: np.ndarray | None = None
    i = 0
    head_done = False
    while i < n_layers - 1:
        layer = params[f"fc{i}"]
        if "masks" not in layer:
            if cur_idx is not None:
                raise NotImplementedError(
                    "unmasked hidden layer with mask-gathered input "
                    f"(layer {i}); reorder dropout slots to a trailing run")
            name = f"fc{i}"
            ops.append(SharedDense(name, d_in=widths[i], d_out=widths[i + 1],
                                   activation=spec.activation))
            plan_params[name] = {"w": layer["w"], "b": layer["b"]}
            i += 1
            continue
        # masked layer i pairs with its successor (hidden or output layer)
        idx = packing.kept_indices(_host_masks(layer["masks"]))
        if cur_idx is None:
            w1p = packing.pack_out_dim(layer["w"], idx)
            d_in = widths[i]
        else:
            w1p = packing.pack_pair_dims(layer["w"], cur_idx, idx)
            d_in = cur_idx.shape[1]
        entry: Params = {"w1p": w1p, "b1p": packing.pack_out_dim(layer["b"],
                                                                 idx)}
        nxt = params[f"fc{i + 1}"]
        nxt_masked = "masks" in nxt
        if nxt_masked:
            nidx = packing.kept_indices(_host_masks(nxt["masks"]))
            entry["w2p"] = packing.pack_pair_dims(nxt["w"], idx, nidx)
            entry["b2p"] = packing.pack_out_dim(nxt["b"], nidx)
            d_out, cur_idx = nidx.shape[1], nidx
        else:
            entry["w2p"] = packing.pack_in_dim(nxt["w"], idx)
            entry["b2"] = nxt["b"]
            d_out, cur_idx = widths[i + 2], None
        name = f"pair{i}"
        ops.append(PackedPair(name, d_in=d_in, d_in_full=widths[i],
                              hidden=widths[i + 1], keep=idx.shape[1],
                              d_out=d_out, d_out_full=widths[i + 2],
                              activation=spec.activation))
        plan_params[name] = entry
        if i + 1 == n_layers - 1:       # the pair consumed the output layer
            if spec.final_activation:
                ops.append(Activation(spec.final_activation))
            head_done = True
        else:
            ops.append(Activation(spec.activation))
        i += 2
    if not head_done:
        layer = params[f"fc{n_layers - 1}"]
        if cur_idx is not None:
            plan_params["head"] = {"wp": packing.pack_in_dim(layer["w"],
                                                             cur_idx),
                                   "b": layer["b"]}
            ops.append(OutputHead("head", d_in=cur_idx.shape[1],
                                  d_in_full=widths[n_layers - 1],
                                  d_out=widths[n_layers],
                                  activation=spec.final_activation,
                                  per_mask=True))
        else:
            plan_params["head"] = {"w": layer["w"], "b": layer["b"]}
            ops.append(OutputHead("head", d_in=widths[n_layers - 1],
                                  d_out=widths[n_layers],
                                  activation=spec.final_activation,
                                  per_mask=False))
    return PackedPlan(ops=tuple(ops), params=plan_params,
                      n_masks=model.n_masks)


def pack_ffn_leaves(ffn: Params, masks) -> Params:
    """Transformer FFN block params {wg?, wu, wd} (leaves optionally stacked
    [R, ...] over scan reps) + masks [N, F] → packed serving leaves
    {wgp?, wup [.., N, D, K], wdp [.., N, K, D]} — the compiler-built form
    ``models.layers.ffn_apply`` executes (via :func:`ffn_leaves_apply`)."""
    idx = packing.kept_indices(_host_masks(masks))

    def out_g(w: jax.Array) -> jax.Array:          # [.., D, F] -> [.., N, D, K]
        return jnp.moveaxis(packing.gather_units(w, idx, axis=-1), 0, -3)

    def in_g(w: jax.Array) -> jax.Array:           # [.., F, D] -> [.., N, K, D]
        return jnp.moveaxis(packing.gather_units(w, idx, axis=-2), 0, -3)

    out = {"wup": out_g(ffn["wu"]["w"]), "wdp": in_g(ffn["wd"]["w"])}
    if "wg" in ffn:
        out["wgp"] = out_g(ffn["wg"]["w"])
    return out


def ffn_leaves_apply(p: Params, x: jax.Array, activation: str) -> jax.Array:
    """Execute packed transformer-FFN leaves: x [B, S, D] with rows grouped
    mask-major (row j uses mask j // (B/N)) → same shape. The gated form
    (wgp present) is silu/gelu-gated; hidden width is the kept K only."""
    act = activation_fn(activation)
    n = p["wdp"].shape[0]
    b = x.shape[0]
    if b % n != 0:
        raise ValueError(
            f"ffn_leaves_apply: batch rows {b} not divisible by the "
            f"packed mask count {n} — rows must be grouped mask-major")
    xg = x.reshape(n, b // n, *x.shape[1:])        # [N, B/N, S, D]
    if "wgp" in p:
        h = act(jnp.einsum("nbsd,ndk->nbsk", xg, p["wgp"])) * \
            jnp.einsum("nbsd,ndk->nbsk", xg, p["wup"])
    else:
        h = act(jnp.einsum("nbsd,ndk->nbsk", xg, p["wup"]))
    y = jnp.einsum("nbsk,nkd->nbsd", h, p["wdp"])
    return y.reshape(x.shape)


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

#: Explicit per-call backend override -> kernel ``interpret=`` flag
#: (None defers to the process-wide probe). One table for both executors.
_BACKEND_INTERPRET: dict[str | None, bool | None] = {
    None: None, "pallas-tpu": False, "pallas-interpret": True}


def _quantize_weight(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-output-channel symmetric int8 of one weight matrix set [.., D, K]
    -> (q int8 [.., D, K], scales bf16 [.., 1, K]).

    The one quantizer every precision path shares — ``distributed.
    compression.quantize_int8``'s per-row symmetric scheme applied along
    each output unit's fan-in (its rows are the *columns* of w, the
    standard per-channel weight layout), so the per-op and fused executors
    see identical quantized values. Scales store as bf16: one scale per
    output unit, lane-aligned next to the weight tile, and the ~2^-9
    relative rounding is far inside the int8 step itself."""
    from repro.distributed import compression
    q, s = compression.quantize_int8(jnp.swapaxes(w, -1, -2))
    return (jnp.swapaxes(q, -1, -2),
            jnp.swapaxes(s, -1, -2).astype(jnp.bfloat16))


def _dequantized(w: jax.Array) -> jax.Array:
    """Round-trip a weight through the serving quantizer: the f32 values the
    int8 kernels compute with (per-op einsum paths use this so every op kind
    of an int8 plan matches the fused int8 graph)."""
    q, s = _quantize_weight(w)
    return q.astype(jnp.float32) * s.astype(jnp.float32)


def _low_bias(b: jax.Array) -> jax.Array:
    """Bias storage dtype of the int8 serving bundle: bf16. Every use site
    (kernel, oracle, einsum paths) upcasts biases before the add, so the
    storage cast is the only value change — and it is shared by the per-op
    and fused executors, which keeps them bitwise-aligned."""
    return b.astype(jnp.bfloat16)


def _run_pair(op: PackedPair, p: Params, h: jax.Array, backend: str | None,
              kernel_kw: dict, precision: Precision = Precision()
              ) -> jax.Array:
    """One PackedPair. Shared input [B, D] with relu dispatches through the
    masked_ffn kernel stack; per-sample input or non-relu activations take
    the sample-major einsum form (same batch-level contraction order).
    int8 precision quantizes here (same quantizer as ``lower_fused``) and
    hands the masked_ffn kernel int8 weights + scale operands."""
    quant = precision.weights == "int8"
    if h.ndim == 2 and op.activation == "relu":
        b2 = p.get("b2")
        if b2 is None:
            b2 = jnp.zeros((p["w2p"].shape[-1],), h.dtype)
        w1p, w2p, b1p = p["w1p"], p["w2p"], p["b1p"]
        scales: tuple[jax.Array, ...] = ()
        if quant:
            w1p, s1 = _quantize_weight(w1p)
            w2p, s2 = _quantize_weight(w2p)
            scales = (s1, s2)
            b1p, b2 = _low_bias(b1p), _low_bias(b2)
        if backend == "xla":
            from repro.kernels.masked_ffn import ref as mffn_ref
            y = mffn_ref.masked_ffn_ref(h, w1p, b1p, w2p, b2, *scales)
        else:
            from repro.kernels.masked_ffn import ops as mffn_ops
            kw = dict(kernel_kw)
            # an explicit interpret= from the caller wins over the backend
            kw.setdefault("interpret", _BACKEND_INTERPRET[backend])
            y = mffn_ops.masked_ffn(h, w1p, b1p, w2p, b2, *scales, **kw)
        if "b2p" in p:
            b2p = _low_bias(p["b2p"]) if quant else p["b2p"]
            y = y + b2p[:, None, :].astype(y.dtype)
        return y
    act = activation_fn(op.activation)
    w1p = _dequantized(p["w1p"]) if quant else p["w1p"]
    w2p = _dequantized(p["w2p"]) if quant else p["w2p"]
    b1p = _low_bias(p["b1p"]) if quant else p["b1p"]
    lead = "bd" if h.ndim == 2 else "nbd"
    hm = act(jnp.einsum(f"{lead},ndk->nbk", h, w1p)
             + b1p[:, None, :].astype(h.dtype))
    y = jnp.einsum("nbk,nkm->nbm", hm, w2p)
    if "b2p" in p:
        b2p = _low_bias(p["b2p"]) if quant else p["b2p"]
        return y + b2p[:, None, :].astype(y.dtype)
    if "b2" in p:
        b2 = _low_bias(p["b2"]) if quant else p["b2"]
        return y + b2.astype(y.dtype)
    return y


def execute(plan: PackedPlan, x: jax.Array, *, backend: str | None = None,
            **kernel_kw) -> jax.Array:
    """Run a PackedPlan on a batch x [B, D] → samples [N, B, d_out].

    backend: None → the process-wide ``compat.kernel_backend`` probe;
    "xla" | "pallas-interpret" | "pallas-tpu" force a tier (in-process A/B —
    the equivalence tests exercise xla and interpret side by side).
    kernel_kw (block_b, sample_major) forward to the kernel wrapper.
    ``plan.precision`` int8 runs every weight through the serving quantizer
    (kernel slots on the masked_ffn path, quantize-dequantize on the shared
    einsum ops) — the same values the fused int8 graph computes with.
    """
    quant = plan.precision.weights == "int8"
    h = x
    for op in plan.ops:
        if isinstance(op, Activation):
            h = activation_fn(op.fn)(h)
        elif isinstance(op, SharedDense):
            p = plan.params[op.name]
            w = _dequantized(p["w"]) if quant else p["w"]
            if h.ndim == 2:
                h = h @ w
            else:
                h = jnp.einsum("nbd,do->nbo", h, w)
            if "b" in p:
                h = h + (_low_bias(p["b"]).astype(h.dtype) if quant
                         else p["b"])
            if op.activation:
                h = activation_fn(op.activation)(h)
        elif isinstance(op, PackedPair):
            h = _run_pair(op, plan.params[op.name], h, backend, kernel_kw,
                          plan.precision)
        elif isinstance(op, OutputHead):
            p = plan.params[op.name]
            if op.per_mask:
                wp = _dequantized(p["wp"]) if quant else p["wp"]
                h = jnp.einsum("nbk,nko->nbo", h, wp)
                if "bp" in p:
                    bp = _low_bias(p["bp"]) if quant else p["bp"]
                    h = h + bp[:, None, :].astype(h.dtype)
            else:
                w = _dequantized(p["w"]) if quant else p["w"]
                lead = "bk" if h.ndim == 2 else "nbk"
                h = jnp.einsum(f"{lead},ko->{'bo' if h.ndim == 2 else 'nbo'}",
                               h, w)
            if "b" in p:
                h = h + (_low_bias(p["b"]).astype(h.dtype) if quant
                         else p["b"])
            if op.activation:
                h = activation_fn(op.activation)(h)
        else:
            raise TypeError(f"unknown plan op {op!r}")
    if h.ndim == 2:                     # no packed ops: one degenerate sample
        h = h[None]
    return _finalize(plan, h)


def _finalize(plan: PackedPlan, h: jax.Array) -> jax.Array:
    """Executor epilogue: un-flatten the kernel sample axis and apply C(.)."""
    h = _unflatten_groups(h, plan.groups, plan.n_masks)
    if plan.out_ranges is not None:     # C(.): clinical range conversion
        lo = jnp.asarray([r[0] for r in plan.out_ranges], h.dtype)
        hi = jnp.asarray([r[1] for r in plan.out_ranges], h.dtype)
        h = lo + h * (hi - lo)
    return h


def _unflatten_groups(h: jax.Array, g: int, n: int) -> jax.Array:
    """[G·N, B, Do] -> [N, B, G·Do] (group-major columns)."""
    if g == 1:
        return h
    b, do = h.shape[1], h.shape[2]
    return jnp.moveaxis(h.reshape(g, n, b, do), 0, 2).reshape(n, b, g * do)


# ---------------------------------------------------------------------------
# fused whole-plan executor (kernels/fused_plan megakernel)
# ---------------------------------------------------------------------------


def lower_fused(plan: PackedPlan
                ) -> tuple[fused_ref.FusedSpec, tuple[jax.Array, ...]]:
    """Lower the op chain to the fused megakernel IR.

    Returns ``(spec, params)``: a hashable :class:`kernels.fused_plan.ref.
    FusedSpec` — a flat chain of dense/elementwise steps with each weight
    tagged sample-shared or per-row — plus the flat param tuple in
    ``param_slots`` order. A trailing :class:`Activation` fuses into the
    preceding dense step; a PackedPair lowers to two dense steps (its hidden
    activation becomes a VMEM-resident intermediate of the megakernel).
    Raises :class:`FusedPlanUnsupported` for op kinds with no fused form.

    When ``plan.precision.weights == "int8"``, every dense weight is
    quantized HERE — once per lowering, per-output-channel symmetric scales
    (``distributed.compression.quantize_int8`` along each unit's fan-in) —
    so the int8 tensor + bf16 scale pair is what the cached executors close
    over and what crosses HBM→VMEM; the dequant happens in-kernel next to
    the matmul. Biases store as bf16 in the same bundle. The fp32 default
    takes the untouched path (the identical param arrays, a scale-free
    spec), so it stays bitwise-gated.
    """
    steps: list[fused_ref.FusedStep] = []
    params: list[jax.Array] = []
    for op in plan.ops:
        if isinstance(op, Activation):
            if steps and steps[-1].kind == "dense" \
                    and steps[-1].activation is None:
                steps[-1] = dataclasses.replace(steps[-1], activation=op.fn)
            else:
                steps.append(fused_ref.FusedStep("act", activation=op.fn))
            continue
        if isinstance(op, SharedDense):
            p = plan.params[op.name]
            steps.append(fused_ref.FusedStep(
                "dense", op.activation, shared_bias="b" in p,
                d_in=op.d_in, d_out=op.d_out))
            params.append(p["w"])
            if "b" in p:
                params.append(p["b"])
        elif isinstance(op, PackedPair):
            p = plan.params[op.name]
            steps.append(fused_ref.FusedStep(
                "dense", op.activation, per_sample=True, sample_bias=True,
                d_in=op.d_in, d_out=op.keep))
            params += [p["w1p"], p["b1p"]]
            steps.append(fused_ref.FusedStep(
                "dense", None, per_sample=True, shared_bias="b2" in p,
                sample_bias="b2p" in p, d_in=op.keep, d_out=op.d_out))
            params.append(p["w2p"])
            if "b2" in p:
                params.append(p["b2"])
            if "b2p" in p:
                params.append(p["b2p"])
        elif isinstance(op, OutputHead):
            p = plan.params[op.name]
            steps.append(fused_ref.FusedStep(
                "dense", op.activation, per_sample=op.per_mask,
                shared_bias="b" in p, sample_bias="bp" in p,
                d_in=op.d_in, d_out=op.d_out))
            params.append(p["wp"] if op.per_mask else p["w"])
            if "b" in p:
                params.append(p["b"])
            if "bp" in p:
                params.append(p["bp"])
        else:
            raise FusedPlanUnsupported(f"op {op!r} has no fused lowering")
    if plan.precision.weights == "int8":
        steps, params = _quantize_lowering(steps, params)
    dense = [s for s in steps if s.kind == "dense"]
    spec = fused_ref.FusedSpec(steps=tuple(steps), n_rows=plan.sample_axis,
                               n_masks=plan.n_masks, groups=plan.groups,
                               d_in=dense[0].d_in, d_out=dense[-1].d_out)
    return spec, tuple(params)


def _quantize_lowering(steps: list, params: list
                       ) -> tuple[list, list]:
    """Rewrite a lowered (steps, params) chain to the int8 serving bundle:
    each dense step's ``w`` becomes (int8 q, bf16 per-output-channel scale)
    and the step is tagged ``w_dtype="int8"`` (which makes ``param_slots``
    emit the extra 'ws' slot); bias params store as bf16."""
    new_steps: list = []
    new_params: list = []
    pi = 0
    for st in steps:
        if st.kind != "dense":
            new_steps.append(st)
            continue
        q, s = _quantize_weight(params[pi])
        pi += 1
        new_steps.append(dataclasses.replace(st, w_dtype="int8"))
        new_params += [q, s]
        if st.shared_bias:
            new_params.append(_low_bias(params[pi]))
            pi += 1
        if st.sample_bias:
            new_params.append(_low_bias(params[pi]))
            pi += 1
    return new_steps, new_params


#: Trace counters of the cached fused executors, keyed by
#: ``(spec, backend, moments)`` — incremented once per jit trace, so
#: repeated same-shape ``predict_packed`` calls must leave them at 1.
#: A registry-backed :class:`repro.obs.registry.KeyedCounter` with the old
#: bare-``collections.Counter`` mapping surface (compatibility alias), so
#: it resets/snapshots/exposes with every other instrument
#: (tests/conftest.py write-isolates it per test).
fused_trace_counts = obs_registry.REGISTRY.keyed_counter(
    "fused_trace_total",
    "jit traces of the cached fused executors, by (spec, backend, stage)")

_RETRACES = obs_registry.REGISTRY.counter(
    "retrace_total", "jit traces of the cached plan executors",
    labels=("stage", "backend"))
_DISPATCH = obs_registry.REGISTRY.counter(
    "kernel_dispatch_total",
    "kernel-backend tier selected at executor trace time",
    labels=("tier", "precision"))


def _note_trace(stage: str, backend: str | None,
                precision: str = "fp32") -> None:
    """Registry + tracer breadcrumbs of ONE jit trace of a cached executor.
    Runs at trace time only — zero steady-state cost; an idle serving loop
    must leave ``retrace_total`` flat (the no-retrace observable the
    tracing-overhead gate in benchmarks/bench_serving.py checks).
    ``precision`` labels the dispatch ("fp32", "int8" weights, or the
    serving path's KV tag, e.g. "kv-bfloat16") so precision regressions
    show in the registry snapshot."""
    from repro import compat
    tier = backend if backend is not None else compat.kernel_backend()
    _RETRACES.inc(stage=stage, backend=backend or "auto")
    _DISPATCH.inc(tier=tier, precision=precision)
    obs_trace.TRACER.event("retrace", stage=stage,
                           backend=backend or "auto", tier=tier,
                           precision=precision)


@functools.lru_cache(maxsize=128)
def _fused_runner(spec: fused_ref.FusedSpec, backend: str | None,
                  moments: bool, block_b: int):
    """One jitted executor per (plan shape-key, backend, mode) — the plan
    analogue of serving/server's ``step_fns`` cache: the returned callable
    is stable across calls, so jit's own shape cache applies and repeated
    ``predict_packed`` calls stop retracing.

    ``run(x, params, bounds)`` returns the executor's final result: the
    group un-flattening (samples mode) and the range conversion C(.) run in
    the same program as the kernel. ``bounds`` is ``(lo, hi)`` device
    arrays of the plan's ``out_ranges``, or None for a plan without C(.).
    """

    prec = ("int8" if any(s.w_dtype == "int8" for s in spec.steps)
            else "fp32")

    def run(x: jax.Array, params: tuple[jax.Array, ...],
            bounds: tuple[jax.Array, jax.Array] | None):
        fused_trace_counts[(spec, backend, moments)] += 1
        _note_trace("fused_plan", backend, prec)
        if backend == "xla":
            fn = (fused_ref.fused_moments_ref if moments
                  else fused_ref.fused_plan_ref)
            out = fn(spec, x, params)
        else:
            from repro.kernels.fused_plan import ops as fp_ops
            out = fp_ops.fused_plan(spec, x, params, moments=moments,
                                    block_b=block_b,
                                    interpret=_BACKEND_INTERPRET[backend])
        if not moments:
            out = _unflatten_groups(out, spec.groups, spec.n_masks)
            if bounds is None:
                return out
            lo, hi = (b.astype(out.dtype) for b in bounds)
            return lo + out * (hi - lo)     # C(.): clinical range conversion
        if bounds is None:
            return out
        mean, std = out                 # [B, G·do], group-major columns
        lo, hi = (b.astype(mean.dtype) for b in bounds)
        # C(.) is affine: it commutes with E[.] and scales the std by |hi-lo|
        return lo + mean * (hi - lo), std * jnp.abs(hi - lo)

    return jax.jit(run)


def fused_executor(plan: PackedPlan, *, moments: bool = False,
                   backend: str | None = None,
                   block_b: int = 128) -> Callable[[jax.Array], Any]:
    """Lower once, serve many: returns ``x -> fused result`` bound to the
    cached jitted runner, so chunk-streaming hot paths (serving/engine) pay
    the Python lowering a single time per call, not once per chunk.

    Each call is one host dispatch of one device program, C(.) included:
    the ``out_ranges`` bounds are uploaded here, once, and passed to the
    runner next to the params, so a device-resident ``x`` moves nothing
    from host to device.

    Raises :class:`FusedPlanUnsupported` immediately when the op chain has
    no fused lowering; the moments-mode VMEM-residency guard fires later,
    from the first ``apply`` (trace time) — callers that want the per-op
    fallback must catch around that first call too.
    """
    if backend not in (None, "xla", "pallas-interpret", "pallas-tpu"):
        raise ValueError(f"unknown backend {backend!r}")
    spec, params = lower_fused(plan)
    runner = _fused_runner(spec, backend, moments, block_b)
    bounds = None
    if plan.out_ranges is not None:
        bounds = tuple(jnp.asarray([r[i] for r in plan.out_ranges],
                                   jnp.float32) for i in (0, 1))

    def apply(x: jax.Array):
        tr = obs_trace.TRACER
        # the jitted call, with its implicit host->device copy of x
        with tr.span("plan.dispatch"):
            out = runner(x, params, bounds)
        # C(.) runs inside the program now; the span stays, empty, so the
        # per-chunk span set that bench/tests/test_bench_spans.py pins
        # still holds, and a traced split reads the epilogue at ~0
        with tr.span("plan.epilogue"):
            return out

    return apply


def execute_fused(plan: PackedPlan, x: jax.Array, *, moments: bool = False,
                  backend: str | None = None, block_b: int = 128):
    """Run the whole plan in ONE kernel launch (kernels/fused_plan).

    x [B, D] -> samples [N, B, d_out], or ``moments=True`` ->
    (mean [B, d_out], std [B, d_out]) reduced over the mask axis *inside*
    the kernel (running Welford mean/M2), so the full sample tensor is
    never materialized. Matches ``execute`` / ``uncertainty.
    predictive_moments(execute(...))`` to fp32 tolerance.

    backend: None -> the process-wide ``compat.kernel_backend`` probe;
    "xla" | "pallas-interpret" | "pallas-tpu" force a tier. Executors are
    cached per (plan shape-key, backend, mode) — see :data:`fused_trace_
    counts`. Raises :class:`FusedPlanUnsupported` when the plan has no
    fused form or (moments mode) its resident footprint exceeds the VMEM
    guard (callers fall back to :func:`execute`).
    """
    return fused_executor(plan, moments=moments, backend=backend,
                          block_b=block_b)(x)


# ---------------------------------------------------------------------------
# fused serving-decode step (kernels/fused_plan decode megakernel)
# ---------------------------------------------------------------------------
#
# The decode-side twin of lower_fused/execute_fused: one serving decode step
# of the whole mask-expanded slot pool — KV gather -> attention over the
# slot-pool cache -> (packed) Bayesian FFN -> in-kernel Welford posterior —
# lowered onto the same FusedStep vocabulary and executed as ONE launch.
# serving/server.step_fns routes its decode hot loop through
# compile_decode_step, with the per-op transformer.decode_step path as the
# FusedPlanUnsupported fallback.


def lower_fused_decode(cfg, *, expand_masks: bool = True
                       ) -> fused_ref.FusedDecodeSpec:
    """Lower a ModelConfig's serving decode step to the fused decode IR.

    The chain is the unrolled attention-block stack
    ``(norm, attn, norm, ffn) × L + (final norm, lm-head dense)`` — scan
    segments flatten rep-major, matching ``_decode_flat_params``. Raises
    :class:`FusedPlanUnsupported` for configs with no fused decode form
    (non-causal, M-RoPE, or any block kind other than attn/local_attn —
    MoE routing and the recurrent families keep the per-op path).
    """
    if not cfg.causal:
        raise FusedPlanUnsupported("encoder-only config has no decode step")
    if cfg.m_rope_sections:
        raise FusedPlanUnsupported("M-RoPE decode has no fused lowering")
    if getattr(cfg, "kv_lora_rank", 0):
        raise FusedPlanUnsupported("latent attention has no fused lowering")
    kv_dtype = getattr(cfg, "kv_dtype", "")
    if kv_dtype == "int8":
        # int8 caches carry per-position scale leaves the single-program
        # decode kernel does not thread; the per-op path serves them.
        raise FusedPlanUnsupported(
            "int8 KV cache has no fused decode lowering (per-op path "
            "dequantizes at the attention gather)")
    d, dh = cfg.d_model, cfg.resolved_head_dim
    rot = int(dh * cfg.rope_pct)
    rot -= rot % 2
    bayes = cfg.bayesian and expand_masks
    n = cfg.mask_samples if bayes else 1
    packed = cfg.bayesian and cfg.packed_ffn_serving
    gated = cfg.activation in ("silu", "gelu")
    ln_bias = cfg.norm == "layernorm"
    if packed:
        from repro.core import masks as masks_lib
        d_hidden = masks_lib.keep_count(cfg.d_ff, cfg.mask_samples,
                                        cfg.mask_scale)
    else:
        d_hidden = cfg.d_ff
    steps: list[fused_ref.FusedStep] = []
    for seg in cfg.segments():
        for kind in seg.pattern:
            if kind not in ("attn", "local_attn"):
                raise FusedPlanUnsupported(
                    f"block kind {kind!r} has no fused decode lowering")
        for _ in range(seg.reps):
            for kind in seg.pattern:
                steps.append(fused_ref.FusedStep(
                    "norm", norm=cfg.norm, shared_bias=ln_bias,
                    d_in=d, d_out=d))
                steps.append(fused_ref.FusedStep(
                    "attn", d_in=d, d_out=d, n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads, head_dim=dh, rot_dim=rot,
                    qkv_bias=cfg.qkv_bias,
                    window=cfg.local_window if kind == "local_attn" else 0))
                steps.append(fused_ref.FusedStep(
                    "norm", norm=cfg.norm, shared_bias=ln_bias,
                    d_in=d, d_out=d))
                steps.append(fused_ref.FusedStep(
                    "ffn", activation=cfg.activation, gated=gated,
                    per_sample=packed, masked=cfg.bayesian and not packed,
                    ffn_bias=not gated and not packed, d_hidden=d_hidden,
                    d_in=d, d_out=d))
    steps.append(fused_ref.FusedStep("norm", norm=cfg.norm,
                                     shared_bias=ln_bias, d_in=d, d_out=d))
    steps.append(fused_ref.FusedStep("dense", d_in=d, d_out=cfg.vocab_size))
    return fused_ref.FusedDecodeSpec(steps=tuple(steps), n_samples=n,
                                     d_model=d, vocab=cfg.vocab_size,
                                     kv_dtype=kv_dtype)


def _decode_mask_ids(cfg, rows: int, expand_masks: bool) -> jax.Array:
    """Per-row mask assignment of the decode pool — the same ids the per-op
    path uses (mask-major groups when expanded, the Masksembles batch-group
    default otherwise)."""
    from repro.core import masksembles
    n = cfg.mask_samples
    if expand_masks:
        return jnp.repeat(jnp.arange(n), rows // n)
    return masksembles.mask_ids_for_batch(rows, n)


def _decode_flat_params(spec: fused_ref.FusedDecodeSpec, cfg, params: Params,
                        rows: int, expand_masks: bool
                        ) -> tuple[jax.Array, ...]:
    """Flatten the transformer param pytree into ``decode_param_slots``
    order (scan-stacked leaves sliced per rep; the Bayesian mask matrix
    pre-gathered per row)."""
    flat: list[jax.Array] = []

    def push_norm(p):
        flat.append(p["scale"])
        if "bias" in p:
            flat.append(p["bias"])

    for si, seg in enumerate(cfg.segments()):
        seg_params = params["segments"][si]
        for r in range(seg.reps):
            for bi in range(len(seg.pattern)):
                block = jax.tree.map(lambda a, r=r: a[r],
                                     seg_params[f"b{bi}"])
                push_norm(block["norm1"])
                at = block["attn"]
                for w in ("wq", "wk", "wv"):
                    flat.append(at[w]["w"])
                    if "b" in at[w]:
                        flat.append(at[w]["b"])
                flat.append(at["wo"]["w"])
                push_norm(block["norm2"])
                ffn = block["ffn"]
                if "wdp" in ffn:                    # packed serving leaves
                    if "wgp" in ffn:
                        flat.append(ffn["wgp"])
                    flat += [ffn["wup"], ffn["wdp"]]
                else:
                    if "wg" in ffn:
                        flat.append(ffn["wg"]["w"])
                    flat.append(ffn["wu"]["w"])
                    if "b" in ffn["wu"]:
                        flat.append(ffn["wu"]["b"])
                    flat.append(ffn["wd"]["w"])
                    if "b" in ffn["wd"]:
                        flat.append(ffn["wd"]["b"])
                    if "masks" in ffn:
                        ids = _decode_mask_ids(cfg, rows, expand_masks)
                        flat.append(ffn["masks"][ids])
    push_norm(params["final_norm"])
    emb = params["embed"]
    flat.append(emb["unembed"]["w"] if "unembed" in emb
                else emb["embed"].T)
    want = len(fused_ref.decode_param_slots(spec))
    if len(flat) != want:
        raise FusedPlanUnsupported(
            f"param pytree does not match the lowered decode spec "
            f"({len(flat)} arrays vs {want} slots)")
    return tuple(flat)


def _decode_flat_caches(cfg, caches) -> tuple[jax.Array, ...]:
    """Flatten pooled KV caches to ``(k, v, kpos)`` per 'attn' step, in the
    lowering's rep-major step order."""
    flat: list[jax.Array] = []
    for si, seg in enumerate(cfg.segments()):
        for r in range(seg.reps):
            for bi in range(len(seg.pattern)):
                c = caches[si][f"b{bi}"]
                flat += [c["k"][r], c["v"][r], c["kpos"][r]]
    return tuple(flat)


def _decode_commit_caches(cfg, caches, knew: jax.Array, vnew: jax.Array,
                          pos: jax.Array):
    """Commit the kernel's fresh per-layer k/v into the pooled caches —
    exactly ``layers.kv_cache_update`` per layer, each written in place
    through a layer view of its segment's pool (same slot formula, same
    written values), so the fused path's caches stay bitwise consistent
    with the per-op decode path's."""
    from repro.models import layers
    ai = 0
    out = []
    for si, seg in enumerate(cfg.segments()):
        pool = dict(caches[si])
        for r in range(seg.reps):
            for bi, kind in enumerate(seg.pattern):
                # the xla ref tier emits f32: the write casts to the cache's
                # dtype (a mixed-dtype scatter is deprecated)
                view = layers.kv_cache_update(
                    dict(pool[f"b{bi}"], layer=r), knew[ai][:, :, None, :],
                    vnew[ai][:, :, None, :], pos,
                    cfg.local_window if kind == "local_attn" else 0)
                pool[f"b{bi}"] = {k: v for k, v in view.items()
                                  if k != "layer"}
                ai += 1
        out.append(pool)
    return out


@functools.lru_cache(maxsize=64)
def _decode_runner(cfg, expand_masks: bool, backend: str | None):
    """One jitted decode-step executor per (config, expansion, backend) —
    the decode analogue of :func:`_fused_runner`: the returned callable is
    stable, so jit's shape cache applies and the serving hot loop never
    retraces (``fused_trace_counts[(spec, backend, "decode")]`` observes
    trace count)."""
    spec = lower_fused_decode(cfg, expand_masks=expand_masks)
    rot = next(s.rot_dim for s in spec.steps if s.kind == "attn")
    donate = (1,) if jax.default_backend() != "cpu" else ()
    prec = f"kv-{spec.kv_dtype}" if spec.kv_dtype else "fp32"

    def run(params, caches, tokens, pos):
        fused_trace_counts[(spec, backend, "decode")] += 1
        _note_trace("decode", backend, prec)
        from repro.models import layers
        rows = tokens.shape[0]
        p = jnp.asarray(pos, jnp.int32)
        pos_r = jnp.broadcast_to(p, (rows,)) if p.ndim == 0 else p
        x = layers.embed_tokens(params["embed"], tokens)[:, 0]
        cos, sin = layers.rope_cos_sin(pos_r, rot, cfg.rope_theta)
        flat = _decode_flat_params(spec, cfg, params, rows, expand_masks)
        fc = _decode_flat_caches(cfg, caches)
        if backend == "xla":
            out = fused_ref.fused_decode_ref(spec, x, flat, fc, pos_r, cos,
                                             sin)
        else:
            from repro.kernels.fused_plan import ops as fp_ops
            out = fp_ops.fused_decode(spec, x, flat, fc, pos_r, cos, sin,
                                      interpret=_BACKEND_INTERPRET[backend])
        mean, rel, knews, vnews = out
        new_caches = _decode_commit_caches(cfg, caches, knews, vnews, pos_r)
        return mean, rel, new_caches

    return jax.jit(run, donate_argnums=donate), spec


def compile_decode_step(cfg, *, expand_masks: bool = True,
                        backend: str | None = None) -> Callable:
    """Lower once, decode many: the fused serving decode step of ``cfg`` as
    a cached jitted executor ``(params, caches, tokens [R,1], pos) ->
    (mean_logp [b, V], rel_unc [b], new_caches)``.

    ``pos`` is a scalar or per-row ``[R]`` vector (the continuous-batching
    form); rows are mask-major (``expand_masks=True``: row ``r`` is mask
    ``r // b``). Raises :class:`FusedPlanUnsupported` immediately when the
    config has no fused decode lowering; the kernel tier's guards (VMEM
    residency; the compiled tier's refusal) fire later, from the first
    call (trace time) — callers that want the per-op fallback must catch
    around that first call too (``serving.server.step_fns`` does).
    """
    if backend not in (None, "xla", "pallas-interpret", "pallas-tpu"):
        raise ValueError(f"unknown backend {backend!r}")
    return _decode_runner(cfg, bool(expand_masks), backend)[0]


def decode_fused_spec(cfg, *, expand_masks: bool = True
                      ) -> fused_ref.FusedDecodeSpec:
    """Static shape-key of the fused decode executor (trace-counter key)."""
    return lower_fused_decode(cfg, expand_masks=expand_masks)


# ---------------------------------------------------------------------------
# bucketed fused prefill (bounded-retrace admission)
# ---------------------------------------------------------------------------
#
# Admission used to retrace the jitted prefill once per *distinct* prompt
# length. The bucketed form zero-pads the prompt to a small set of length
# buckets (powers of two up to max_seq, plus max_seq itself) and runs ONE
# prefill graph per bucket with the true length as a *traced* scalar: the
# last-token logits are gathered at length-1 (causal attention makes that
# position blind to the pad tail) and the pad tail's cache entries are
# trimmed back to the init state — the exact-length prefill's caches, with
# the distinct trace count bounded by the bucket set instead of the
# prompt-length set. Support is a property of the cache layout
# (:func:`prefill_spec`): every cache must keep slot == position (global
# GQA K/V, the MLA latent) and every MoE layer must route dropless, so
# that pad tokens take no capacity from real ones. Configs it rejects fall
# back to the per-length exact prefill in serving/server.step_fns.


@functools.lru_cache(maxsize=None)
def prefill_buckets(max_seq: int,
                    buckets: tuple[int, ...] | None = None
                    ) -> tuple[int, ...]:
    """Resolve the prefill length-bucket set against a cache capacity.

    ``None`` -> powers of two below ``max_seq`` plus ``max_seq`` itself
    (every length <= max_seq has a bucket, pad waste < 2x). An explicit set
    is validated loudly — empty or non-positive bucket sets raise — then
    sorted, deduplicated, and capped at ``max_seq`` (a bucket beyond the
    cache capacity could never be prefilled)."""
    if max_seq < 1:
        raise ValueError(f"max_seq {max_seq} < 1")
    if buckets is None:
        out, b = [], 1
        while b < max_seq:
            out.append(b)
            b <<= 1
        out.append(max_seq)
        return tuple(sorted(set(out)))
    vals = tuple(int(b) for b in buckets)
    if not vals:
        raise ValueError("empty prefill bucket set (use None for the "
                         "power-of-two default, or () upstream to disable "
                         "bucketing)")
    if any(b < 1 for b in vals):
        raise ValueError(f"non-positive prefill bucket in {vals}")
    return tuple(sorted({b for b in vals if b <= max_seq}))


def prefill_bucket(length: int, max_seq: int,
                   buckets: tuple[int, ...] | None = None) -> int | None:
    """Smallest bucket >= ``length`` (None when no bucket covers it — the
    caller falls back to an exact-length prefill)."""
    for b in prefill_buckets(max_seq, buckets):
        if b >= length:
            return b
    return None


@dataclasses.dataclass(frozen=True)
class PrefillSpec:
    """Static key of one config's bucketed prefill (the trace-counter key):
    the config and its row expansion."""
    cfg: Any
    n_samples: int

    @property
    def kv_dtype(self) -> str:
        return self.cfg.kv_dtype


def prefill_spec(cfg, *, expand_masks: bool = True) -> PrefillSpec:
    """The bucketed prefill's key, and its support gate: raises
    :class:`FusedPlanUnsupported` unless zero-padding a prompt to a bucket
    is exact for every cache of ``cfg`` — global attention (GQA K/V or the
    MLA latent) keeps slot == position, so the pad tail is disjoint from
    real context and ``transformer.cache_trim_positions`` restores it;
    dropless MoE routes each token on its own. Refused: non-causal and
    M-RoPE configs, local-attention rolling caches (pad writes would evict
    real context), recurrent state (pad tokens advance it), capacity MoE
    (pad tokens take capacity from real ones) and int8 KV caches."""
    if not cfg.causal:
        raise FusedPlanUnsupported("encoder-only config has no decode step")
    if cfg.m_rope_sections:
        raise FusedPlanUnsupported("M-RoPE positions are not padded")
    if cfg.kv_dtype == "int8":
        raise FusedPlanUnsupported("int8 KV cache prefills at exact length")
    for seg in cfg.segments():
        for kind in seg.pattern:
            if kind == "local_attn" and cfg.local_window:
                raise FusedPlanUnsupported(
                    "local-attention rolling cache cannot take padded-bucket "
                    "prefill (pad positions would evict real context)")
            if kind == "moe" and not cfg.moe_dropless:
                raise FusedPlanUnsupported(
                    "capacity-routed MoE: pad tokens would take capacity")
            if kind not in ("attn", "local_attn", "moe"):
                raise FusedPlanUnsupported(
                    f"block kind {kind!r} carries state the pad would "
                    f"advance")
    bayes = cfg.bayesian and expand_masks
    return PrefillSpec(cfg, cfg.mask_samples if bayes else 1)


@functools.lru_cache(maxsize=256)
def _prefill_runner(cfg, expand_masks: bool, bucket: int, max_seq: int,
                    backend: str | None):
    """One jitted bucketed-prefill executor per (config, expansion, bucket,
    capacity, backend) — stable across servers, so jit's shape cache applies
    and ``fused_trace_counts[(spec, backend, "prefill", bucket, max_seq)]``
    observes the trace count (bounded by the bucket set)."""
    spec = prefill_spec(cfg, expand_masks=expand_masks)
    bayes = cfg.bayesian and expand_masks
    n = spec.n_samples
    prec = f"kv-{spec.kv_dtype}" if spec.kv_dtype else "fp32"

    def run(params, tokens, length):
        fused_trace_counts[(spec, backend, "prefill", bucket, max_seq)] += 1
        _note_trace("prefill", backend, prec)
        from repro.models import transformer
        rows = tokens.shape[0]
        ids = jnp.repeat(jnp.arange(n), rows // n) if bayes else None
        ln = jnp.asarray(length, jnp.int32)
        logits, caches, counts = transformer.prefill(
            cfg, params, {"tokens": tokens}, max_seq=max_seq,
            mask_ids=ids, last_index=ln - 1, return_counts=True)
        caches = transformer.cache_trim_positions(caches, ln)
        mean, rel = unc_lib.token_posterior(logits, n)
        if counts is None:
            return mean, rel, caches
        return mean, rel, caches, counts

    return jax.jit(run), spec


def compile_prefill_step(cfg, bucket: int, max_seq: int, *,
                         expand_masks: bool = True,
                         backend: str | None = None) -> Callable:
    """The bucketed prefill of ``cfg`` at one length bucket, as a cached
    jitted executor ``(params, tokens [R, bucket], length) ->
    (mean_logp [b, V], rel_unc [b], caches)``.

    ``tokens`` is the prompt zero-padded to ``bucket`` columns; ``length``
    (the true prompt length, a *traced* scalar) selects the logits position
    and the cache-trim boundary — so every length sharing a bucket shares
    one trace. ``backend`` is a provenance label on the trace counter (the
    prefill graph itself lowers through XLA on every tier); raises
    :class:`FusedPlanUnsupported` via :func:`prefill_spec` when
    padded-bucket prefill would not be exact."""
    if backend not in (None, "xla", "pallas-interpret", "pallas-tpu"):
        raise ValueError(f"unknown backend {backend!r}")
    if not 1 <= bucket <= max_seq:
        raise ValueError(f"bucket {bucket} outside [1, max_seq={max_seq}]")
    return _prefill_runner(cfg, bool(expand_masks), int(bucket),
                           int(max_seq), backend)[0]


def decode_stage_traffic(spec: fused_ref.FusedDecodeSpec, rows: int,
                         max_seq: int, bytes_per_el: int = 2, *,
                         fused: bool = True
                         ) -> dict[str, sched_lib.TrafficModel]:
    """Per-stage split of :func:`decode_traffic`: one TrafficModel per
    step kind (``norm``/``attn``/``ffn``/``dense`` — attn includes its
    KV-cache bytes) plus an ``interstage`` entry holding the inter-launch
    activation traffic and the launch count. Sums field-for-field to
    :func:`decode_traffic` (asserted in tests/test_obs.py).

    Pricing is per tensor family: weights at ``bytes_per_el``, KV-cache k/v
    rows at the spec's ``kv_dtype`` width (int8 adds its per-position f32
    scale leaves), and the int32 ``kpos`` bookkeeping at its true 4 bytes."""
    d, v, n = spec.d_model, spec.vocab, spec.n_samples
    b = rows // n
    kv_b = _dtype_bytes(spec.kv_dtype, bytes_per_el)
    acc: dict[str, list[int]] = {}

    def add(kind: str, w: int = 0, kv: int = 0, pos: int = 0,
            scale: int = 0, fl: int = 0) -> None:
        cur = acc.setdefault(kind, [0, 0, 0, 0, 0])
        for j, inc in enumerate((w, kv, pos, scale, fl)):
            cur[j] += inc

    layers_l = 0
    for st in spec.steps:
        if st.kind == "norm":
            add("norm", w=d * (2 if st.shared_bias else 1))
        elif st.kind == "attn":
            hh, hkv, dh = st.n_heads, st.n_kv_heads, st.head_dim
            smax = min(st.window, max_seq) if st.window else max_seq
            proj = d * hh * dh + 2 * d * hkv * dh + hh * dh * d
            if st.qkv_bias:
                proj += hh * dh + 2 * hkv * dh
            kv_el = rows * hkv * smax * dh * 2 + rows * hkv * dh * 2
            scale_el = (rows * hkv * smax + rows * hkv
                        if spec.kv_dtype == "int8" else 0)
            add("attn", w=proj, kv=kv_el, pos=rows * smax + rows,
                scale=scale_el,
                fl=2 * rows * proj + 4 * rows * hh * dh * (smax + 1))
            layers_l += 1
        elif st.kind == "ffn":
            mats = 3 if st.gated else 2
            if st.per_sample:
                add("ffn", w=n * mats * d * st.d_hidden,
                    fl=2 * rows * mats * d * st.d_hidden)
            else:
                w = mats * d * st.d_hidden \
                    + (st.d_hidden + d if st.ffn_bias else 0)
                if st.masked:
                    w += n * st.d_hidden
                add("ffn", w=w, fl=2 * rows * mats * d * st.d_hidden)
        elif st.kind == "dense":
            add("dense",
                w=st.d_in * st.d_out + (st.d_out if st.shared_bias else 0),
                fl=2 * rows * st.d_in * st.d_out)
        elif st.kind == "act":
            pass  # elementwise on the VMEM-resident state: no HBM traffic
        else:
            raise ValueError(
                f"decode_stage_traffic: unpriced step kind {st.kind!r} — "
                "a kind the kernels execute must also be traffic-priced "
                "(extend this table alongside fused_plan kernel/ref)")
    if fused:
        act_el = rows * d + b * v + b
        launches = 1
    else:
        act_el = layers_l * 4 * rows * d + rows * d + 2 * rows * v \
            + b * v + b
        launches = 2 * layers_l + 2
    out = {kind: sched_lib.TrafficModel(
        weight_bytes=w * bytes_per_el + kv * kv_b + pos * 4 + scale * 4,
        act_bytes=0, flops=fl, weight_loads=0)
        for kind, (w, kv, pos, scale, fl) in acc.items()}
    out["interstage"] = sched_lib.TrafficModel(
        weight_bytes=0, act_bytes=act_el * bytes_per_el, flops=0,
        weight_loads=launches)
    return out


def decode_traffic(spec: fused_ref.FusedDecodeSpec, rows: int, max_seq: int,
                   bytes_per_el: int = 2, *, fused: bool = True
                   ) -> sched_lib.TrafficModel:
    """Modeled HBM traffic of ONE pool decode step, priced from the spec.

    Weights and KV-cache rows cross HBM once per *launch* in either path
    (``weight_bytes`` counts both); the fused/per-op difference is (a) the
    inter-stage activations — per-op round-trips the ``[R, D]`` residual at
    every sub-layer boundary and materializes the ``[R, V]`` logits twice
    (lm-head write + posterior read), fused keeps them VMEM-resident and
    emits only the already-reduced ``(mean [b, V], rel [b])`` — and (b)
    launch count: ``weight_loads`` holds launches per token (per-op:
    ``2·L + 2`` — attention and FFN per layer, lm head, posterior; fused:
    1), each priced at ``kernel_fill_us`` by
    :func:`decode_modeled_latency`. The per-stage split this aggregates is
    :func:`decode_stage_traffic`.
    """
    stages = decode_stage_traffic(spec, rows, max_seq, bytes_per_el,
                                  fused=fused)
    return sched_lib.TrafficModel(
        weight_bytes=sum(t.weight_bytes for t in stages.values()),
        act_bytes=sum(t.act_bytes for t in stages.values()),
        flops=sum(t.flops for t in stages.values()),
        weight_loads=sum(t.weight_loads for t in stages.values()))


def decode_modeled_latency(spec: fused_ref.FusedDecodeSpec, rows: int,
                           max_seq: int, *,
                           tpu: latency_model.TpuSpec = latency_model.V5E,
                           bytes_per_el: int = 2,
                           fused: bool = True) -> float:
    """Eq.-2-analogue latency of one pool decode step: roofline over the
    decode traffic plus one ``kernel_fill_us`` per launch — the launch term
    is what dominates the per-op path at pool-sized batches, which is the
    whole point of the fused decode step."""
    tm = decode_traffic(spec, rows, max_seq, bytes_per_el, fused=fused)
    return max(tm.flops / tpu.peak_flops_bf16, tm.total_bytes / tpu.hbm_bw) \
        + tm.weight_loads * tpu.kernel_fill_us * 1e-6
