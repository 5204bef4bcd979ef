"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what the Pallas interpreter accepts: blocks not
aligned to the (8, 128) tiling, more scoped VMEM than the kernel was given,
programs that do not fit the device. Each test here lowers one kernel or
step at real widths with shapes only, compiles it for one v5e chip, and
checks that it fits in 16 GB and that a kernel is present where one is
expected. Nothing runs, so nothing here is a timing.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core import masks as masks_lib
from repro.core import plan as plan_lib
from repro.ivim import model as ivim_model
from repro.ivim import physics
from repro.kernels.fused_plan import kernel as fp_kernel
from repro.kernels.fused_plan import ops as fp_ops
from repro.kernels.masked_ffn import kernel as mffn_kernel
from repro.kernels.masked_ffn import ops as mffn_ops
from repro.models import build_model, transformer
from repro.serving import server as server_lib

HBM_BYTES = 16 * 10 ** 9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _check(compiled, *, kernel: bool):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    return total


def _ivim_plan(b_values):
    cfg = ivim_model.IvimConfig(b_values=b_values, n_masks=4)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    return ivim_model.pack_for_serving(cfg, params, state)


def _compile_fused(plan, one_chip, *, moments: bool, batch: int = 4096):
    """The fused kernel as ops.fused_plan would launch it on the chip:
    padded params, the guard's block size, the guard's VMEM budget."""
    spec, params = plan_lib.lower_fused(plan)
    block_b = 128
    fp_ops.check_vmem(spec, block_b, moments=moments)
    padded = jax.eval_shape(lambda p: fp_ops._pad_params(spec, p),
                            tuple(params))
    x = _sds((batch, -(-spec.d_in // 128) * 128), jnp.float32, one_chip)
    fn = functools.partial(fp_kernel.fused_plan_pallas, spec=spec,
                           block_b=block_b, moments=moments,
                           vmem_limit=fp_ops.VMEM_MOMENTS_LIMIT)
    return jax.jit(fn).lower(x, _on(padded, one_chip)).compile()


@pytest.mark.parametrize("protocol", ["clinical", "dense"])
def test_fused_moments_compiles_ivim(protocol, one_chip):
    bv = (physics.CLINICAL_B_VALUES if protocol == "clinical"
          else physics.DENSE_B_VALUES)
    _check(_compile_fused(_ivim_plan(bv), one_chip, moments=True),
           kernel=True)


def test_fused_samples_compiles_ivim(one_chip):
    plan = _ivim_plan(physics.DENSE_B_VALUES)
    _check(_compile_fused(plan, one_chip, moments=False), kernel=True)


@pytest.mark.parametrize("moments", [True, False])
def test_fused_runner_compiles_with_range_conversion(moments, one_chip):
    """The engine's whole per-chunk program: the kernel with C(.) (and, for
    samples, the group un-flattening) in one jitted ``run``, the range
    bounds passed as device arguments."""
    plan = _ivim_plan(physics.CLINICAL_B_VALUES)
    spec, params = plan_lib.lower_fused(plan)
    run = plan_lib._fused_runner(spec, "pallas-tpu", moments, 128)
    x = _sds((4096, spec.d_in), jnp.float32, one_chip)
    bounds = (_sds((4,), jnp.float32, one_chip),) * 2
    compiled = run.lower(x, _on(params, one_chip), bounds).compile()
    _check(compiled, kernel=True)
    out = compiled.output_shardings
    assert len(jax.tree.leaves(out)) == (2 if moments else 1)


def _compile_masked_ffn(n, d, k, d2, one_chip, *, int8: bool,
                        batch: int = 4096):
    """The per-op kernel as ops.masked_ffn would launch it on the chip:
    the wrapper's hidden tile and VMEM budget at padded widths."""
    wdt = jnp.int8 if int8 else jnp.float32
    args = [_sds((batch, d), jnp.float32, one_chip),
            _sds((n, d, k), wdt, one_chip),
            _sds((n, k), jnp.float32, one_chip),
            _sds((n, k, d2), wdt, one_chip),
            _sds((d2,), jnp.float32, one_chip)]
    if int8:
        args += [_sds((n, 1, k), jnp.bfloat16, one_chip),
                 _sds((n, 1, d2), jnp.bfloat16, one_chip)]
    block_k = mffn_ops.pick_block_k(d, k, d2, 128,
                                    w_bytes=1 if int8 else 4)
    fn = functools.partial(mffn_kernel.masked_ffn_pallas, block_b=128,
                           block_k=block_k, vmem_limit=mffn_ops.VMEM_LIMIT)
    return jax.jit(fn).lower(*args).compile(), block_k


@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_masked_ffn_compiles(precision, one_chip):
    """The per-op kernel at the IVIM dense-protocol plan's padded shapes:
    16 kernel rows (4 sub-networks x 4 masks), 104 b-values -> 128 lanes."""
    compiled, block_k = _compile_masked_ffn(16, 128, 128, 128, one_chip,
                                            int8=precision == "int8")
    assert block_k == 128
    _check(compiled, kernel=True)


@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_masked_ffn_tiles_wide_widths(precision, one_chip):
    """Widths whose per-sample weights exceed the VMEM budget take a hidden
    tile narrower than K, and the tiled kernel compiles within it."""
    d, k = 4096, 16384
    compiled, block_k = _compile_masked_ffn(4, d, k, d, one_chip,
                                            int8=precision == "int8")
    assert 128 <= block_k < k and k % block_k == 0
    _check(compiled, kernel=True)


def _mlp_plan(d, h):
    masks = masks_lib.generate_masks(masks_lib.MaskSpec(
        width=h, n_masks=4, scale=2.0, seed=0))
    return plan_lib.compile_masked_ffn(
        jnp.zeros((d, h)), jnp.zeros(h), jnp.zeros((h, d)), jnp.zeros(d),
        masks)


def test_vmem_guard_agrees_with_compiler(one_chip):
    """A plan the guard admits compiles at the guard's budget; one just
    over it raises FusedPlanUnsupported, and the per-op path the fallbacks
    then take compiles for it."""
    under = _mlp_plan(2048, 2560)
    spec, _ = plan_lib.lower_fused(under)
    need = fp_ops.fused_vmem_bytes(spec, 128)
    assert 0.9 * fp_ops.VMEM_MOMENTS_LIMIT < need <= fp_ops.VMEM_MOMENTS_LIMIT
    _check(_compile_fused(under, one_chip, moments=True), kernel=True)
    over = _mlp_plan(2176, 2560)
    spec, _ = plan_lib.lower_fused(over)
    need = fp_ops.fused_vmem_bytes(spec, 128)
    assert fp_ops.VMEM_MOMENTS_LIMIT < need < 1.1 * fp_ops.VMEM_MOMENTS_LIMIT
    with pytest.raises(fp_ops.FusedPlanUnsupported, match="resident bytes"):
        fp_ops.check_vmem(spec, 128, moments=True)

    def per_op(params, x):
        plan = dataclasses.replace(over, params=params)
        return plan_lib.execute(plan, x, backend="pallas-tpu")

    x = _sds((4096, 2176), jnp.float32, one_chip)
    _check(jax.jit(per_op).lower(_on(over.params, one_chip), x).compile(),
           kernel=True)


# ---------------------------------------------------------------------------
# qwen2-1.5b at published widths, N = 4 masks, a 4-slot pool of 512
# ---------------------------------------------------------------------------

SLOTS, MAX_SEQ = 4, 512


@pytest.fixture(scope="module")
def qwen(one_chip):
    cfg = registry.get_config("qwen2-1.5b", mask_samples=4)
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return cfg, _on(params, one_chip)


def test_qwen2_decode_step_compiles(qwen, one_chip):
    cfg, params = qwen
    rows = SLOTS * cfg.mask_samples
    caches = _on(transformer.cache_specs(cfg, rows, MAX_SEQ), one_chip)
    decode = server_lib.step_fns(cfg, fused=False).decode
    compiled = decode.lower(params, caches,
                            _sds((rows, 1), jnp.int32, one_chip),
                            _sds((rows,), jnp.int32, one_chip)).compile()
    total = _check(compiled, kernel=False)
    weights = sum(np.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(params))
    assert total > weights          # the whole model really is resident


def test_qwen2_bucketed_prefill_compiles(qwen, one_chip):
    cfg, params = qwen
    step = plan_lib.compile_prefill_step(cfg, 256, MAX_SEQ)
    compiled = step.lower(
        params, _sds((cfg.mask_samples, 256), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    _check(compiled, kernel=False)


# ---------------------------------------------------------------------------
# the decode step writes the cells' pools in place
# ---------------------------------------------------------------------------

_MOVES = {"copy", "copy-start", "copy-done", "transpose"}
_VIEWS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _hlo_computations(text):
    """{computation: {instruction: (shape, opcode, operands)}} of an HLO
    module's text, and the names of the fused computations."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), {})
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]"
                     r"(?:\{[^}]*\})? ([\w\-]+)\((.*)", line)
        if cur is None or not m:
            continue
        name, dims, op, rest = m.groups()
        depth, end = 1, 0
        while depth and end < len(rest):
            depth += {"(": 1, ")": -1}.get(rest[end], 0)
            end += 1
        shape = tuple(int(d) for d in dims.split(",") if d)
        callee = re.search(r"calls=%([\w.\-]+)", rest)
        cur[name] = (shape, op, re.findall(r"%([\w.\-]+)", rest[:end]),
                     callee.group(1) if callee else None,
                     "ROOT" in line.split("=")[0])
    fused = {c[3] for comp in comps.values() for c in comp.values()
             if c[1] == "fusion" and c[3]}
    return comps, fused


def _pool_traffic(compiled, caches):
    """The instructions outside fused computations — in the entry and the
    layer loop — that output a whole pool leaf or one layer's slice of it,
    by what they do: ``write`` (a scatter, or a dynamic-update-slice of one
    position per row, in place), ``slice`` (a fusion that materialises a
    layer's slice) and ``rewrite`` (everything else that moves such a
    buffer: a copy, a transpose, a whole-slice update). The leaves looked
    for are those that hold K/V or latents; kpos is a mask's size."""
    leaves = [a for path, a in jax.tree_util.tree_leaves_with_path(caches)
              if "kpos" not in jax.tree_util.keystr(path)]
    stacks = {tuple(a.shape) for a in leaves}
    slices = {s[1:] for s in stacks} | {(1,) + s[1:] for s in stacks}
    # one position of every row of a leaf [L, B, S, ...]: B x the rest
    position = max(int(np.prod(s[1:2] + s[3:])) for s in stacks)
    comps, fused = _hlo_computations(compiled.as_text())
    out = {"write": [], "slice": [], "rewrite": []}

    def update_is_position(comp, operands):
        upd = comp.get(operands[1])
        return upd is not None and np.prod(upd[0]) <= position

    for cname, comp in comps.items():
        if cname in fused:
            continue
        for name, (shape, op, operands, callee, _) in comp.items():
            if (shape not in stacks and shape not in slices) or \
                    op in _VIEWS:
                continue
            kind = "rewrite"
            if op == "scatter":
                kind = "write"
            elif op == "dynamic-update-slice":
                if update_is_position(comp, operands):
                    kind = "write"
            elif op == "fusion" and callee in comps:
                body = comps[callee]
                _, rop, rops, _, _ = next(v for v in body.values() if v[4])
                if rop == "scatter" or (rop == "dynamic-update-slice"
                                        and update_is_position(body, rops)):
                    kind = "write"
                elif rop == "dynamic-slice":
                    kind = "slice"
            out[kind].append(f"{name} {op} {list(shape)}")
    return out


def _compile_pool_decode(cfg, params, rows, smax, one_chip):
    """The per-op decode at a cell's pool, its caches donated as on the
    chip (``server._donate_argnums`` gives none on the CPU backend)."""
    caches = _on(transformer.cache_specs(cfg, rows, smax), one_chip)
    decode = jax.jit(server_lib.step_fns(cfg, fused=False).decode,
                     donate_argnums=(1,))
    compiled = decode.lower(params, caches,
                            _sds((rows, 1), jnp.int32, one_chip),
                            _sds((rows,), jnp.int32, one_chip)).compile()
    return compiled, _pool_traffic(compiled, caches)


def test_chat_decode_writes_its_pool_in_place(qwen, one_chip):
    """qwen2-1.5b.chat's pool (24 slots x 4 masks x 2048, 5.64 GB): one
    position per row is scattered into the pool the layer loop carries,
    and no copy of the pool or rewrite of a layer's slice is left. The
    compiler still materialises the layer's K and V slices for the
    attention (two per layer, outside HBM temporaries): their count is
    held so that more cannot come back unseen."""
    cfg, params = qwen
    compiled, ops = _compile_pool_decode(cfg, params, 24 * cfg.mask_samples,
                                         2048, one_chip)
    assert ops["rewrite"] == [], ops
    assert len(ops["write"]) == 2, ops          # K and V
    assert len(ops["slice"]) == 2, ops
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_longqa_decode_writes_its_pool_in_place(moonlight, one_chip):
    """moonlight-16b-a3b.longqa's latent pool (12 slots x 4 masks x 8192,
    5 layers, 2.27 GB): each row's latent is written in place at its one
    position, with no copy of the pool around the layer loop, and the
    absorbed attention reads the layer from the pool inside its own
    fusion."""
    cfg, params = moonlight
    compiled, ops = _compile_pool_decode(cfg, params, 12 * cfg.mask_samples,
                                         8192, one_chip)
    assert ops["rewrite"] == [], ops
    assert ops["write"], ops
    assert ops["slice"] == [], ops
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# ---------------------------------------------------------------------------
# moonlight-16b-a3b's first stage at published widths (5 layers: the dense
# layer and four of 64 experts each), the longqa cell's 12 slots x 8192
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def moonlight(one_chip):
    cfg = registry.get_config("moonlight-16b-a3b", n_layers=5,
                              mask_samples=4)
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return cfg, _on(params, one_chip)


def test_moonlight_decode_step_compiles(moonlight, one_chip):
    """Absorbed latent attention over the 2.27 GB latent pool and the
    grouped expert matmuls fit one chip beside 6.19 GB of weights. The TPU
    compiler lowers ``jax.lax.ragged_dot`` to Mosaic kernels of its own
    (``ragged-dot-metadata``, ``ragged-dot-none``): the program's only
    custom calls."""
    cfg, params = moonlight
    rows = 12 * cfg.mask_samples
    caches = _on(transformer.cache_specs(cfg, rows, 8192), one_chip)
    fns = server_lib.step_fns(cfg, fused=False)
    lowered = fns.decode.lower(params, caches,
                               _sds((rows, 1), jnp.int32, one_chip),
                               _sds((rows,), jnp.int32, one_chip))
    assert len(lowered.out_info) == 4       # with the per-expert counts
    compiled = lowered.compile()
    _check(compiled, kernel=True)
    assert "ragged-dot" in compiled.as_text()


def test_moonlight_bucketed_prefill_compiles(moonlight, one_chip):
    cfg, params = moonlight
    step = plan_lib.compile_prefill_step(cfg, 1024, 8192)
    compiled = step.lower(
        params, _sds((cfg.mask_samples, 1024), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    _check(compiled, kernel=True)       # the ragged-dot kernels, as above


def test_fused_decode_refused_on_chip(one_chip):
    """Mosaic cannot lower the fused decode kernel's per-row KV gather, so
    the compiled tier refuses it with FusedPlanUnsupported (the server then
    decodes per-op). If the kernel ever lowers, this test says to lift the
    refusal in ``fused_plan.ops.fused_decode``."""
    from repro.models import layers
    cfg = registry.smoke_config("qwen2-1.5b", d_model=128, n_heads=1,
                                n_kv_heads=1, head_dim=128, d_ff=256,
                                vocab_size=512)
    params = _on(jax.eval_shape(build_model(cfg).init,
                                jax.random.PRNGKey(0)), one_chip)
    rows = 4 * cfg.mask_samples
    caches = _on(transformer.cache_specs(cfg, rows, 128), one_chip)
    tok = _sds((rows, 1), jnp.int32, one_chip)
    pos = _sds((rows,), jnp.int32, one_chip)
    step = plan_lib.compile_decode_step(cfg, backend="pallas-tpu")
    with pytest.raises(plan_lib.FusedPlanUnsupported, match="Mosaic"):
        step.lower(params, caches, tok, pos)
    spec = plan_lib.decode_fused_spec(cfg)

    def raw(params, caches, tokens, pos):
        rot = next(s.rot_dim for s in spec.steps if s.kind == "attn")
        x = layers.embed_tokens(params["embed"], tokens)[:, 0]
        cos, sin = layers.rope_cos_sin(pos, rot, cfg.rope_theta)
        flat = plan_lib._decode_flat_params(spec, cfg, params, rows, True)
        fc = plan_lib._decode_flat_caches(cfg, caches)
        return fp_kernel.fused_decode_pallas(x, flat, fc, pos, cos, sin,
                                             spec=spec, interpret=False)

    with pytest.raises(ValueError, match="gather|[Ss]hape"):
        jax.jit(raw).lower(params, caches, tok, pos).compile()
