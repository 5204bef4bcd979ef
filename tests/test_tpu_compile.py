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
