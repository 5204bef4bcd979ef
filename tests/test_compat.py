"""The portability layer itself: every shim must resolve against the
*installed* JAX (this suite is exactly what catches upstream API drift), and
the mesh/shard_map shims must round-trip on a 1-device mesh in-process
(multi-device behaviour is covered by tests/test_distributed.py)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat


# ---------------------------------------------------------------------------
# every shim resolves
# ---------------------------------------------------------------------------

def test_version_tuple():
    assert len(compat.JAX_VERSION) >= 2
    assert compat.JAX_VERSION >= (0, 9, 0), (
        "compat.py is written for the installed jax 0.9.0")


def test_all_shims_resolve():
    for name in compat.__all__:
        assert hasattr(compat, name), name
    for fn in (compat.make_mesh, compat.set_mesh, compat.use_mesh,
               compat.get_mesh, compat.shard_map, compat.tree_map,
               compat.tree_leaves, compat.tree_flatten,
               compat.tree_unflatten, compat.tree_structure,
               compat.tree_map_with_path, compat.tree_flatten_with_path,
               compat.default_backend, compat.on_tpu, compat.kernel_backend,
               compat.pallas_interpret_default, compat.version_summary):
        assert callable(fn), fn


def test_tree_aliases_behave():
    tree = {"a": jnp.ones(3), "b": {"c": jnp.zeros(2)}}
    doubled = compat.tree_map(lambda x: x * 2, tree)
    assert float(doubled["a"][0]) == 2.0
    leaves, treedef = compat.tree_flatten(tree)
    assert len(leaves) == len(compat.tree_leaves(tree)) == 2
    back = compat.tree_unflatten(treedef, leaves)
    assert compat.tree_structure(back) == treedef
    paths = [p for p, _ in compat.tree_flatten_with_path(tree)[0]]
    assert len(paths) == 2


def test_kernel_backend_valid_and_stable():
    b = compat.kernel_backend()
    assert b in compat.KERNEL_BACKENDS
    assert compat.kernel_backend() == b          # cached, one probe
    assert compat.pallas_interpret_default() == (b == "pallas-interpret")
    # off-TPU the select must never claim the compiled-TPU backend
    if not compat.on_tpu() and not os.environ.get("REPRO_KERNEL_BACKEND"):
        assert b != "pallas-tpu"


def test_import_pallas_kernel_and_backend_for():
    mod = compat.import_pallas_kernel("repro.kernels.moments.kernel")
    # in this environment Pallas is importable, so the module must load and
    # the dispatcher backend must agree with the process-wide probe
    assert mod is not None and hasattr(mod, "moments_pallas")
    assert compat.kernel_backend_for(mod) == compat.kernel_backend()
    assert compat.kernel_backend_for(None) == "xla"
    # a broken kernel module while Pallas is present is a bug, not a reason
    # to silently fall back to the reference path
    import pytest
    with pytest.raises(ImportError, match="no_such_kernel"):
        compat.import_pallas_kernel("repro.kernels.moments.no_such_kernel")


def test_version_summary_is_json_friendly():
    import json
    s = compat.version_summary()
    assert s["jax"] == jax.__version__
    json.dumps(s)


# ---------------------------------------------------------------------------
# 1-device round-trips (the main pytest process sees exactly 1 CPU device)
# ---------------------------------------------------------------------------

def test_make_mesh_one_device():
    mesh = compat.make_mesh((1,), ("x",))
    assert mesh.axis_names == ("x",)
    assert mesh.shape["x"] == 1
    # the mesh is usable for explicit shardings immediately
    x = jax.device_put(jnp.arange(4.0), NamedSharding(mesh, P("x")))
    np.testing.assert_array_equal(np.asarray(x), np.arange(4.0))


def test_set_mesh_roundtrip():
    mesh = compat.make_mesh((1,), ("x",))
    prev = compat.set_mesh(mesh)
    try:
        assert compat.get_mesh() is mesh
        y = jax.jit(lambda a: a + 1)(jnp.zeros(3))
        np.testing.assert_array_equal(np.asarray(y), 1.0)
    finally:
        compat.set_mesh(prev)
    # restoring the previous default (None included) really uninstalls it
    assert compat.get_mesh() is prev
    if prev is None:
        assert not jax.sharding.get_abstract_mesh().axis_names


def test_use_mesh_scopes():
    mesh = compat.make_mesh((1,), ("x",))
    with compat.use_mesh(mesh) as m:
        assert m is mesh
        y = jax.jit(lambda a: a * 3)(jnp.ones(2))
    np.testing.assert_array_equal(np.asarray(y), 3.0)


def test_shard_map_roundtrip_one_device():
    mesh = compat.make_mesh((1,), ("x",))
    f = compat.shard_map(lambda a: a * 2, mesh=mesh,
                         in_specs=P("x"), out_specs=P("x"))
    x = jnp.arange(8.0)
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x) * 2)


def test_shard_map_check_vma_translates():
    """check_vma forwards to jax.shard_map."""
    mesh = compat.make_mesh((1,), ("x",))

    def body(a):
        return jax.lax.psum(a, "x")

    f = compat.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P(),
                         check_vma=False)
    out = f(jnp.arange(4.0))
    np.testing.assert_array_equal(np.asarray(out), np.arange(4.0))


def test_shard_map_under_set_mesh():
    """set_mesh + shard_map compose (the dryrun/test_distributed pattern)."""
    mesh = compat.make_mesh((1,), ("x",))
    prev = compat.set_mesh(mesh)
    try:
        f = compat.shard_map(lambda a: a + 1, mesh=mesh,
                             in_specs=P(), out_specs=P(), check_vma=False)
        np.testing.assert_array_equal(np.asarray(f(jnp.zeros(2))), 1.0)
    finally:
        compat.set_mesh(prev)


# ---------------------------------------------------------------------------
# serving mesh scope: sharding hints must see the mesh
# ---------------------------------------------------------------------------

def test_mesh_scope_populates_abstract_mesh():
    from repro.serving.server import mesh_scope
    mesh = compat.make_mesh((1,), ("data",))
    assert not jax.sharding.get_abstract_mesh().axis_names
    with mesh_scope(mesh):
        am = jax.sharding.get_abstract_mesh()
        assert am.axis_names == ("data",)
    assert not jax.sharding.get_abstract_mesh().axis_names


def test_constrain_emits_sharding_constraint_under_mesh_scope():
    from repro.models import layers
    from repro.serving.server import mesh_scope
    mesh = compat.make_mesh((1,), ("data",))

    def f(x):
        return layers.constrain(x, ("batch", None)) * 2

    x = jnp.ones((4, 8))
    assert "sharding_constraint" not in str(jax.make_jaxpr(f)(x))
    with mesh_scope(mesh):
        assert "sharding_constraint" in str(jax.make_jaxpr(f)(x))
        assert layers.axis_size("data") == 1
        np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)), 2.0)


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------

_CACHE_SNIPPET = """
import json, jax, jax.numpy as jnp
from repro import compat
where = compat.enable_compilation_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda a: a * 3 + 1)(jnp.ones(7)).block_until_ready()
print(json.dumps({"where": where,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _cache_probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_SNIPPET], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compilation_cache_respects_env_dir(tmp_path):
    d = str(tmp_path / "jcache")
    got = _cache_probe(d)
    assert got == {"where": d, "config": d}
    assert os.listdir(d), "the compiled program was not cached there"


def test_compilation_cache_fixed_checkout_dir():
    """Unset, the cache goes to one fixed in-checkout path (never a temp,
    pid- or time-derived name) that .gitignore lists."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compat.COMPILE_CACHE_DIR == want
    # importing compat set nothing: the helper only acts when called
    assert jax.config.jax_compilation_cache_dir != want
    got = _cache_probe(None)
    assert got == {"where": want, "config": want}
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
