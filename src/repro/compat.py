"""The single choke-point for JAX's drifted APIs, written for jax 0.9.0.

The mesh, shard_map and tree spellings below have moved between JAX
releases. Every other module under src/repro/ goes through the names here
(the ``repro.analysis`` compat-drift rule rejects the direct spellings), so
the next move is absorbed in one file:

  =====================  ==============================================
  symbol                 jax 0.9.0 home
  =====================  ==============================================
  ``shard_map``          ``jax.shard_map`` (``check_vma=``)
  ``set_mesh``           ``jax.sharding.set_mesh`` (process default)
  ``use_mesh``           ``jax.sharding.set_mesh`` as a context manager
  ``make_mesh``          ``jax.make_mesh(..., axis_types=Auto...)``
  ``AxisType``           ``jax.sharding.AxisType``
  tree utilities         ``jax.tree.*`` / ``jax.tree_util.*``
  =====================  ==============================================

Kernel backend selection lives here too: the ``kernels/*/ops.py``
dispatchers call :func:`kernel_backend` once per process (lazily, on the
first kernel call — never at import) and get one of
``"pallas-tpu"`` (compiled Pallas on a real TPU), ``"pallas-interpret"``
(Pallas interpreter on CPU/GPU — bit-accurate, slow), or ``"xla"`` (the
pure-jnp reference path, used when Pallas itself cannot be imported).
``REPRO_KERNEL_BACKEND`` overrides the probe for A/B testing.

Importing this module must NOT initialize jax backends (the dry-run pins
``XLA_FLAGS`` before first device init), so every platform probe is behind a
cached function, never module-level.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Callable

import jax

__all__ = [
    "JAX_VERSION", "AxisType", "make_mesh", "set_mesh", "use_mesh",
    "get_mesh", "shard_map", "tree_map", "tree_leaves", "tree_flatten",
    "tree_unflatten", "tree_structure", "tree_map_with_path",
    "tree_flatten_with_path", "default_backend", "on_tpu",
    "kernel_backend", "pallas_interpret_default", "import_pallas_kernel",
    "kernel_backend_for", "version_summary", "KERNEL_BACKENDS",
    "COMPILE_CACHE_DIR", "enable_compilation_cache",
]

JAX_VERSION: tuple[int, ...] = tuple(
    int(p) for p in jax.__version__.split(".")[:3] if p.isdigit())

tree_map: Callable = jax.tree.map
tree_leaves: Callable = jax.tree.leaves
tree_flatten: Callable = jax.tree_util.tree_flatten
tree_unflatten: Callable = jax.tree_util.tree_unflatten
tree_structure: Callable = jax.tree_util.tree_structure
tree_map_with_path: Callable = jax.tree_util.tree_map_with_path
tree_flatten_with_path: Callable = jax.tree_util.tree_flatten_with_path


# ---------------------------------------------------------------------------
# meshes
# ---------------------------------------------------------------------------

AxisType = jax.sharding.AxisType


def make_mesh(axis_shapes: tuple[int, ...], axis_names: tuple[str, ...], *,
              axis_types: Any = "auto", devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with all-Auto axes by default (jax 0.9.0 defaults
    to Explicit). Pass a tuple of ``AxisType`` members for anything else."""
    types = ((AxisType.Auto,) * len(axis_names) if axis_types == "auto"
             else axis_types)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=types,
                         devices=devices)


# The process default installed by set_mesh: the mesh, and the handle whose
# __exit__ restores what was installed before it.
_current_mesh: jax.sharding.Mesh | None = None
_installed: Any = None


def set_mesh(mesh: jax.sharding.Mesh | None):
    """Install ``mesh`` as the default mesh (``None`` clears it); returns the
    previously installed one. Like ``jax.sharding.set_mesh``, the default is
    thread-local: install it from the thread that traces."""
    global _current_mesh, _installed
    prev = _current_mesh
    if _installed is not None:
        _installed.__exit__(None, None, None)
        _installed = None
    if mesh is not None:
        _installed = jax.sharding.set_mesh(mesh)
    _current_mesh = mesh
    return prev


def get_mesh() -> jax.sharding.Mesh | None:
    """The mesh most recently installed through :func:`set_mesh`."""
    return _current_mesh


@contextlib.contextmanager
def use_mesh(mesh: jax.sharding.Mesh):
    """Scoped default mesh: inside, ``jax.sharding.get_abstract_mesh()`` is
    ``mesh``'s, so sharding hints (``models.layers.constrain``) apply."""
    with jax.sharding.set_mesh(mesh):
        yield mesh


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: bool | None = None, **kwargs) -> Callable:
    """``jax.shard_map``; ``check_vma=None`` keeps JAX's default."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


# ---------------------------------------------------------------------------
# platform probing + kernel backend selection
# ---------------------------------------------------------------------------

KERNEL_BACKENDS = ("pallas-tpu", "pallas-interpret", "xla")


@functools.cache
def default_backend() -> str:
    """Cached ``jax.default_backend()`` (first call initializes devices)."""
    return jax.default_backend()


def on_tpu() -> bool:
    return default_backend() == "tpu"


@functools.cache
def kernel_backend() -> str:
    """Pick the kernel execution backend once per process.

    Order: compiled Pallas on real TPUs; the Pallas interpreter everywhere
    else Pallas imports (bit-accurate emulation of the same kernels); the
    pure-XLA reference implementations when Pallas is absent entirely.
    ``REPRO_KERNEL_BACKEND`` (one of ``KERNEL_BACKENDS``) overrides the probe.
    """
    forced = os.environ.get("REPRO_KERNEL_BACKEND", "").strip()
    if forced:
        if forced not in KERNEL_BACKENDS:
            raise ValueError(
                f"REPRO_KERNEL_BACKEND={forced!r} not in {KERNEL_BACKENDS}")
        return forced
    if on_tpu():
        return "pallas-tpu"
    try:
        # the kernels need pltpu (memory spaces etc.) even in interpret mode,
        # so a pallas-without-pltpu install must fall back to the reference
        import jax.experimental.pallas      # noqa: F401
        import jax.experimental.pallas.tpu  # noqa: F401
        return "pallas-interpret"
    except Exception:  # noqa: BLE001 — any import failure means no Pallas
        return "xla"


def pallas_interpret_default() -> bool:
    """Resolution of ``interpret=None`` in the kernel wrappers."""
    return kernel_backend() == "pallas-interpret"


def import_pallas_kernel(module_name: str):
    """Import a ``kernels/*/kernel.py`` module for an ops dispatcher.

    Returns ``None`` only when Pallas itself is unavailable (the xla tier).
    An ImportError raised from a broken kernel module while Pallas imports
    fine is a real bug and is re-raised — silently degrading a TPU
    deployment to the reference path would be far worse than crashing.
    """
    import importlib
    try:
        return importlib.import_module(module_name)
    except ImportError:
        try:
            import jax.experimental.pallas      # noqa: F401
            import jax.experimental.pallas.tpu  # noqa: F401
        except Exception:  # noqa: BLE001
            return None
        raise


def kernel_backend_for(kernel_module) -> str:
    """Backend for a dispatcher whose kernel module came from
    :func:`import_pallas_kernel`: ``"xla"`` iff the module is absent, the
    process-wide :func:`kernel_backend` probe otherwise. Lazy — safe to call
    only at trace/first-call time, never at import."""
    return "xla" if kernel_module is None else kernel_backend()


#: The persistent compilation cache's home when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: one fixed directory in the checkout (listed in .gitignore).
#: The path is part of the cache key, so it never moves.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this at start-up, never at import. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing else is set here; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def version_summary() -> dict:
    """Stamp for dry-run/sweep artifacts: what actually ran this process."""
    return {"jax": jax.__version__,
            "backend": default_backend(),
            "kernel_backend": kernel_backend()}
