"""The JAX API surface that drifts between releases, in one module.

The repo runs on one installed JAX (0.9.0).  Mesh constructors,
``shard_map``, 64-bit mode and the persistent compilation cache have all
changed shape across JAX releases, so code elsewhere reaches them through
these helpers (lint rule MLOS001 enforces it): the next JAX bump is a
one-file patch, and no call site sniffs versions.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Sequence

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

__all__ = ["make_mesh", "abstract_mesh", "mesh_axis_sizes", "shard_map", "enable_x64",
           "enable_compilation_cache", "reset_compilation_cache"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto-typed."""
    axes = tuple(axes)
    kwargs: Dict[str, Any] = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kwargs)


def abstract_mesh(shape: Sequence[int], axes: Sequence[str]) -> AbstractMesh:
    """A device-free mesh of the given shape (sharding rules, tests)."""
    return AbstractMesh(tuple(shape), tuple(axes))


def mesh_axis_sizes(mesh: Any) -> Dict[str, int]:
    """``{axis_name: size}`` for Mesh and AbstractMesh alike."""
    return dict(mesh.shape)


def shard_map(f: Any, *, mesh: Any, in_specs: Any, out_specs: Any,
              check_vma: bool = True) -> Any:
    """``jax.shard_map``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


def enable_x64() -> contextlib.AbstractContextManager:
    """Context manager: arrays built and programs traced inside it are 64-bit;
    outside it the process keeps JAX's 32-bit default."""
    return jax.enable_x64(True)


def enable_compilation_cache(cache_dir: str) -> None:
    """Point JAX's persistent compilation cache at ``cache_dir``.

    A no-op when JAX already uses that directory, as it does when
    ``JAX_COMPILATION_CACHE_DIR`` names it.  The persistence thresholds stay
    at JAX's defaults (compiles of a second or more): the train, prefill and
    decode steps, where the cold-restart cost lives, all clear them.
    """
    if jax.config.jax_compilation_cache_dir == cache_dir:
        return
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # A compile that ran before this call latched "no cache"; drop the
    # latched handle so the next compile opens the directory.
    reset_compilation_cache()


def reset_compilation_cache() -> None:
    """Drop the in-memory cache handle so the next compile re-reads the
    configured directory (tests switch cache dirs in-process)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    cc.reset_cache()
