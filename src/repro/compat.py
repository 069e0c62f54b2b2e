"""repro.compat — the single place where drifting JAX APIs are spelled.

JAX moves fast: symbols migrate between ``jax.experimental`` and the
top-level namespace, keyword names change (``check_rep`` → ``check_vma``),
and Pallas TPU compiler params were renamed (``TPUCompilerParams`` →
``CompilerParams``).  Every module in this repo that touches one of those
APIs imports it from here, so the codebase spells each API exactly once
and a JAX upgrade is a one-file change.  Each name below is the spelling of
the installed JAX (0.9).

Policy (see ROADMAP.md): new call sites of a version-drifting JAX API MUST
be added here first and imported from ``repro.compat`` — never spelled
directly.  ``tests/test_compat_policy.py`` greps the tree to enforce it.

Covered APIs:

  shard_map               ``jax.shard_map`` (``check_vma`` kwarg).
  tree_flatten_with_path  ``jax.tree.flatten_with_path``.
  tpu_compiler_params     ``pltpu.CompilerParams``.
  make_mesh               ``jax.make_mesh(..., axis_types=...)``.
"""
from __future__ import annotations

from typing import Any, Callable

import jax

shard_map = jax.shard_map


def tree_flatten_with_path(tree, is_leaf: Callable | None = None):
    """``jax.tree.flatten_with_path`` -> ([(path, leaf)], treedef)."""
    return jax.tree.flatten_with_path(tree, is_leaf=is_leaf)


def tpu_compiler_params(**kwargs) -> Any:
    """``pltpu.CompilerParams(**kwargs)``, e.g.
    ``tpu_compiler_params(dimension_semantics=("parallel", "arbitrary"))``.

    Pallas TPU is imported lazily: only kernel modules pay the import.
    """
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def make_mesh(axis_shapes, axis_names, *, devices=None, explicit_axes=()):
    """``jax.make_mesh`` with every axis Auto except those named in
    ``explicit_axes``, which use Explicit sharding semantics."""
    axis_type = jax.sharding.AxisType
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=tuple(axis_type.Explicit if n in explicit_axes
                         else axis_type.Auto for n in axis_names),
        devices=devices)


__all__ = [
    "make_mesh",
    "shard_map",
    "tpu_compiler_params",
    "tree_flatten_with_path",
]
