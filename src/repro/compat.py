"""Thin helpers over the JAX API surface the repo uses (DESIGN.md §7).

The repo supports one installation: Python 3.12 with jax/jaxlib 0.9.0
(pinned in ``pyproject.toml``).  Call sites in ``src/`` and ``tests/``
still go through this module for the mesh/SPMD calls, so the repo's
conventions (all-Auto mesh axes, ``check_vma`` off) live in one place:

* :func:`shard_map`    — ``jax.shard_map`` with ``check_vma=False``.
* :func:`make_mesh`    — ``jax.make_mesh`` with all-Auto ``axis_types``.
* :func:`ppermute` / :func:`axis_size` — ``jax.lax`` collectives.
* :func:`cost_analysis` — ``compiled.cost_analysis()`` as a dict.
* :func:`x64_enabled` / :func:`default_count_dtype` /
  :func:`canonical_count_dtype` — the count dtype under the process's
  x64 setting.
* :func:`check_count_overflow` — the int32 fallback guard used by
  :func:`repro.core.api.count_triangles`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "axis_size",
    "canonical_count_dtype",
    "check_count_overflow",
    "cost_analysis",
    "default_count_dtype",
    "make_mesh",
    "ppermute",
    "shard_map",
    "x64_enabled",
]


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with all-Auto axes (the repo's convention)."""
    axis_shapes = tuple(axis_shapes)
    return jax.make_mesh(
        axis_shapes, tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_shapes),
    )


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with the repo's ``check_vma=False`` default."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def ppermute(x, axis_name, perm):
    """``jax.lax.ppermute``."""
    return jax.lax.ppermute(x, axis_name, perm=perm)


def axis_size(axis_name) -> int:
    """Size of a mapped mesh axis, as a static int."""
    return jax.lax.axis_size(axis_name)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()``; ``{}`` when the backend reports
    nothing."""
    return compiled.cost_analysis() or {}


# ----------------------------------------------------------------------
# x64 / count dtype
# ----------------------------------------------------------------------
def x64_enabled() -> bool:
    """Whether 64-bit mode is on."""
    return bool(jax.config.jax_enable_x64)


def default_count_dtype():
    """int64 when x64 is enabled, else int32 (callers must then guard the
    final count with :func:`check_count_overflow`)."""
    return jnp.int64 if x64_enabled() else jnp.int32


def canonical_count_dtype(dtype=None):
    """Resolve a requested count dtype to what this process supports.

    ``None`` means :func:`default_count_dtype`.  An explicit int64 request
    under x64-off is canonicalized to int32 *here*, once, at the build
    boundary — XLA would truncate it anyway, but doing it eagerly keeps
    every ``jnp.zeros``/``astype`` in the kernels warning-free, which in
    turn lets the test suite treat the "Explicitly requested dtype ...
    truncated" UserWarning as an error (an accidental-truncation tripwire).
    The int32 fallback stays guarded by :func:`check_count_overflow`.
    """
    if dtype is None:
        return default_count_dtype()
    return jnp.dtype(jax.dtypes.canonicalize_dtype(jnp.dtype(dtype)))


_INT32_MAX = 2**31 - 1


def check_count_overflow(total: int, count_dtype) -> int:
    """Validate a final triangle count accumulated in ``count_dtype``.

    int32 accumulation wraps silently in XLA; a negative or saturated
    total is unambiguous evidence of overflow, so fail loudly instead of
    returning garbage.  Returns ``total`` unchanged when plausible.
    """
    if jnp.dtype(count_dtype) == jnp.dtype(jnp.int32) and (
        total < 0 or total >= _INT32_MAX
    ):
        raise OverflowError(
            f"triangle count overflowed int32 (got {total}); enable x64 "
            "(jax.config.update('jax_enable_x64', True)) or pass "
            "count_dtype=jnp.int64"
        )
    return total
