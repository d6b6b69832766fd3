"""Version-compatibility shims for moving parts of the JAX API surface.

Keep every cross-version resolution here so call sites stay on one spelling.
"""
from __future__ import annotations

from typing import Any, Callable

import jax

__all__ = ["shard_map"]


def shard_map(
    f: Callable,
    *,
    mesh,
    in_specs,
    out_specs,
    check_vma: bool | None = None,
) -> Callable:
    """``jax.shard_map``; ``check_vma=None`` keeps the library default."""
    kwargs: dict[str, Any] = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )
