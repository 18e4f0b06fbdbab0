"""Thin helpers over the pinned JAX 0.9 API.

``make_mesh`` forces Auto-typed mesh axes (``jax.make_mesh`` defaults to
Explicit axes on 0.9, which the sharding rules here do not use), and
``keystr_simple`` renders key paths in the slash-joined form that
checkpoint manifests and the partitioning tables key leaves by.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kwargs,
    )


def keystr_simple(path, separator: str = "/") -> str:
    """Render a JAX key path as simple names joined by ``separator``
    (``a/b/0``): each entry contributes its bare payload (dict key,
    sequence index, or attribute name) with no quotes or brackets."""
    parts = []
    for entry in path:
        for attr in ("key", "idx", "name"):
            if hasattr(entry, attr):
                parts.append(str(getattr(entry, attr)))
                break
        else:  # unknown entry type: fall back to its repr, stripped
            parts.append(str(entry).strip(".[]'\""))
    return separator.join(parts)
