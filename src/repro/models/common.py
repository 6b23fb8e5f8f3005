"""Shared model building blocks (pure-function style, explicit param pytrees).

No flax/haiku in this container — modules are (init, apply) function pairs
over nested dicts.  Sharding is expressed with logical axes resolved against
the active mesh:  "dp" -> ("pod","data") folded, "tp" -> "model".
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


Array = jax.Array


# ---------------------------------------------------------------------------
# Logical sharding
# ---------------------------------------------------------------------------


def mesh_axis_names() -> tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    return () if mesh.empty else tuple(mesh.axis_names)


def resolve_axis(logical: str | None):
    """Map a logical axis name to concrete mesh axes (None if mesh lacks it)."""
    names = mesh_axis_names()
    if logical is None:
        return None
    if logical == "dp":
        axes = tuple(a for a in ("pod", "data") if a in names)
        return axes if axes else None
    if logical == "tp":
        return "model" if "model" in names else None
    raise ValueError(logical)


def logical_spec(*logical: str | None) -> P:
    return P(*[resolve_axis(a) for a in logical])


def axis_size(logical: str) -> int:
    """Product of mesh extents behind a logical axis (1 if absent)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1
    concrete = resolve_axis(logical)
    if concrete is None:
        return 1
    if isinstance(concrete, tuple):
        out = 1
        for a in concrete:
            out *= mesh.shape[a]
        return out
    return mesh.shape[concrete]


def tp_if_divisible(dim: int):
    """'model' iff dim divides evenly over the TP extent (else replicate)."""
    return resolve_axis("tp") if dim % max(axis_size("tp"), 1) == 0 else None


def dp_if_divisible(dim: int):
    return resolve_axis("dp") if dim % max(axis_size("dp"), 1) == 0 else None


def constrain(x: Array, *logical: str | None) -> Array:
    """with_sharding_constraint on logical axes; no-op without a mesh.

    Divisibility-guarded: a dim that does not divide its axis extent is left
    unconstrained (e.g. 8 KV heads under 16-way TP)."""
    if not mesh_axis_names():
        return x
    mesh = jax.sharding.get_abstract_mesh()
    spec = []
    for dim, name in zip(x.shape, logical):
        ax = resolve_axis(name)
        if ax is None:
            spec.append(None)
            continue
        extent = 1
        for a in ax if isinstance(ax, tuple) else (ax,):
            extent *= mesh.shape[a]
        spec.append(ax if dim % extent == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# Initializers / layers
# ---------------------------------------------------------------------------


def dense_init(key: Array, d_in: int, d_out: int, dtype, scale: float | None = None):
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * s).astype(dtype)


def rms_norm(x: Array, gamma: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * gamma.astype(jnp.float32)).astype(dt)


def swiglu(x: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(d_head: int, theta: float) -> Array:
    exps = jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head
    return 1.0 / (theta**exps)  # [d_head/2]


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: [..., S, H, dh] (dh even); positions: broadcastable to [..., S]."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)  # [dh/2]
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # [...,S,1,dh/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def count_params(params: Any) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


def cast_tree(params: Any, dtype) -> Any:
    return jax.tree_util.tree_map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    )
