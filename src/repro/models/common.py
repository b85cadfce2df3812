"""Shared model building blocks: norms, RoPE, init, softcap, sharding."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "dense_init", "rms_norm", "layer_norm", "rope", "apply_rope", "softcap",
    "constrain", "BATCH_AXES", "MODEL_AXIS",
]

# Logical axis conventions (see launch/mesh.py): batch-like dims shard over
# ("pod", "data"); hidden/head/expert dims shard over "model".
BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """LeCun-normal (fan-in) init, the usual transformer default."""
    fan_in = shape[in_axis]
    return jax.random.normal(key, shape, dtype) * (fan_in ** -0.5)


def rms_norm(x, scale, eps: float = 1e-6, zero_centered: bool = True):
    """RMSNorm; ``zero_centered`` follows gemma's (1 + scale) convention.

    The reduction runs in f32, but for bf16 inputs the normalize/scale
    multiplies stay in bf16 (normalizer rounded): upcasting the whole
    residual tensor to f32 doubled backward HBM traffic through every norm
    fusion chain (EXPERIMENTS.md §Perf olmoe iteration 5).
    """
    w = (1.0 + scale) if zero_centered else scale
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    if x.dtype == jnp.bfloat16:
        return x * r.astype(x.dtype) * w.astype(x.dtype)
    return (x.astype(jnp.float32) * r
            * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale + bias).astype(dt)


def rope(positions, head_dim: int, theta: float = 10000.0):
    """Rotary position embedding tables.

    positions: i32[...]; returns (sin, cos) of shape [..., head_dim//2].
    """
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x, sin, cos):
    """x: [..., T, n_heads, head_dim]; sin/cos: [..., T, head_dim//2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin_ = sin[..., None, :]     # broadcast over heads
    cos_ = cos[..., None, :]
    out = jnp.concatenate([x1 * cos_ - x2 * sin_,
                           x2 * cos_ + x1 * sin_], axis=-1)
    return out.astype(x.dtype)


def softcap(x, cap):
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def constrain(x, *spec):
    """Apply a sharding constraint if we're under a mesh; no-op otherwise.

    Axes the mesh doesn't have, and axes whose size does not divide the
    corresponding array dimension (e.g. 8 kv heads on a 16-way model axis),
    are dropped — the constraint degrades gracefully across mesh shapes.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return x
        sizes = dict(mesh.shape)
    except Exception:
        return x

    def keep(axis, dim):
        return axis in sizes and dim % sizes[axis] == 0

    fixed = []
    for i, s in enumerate(spec):
        dim = x.shape[i] if i < x.ndim else 1
        if s is None:
            fixed.append(None)
        elif isinstance(s, tuple):
            pick, prod = [], 1
            for a in s:
                if a in sizes and dim % (prod * sizes[a]) == 0:
                    pick.append(a)
                    prod *= sizes[a]
            fixed.append(tuple(pick) if pick else None)
        else:
            fixed.append(s if keep(s, dim) else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))
