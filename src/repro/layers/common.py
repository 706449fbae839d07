"""Shared layer utilities: initializers, norms, activations."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(key, shape, dtype, scale=None):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) >= 1 else 1
    if len(shape) >= 2:
        fan_in = int(np.prod(shape[:-1]))
    std = scale if scale is not None else 1.0 / np.sqrt(max(1, fan_in))
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32) * std).astype(dtype)


def rmsnorm_init(dim, dtype):
    return {"scale": jnp.zeros((dim,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


def layernorm_init(dim, dtype):
    return {"scale": jnp.zeros((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))
            + params["bias"].astype(jnp.float32)).astype(x.dtype)


def make_norm(kind: str, eps: float = 1e-6):
    if kind == "rmsnorm":
        return rmsnorm_init, functools.partial(rmsnorm, eps=eps)
    if kind == "layernorm":
        return layernorm_init, functools.partial(layernorm, eps=eps)
    raise ValueError(kind)


def activation_fn(name: str):
    if name in ("swiglu", "silu"):
        return jax.nn.silu
    if name in ("geglu", "gelu"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)
