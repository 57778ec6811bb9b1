"""Pieces the plain references share: the seeded initialiser, LayerNorm, the
float32 (or float8 control) matmul, and the reduction of logits to what the
comparison reads."""

from __future__ import annotations

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def static(m: dict) -> tuple:
    """The scalar entries of a config's ``model`` dict, hashable for jit."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (bool, int, float, str))))


def trunc_normal(key, shape, std: float):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def layernorm(x, scale=None, bias=None, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    if scale is not None:
        y = y * scale
    if bias is not None:
        y = y + bias
    return y


def _fp8(x, axis: int):
    """Round x to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def matmul(a, w, *, quant: bool = False):
    """a (..., n) @ w (n, m) in float32; with ``quant``, from operands
    rounded to float8 per row of ``a`` and per column of ``w``."""
    if quant:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return a @ w


def reduce_logits(logits, probes):
    """logits (B, P, V); probes (B, K, P) -> (best (B, P), the logits at
    the probes (B, K, P), argmax (B, P))."""
    at = jnp.take_along_axis(logits[:, None], probes[..., None], axis=-1)
    return (jnp.max(logits, axis=-1), at[..., 0],
            jnp.argmax(logits, axis=-1).astype(jnp.int32))
