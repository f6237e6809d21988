"""What every model family's reference and counts share: the seed's key,
matrix products at ``HIGHEST`` (or, for the control, on operands
rounded to float8), RMSNorm, and the roofline's least time.

Nothing here knows a family's layout; ``bench/reference/<family>.py``
builds on it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
BF16 = 2                # bytes of a bf16 operand


def seed_key(seed: int):
    """A key for any whole ``seed`` up to 63 bits (two 31-bit halves)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def q8(x):
    """x rounded to float8 e4m3 with one scale per tensor.  The gradient
    passes straight through the rounding (JAX differentiates a cast to
    float8 as zero), so the backward pass sees the rounded operands."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(spec, a, b, prec):
    """``einsum`` at ``HIGHEST``; with ``prec="fp8"`` (the control) both
    operands are rounded by ``q8`` first."""
    if prec == "fp8":
        a, b = q8(a), q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def roofline_time(flops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound binds)."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
