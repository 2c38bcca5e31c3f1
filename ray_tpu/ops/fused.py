"""Fused elementwise pallas kernels: RMSNorm (+ residual add).

HBM-bandwidth ops: one pass over the activation instead of the
separate mean/rsqrt/mul HLOs (XLA usually fuses these anyway inside a
jit; the kernel guarantees it at library boundaries and keeps the f32
statistics on-chip). Analytic custom-vjp backward in plain JAX.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import jax_compat


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    normed = x * lax.rsqrt(var + eps)
    o_ref[...] = (normed * scale_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)


def _rmsnorm_fwd_impl(x2d, scale, eps: float, interpret: bool):
    rows, d = x2d.shape
    block_rows = rows
    # Keep a tile under ~2MB of VMEM f32.
    max_rows = max(1, (512 * 1024) // max(d, 1))
    while block_rows > max_rows and block_rows % 2 == 0:
        block_rows //= 2
    spec_kwargs = {} if interpret else {"memory_space": pltpu.VMEM}
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0), **spec_kwargs),
            pl.BlockSpec((d,), lambda i: (0,), **spec_kwargs),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0),
                               **spec_kwargs),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
        name="rmsnorm_fwd",
    )(x2d, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm_core(x2d, scale, eps, interpret):
    return _rmsnorm_fwd_impl(x2d, scale, eps, interpret)


def _rms_fwd(x2d, scale, eps, interpret):
    return _rmsnorm_fwd_impl(x2d, scale, eps, interpret), (x2d, scale)


def _rms_bwd(eps, interpret, res, g):
    x2d, scale = res
    x = x2d.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    s = scale.astype(jnp.float32)
    d = x.shape[-1]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = lax.rsqrt(var + eps)
    normed = x * inv
    d_scale = jnp.sum(gf * normed, axis=0)
    # d/dx of x*inv(x): inv * g*s − x * (x·(g*s)) * inv³ / d
    gs = gf * s
    dot = jnp.sum(gs * x, axis=-1, keepdims=True)
    dx = inv * gs - x * dot * inv ** 3 / d
    return dx.astype(x2d.dtype), d_scale.astype(scale.dtype)


_rmsnorm_core.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x, scale, eps: float = 1e-5, interpret: bool | None = None):
    """Fused RMSNorm over the last axis. x: [..., D], scale: [D].
    ``interpret=None`` interprets on the CPU platform only."""
    if interpret is None:
        interpret = jax_compat.interpret_kernels()
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    out = _rmsnorm_core(x2d, scale, eps, interpret)
    return out.reshape(shape)
