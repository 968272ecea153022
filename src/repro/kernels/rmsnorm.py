"""Fused RMSNorm Pallas kernel: one HBM pass (read x, write normed x) instead
of XLA's separate mean-square reduce + scale passes.  Grid over row blocks;
the full feature dim lives in VMEM (d_model <= 8192 -> 32 KB/row fp32)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                       # [br, d]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * (1.0 + w_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "eps", "interpret"))
def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool | None = None) -> jax.Array:
    """x: [..., D]; w: [D]."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    xf = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((rows + pad) // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        interpret=resolve_interpret(interpret),
    )(xf, w)
    return out[:rows].reshape(orig_shape)
