"""Mamba-1 selective-scan Pallas kernel (falcon-mamba).

h_t[c, n] = a_t[c, n] * h_{t-1}[c, n] + bx_t[c, n];  y_t[c] = h_t[c, :] @ c_t

The kernel takes its decay and input in either of two forms:

* arrays ``a``, ``bx`` [B, S, D, N], built by the caller;
* :class:`Factored`: ``dt``, ``x`` [B, S, D], ``A`` [D, N] and ``B``
  [B, S, N], from which each step builds ``a_t = exp(dt_t * A)`` and
  ``bx_t = dt_t * x_t * B_t`` in registers, so the [B, S, D, N] tensors
  never exist in HBM (discretisation inside the scan).

Grid: (batch, time chunks, channel blocks), channels innermost, so the B/C
columns of a time chunk stay in VMEM while every channel block walks it.
The state of every channel block, state-major [N, bc] (the state index on
sublanes, channels on lanes: one time step of a [16, 128] block is two full
vregs), stays in VMEM scratch across the time chunks.  One time-loop body
serves both forms; each step reads its row of the tiles straight from the
refs (ref-level indexing: Mosaic has no value-level dynamic slice) and
writes one output row.  TPU adaptation of the paper's loop-offload idea for
an attention-free arch: the scan loop is the arch's hottest loop statement,
and VMEM residency of the state is what the FPGA implementation would get
from BRAM.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# time steps per iteration of the kernel's time loop: only the state
# update carries from one step to the next, so unrolled steps overlap
TIME_UNROLL = 16


class Factored(NamedTuple):
    """The scan's decay and input before discretisation.

    ``a = exp(dt[..., None] * A)`` and ``bx = (dt * x)[..., None] *
    B[:, :, None, :]``, each [B, S, D, N], are built where they are used.
    A step with ``dt = 0`` is the identity (a = 1, bx = 0): padding is
    masked by zeroing ``dt``."""
    dt: jax.Array       # [B, S, D]
    x: jax.Array        # [B, S, D]
    A: jax.Array        # [D, N] float32 (negative)
    B: jax.Array        # [B, S, N]

    @property
    def shape(self) -> tuple:
        """Shape of the [B, S, D, N] decay and input this form stands for."""
        return (*self.dt.shape, self.A.shape[-1])

    def expand(self) -> tuple[jax.Array, jax.Array]:
        """(a, bx) arrays in ``x``'s dtype: ``a`` computed in float32."""
        a = jnp.exp(self.dt[..., None].astype(jnp.float32) * self.A)
        bx = (self.dt * self.x)[..., None] * self.B[:, :, None, :]
        return a.astype(self.x.dtype), bx.astype(self.x.dtype)


def _ssm_kernel(*refs, factored: bool, time_chunk: int):
    # arrays: float32 a/bx [1, T, N, bc]; factored: dt/x [1, T, bc] in the
    # model's dtype, A [N, bc] float32; both: c (and B) [1, T, N, 1]
    # float32, h0/hf [1, N, bc], y [1, T, bc], state scratch [D/bc, N, bc]
    if factored:
        (dt_ref, x_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hf_ref, h_ref,
         dt32_ref, u_ref) = refs
        # widen the chunk's dt and dt * x once, aligned, into float32 rows
        dt = dt_ref[0].astype(jnp.float32)
        dt32_ref[...] = dt
        u_ref[...] = dt * x_ref[0].astype(jnp.float32)
        decay_a = a_ref[...]

        def decay_input(t):
            a_t = jnp.exp(dt32_ref[pl.ds(t, 1), :] * decay_a)     # [N, bc]
            return a_t, u_ref[pl.ds(t, 1), :] * b_ref[0, t]
    else:
        a_ref, bx_ref, c_ref, h0_ref, y_ref, hf_ref, h_ref = refs

        def decay_input(t):
            return a_ref[0, t], bx_ref[0, t]

    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_ref[j] = h0_ref[0]

    def step(t, h):
        a_t, bx_t = decay_input(t)
        h = a_t * h + bx_t                                       # [N, bc]
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(h * c_ref[0, t], axis=0,
                                           keepdims=True)
        return h

    unroll = math.gcd(TIME_UNROLL, time_chunk)

    def steps(i, h):                     # Mosaic unrolls no loop partly
        for k in range(unroll):
            h = step(i * unroll + k, h)
        return h

    h = jax.lax.fori_loop(0, time_chunk // unroll, steps, h_ref[j])
    h_ref[j] = h
    # written every chunk: the block's last write-back holds the final state
    hf_ref[0] = h


@functools.partial(jax.jit, static_argnames=("block_c", "time_chunk", "interpret"))
def ssm_scan(a, bx, c: jax.Array, h0: jax.Array, *,
             block_c: int = 128, time_chunk: int = 64,
             interpret: bool | None = None):
    """a, bx: [B, S, D, N] arrays, or ``a`` a :class:`Factored` and ``bx``
    None; c: [B, S, N]; h0: [B, D, N].
    Returns (y [B, S, D] in the dtype of ``a`` (of ``x`` when factored),
    h_final [B, D, N] float32).

    Arrays need ``time_chunk | S``; the factored form pads S to a multiple
    of it with identity steps (dt = 0).  VMEM per step (float32): the
    state of all D channels N * D, the B/C columns time_chunk * N * 128
    each (lane-padded), the output tile time_chunk * block_c, and either
    the a/bx tiles 2 * time_chunk * N * block_c or the dt/x tiles with
    their widened copies 4 * time_chunk * block_c; at N=16, D=8192 about
    1.7 MB factored and 2 MB arrays at the defaults, 4.5 MB factored at
    the seq variant's (1024, 128); double-buffering the blocks adds about
    as much again."""
    factored = isinstance(a, Factored)
    bsz, s, d, n = a.shape
    block_c = min(block_c, d)
    time_chunk = min(time_chunk, s)
    assert d % block_c == 0
    f32 = jnp.float32
    pad = (-s) % time_chunk
    if factored:
        # state-major A; dt = 0 makes every padded step the identity
        dt, x, bmat = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (a.dt, a.x, a.B))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        ins = (dt, x, a.A.T.astype(f32), bmat[..., None].astype(f32))
        out_dtype = a.x.dtype
    else:
        assert pad == 0
        # state-major float32 layout (one-row stores need 32-bit tiles).
        # The transposes and casts run as XLA passes over HBM before the
        # kernel: XLA cannot fuse them into the Pallas call.
        ins = (jnp.swapaxes(a, 2, 3).astype(f32),             # [B, S, N, D]
               jnp.swapaxes(bx, 2, 3).astype(f32))
        out_dtype = a.dtype
    sp = s + pad
    # The C (and B) column [B, S, N, 1] takes a full 128-lane tile per row
    # in the chip's tiled layout; its block index ignores the channel
    # block, so each column is fetched once per time chunk.
    c_t = c[..., None].astype(f32)                           # [B, S, N, 1]
    h0_t = jnp.swapaxes(h0, 1, 2).astype(f32)                # [B, N, D]

    column = pl.BlockSpec((1, time_chunk, n, 1), lambda i, t, j: (i, t, 0, 0))
    rows = pl.BlockSpec((1, time_chunk, block_c), lambda i, t, j: (i, t, j))
    state = pl.BlockSpec((1, n, block_c), lambda i, t, j: (i, 0, j))
    if factored:
        in_specs = [rows, rows,
                    pl.BlockSpec((n, block_c), lambda i, t, j: (0, j)),
                    column]
        scratch = [pltpu.VMEM((time_chunk, block_c), f32)] * 2
    else:
        tile = pl.BlockSpec((1, time_chunk, n, block_c),
                            lambda i, t, j: (i, t, 0, j))
        in_specs = [tile, tile]
        scratch = []
    y, hf = pl.pallas_call(
        functools.partial(_ssm_kernel, factored=factored,
                          time_chunk=time_chunk),
        grid=(bsz, sp // time_chunk, d // block_c),
        in_specs=[*in_specs, column, state],
        out_specs=[rows, state],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, sp, d), f32),
            jax.ShapeDtypeStruct((bsz, n, d), f32),
        ],
        scratch_shapes=[pltpu.VMEM((d // block_c, n, block_c), f32),
                        *scratch],
        interpret=resolve_interpret(interpret),
    )(*ins, c_t, h0_t)
    return y[:, :s].astype(out_dtype), jnp.swapaxes(hf, 1, 2)
