"""Mamba-1 selective-scan Pallas kernel (falcon-mamba).

h_t[c, n] = a_t[c, n] * h_{t-1}[c, n] + bx_t[c, n];  y_t[c] = h_t[c, :] @ c_t

Grid: (batch, channel blocks, time chunks), time innermost and sequential.
The kernel works on the state-major layout [B, S, N, D] — the state index on
sublanes, channels on lanes — so one time step of a [N=16, 128] block is two
full vregs rather than 128 rows of 16 live lanes.  The state [N, bc] stays in
VMEM scratch across the time chunks of one channel block; each step reads
its row of the decay/input tiles straight from the refs (ref-level indexing:
Mosaic has no value-level dynamic slice) and writes one output row.  TPU
adaptation of the paper's loop-offload idea for an attention-free arch: the
scan loop is the arch's hottest loop statement, and VMEM residency of the
state is what the FPGA implementation would get from BRAM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssm_kernel(a_ref, bx_ref, c_ref, h0_ref, y_ref, hf_ref, h_ref, *,
                time_chunk: int):
    # float32 a/bx: [1, T, N, bc]; c: [1, T, N, 1]; h0/hf: [1, N, bc];
    # y: [1, T, bc]
    tc = pl.program_id(2)

    @pl.when(tc == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    def step(t, h):
        h = a_ref[0, t] * h + bx_ref[0, t]                  # [N, bc]
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(h * c_ref[0, t], axis=0,
                                           keepdims=True)
        return h

    h_ref[...] = jax.lax.fori_loop(0, time_chunk, step, h_ref[...])

    @pl.when(tc == pl.num_programs(2) - 1)
    def _finish():
        hf_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("block_c", "time_chunk", "interpret"))
def ssm_scan(a: jax.Array, bx: jax.Array, c: jax.Array, h0: jax.Array, *,
             block_c: int = 128, time_chunk: int = 64,
             interpret: bool | None = None):
    """a, bx: [B, S, D, N]; c: [B, S, N]; h0: [B, D, N].
    Returns (y [B, S, D], h_final [B, D, N] float32).

    VMEM per step (float32): a/bx tiles 2 * time_chunk * N * block_c, the
    C column time_chunk * N * 128 (lane-padded), the output tile
    time_chunk * block_c and the state N * block_c; at the defaults with
    N=16 ~= 1.6 MB, double-buffered ~= 3.2 MB."""
    bsz, s, d, n = a.shape
    block_c = min(block_c, d)
    time_chunk = min(time_chunk, s)
    assert d % block_c == 0 and s % time_chunk == 0

    # state-major float32 layout (one-row stores need 32-bit tiles).  The
    # transposes and casts run as XLA passes over HBM before the kernel:
    # XLA cannot fuse them into the Pallas call.  The C column [B, S, N, 1]
    # takes a full 128-lane tile per row in the chip's tiled layout.
    f32 = jnp.float32
    a_t = jnp.swapaxes(a, 2, 3).astype(f32)                  # [B, S, N, D]
    bx_t = jnp.swapaxes(bx, 2, 3).astype(f32)
    c_t = c[..., None].astype(f32)                           # [B, S, N, 1]
    h0_t = jnp.swapaxes(h0, 1, 2).astype(f32)                # [B, N, D]

    tile = pl.BlockSpec((1, time_chunk, n, block_c),
                        lambda i, j, t: (i, t, 0, j))
    state = pl.BlockSpec((1, n, block_c), lambda i, j, t: (i, 0, j))
    y, hf = pl.pallas_call(
        functools.partial(_ssm_kernel, time_chunk=time_chunk),
        grid=(bsz, d // block_c, s // time_chunk),
        in_specs=[
            tile,
            tile,
            pl.BlockSpec((1, time_chunk, n, 1), lambda i, j, t: (i, t, 0, 0)),
            state,
        ],
        out_specs=[
            pl.BlockSpec((1, time_chunk, block_c), lambda i, j, t: (i, t, j)),
            state,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), f32),
            jax.ShapeDtypeStruct((bsz, n, d), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, block_c), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a_t, bx_t, c_t, h0_t)
    return y.astype(a.dtype), jnp.swapaxes(hf, 1, 2)
