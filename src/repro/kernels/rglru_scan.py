"""RG-LRU linear-recurrence Pallas kernel (recurrentgemma).

h_t = a_t * h_{t-1} + b_t, diagonal over channels.  Grid: (batch, channel
blocks, time chunks), time innermost and sequential; the state [1, bc]
stays in VMEM scratch across the time chunks of one channel block (the
recurrence is latency-bound, not MXU work — on TPU the win is keeping the
[T, bc] tiles resident in VMEM instead of T separate HBM round-trips,
exactly the Griffin production approach).  Each step reads and writes one
row of the tiles through the refs (ref-level indexing: Mosaic has no
value-level dynamic slice).  Channel blocks are lane-aligned (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, hf_ref, h_ref, *,
                  time_chunk: int):
    # float32 a/b/y: [1, T, bc]; h0/hf: [1, 1, bc]
    tc = pl.program_id(2)

    @pl.when(tc == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    def step(t, h):
        h = a_ref[0, pl.ds(t, 1), :] * h + b_ref[0, pl.ds(t, 1), :]  # [1, bc]
        y_ref[0, pl.ds(t, 1), :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, time_chunk, step, h_ref[...])

    @pl.when(tc == pl.num_programs(2) - 1)
    def _finish():
        hf_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("block_c", "time_chunk", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array, *, block_c: int = 128,
               time_chunk: int = 128, interpret: bool | None = None):
    """a, b: [B, S, D]; h0: [B, D] -> (h_all [B, S, D], h_final [B, D]
    float32).

    VMEM per step: 3 * time_chunk * block_c float32 tiles (a, b, y) + the
    state; double-buffered at the defaults ~= 0.4 MB."""
    bsz, s, d = a.shape
    block_c = min(block_c, d)
    time_chunk = min(time_chunk, s)
    assert d % block_c == 0 and s % time_chunk == 0

    # float32 tiles: Mosaic stores single rows only into 32-bit tiles.  The
    # casts run as XLA passes over HBM before the kernel.
    f32 = jnp.float32
    tile = pl.BlockSpec((1, time_chunk, block_c), lambda i, j, t: (i, t, j))
    state = pl.BlockSpec((1, 1, block_c), lambda i, j, t: (i, 0, j))
    y, hf = pl.pallas_call(
        functools.partial(_rglru_kernel, time_chunk=time_chunk),
        grid=(bsz, d // block_c, s // time_chunk),
        in_specs=[tile, tile, state],
        out_specs=[tile, state],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), f32),
            jax.ShapeDtypeStruct((bsz, 1, d), f32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a.astype(f32), b.astype(f32), h0[:, None, :].astype(f32))
    return y.astype(a.dtype), hf[:, 0]
