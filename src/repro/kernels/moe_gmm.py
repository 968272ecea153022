"""Grouped matrix products of a dropless expert layer (Pallas TPU).

The rows of ``x`` are (token, expert) pairs sorted by the expert they were
routed to, each expert's rows padded to whole tiles of ``tm`` rows, so that
every row tile belongs to one expert: ``tile_group[i]`` names it.  Only the
first ``n_tiles`` tiles hold rows.  The grid walks just those (its size is a
traced value, read before the kernel starts): an expert that no pair reached
costs nothing, and one that some did has its weights read once per row tile
of its pairs.  Rows past ``n_tiles * tm`` are left unwritten.

Two kernels, each a ``pallas_call`` named ``moe_gmm`` (the name the device
trace shows):

* :func:`gate_up`: ``h = silu(x @ w_gate[g]) * (x @ w_up[g])``, both
  products accumulated in float32 over K tiles, ``h`` stored in x's dtype;
* :func:`down`: ``y = h @ w_down[g]``, accumulated and returned in float32.

Grid: (N tiles, row tiles, K tiles), K innermost, so an output tile stays in
VMEM while its products accumulate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NAME = "moe_gmm"

# (tk, tn) of the weight blocks: large blocks where row tiles are short
# (decode: the step streams every hit expert's weights once, and fewer grid
# steps cost less), smaller where rows fill the matrix unit (prefill)
_BLOCKS_FEW_ROWS = (1024, 2048)
_BLOCKS_MANY_ROWS = (512, 1024)
_FEW_ROWS = 128
_VMEM_LIMIT = 64 * 1024 * 1024


def row_tile(pairs: int, num_experts: int) -> int:
    """Rows per tile for ``pairs`` routed pairs over ``num_experts``: the
    expected pairs per expert, as a power of two in [16, 512]."""
    per_expert = max(1, -(-pairs // num_experts))
    return int(min(512, max(16, 1 << (per_expert - 1).bit_length())))


def padded_rows(pairs: int, groups: int, tm: int) -> int:
    """Rows that hold ``pairs`` rows in ``groups`` groups, each padded to
    whole tiles of ``tm``, whatever their sizes: the dropless bound."""
    return -(-(pairs + groups * (tm - 1)) // tm) * tm


def _blocks(tm: int, k: int, n: int) -> tuple[int, int]:
    tk, tn = _BLOCKS_FEW_ROWS if tm < _FEW_ROWS else _BLOCKS_MANY_ROWS
    tk, tn = min(tk, k), min(tn, n)
    assert k % tk == 0 and n % tn == 0, (k, n, tk, tn)
    return tk, tn


def _gate_up_kernel(groups_ref, n_ref, x_ref, wg_ref, wu_ref, h_ref,
                    acc_g, acc_u):
    del groups_ref, n_ref
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[...]
    acc_g[...] += jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    acc_u[...] += jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _store():
        g = acc_g[...]
        h_ref[...] = (g * jax.nn.sigmoid(g) * acc_u[...]).astype(h_ref.dtype)


def _down_kernel(groups_ref, n_ref, h_ref, wd_ref, y_ref):
    del groups_ref, n_ref

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[...] += jnp.dot(h_ref[...], wd_ref[...],
                          preferred_element_type=jnp.float32)


def _call(kernel, x, weights, tile_group, n_tiles, tm, out_dtype, scratch,
          interpret):
    m, k = x.shape
    n = weights[0].shape[-1]
    tk, tn = _blocks(tm, k, n)
    rows = pl.BlockSpec((tm, tk), lambda j, i, kk, g, nt: (i, kk))
    weight = pl.BlockSpec((None, tk, tn), lambda j, i, kk, g, nt: (g[i], kk, j))
    out = pl.BlockSpec((tm, tn), lambda j, i, kk, g, nt: (i, j))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[rows] + [weight] * len(weights),
            out_specs=out,
            grid=(n // tn, n_tiles[0], k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
        name=NAME,
    )(tile_group, n_tiles, x, *weights)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def gate_up(x, w_gate, w_up, tile_group, n_tiles, *, tm: int,
            interpret: bool | None = None):
    """x: [M, D] (M a multiple of ``tm``); w_gate, w_up: [G, D, F];
    tile_group: [M // tm] int32; n_tiles: [1] int32.  Returns h [M, F] in
    x's dtype, its rows past ``n_tiles * tm`` unwritten."""
    return _call(_gate_up_kernel, x, (w_gate, w_up), tile_group, n_tiles, tm,
                 x.dtype, 2, interpret)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def down(h, w_down, tile_group, n_tiles, *, tm: int,
         interpret: bool | None = None):
    """h: [M, F]; w_down: [G, F, D].  Returns y [M, D] float32, its rows
    past ``n_tiles * tm`` unwritten."""
    return _call(_down_kernel, h, (w_down,), tile_group, n_tiles, tm,
                 jnp.float32, 0, interpret)
