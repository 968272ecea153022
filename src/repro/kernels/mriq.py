"""MRI-Q Pallas kernel — the paper's second evaluation app (Parboil).

computeQ: for every voxel i, accumulate over k-space samples j:
    t        = kx[j]*x[i] + ky[j]*y[i] + kz[j]*z[i]      (phase in cycles)
    Q_re[i] += phiMag[j] * cos(2*pi*t)
    Q_im[i] += phiMag[j] * sin(2*pi*t)

TPU adaptation (vs. the paper's FPGA pipeline): the VPU alone does the
arithmetic, in float32, with no MXU pass.  Voxels lie dense over sublanes
and lanes: x, y, z and Q are reshaped to [numX/128, 128], and a grid step
owns ``block_x / 128`` such rows, which stay in vector registers with the
two sums.  k-space samples are scalars: a chunk of ``block_k`` samples
(rows kx, ky, kz, phiMag) sits in SMEM, the inner grid axis walks the
chunks, and a loop over the chunk updates the sums with scalar-times-vector
products, so there is no broadcast and no cross-lane reduction.  Each
chunk's sums start from zero and are added to the output block, which is
revisited across the inner axis.

Q_re and Q_im leave as one [2, numX/128, 128] array.  Taking the two apart
is a copy that XLA fuses and may keep in VMEM for the next region; two
outputs would flatten by bitcast and stay in HBM, where a scalar consumer
(the loop-faithful checksum the planner pairs the kernel with) reads each
element from HBM.

sin and cos share one range reduction, in cycles (``sincos``).  The
reduction is exact; the reference's ``2*pi*t`` in radians rounds a phase
of up to about 300 rad before jnp.sin and jnp.cos reduce it again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128
SUBLANES = 8
# Rows of 128 voxels per grid step and samples per loop step, measured at
# Parboil large on a TPU v5e: 32 rows (four vector registers per array)
# unrolled by 8 took 4.2 ms, 16 rows 4.5 ms, 8 rows 5.4 ms; by 4, 4.4 ms.
MAX_ROWS = 32
UNROLL = 8
# Samples per SMEM chunk: (kx, ky, kz, phiMag) of 2,048 samples is 32 KiB.
MAX_CHUNK = 2048

# Cephes sinf/cosf on [-pi/4, pi/4], in theta^2
_SIN = (-1.9515295891e-4, 8.3321608736e-3, -1.6666654611e-1)
_COS = (2.443315711809948e-5, -1.388731625493765e-3, 4.166664568298827e-2)
_TWO_PI = float(np.float32(2.0 * np.pi))


def sincos(t):
    """(sin(2*pi*t), cos(2*pi*t)) of float32 phases ``t`` in cycles,
    |t| < 2**21, with one range reduction for both.

    n = floor(4t + 1/2) names the quarter cycle and r = t - n/4 lies in
    [-1/8, 1/8].  That subtraction is exact: n/4 is a multiple of t's ulp
    and |r| <= |t|, so r needs no more bits than t has.  The one rounding
    left before the polynomials is theta = 2*pi*r in [-pi/4, pi/4].  n mod 4
    then picks (s, c), (c, -s), (-s, -c) or (-c, s) for (sin, cos)."""
    n = jnp.floor(4.0 * t + 0.5)
    r = t - 0.25 * n
    theta = _TWO_PI * r
    z = theta * theta
    s = ((_SIN[0] * z + _SIN[1]) * z + _SIN[2]) * z * theta + theta
    c = ((_COS[0] * z + _COS[1]) * z + _COS[2]) * z * z - 0.5 * z + 1.0
    q = n.astype(jnp.int32)
    odd = (q & 1) != 0
    sin = jnp.where(odd, c, s)
    cos = jnp.where(odd, s, c)
    # sin is negated in quadrants 2 and 3, cos in 1 and 2: flip the sign bit
    return _flip_sign(sin, (q & 2) << 30), _flip_sign(cos, ((q + 1) & 2) << 30)


def _flip_sign(v, bit):
    i32 = jax.lax.bitcast_convert_type(v, jnp.int32)
    return jax.lax.bitcast_convert_type(i32 ^ bit, jnp.float32)


def _mriq_kernel(k_ref, x_ref, y_ref, z_ref, q_ref):
    # k_ref: SMEM [4, block_k] rows (kx, ky, kz, phiMag); the rest [rows, 128]
    x, y, z = x_ref[...], y_ref[...], z_ref[...]

    def sample(j, acc):
        qr, qi = acc
        t = x * k_ref[0, j] + y * k_ref[1, j] + z * k_ref[2, j]
        sin, cos = sincos(t)
        pm = k_ref[3, j]
        return qr + pm * cos, qi + pm * sin

    def samples(i, acc):      # Mosaic unrolls a loop only fully: by hand
        for u in range(UNROLL):
            acc = sample(i * UNROLL + u, acc)
        return acc

    zero = jnp.zeros_like(x)
    qr, qi = jax.lax.fori_loop(0, k_ref.shape[1] // UNROLL, samples,
                               (zero, zero))

    @pl.when(pl.program_id(1) == 0)
    def _init():
        q_ref[...] = jnp.zeros_like(q_ref)

    q_ref[0] += qr
    q_ref[1] += qi


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def default_blocks(num_x: int, num_k: int) -> tuple[int, int]:
    """(block_x, block_k) for these sizes: up to ``MAX_ROWS`` rows of 128
    voxels (at least one full vector register of 8 rows), and the samples in
    chunks of up to ``MAX_CHUNK``, a multiple of 128."""
    rows = min(MAX_ROWS, _round_up(-(-num_x // LANES), SUBLANES))
    return rows * LANES, min(MAX_CHUNK, _round_up(num_k, LANES))


@functools.partial(jax.jit, static_argnames=("block_x", "block_k", "interpret"))
def mriq_compute_q(x, y, z, kx, ky, kz, phi_mag, *, block_x: int | None = None,
                   block_k: int | None = None, interpret: bool | None = None):
    """All inputs f32 1-D.  Returns (Q_re [numX], Q_im [numX]).

    ``block_x`` voxels per grid step (a multiple of 8 x 128) and ``block_k``
    samples per chunk (a multiple of 128) default to ``default_blocks``.
    VMEM per step: x, y, z and the two sums, 5 x block_x floats, double
    buffered; SMEM: 4 x block_k floats, double buffered."""
    num_x = x.shape[0]
    num_k = kx.shape[0]
    bx, bk = default_blocks(num_x, num_k)
    block_x, block_k = block_x or bx, block_k or bk
    if block_x % (SUBLANES * LANES) or block_k % LANES:
        raise ValueError(f"block_x={block_x} must be a multiple of "
                         f"{SUBLANES * LANES}, block_k={block_k} of {LANES}")
    px = (-num_x) % block_x
    pk = (-num_k) % block_k
    # padded voxels are dropped below; padded samples carry phiMag 0
    x, y, z = (jnp.pad(a, (0, px)).reshape(-1, LANES) for a in (x, y, z))
    traj = jnp.stack([jnp.pad(a, (0, pk)) for a in (kx, ky, kz, phi_mag)])

    rows = block_x // LANES
    grid = ((num_x + px) // block_x, (num_k + pk) // block_k)
    voxels = pl.BlockSpec((rows, LANES), lambda i, j: (i, 0))
    q = pl.pallas_call(
        _mriq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, block_k), lambda i, j: (0, j),
                         memory_space=pltpu.SMEM),
            voxels, voxels, voxels,
        ],
        out_specs=pl.BlockSpec((2, rows, LANES), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, *x.shape), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(traj, x, y, z)
    return q[0].reshape(-1)[:num_x], q[1].reshape(-1)[:num_x]
