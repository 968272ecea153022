"""MRI-Q Pallas kernel — the paper's second evaluation app (Parboil).

computeQ: for every voxel i, accumulate over k-space samples j:
    phase    = 2*pi * (kx[j]*x[i] + ky[j]*y[i] + kz[j]*z[i])
    Q_re[i] += phiMag[j] * cos(phase)
    Q_im[i] += phiMag[j] * sin(phase)

TPU adaptation (vs. the paper's FPGA pipeline): grid = (voxel blocks,
k-space chunks).  The phase matrix for one (block_x × block_k) tile is an
MXU matmul of the [block_x, 4] coordinate tile against the [4, block_k]
trajectory tile; sin/cos run on the VPU (transcendental-bound — this is the
kernel's roofline term); the phiMag reduction is a [block_x, block_k] @
[block_k] matvec.  Accumulation across k chunks uses the output ref
(revisited across the inner grid dim) with @pl.when init.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _mriq_kernel(xyz_ref, traj_ref, qr_ref, qi_ref):
    # xyz: [block_x, 4] (x, y, z, 0); traj: [4, block_k] rows (kx, ky, kz, 0)
    # phiMag folded into traj row 3?  No — phiMag must scale cos/sin, so traj
    # carries it as a separate row: traj rows = (kx, ky, kz, phiMag).
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        qr_ref[...] = jnp.zeros_like(qr_ref)
        qi_ref[...] = jnp.zeros_like(qi_ref)

    xyz = xyz_ref[...]                               # [bx, 4]
    traj = traj_ref[...]                             # [4, bk]
    # traj row 3 is phiMag, but xyz col 3 is zero, so the matmul ignores it.
    # All three contractions at HIGHEST: Mosaic's default takes float32
    # operands in one bfloat16 MXU pass, which leaves the result 6.7e-03 off
    # a float32 reference on a v5e (2.3e-03 with only the phase at HIGHEST;
    # 2.8e-07 with all three, for 1.39x the kernel time).
    hi = jax.lax.Precision.HIGHEST
    phase = 2.0 * jnp.pi * jnp.dot(xyz, traj, precision=hi,
                                   preferred_element_type=jnp.float32)
    pm = traj[3, :]                                  # [bk]
    qr_ref[...] += jnp.dot(jnp.cos(phase), pm[:, None], precision=hi,
                           preferred_element_type=jnp.float32)
    qi_ref[...] += jnp.dot(jnp.sin(phase), pm[:, None], precision=hi,
                           preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_x", "block_k", "interpret"))
def mriq_compute_q(x, y, z, kx, ky, kz, phi_mag, *, block_x: int = 256,
                   block_k: int = 512, interpret: bool | None = None):
    """All inputs f32 1-D.  Returns (Q_re [numX], Q_im [numX]).

    VMEM per step: bx*4 + 4*bk + bx*bk (phase tile) floats
    ~= (1024 + 2048 + 131072)*4B ~= 0.5 MB for the defaults."""
    num_x = x.shape[0]
    num_k = kx.shape[0]
    px = (-num_x) % block_x
    pk = (-num_k) % block_k
    xyz = jnp.stack([jnp.pad(x, (0, px)), jnp.pad(y, (0, px)),
                     jnp.pad(z, (0, px)),
                     jnp.zeros(num_x + px, jnp.float32)], axis=1)   # [X, 4]
    traj = jnp.stack([jnp.pad(kx, (0, pk)), jnp.pad(ky, (0, pk)),
                      jnp.pad(kz, (0, pk)),
                      jnp.pad(phi_mag, (0, pk))], axis=0)           # [4, K]

    grid = ((num_x + px) // block_x, (num_k + pk) // block_k)
    qr, qi = pl.pallas_call(
        _mriq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_x, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((4, block_k), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_x, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_x, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_x + px, 1), jnp.float32),
            jax.ShapeDtypeStruct((num_x + px, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xyz, traj)
    return qr[:num_x, 0], qi[:num_x, 0]
