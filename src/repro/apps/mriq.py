"""MRI-Q (Parboil) — paper app #2.

The Parboil C source has 16 loop statements (paper §5.1.2).  Pipeline:
ComputePhiMag loop -> ComputeQ (outer voxel loop x inner k-space loop, the
hot nest) -> result checksum loop.  ``ref`` variants mirror the C loop
structure (sequential fori over k-space samples); ``offload`` is XLA's
blocked outer-product form; ``pallas`` the VPU kernel of kernels/mriq.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_apps import MRIQ_BENCH, MRIQ_FULL, MriQConfig
from repro.core.program import OffloadableProgram, Region
from repro.core.regions import Impl, dispatch, register_variant
from repro.kernels.mriq import mriq_compute_q
from repro.kernels.ref import mriq_ref


# ---------------------------------------------------------------------------
# Region: mriq_phimag  (|phi|^2 loop over k-space samples)
# ---------------------------------------------------------------------------
@register_variant("mriq_phimag", "ref")
def _phimag_ref(phi_r, phi_i):
    n = phi_r.shape[0]

    def step(j, acc):
        return acc.at[j].set(phi_r[j] * phi_r[j] + phi_i[j] * phi_i[j])

    return jax.lax.fori_loop(0, n, step, jnp.zeros_like(phi_r))


@register_variant("mriq_phimag", "offload")
def _phimag_offload(phi_r, phi_i):
    return phi_r * phi_r + phi_i * phi_i


# ---------------------------------------------------------------------------
# Region: compute_q  (the hot double loop)
# ---------------------------------------------------------------------------
@register_variant("compute_q", "ref")
def _q_ref(x, y, z, kx, ky, kz, pm):
    """Loop-faithful: sequential over k-space samples (C inner loop),
    vectorized over voxels (what a -O3 compiler autovectorizes)."""
    num_k = kx.shape[0]

    def step(j, acc):
        qr, qi = acc
        ph = 2.0 * jnp.pi * (kx[j] * x + ky[j] * y + kz[j] * z)
        return qr + pm[j] * jnp.cos(ph), qi + pm[j] * jnp.sin(ph)

    zero = jnp.zeros_like(x)
    return jax.lax.fori_loop(0, num_k, step, (zero, zero))


@register_variant("compute_q", "offload")
def _q_offload(x, y, z, kx, ky, kz, pm):
    """Blocked outer-product formulation, left to XLA."""
    return mriq_ref(x, y, z, kx, ky, kz, pm, chunk=2048)


@register_variant("compute_q", "pallas")
def _q_pallas(x, y, z, kx, ky, kz, pm):
    return mriq_compute_q(x, y, z, kx, ky, kz, pm)


# ---------------------------------------------------------------------------
# Region: mriq_check  (result checksum loop)
# ---------------------------------------------------------------------------
@register_variant("mriq_check", "ref")
def _check_ref(qr, qi):
    n = qr.shape[0]

    def step(i, acc):
        return acc + qr[i] * qr[i] + qi[i] * qi[i]

    return jax.lax.fori_loop(0, n, step, jnp.zeros((), qr.dtype))


@register_variant("mriq_check", "offload")
def _check_offload(qr, qi):
    return jnp.sum(qr * qr + qi * qi)


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------
def _pipeline(impl: Impl):
    def run(x, y, z, kx, ky, kz, phi_r, phi_i):
        pm = dispatch("mriq_phimag", impl, phi_r, phi_i)
        qr, qi = dispatch("compute_q", impl, x, y, z, kx, ky, kz, pm)
        chk = dispatch("mriq_check", impl, qr, qi)
        return qr, qi, chk
    return run


def _sample(cfg: MriQConfig):
    def make(key):
        ks = jax.random.split(key, 8)
        x, y, z = (jax.random.normal(ks[i], (cfg.num_x,), jnp.float32)
                   for i in range(3))
        kx, ky, kz = (jax.random.normal(ks[3 + i], (cfg.num_k,), jnp.float32) * 0.1
                      for i in range(3))
        phi_r = jax.random.normal(ks[6], (cfg.num_k,), jnp.float32)
        phi_i = jax.random.normal(ks[7], (cfg.num_k,), jnp.float32)
        return x, y, z, kx, ky, kz, phi_r, phi_i
    return make


def make_program(cfg: MriQConfig = MRIQ_BENCH,
                 analysis_cfg: MriQConfig = MRIQ_FULL) -> OffloadableProgram:
    fx = jax.ShapeDtypeStruct((analysis_cfg.num_x,), jnp.float32)
    fk = jax.ShapeDtypeStruct((analysis_cfg.num_k,), jnp.float32)
    regions = [
        Region("mriq_phimag", _phimag_ref, (fk, fk)),
        Region("compute_q", _q_ref, (fx, fx, fx, fk, fk, fk, fk)),
        Region("mriq_check", _check_ref, (fx, fx)),
    ]
    return OffloadableProgram(
        name="mriq",
        regions=regions,
        build=_pipeline,
        sample_inputs=_sample(cfg),
        source_loop_count=16,
        description="Parboil MRI-Q (paper app #2)",
    )
