"""Time-domain FIR filter bank (HPEC challenge tdFIR) — paper app #1.

The HPEC C source has 36 loop statements (paper §5.1.2); we reproduce its
computational pipeline with one offloadable region per loop nest that
matters, each with a loop-faithful ``ref`` variant (structured like the C
loops: explicit iteration, per-bank dynamic slices) and a restructured
``offload`` variant (what the FPGA OpenCL kernel / our Pallas kernel
computes in one shot).

Pipeline: load/scale input -> FIR bank (the hot triple loop) -> output
scaling -> per-bank energy verification.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_apps import TDFIR_BENCH, TDFIR_FULL, TdFirConfig
from repro.core.program import OffloadableProgram, Region
from repro.core.regions import (Impl, TuningSpace, dispatch,
                                register_variant)
from repro.core.resources import VMEM_BUDGET
from repro.kernels.fir import fir_filter_bank, fir_vmem_bytes
from repro.kernels.ref import fir_ref


# ---------------------------------------------------------------------------
# Region: fir_load  (input conditioning loop over banks)
# ---------------------------------------------------------------------------
@register_variant("fir_load", "ref")
def _load_ref(x):
    m = x.shape[0]

    def bank(i, acc):
        row = jax.lax.dynamic_slice_in_dim(x, i, 1, 0)
        row = row * (1.0 / jnp.sqrt(jnp.mean(jnp.abs(row) ** 2) + 1e-9))
        return jax.lax.dynamic_update_slice_in_dim(acc, row, i, 0)

    return jax.lax.fori_loop(0, m, bank, jnp.zeros_like(x))


@register_variant("fir_load", "offload")
def _load_offload(x):
    scale = 1.0 / jnp.sqrt(jnp.mean(jnp.abs(x) ** 2, axis=1, keepdims=True) + 1e-9)
    return x * scale


# ---------------------------------------------------------------------------
# Region: fir_bank  (the hot loop: banks x samples x taps)
# ---------------------------------------------------------------------------
@register_variant("fir_bank", "ref")
def _fir_ref(x, h):
    return fir_ref(x, h)          # fori over taps (loop-faithful)


@register_variant("fir_bank", "offload")
def _fir_offload(x, h):
    """Restructured with the paper's own speedup technique: FULL loop
    unrolling of the tap loop (paper §3.3 'loop unrolling', knob b -> K).
    Every tap becomes a static shifted MAC that XLA fuses into one pass."""
    n = x.shape[1]
    k = h.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0)))
    acc = jnp.zeros_like(x)
    for j in range(k):                      # unrolled at trace time
        acc = acc + h[:, j:j + 1] * jax.lax.slice_in_dim(
            xp, k - 1 - j, k - 1 - j + n, axis=1)
    return acc


def _fir_tile_ok(p, args) -> bool:
    """fir_bank tile legality: block_n divides the sample count, tap_unroll
    divides the tap count, and the per-grid-step VMEM footprint fits the
    budget.  Unbound queries (no args) accept every point."""
    if not args:
        return True
    try:
        m, n, k = args[0].shape[0], args[0].shape[1], args[1].shape[1]
    except (IndexError, AttributeError):
        return True
    bn, tu = p["block_n"], p["tap_unroll"]
    return (bn <= n and n % bn == 0 and tu <= k and k % tu == 0
            and fir_vmem_bytes(m, k, bn) <= VMEM_BUDGET)


@register_variant("fir_bank", "pallas", tuning=TuningSpace(
    axes={"block_n": (128, 256, 512, 1024), "tap_unroll": (1, 2, 4, 8)},
    defaults={"block_n": 512, "tap_unroll": 1},
    validity=_fir_tile_ok))
def _fir_pallas(x, h, *, block_n=512, tap_unroll=1):
    return fir_filter_bank(x, h, block_n=block_n, tap_unroll=tap_unroll)


# ---------------------------------------------------------------------------
# Region: fir_scale  (output normalization loop)
# ---------------------------------------------------------------------------
@register_variant("fir_scale", "ref")
def _scale_ref(y):
    m = y.shape[0]

    def bank(i, acc):
        row = jax.lax.dynamic_slice_in_dim(y, i, 1, 0) * (1.0 / y.shape[1])
        return jax.lax.dynamic_update_slice_in_dim(acc, row, i, 0)

    return jax.lax.fori_loop(0, m, bank, jnp.zeros_like(y))


@register_variant("fir_scale", "offload")
def _scale_offload(y):
    return y * (1.0 / y.shape[1])


# ---------------------------------------------------------------------------
# Region: fir_energy  (verification loop: per-bank output energy)
# ---------------------------------------------------------------------------
@register_variant("fir_energy", "ref")
def _energy_ref(y):
    m = y.shape[0]

    def bank(i, acc):
        row = jax.lax.dynamic_slice_in_dim(y, i, 1, 0)
        return acc.at[i].set(jnp.sum(jnp.abs(row) ** 2))

    return jax.lax.fori_loop(0, m, bank, jnp.zeros((m,), jnp.float32))


@register_variant("fir_energy", "offload")
def _energy_offload(y):
    return jnp.sum(jnp.abs(y) ** 2, axis=1).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------
def _pipeline(impl: Impl):
    def run(x, h):
        x = dispatch("fir_load", impl, x)
        y = dispatch("fir_bank", impl, x, h)
        y = dispatch("fir_scale", impl, y)
        e = dispatch("fir_energy", impl, y)
        return y, e
    return run


def _sample(cfg: TdFirConfig):
    def make(key):
        k1, k2 = jax.random.split(key)
        x = (jax.random.normal(k1, (cfg.n_banks, cfg.n_samples))
             + 1j * jax.random.normal(k1, (cfg.n_banks, cfg.n_samples))
             ).astype(jnp.complex64)
        h = (jax.random.normal(k2, (cfg.n_banks, cfg.n_taps))
             + 1j * jax.random.normal(k2, (cfg.n_banks, cfg.n_taps))
             ).astype(jnp.complex64)
        return x, h
    return make


def make_program(cfg: TdFirConfig = TDFIR_FULL,
                 analysis_cfg: TdFirConfig = TDFIR_FULL) -> OffloadableProgram:
    x_abs = jax.ShapeDtypeStruct((analysis_cfg.n_banks, analysis_cfg.n_samples),
                                 jnp.complex64)
    h_abs = jax.ShapeDtypeStruct((analysis_cfg.n_banks, analysis_cfg.n_taps),
                                 jnp.complex64)
    y_abs = x_abs
    regions = [
        Region("fir_load", _load_ref, (x_abs,)),
        Region("fir_bank", _fir_ref, (x_abs, h_abs)),
        Region("fir_scale", _scale_ref, (y_abs,)),
        Region("fir_energy", _energy_ref, (y_abs,)),
    ]
    return OffloadableProgram(
        name="tdfir",
        regions=regions,
        build=_pipeline,
        sample_inputs=_sample(cfg),
        source_loop_count=36,
        description="HPEC time-domain FIR filter bank (paper app #1)",
    )
