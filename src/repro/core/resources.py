"""Resource estimation — the paper's Step 3 (HDL-stage precompile analogue).

On FPGA: generate per-loop OpenCL, compile *only to the HDL stage* (minutes),
read Flip-Flop/LUT utilization.  On TPU: lower the variant with
``jax.jit(...).lower()`` (seconds, no full compile), read

* ``vmem_bytes``   — the kernel's VMEM working set.  For Pallas variants this
  comes from the registered BlockSpec-tile estimator (the tiles ARE the VMEM
  claim); for XLA variants, from the largest live intermediate in the jaxpr
  (a fusion-tile proxy).
* ``hlo_ops``      — lowered StableHLO op count ("logic utilization" proxy).
* ``lower_seconds``— the precompile cost itself (recorded, like the paper's
  minutes-level HDL pass).

``resource_fraction`` = vmem_bytes / 16 MiB, the denominator of the paper's
resource efficiency.  Patterns whose summed fraction exceeds the cap are
never built (paper: combinations over the FPGA resource limit are skipped).

These Step-3 estimates do double duty: together with the Step-2 analysis
counts (flops / bytes / transcendentals / alignment) they seed the roofline
``CostModel`` (core/cost_model.py) that the ``surrogate`` search strategy
uses to score whole genome populations without spending measurements.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fir import fir_vmem_bytes

VMEM_BUDGET = 16 * 1024 * 1024      # 16 MiB per TPU core

# (region, variant) -> fn(*abstract_args) -> vmem bytes.  Mirrors each
# kernel's BlockSpec tiling (documented in the kernel files).
_VMEM_ESTIMATORS: dict[tuple[str, str], Callable] = {}


def register_vmem_estimator(region: str, variant: str):
    def deco(fn):
        _VMEM_ESTIMATORS[(region, variant)] = fn
        return fn
    return deco


def _default_vmem_estimate(fn, args) -> float:
    """Largest live intermediate tensor in the jaxpr — proxy for the fusion
    tile an XLA variant would hold resident."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    biggest = 0

    def walk(j):
        nonlocal biggest
        for eqn in j.eqns:
            for v in eqn.outvars:
                if v.aval.shape:
                    biggest = max(biggest, int(np.prod(v.aval.shape))
                                  * jnp.dtype(v.aval.dtype).itemsize)
            if not hasattr(eqn, "params"):
                continue
            for p in ("jaxpr", "body_jaxpr", "call_jaxpr", "cond_jaxpr"):
                inner = eqn.params.get(p)
                if inner is not None:
                    walk(getattr(inner, "jaxpr", inner))
            # `cond` carries its arms in `branches`, not a single sub-jaxpr;
            # skipping them let conditional regions under-report VMEM
            for br in eqn.params.get("branches", ()) or ():
                walk(getattr(br, "jaxpr", br))
    walk(jaxpr.jaxpr)
    return float(min(biggest, 8 * VMEM_BUDGET))


@dataclass
class ResourceEstimate:
    region: str
    variant: str
    vmem_bytes: float
    hlo_ops: int
    lower_seconds: float
    lower_ok: bool
    error: str = ""

    @property
    def resource_fraction(self) -> float:
        """Fraction of the VMEM budget (>1.0 = spills, like FPGA overflow)."""
        return self.vmem_bytes / VMEM_BUDGET


def precompile(region: str, variant: str, fn: Callable, args,
               static_kwargs: Optional[dict] = None) -> ResourceEstimate:
    """The cheap lowering pass.  ``args`` may be ShapeDtypeStructs."""
    static_kwargs = static_kwargs or {}
    t0 = time.perf_counter()
    try:
        lowered = jax.jit(lambda *a: fn(*a, **static_kwargs)).lower(*args)
        text = lowered.as_text()
        hlo_ops = sum(1 for line in text.splitlines() if "=" in line)
        est = _VMEM_ESTIMATORS.get((region, variant))
        vmem = float(est(*args)) if est else _default_vmem_estimate(
            lambda *a: fn(*a, **static_kwargs), args)
        return ResourceEstimate(region, variant, vmem, hlo_ops,
                                time.perf_counter() - t0, True)
    except Exception as e:  # noqa: BLE001 — a failed lower = unusable variant
        return ResourceEstimate(region, variant, float("inf"), 0,
                                time.perf_counter() - t0, False, f"{type(e).__name__}: {e}")


def precompile_many(jobs, mapper=map) -> list[ResourceEstimate]:
    """Step-3 fan-out: lower many (region, variant) pairs at once.

    ``jobs`` are ``(region, variant, fn, args, static_kwargs)`` tuples;
    ``mapper`` is any order-preserving map — the planner passes
    ``VerificationExecutor.map_concurrent`` so the per-pair lowering calls
    (each independent, like the paper's per-loop HDL-stage compiles) run
    concurrently under ``verify_workers``.  Results come back in job order,
    so the efficiency ranking downstream is identical at any worker count.
    """
    return list(mapper(lambda j: precompile(*j), list(jobs)))


# ---------------------------------------------------------------------------
# VMEM estimators mirroring the kernels' BlockSpecs
# ---------------------------------------------------------------------------
@register_vmem_estimator("fir_bank", "pallas")
def _fir_vmem(x, h, *_):
    return fir_vmem_bytes(x.shape[0], h.shape[-1], min(512, x.shape[-1]))


@register_vmem_estimator("compute_q", "pallas")
def _mriq_vmem(x, *_):
    bx, bk = 256, 512
    return 4.0 * (bx * 4 + 4 * bk + 3 * bx * bk)


@register_vmem_estimator("attn_core", "pallas")
def _flash_vmem(q, k, v, *_):
    d = q.shape[-1]
    bq, bk = 256, 512
    return 4.0 * (bq * d + 2 * bk * d + bq * bk + 2 * bq * d)


@register_vmem_estimator("rglru_scan", "pallas")
def _rglru_vmem(a, b, h0, *_):
    bc, tc = 128, 128
    return 4.0 * (3 * tc * bc + bc)            # a, b, y tiles + state


@register_vmem_estimator("ssm_scan", "pallas")
def _ssm_vmem(a, bx, c, h0, *_):
    # state-major tiles [tc, N, bc]; the C column [tc, N, 1] is lane-padded
    n = a.shape[-1]
    bc, tc = 128, 64
    return 4.0 * (2 * tc * n * bc + tc * n * 128 + tc * bc + n * bc)
