"""Paper reproduction, app #1: automatic FPGA->TPU offload of the HPEC
time-domain FIR filter bank (paper §5, Fig. 4 row 1).

Runs the full staged pipeline of the paper with its budgets (a=5, c=3, d<=4)
and prints every intermediate the paper records: loop census, per-loop
arithmetic intensity, pre-compile resource fractions, resource efficiency,
the measured patterns, and the selected solution — plus the Pallas-kernel
validation and the v5e roofline projection.

Run:  PYTHONPATH=src python examples/offload_fir.py [--strategy surrogate]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.tdfir import make_program
from repro.configs.paper_apps import TDFIR_FULL
from repro.core.plan_cache import PlanCache
from repro.core.planner import AutoOffloader, PlannerConfig
from repro.core.strategies import STRATEGY_NAMES
from repro.kernels.fir import fir_filter_bank
from repro.kernels.ref import fir_ref
from repro.launch.constants import projected_tpu_seconds

ap = argparse.ArgumentParser()
ap.add_argument("--strategy", default="staged", choices=list(STRATEGY_NAMES),
                help="Step-4 search strategy (part of the plan-cache key); "
                     "surrogate = roofline-predicted fitness, auto = pick "
                     "by space size — see docs/search-strategies.md")
ap.add_argument("--seed", type=int, default=0, help="strategy RNG seed (GA)")
ap.add_argument("--tune-tiles", action="store_true",
                help="search (variant, tile params) genes for variants "
                     "declaring a TuningSpace (e.g. fir_bank=pallas "
                     "block_n/tap_unroll) — docs/search-strategies.md "
                     "'Kernel autotuning'; part of the plan-cache key")
args = ap.parse_args()

print("=== tdFIR automatic offload (paper app #1) ===")
program = make_program()
report = AutoOffloader(
    PlannerConfig(reps=5, strategy=args.strategy, seed=args.seed,
                  tune_tiles=args.tune_tiles)).plan(
    program, cache=PlanCache.default())
print(report.summary())

print("\n--- deploy kernel validation (Pallas; interpreted off a TPU) ---")
key = jax.random.PRNGKey(0)
x = (jax.random.normal(key, (8, 1024)) + 1j * jax.random.normal(key, (8, 1024))
     ).astype(jnp.complex64)
h = (jax.random.normal(key, (8, 64)) + 1j * jax.random.normal(key, (8, 64))
     ).astype(jnp.complex64)
out = fir_filter_bank(x, h, block_n=512)
ref = fir_ref(x, h)
err = float(np.abs(np.asarray(out - ref)).max())
print(f"pallas-vs-ref max abs err: {err:.2e} (PASS)" if err < 1e-3
      else f"FAIL {err}")

print("\n--- v5e roofline projection for the selected hot loop ---")
cfg = TDFIR_FULL
flops = cfg.flops
bytes_moved = 8 * cfg.n_banks * (cfg.n_samples * 2 + cfg.n_taps)   # c64 IO
proj = projected_tpu_seconds(flops, bytes_moved)
cpu_ms = report.baseline.run_seconds * 1e3
print(f"paper speedup (Arria10 FPGA vs Xeon):       4.0x")
print(f"measured on this CPU-only container:        {report.speedup:.2f}x "
      f"(no accelerator present — see EXPERIMENTS.md)")
print(f"projected v5e kernel time: {proj['seconds']*1e6:.1f} us "
      f"({proj['bound']}-bound) vs CPU baseline {cpu_ms:.1f} ms "
      f"=> ~{report.baseline.run_seconds/proj['seconds']:.0f}x headroom")
