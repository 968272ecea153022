"""Paper reproduction, app #2: automatic offload of Parboil MRI-Q
(paper §5, Fig. 4 row 2).  Same staged pipeline as examples/offload_fir.py.

Run:  PYTHONPATH=src python examples/offload_mriq.py [--strategy surrogate]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.mriq import make_program
from repro.configs.paper_apps import MRIQ_FULL
from repro.core.plan_cache import PlanCache
from repro.core.planner import AutoOffloader, PlannerConfig
from repro.core.strategies import STRATEGY_NAMES
from repro.kernels.mriq import mriq_compute_q
from repro.kernels.ref import mriq_ref
from repro.launch.constants import projected_tpu_seconds

ap = argparse.ArgumentParser()
ap.add_argument("--strategy", default="staged", choices=list(STRATEGY_NAMES),
                help="Step-4 search strategy (part of the plan-cache key); "
                     "surrogate = roofline-predicted fitness, auto = pick "
                     "by space size — see docs/search-strategies.md")
ap.add_argument("--seed", type=int, default=0, help="strategy RNG seed (GA)")
ap.add_argument("--tune-tiles", action="store_true",
                help="search (variant, tile params) genes for variants "
                     "declaring a TuningSpace — docs/search-strategies.md "
                     "'Kernel autotuning'; part of the plan-cache key")
args = ap.parse_args()

print("=== MRI-Q automatic offload (paper app #2) ===")
program = make_program()
report = AutoOffloader(
    PlannerConfig(reps=5, strategy=args.strategy, seed=args.seed,
                  tune_tiles=args.tune_tiles)).plan(
    program, cache=PlanCache.default())
print(report.summary())

print("\n--- deploy kernel validation (Pallas; interpreted off a TPU) ---")
ks = jax.random.split(jax.random.PRNGKey(0), 7)
x, y, z = (jax.random.normal(ks[i], (512,)) for i in range(3))
kx, ky, kz = (jax.random.normal(ks[3 + i], (256,)) * 0.1 for i in range(3))
pm = jax.random.uniform(ks[6], (256,))
qr, qi = mriq_compute_q(x, y, z, kx, ky, kz, pm)
qr_ref, qi_ref = mriq_ref(x, y, z, kx, ky, kz, pm)
err = float(max(np.abs(np.asarray(qr - qr_ref)).max(),
                np.abs(np.asarray(qi - qi_ref)).max()))
print(f"pallas-vs-ref max abs err: {err:.2e} (PASS)" if err < 5e-3
      else f"FAIL {err}")

print("\n--- v5e roofline projection for the selected hot loop ---")
cfg = MRIQ_FULL
flops = cfg.flops
transcendentals = 2.0 * cfg.num_x * cfg.num_k          # sin + cos per pair
bytes_moved = 4.0 * (3 * cfg.num_x + 4 * cfg.num_k + 2 * cfg.num_x)
proj = projected_tpu_seconds(flops, bytes_moved, transcendentals)
print(f"paper speedup (Arria10 FPGA vs Xeon):       7.1x")
print(f"measured on this CPU-only container:        {report.speedup:.2f}x")
print(f"projected v5e kernel time: {proj['seconds']*1e3:.2f} ms "
      f"({proj['bound']}-bound) vs CPU baseline "
      f"{report.baseline.run_seconds*1e3:.0f} ms (bench size)")
