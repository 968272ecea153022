"""Chip smoke run: the system's main path once, on one TPU chip.

Three phases, each through the entry points a user calls:

1. **kernels** — every Pallas variant on the main path (``fir_bank``,
   ``compute_q``, ``ssm_scan``, ``attn_core``) compiled at real sizes,
   checked for a Mosaic kernel (``tpu_custom_call``) in the compiled text
   when the backend is a TPU, run once and compared with its ``ref``
   variant within the tolerance written next to it;
2. **planner** — ``AutoOffloader`` at the default ``PlannerConfig`` on the
   paper's two apps at paper sizes, against an empty plan cache; no
   variant may fail to lower and no measurement may error, the selected
   pattern must reproduce the all-ref outputs, and a second plan must be a
   cache hit with zero measurements;
3. **serving** — ``launch.serve.serve`` (the code behind ``python -m
   repro.launch.serve``) on falcon-mamba-7b at published widths with random
   weights, under the default plan and again with ``ssm_scan=pallas``; no
   rollback, not degraded, and first-token logits near those of the
   float32 reference scan; then the ``--auto-offload`` planning path
   against a fresh plan cache.

Each phase function returns its failures as strings (empty = passed) and
prints its results as it goes; they are not benchmark numbers.  The phase
functions take their sizes as arguments, so that tests run them on
``reduced()`` configs in interpret mode.  ``main`` runs them at full size
and only on a TPU: it never falls back to the CPU.

Run from the repo root on a machine with a TPU: ``python3 chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import traceback
from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import repro.kernels.ops  # noqa: F401 — registers the model pallas variants
import repro.models.blocks  # noqa: F401 — registers the attn_core variants
import repro.models.ssm  # noqa: F401 — registers the ssm_scan variants
from repro.apps import mriq as mriq_app
from repro.apps import tdfir as tdfir_app
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.configs.paper_apps import (MRIQ_FULL, TDFIR_FULL, MriQConfig,
                                      TdFirConfig)
from repro.core.plan_cache import PlanCache
from repro.core.regions import Impl, variants
from repro.hw import peaks
from repro.launch.compile_cache import REPO_ROOT, enable_compile_cache

SERVE_ARCH = "falcon-mamba-7b"
ATTN_ARCH = "mistral-nemo-12b"       # 32 q / 8 kv heads, head_dim 128
SSM_SEQ = 512                        # kernel phase: prefill length
ATTN_SEQ = 4096
# the serving phase keeps all layers only while weights plus the compiled
# prefill take at most this share of the device's memory
HBM_SHARE = 0.9
# The served plans' first-token logits are compared with those of the
# float32 reference scan (associative, float32 state) on the same engine,
# weights and prompts.
REF_SCAN = {"ssm_scan": "offload"}
# Largest relative L2 distance allowed from the reference, over the served
# prompts.  On a v5e at full width (64 layers, random weights) both plans
# read 5.138e-02: their scan discretises in float32, while the reference
# scan takes the decay and input rounded to bfloat16 (when those reached
# the scan rounded, pallas read 3.048e-02 and the bfloat16-state seq scan
# 5.188e-02); the bfloat16 activations' rounding compounds over the layers.
# Faults planted in the pallas scan read 0.138 (one of the 16 state terms
# dropped) and 0.674 (output shifted one step).  The reduced model reads
# 8.0e-03 clean and 0.106 with the shifted output (CPU).  Smaller faults (a 1% input
# scaling, the state reset every quarter prompt) stay at the floor; the
# kernel phase catches them at its 1e-4 tolerance.
LOGITS_TOL = 0.08
# selected pattern vs all-ref outputs, max |diff| / max |ref| per output
APP_TOLS = {
    # float32 on both sides; only the summation order differs
    "tdfir": 1e-4,
    # the all-ref checksum is a sequential float32 sum over 262,144 voxels:
    # 3.23e-04 off a float64 sum, where a tree sum is 3e-08 off (CPU and
    # v5e alike).  One bfloat16 MXU pass in compute_q reads 2.6e-03.
    "mriq": 1e-3,
}


def _rel_max_err(out, ref) -> float:
    """max |out - ref| / max |ref| over matching leaves (inf if any output
    is not finite)."""
    worst = 0.0
    for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        o = np.asarray(o).astype(np.complex128)
        r = np.asarray(r).astype(np.complex128)
        if not np.all(np.isfinite(o)):
            return float("inf")
        worst = max(worst, float(np.max(np.abs(o - r)))
                    / max(float(np.max(np.abs(r))), 1e-30))
    return worst


# ---------------------------------------------------------------------------
# Phase 1: kernels
# ---------------------------------------------------------------------------
@dataclass
class KernelCase:
    """One Pallas variant at one size, with its tolerance against ``ref``
    (max |pallas - ref| / max |ref|) and the reason for it."""
    region: str
    args: tuple
    kwargs: dict
    tol: float
    why: str


def kernel_cases(key: jax.Array, *, fir: TdFirConfig, mriq: MriQConfig,
                 ssm: ModelConfig, ssm_seq: int, attn: ModelConfig,
                 attn_seq: int) -> list[KernelCase]:
    """The main path's kernels with inputs drawn from ``key``."""
    ks = jax.random.split(key, 12)
    cplx = lambda k, shape: (jax.random.normal(k, shape)            # noqa: E731
                             + 1j * jax.random.normal(
                                 jax.random.fold_in(k, 1), shape)
                             ).astype(jnp.complex64)
    fir_args = (cplx(ks[0], (fir.n_banks, fir.n_samples)),
                cplx(ks[1], (fir.n_banks, fir.n_taps)))
    mriq_args = tuple(
        [jax.random.normal(ks[2 + i], (mriq.num_x,)) for i in range(3)]
        + [0.1 * jax.random.normal(ks[5 + i], (mriq.num_k,))
           for i in range(3)]
        + [jax.random.uniform(ks[8], (mriq.num_k,))])
    d, n = ssm.d_inner, ssm.ssm_state
    ssm_args = (jax.random.uniform(ks[9], (1, ssm_seq, d, n), jnp.float32,
                                   0.5, 0.99),
                0.1 * jax.random.normal(ks[10], (1, ssm_seq, d, n)),
                jax.random.normal(ks[11], (1, ssm_seq, n)),
                jnp.zeros((1, d, n), jnp.float32))
    qkv = [jax.random.normal(jax.random.fold_in(key, 100 + i),
                             (1, h, attn_seq, attn.head_dim), jnp.bfloat16)
           for i, h in enumerate((attn.num_heads, attn.num_kv_heads,
                                  attn.num_kv_heads))]
    return [
        KernelCase("fir_bank", fir_args, {}, 1e-4,
                   "float32 complex MACs on the VPU on both sides; only the "
                   "grouping of each MAC's four real products differs"),
        KernelCase("compute_q", mriq_args, {}, 1e-4,
                   "float32 on the VPU on both sides; the kernel reduces "
                   "the phase exactly in cycles where ref rounds 2*pi*t in "
                   "radians, and sums each chunk's samples in turn"),
        KernelCase("ssm_scan", ssm_args, {}, 1e-4,
                   "float32 recurrence on both sides; the reference's "
                   "associative scan regroups the products"),
        KernelCase("attn_core", tuple(qkv), {"causal": True}, 2e-2,
                   "bfloat16 operands and output: one bfloat16 rounding of "
                   "the probabilities and of the output (2^-8 relative)"),
    ]


def run_kernels(cases: list[KernelCase]) -> list[str]:
    """Compile, run and check each case; returns the failures."""
    mosaic = jax.default_backend() == "tpu"
    failures = []
    for case in cases:
        fns = variants(case.region)
        compiled = jax.jit(partial(fns["pallas"], **case.kwargs)).lower(
            *case.args).compile()
        if mosaic and "tpu_custom_call" not in compiled.as_text():
            failures.append(f"kernel {case.region}: no Mosaic kernel in the "
                            "compiled program")
        out = compiled(*case.args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(partial(fns["ref"], **case.kwargs))(*case.args)
        err = _rel_max_err(out, ref)
        ok = err <= case.tol
        print(f"kernel {case.region}: {'mosaic' if mosaic else 'interpret'}"
              f", max err/max|ref| {err:.3e} (tol {case.tol:g}: {case.why})"
              f" {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            failures.append(f"kernel {case.region}: error {err:.3e} > "
                            f"tol {case.tol:g}")
    return failures


# ---------------------------------------------------------------------------
# Phase 2: the planner on the paper apps
# ---------------------------------------------------------------------------
def plan_failures(report) -> list[str]:
    """What the planner's fallbacks would hide: a non-ref variant that did
    not lower, and a measurement (baseline included) that errored."""
    bad = [f"{c.region}/{var} did not lower: {est.error}"
           for c in report.candidates
           for var, est in c.variant_estimates.items() if not est.lower_ok]
    bad += [f"measurement {m.pattern} failed: {m.error}"
            for m in [report.baseline, *report.measurements]
            if m is not None and not m.ok]
    return bad


def run_planner(programs: list, cache_path: Path, seed: int) -> list[str]:
    """Plan each program at the default ``PlannerConfig`` against an empty
    cache at ``cache_path``, check the selection, then plan again."""
    from repro.core.planner import AutoOffloader, PlannerConfig

    cache_path.unlink(missing_ok=True)
    cache = PlanCache(cache_path)
    offloader = AutoOffloader(PlannerConfig())
    failures = []
    for prog in programs:
        report = offloader.plan(prog, cache=cache)
        bad = [f"plan {prog.name}: {b}" for b in plan_failures(report)]
        inputs = prog.sample_inputs(jax.random.PRNGKey(seed))
        chosen = jax.jit(prog.build(report.best_impl()))(*inputs)
        all_ref = jax.jit(prog.build(Impl()))(*inputs)
        err = _rel_max_err(chosen, all_ref)
        tol = APP_TOLS[prog.name]
        if err > tol:
            bad.append(f"plan {prog.name}: selected pattern differs from "
                       f"all-ref by {err:.3e} > {tol:g}")
        again = offloader.plan(prog, cache=cache)
        if not again.from_cache or again.measurements:
            bad.append(f"plan {prog.name}: re-plan was not a cache hit "
                       f"with zero measurements")
        print(f"plan {prog.name}: {len(report.measurements)} measured, "
              f"selected {report.best_pattern or 'all-ref'} (speedup "
              f"{report.speedup:.2f}x), max err/max|ref| vs all-ref "
              f"{err:.3e} (tol {tol:g}), re-plan "
              f"{'cache hit' if again.from_cache else 'MISS'} with "
              f"{len(again.measurements)} measured "
              f"{'ok' if not bad else 'FAIL'}", flush=True)
        failures += bad
    return failures


# ---------------------------------------------------------------------------
# Phase 3: serving
# ---------------------------------------------------------------------------
def fit_layers(cfg: ModelConfig, hbm_bytes: float | None, *,
               ctx: int, bucket: int) -> ModelConfig:
    """``cfg`` with all its layers when weights plus the compiled prefill's
    memory take at most ``HBM_SHARE`` of ``hbm_bytes``, else with half of
    them.  ``None`` (a backend that reports no limit) keeps every layer."""
    from repro.models import factory as F
    from repro.models.params import param_bytes

    if hbm_bytes is None:
        return cfg
    weights = param_bytes(F.template(cfg))
    prefill = jax.jit(F.make_bucketed_prefill_step(
        cfg, impl=F.default_impl(cfg), ctx=ctx))
    mem = prefill.lower(
        F.abstract_params(cfg),
        {"tokens": jax.ShapeDtypeStruct((1, bucket), jnp.int32)},
        jax.ShapeDtypeStruct((), jnp.int32)).compile().memory_analysis()
    need = weights + mem.temp_size_in_bytes + mem.output_size_in_bytes
    keep = need <= HBM_SHARE * hbm_bytes
    print(f"serve {cfg.name}: {cfg.num_layers} layers need "
          f"{need / 2**30:.2f} GiB (weights {weights / 2**30:.2f} GiB) of "
          f"{hbm_bytes / 2**30:.2f} GiB; "
          + ("all layers kept" if keep
             else f"cut to {cfg.num_layers // 2} layers"), flush=True)
    return cfg if keep else dataclasses.replace(
        cfg, num_layers=cfg.num_layers // 2)


def _first_logits(run, impl) -> list[np.ndarray]:
    """First-token logits of each served prompt, from the engine's own
    prefill trace for ``impl`` (memoized: no new compile)."""
    engine = run.engine
    gen = engine.prepare_plan(impl, warm=False)
    rows = []
    for req in run.done:
        padded = np.zeros(req.bucket, np.int32)
        padded[:req.tokens.size] = req.tokens
        logits, _ = gen.prefill(engine.params,
                                {"tokens": jnp.asarray(padded[None])},
                                jnp.asarray(req.tokens.size, jnp.int32))
        rows.append(np.asarray(logits[0, -1], np.float32))
    return rows


def run_serving(cfg: ModelConfig, *, seed: int, slots: int, prompt_len: int,
                new_tokens: int, requests: int, hbm_bytes: float | None,
                plan_arch: str | None, plan_cache_path: Path) -> list[str]:
    """Serve ``requests`` requests under the default plan and under
    ``ssm_scan=pallas``, compare each plan's first-token logits with the
    float32 reference scan's, then run the ``--auto-offload`` planning path
    for ``plan_arch`` (None skips it)."""
    from repro.launch.serve import make_offloader, plan_offload, serve
    from repro.models import factory as F

    cfg = fit_layers(cfg, hbm_bytes, ctx=prompt_len + new_tokens + cfg.n_front,
              bucket=prompt_len)
    params = F.init_params(cfg, jax.random.PRNGKey(seed))
    failures = []
    ref = None
    for name, impl in (("default", None), ("ssm_scan=pallas",
                                           {"ssm_scan": "pallas"})):
        run = serve(cfg, params, slots=slots, prompt_len=prompt_len,
                    new_tokens=new_tokens, requests=requests, seed=seed,
                    impl=impl, vary_lengths=True)
        s = run.engine.stats()
        served = [len(r.generated) for r in run.done]
        bad = []
        if s["rollbacks"] or s["degraded"]:
            bad.append(f"serve {name}: {s['rollbacks']} rollback(s), "
                       f"degraded={s['degraded']} ({run.engine.last_fault})")
        if served != [new_tokens] * requests:
            bad.append(f"serve {name}: generated {served}, expected "
                       f"{new_tokens} tokens for each of {requests}")
        if ref is None:     # same weights and prompts in every run
            ref = _first_logits(run, REF_SCAN)
        dist = max(float(np.linalg.norm(a - b) / np.linalg.norm(b))
                   for a, b in zip(_first_logits(run, impl), ref))
        if dist > LOGITS_TOL:
            bad.append(f"serve {name}: first-token logits differ from the "
                       f"float32 reference scan by {dist:.3e}")
        print(f"serve {cfg.name} [{name}]: {len(run.done)} requests, "
              f"{s['generated_tokens']} tokens, buckets {s['buckets']}, "
              f"{s['rollbacks']} rollbacks, first-token logits vs float32 "
              f"reference scan: relative L2 {dist:.3e} (tol {LOGITS_TOL:g}) "
              f"{'ok' if not bad else 'FAIL'}", flush=True)
        failures += bad
    if plan_arch is not None:
        plan_cache_path.unlink(missing_ok=True)
        report = plan_offload(plan_arch, PlanCache(plan_cache_path),
                              make_offloader())
        failures += [f"auto-offload {plan_arch}: {b}"
                     for b in plan_failures(report)]
    return failures


# ---------------------------------------------------------------------------
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every input, weight and request")
    ap.add_argument("--out", default=str(REPO_ROOT / "chiprun_out"
                                         / "chip_smoke"),
                    help="directory for the run's plan caches")
    args = ap.parse_args(argv)
    enable_compile_cache()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    chip = peaks(dev.device_kind)      # raises for a kind the table lacks
    print(f"device {dev.device_kind}: {chip.bf16_flops:g} bf16 FLOP/s, "
          f"{chip.hbm_bw:g} HBM B/s ({chip.source})", flush=True)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    stats = dev.memory_stats() or {}
    phases = [
        ("kernels", lambda: run_kernels(kernel_cases(
            jax.random.PRNGKey(args.seed), fir=TDFIR_FULL, mriq=MRIQ_FULL,
            ssm=get_config(SERVE_ARCH), ssm_seq=SSM_SEQ,
            attn=get_config(ATTN_ARCH), attn_seq=ATTN_SEQ))),
        ("planner", lambda: run_planner(
            [tdfir_app.make_program(TDFIR_FULL),
             mriq_app.make_program(MRIQ_FULL)],
            out / "plans.json", args.seed)),
        ("serving", lambda: run_serving(
            get_config(SERVE_ARCH), seed=args.seed, slots=4, prompt_len=128,
            new_tokens=32, requests=4, hbm_bytes=stats.get("bytes_limit"),
            plan_arch=SERVE_ARCH, plan_cache_path=out / "serve_plans.json")),
    ]
    failures = []
    for name, phase in phases:
        try:
            failures += phase()
        except Exception:  # noqa: BLE001 — record the phase, run the rest
            failures.append(f"phase {name} raised:\n{traceback.format_exc()}")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0
