"""Production serving launcher, driven end-to-end by the continuous-batching
``ServeEngine`` — the same code path the engine tests and the planner's
``--auto-offload`` patterns exercise.

  PYTHONPATH=src python -m repro.launch.serve --arch falcon-mamba-7b \
      --reduced --slots 4 --prompt-len 64 --new-tokens 64

With ``--auto-offload`` the launcher runs the block-level offload planner
over the arch's regions first and serves with the selected pattern.  The
search result persists in the plan cache (``--plan-cache``), so only the
first launch on a given (arch, shapes, backend) pays for the measurements —
every later launch applies the cached pattern immediately (the paper's
"once written code, automatically configured per placed hardware").
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass

import jax

from repro.configs import get_config
from repro.core.plan_cache import (DEFAULT_CACHE_ENV, DEFAULT_CACHE_PATH,
                                   PlanCache)
from repro.core.strategies import STRATEGY_NAMES
from repro.launch.compile_cache import enable_compile_cache
from repro.models import factory as F
from repro.serving.engine import ServeEngine
from repro.serving.sampling import GREEDY, SamplingParams


def make_offloader(reps: int = 2, strategy: str = "staged", seed: int = 0,
                   verify_workers: int = 1, tune_tiles: bool = False):
    """One long-lived AutoOffloader for launch-time planning AND every
    online replan: its offloader-lifetime CompileCache keeps re-opened
    searches verifying through warm executables."""
    from repro.core.planner import AutoOffloader, PlannerConfig
    return AutoOffloader(PlannerConfig(
        reps=reps, strategy=strategy, seed=seed,
        verify_workers=verify_workers, tune_tiles=tune_tiles))


def plan_offload(arch: str, cache: PlanCache, offloader):
    """Best cached/measured offload pattern for the arch's block regions
    (the ``--auto-offload`` path).  Returns the ``PlanReport``; its
    ``best_impl()`` is served merged over the architectural defaults.
    ``offloader`` is shared with an online replanner when there is one
    (its CompileCache stays warm across searches)."""
    from repro.models.offload_program import make_lm_program

    report = offloader.plan(make_lm_program(arch), cache=cache)
    src = ("plan cache" if report.from_cache
           else f"measured search [{report.strategy}]")
    print(f"auto-offload [{src}]: {report.best_pattern or 'all-ref'} "
          f"(speedup {report.speedup:.2f}x)")
    return report


def make_replan_fn(arch: str, offloader, cache: PlanCache,
                   default_seq: int = 128):
    """The production ``Replanner.plan_fn``: regime conditions from
    ``conditions_from_stats`` become the program's ``plan_extra`` (re-keying
    the plan per regime) and the dominant bucket becomes the measurement
    ``seq`` (timings reflect the live prompt lengths).  A regime shift that
    keeps the shapes re-opens the search fully ledger-primed — zero new
    measurement budget on known patterns."""
    from repro.models.offload_program import make_lm_program

    def plan_fn(conditions: dict):
        seq = int(conditions.get("dominant_bucket") or 0) or default_seq
        prog = make_lm_program(arch, seq=max(seq, 8),
                               plan_extra=dict(conditions))
        return offloader.plan(prog, cache=cache)
    return plan_fn


@dataclass
class ServeRun:
    """What one :func:`serve` call did: the engine (its ``stats()`` hold
    the counters), the finished requests and the wall seconds they took."""
    engine: ServeEngine
    done: list
    wall_s: float


def serve(cfg, params, *, slots: int, prompt_len: int, new_tokens: int,
          requests: int, seed: int = 0, impl=None,
          sampling: SamplingParams = GREEDY, vary_lengths: bool = False,
          replanner=None) -> ServeRun:
    """Serve ``requests`` synthetic requests (drawn from ``seed``) on a
    fresh ``ServeEngine`` until all finish.  ``vary_lengths`` staggers
    prompt lengths over four steps down from ``prompt_len`` so that
    several prefill buckets are exercised."""
    ctx = prompt_len + new_tokens + cfg.n_front
    engine = ServeEngine(cfg, params, slots=slots, ctx=ctx, seed=seed,
                         impl=impl)
    if replanner is not None:
        engine.attach_replanner(replanner)
    key = jax.random.PRNGKey(seed)
    for r in range(requests):
        plen = prompt_len
        if vary_lengths:
            plen = max(1, prompt_len - (r % 4) * (prompt_len // 4))
        tokens, frontend = F.synthetic_request(cfg, plen,
                                               jax.random.fold_in(key, r))
        engine.submit(tokens, max_new_tokens=new_tokens, sampling=sampling,
                      frontend=frontend)
    t0 = time.perf_counter()
    done = engine.run_to_completion()
    return ServeRun(engine, done, time.perf_counter() - t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4,
                    help="concurrent decode slots (old --batch)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--requests", type=int, default=12,
                    help="number of requests to serve")
    ap.add_argument("--vary-lengths", action="store_true",
                    help="stagger prompt lengths to exercise prefill buckets")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--auto-offload", action="store_true",
                    help="plan (or reuse the cached) offload pattern first")
    ap.add_argument("--offload-strategy", default="staged",
                    choices=list(STRATEGY_NAMES),
                    help="Step-4 search strategy for --auto-offload "
                         "(staged = paper heuristic, genetic = GA over "
                         "mixed genomes, surrogate = roofline-predicted "
                         "fitness with top-k real measurements, exhaustive "
                         "= tiny-space oracle, auto = pick by space size); "
                         "part of the plan-cache key")
    ap.add_argument("--offload-seed", type=int, default=0,
                    help="strategy RNG seed for --auto-offload; kept "
                         "separate from --seed (sampling) so varying the "
                         "sampling seed never re-keys the plan cache")
    ap.add_argument("--tune-tiles", action="store_true",
                    help="autotune kernel tile parameters during "
                         "--auto-offload: the Step-4 genome becomes "
                         "(variant, tile params) for variants declaring a "
                         "TuningSpace (docs/search-strategies.md, 'Kernel "
                         "autotuning'); part of the plan-cache key")
    ap.add_argument("--verify-workers", type=int, default=1,
                    help="concurrent AOT-compile threads for the planner's "
                         "pattern verification (core/executor.py); the "
                         "selected pattern is identical at any width — "
                         "raise it on hosts with spare cores to cut "
                         "plan-time wall-clock")
    ap.add_argument("--plan-cache",
                    default=os.environ.get(DEFAULT_CACHE_ENV,
                                           DEFAULT_CACHE_PATH),
                    help="plan-cache JSON path (used with --auto-offload; "
                         f"default honors ${DEFAULT_CACHE_ENV})")
    ap.add_argument("--replan-every", type=int, default=0,
                    help="online replanning: re-open the offload search "
                         "every N engine ticks on a background thread and "
                         "hot-swap a strictly-better plan between ticks "
                         "(0 = off; docs/serving-replanning.md)")
    ap.add_argument("--replan-on-drift", action="store_true",
                    help="online replanning: re-plan when the live serving "
                         "regime (bucket mix, occupancy, decode/prefill "
                         "balance) drifts from the planned one")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    replanning = bool(args.replan_every or args.replan_on_drift)
    cache = PlanCache(args.plan_cache)
    offloader = None
    if args.auto_offload or replanning:
        offloader = make_offloader(strategy=args.offload_strategy,
                                   seed=args.offload_seed,
                                   verify_workers=args.verify_workers,
                                   tune_tiles=args.tune_tiles)
    impl = None
    if args.auto_offload:
        impl = plan_offload(args.arch, cache, offloader).best_impl()
    params = F.init_params(cfg, jax.random.PRNGKey(args.seed))
    replanner = None
    if replanning:
        from repro.serving.replan import Replanner, ReplanConfig
        # share the offloader's quarantine: a plan the engine rolled back
        # (or the canary vetoed) stops being proposed by the very next
        # background search
        replanner = Replanner(
            make_replan_fn(args.arch, offloader, cache,
                           default_seq=args.prompt_len),
            config=ReplanConfig(every_ticks=args.replan_every,
                                on_drift=args.replan_on_drift),
            quarantine=offloader.quarantine)
    run = serve(cfg, params, slots=args.slots, prompt_len=args.prompt_len,
                new_tokens=args.new_tokens, requests=args.requests,
                seed=args.seed, impl=impl,
                sampling=SamplingParams(temperature=args.temperature,
                                        top_k=args.top_k),
                vary_lengths=args.vary_lengths, replanner=replanner)
    s = run.engine.stats()
    for req in run.done:
        print(f"req {req.rid}: prompt {req.tokens.size:4d} "
              f"(bucket {req.bucket:4d}) | wait {req.queue_wait_s*1e3:7.1f} ms "
              f"| ttft {req.ttft_s*1e3:7.1f} ms | decode "
              f"{req.decode_tps:8.1f} tok/s")
    print(f"served {s['requests_finished']} requests / "
          f"{s['generated_tokens']} tokens in {run.wall_s:.2f} s "
          f"({s['generated_tokens']/run.wall_s:.1f} tok/s aggregate)")
    print(f"prefill compilations: {s['prefill_traces']} "
          f"(buckets {s['buckets']})")
    if replanner is not None:
        replanner.close(timeout=60.0)
        rs = replanner.stats()
        print(f"replanning: {rs['replans']} search(es), "
              f"{rs['offers']} offered, {s['swaps']} swap(s) installed "
              f"(plan generation {s['plan_generation']})")
        if rs["canary_rejects"] or s["rollbacks"]:
            print(f"fault tolerance: {rs['canary_rejects']} canary "
                  f"reject(s), {s['rollbacks']} rollback(s)"
                  + (f" [degraded: {run.engine.last_fault}]"
                     if s["degraded"] else ""))
        if replanner.last_error is not None:
            print(f"replanner error: {replanner.last_error}")


if __name__ == "__main__":
    main()
