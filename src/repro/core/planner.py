"""The paper's automatic loop-offload planner (§3.3, Fig. 2) — TPU-native,
extended to mixed offload destinations (Yamato, arXiv 2011.12431).

Pipeline, faithful to the paper with the FPGA->TPU substitutions of
DESIGN.md §2:

  Step 1  code analysis        — region census + jaxpr loop census
  Step 2  AI filter            — arithmetic intensity per region, keep top-a
  Step 3  resource filter      — cheap lowering of EVERY registered offload
                                 variant of each surviving region ->
                                 vmem fraction; efficiency = AI / fraction;
                                 rank (region, variant) pairs, keep the
                                 top-c regions (each with its variant
                                 ranking)
  Step 4  measured search      — a pluggable ``SearchStrategy``
                                 (core/strategies.py) proposes patterns
                                 ask–tell through a ``MeasurementLedger``;
                                 total measured patterns <= d, no pattern
                                 measured twice (baseline excluded, as in
                                 the paper where all-CPU is the pre-existing
                                 reference).  ``staged`` is the paper's
                                 3-round heuristic; ``genetic`` the
                                 companion papers' GA over mixed genomes;
                                 ``exhaustive`` the tiny-space oracle.
  Step 5  select               — fastest measured pattern; the selected
                                 mapping is the measurement's own structured
                                 ``Impl`` (no string re-parsing)

Because Step 3 ranks (region, variant) pairs rather than regions with one
pinned variant, the measured patterns may mix destinations across regions —
e.g. ``{fir_bank: pallas, fir_load: offload}`` — which is exactly the
mixed-offloading-destination extension of the follow-up paper.

Plans are cacheable: ``plan(..., cache=...)`` consults/updates a persistent
``PlanCache`` keyed by program name + abstract arg shapes/dtypes + variant
registry + backend + planner config, so an application is searched once per
placed hardware and then served from the cache with zero new measurements.

Defaults a=5, c=3, d=4 match the paper's evaluation conditions (§5.1.2).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax

from repro import obs
from repro.core import search
from repro.core.cost_model import CostModel
from repro.core.executor import (CompileCache, FaultPolicy,
                                 VerificationExecutor, VerifyJob,
                                 compile_key, measure_with_retry)
from repro.core.intensity import RegionAnalysis, analyze_region, count_loops
from repro.core.plan_cache import (PlanCache, measurement_cache_key,
                                   plan_cache_key, resolve_cache)
from repro.core.program import OffloadableProgram
from repro.core.regions import (BoundTuningSpace, Impl, offload_variants,
                                tuning_space)
from repro.core.resources import ResourceEstimate, precompile_many
from repro.core.search import Measurement, MeasurementLedger
from repro.core.strategies import SearchCandidate, SearchState, make_strategy


@dataclass(frozen=True)
class PlannerConfig:
    """Every knob of the automatic offload planner.

    All fields except ``reps``/``warmup`` participate in the plan-cache
    key; ``seed`` and the ``ga_*`` knobs participate only for strategies
    that read them (``genetic``/``surrogate``/``auto`` — they cannot change
    a staged or exhaustive trajectory).  See docs/plan-cache.md.

    Pipeline budgets (paper §5.1.2 defaults):

    * ``top_a`` (int, 5)            — Step-2 arithmetic-intensity filter
      width: regions kept after AI ranking.
    * ``top_c`` (int, 3)            — Step-3 resource-efficiency filter
      width: regions kept after (region, variant) ranking.
    * ``max_measurements`` (int, 4) — the paper's ``d``: Step-4 patterns
      that may consume real measurements (ledger hits are free).
    * ``resource_cap`` (float, 1.0) — summed VMEM fraction a combined
      pattern may claim; over-cap patterns are never built.
    * ``unroll_b`` (int, 1)         — kernel unroll knob (paper's ``b``).
    * ``tune_tiles`` (bool, False)  — widen the Step-4 genome from
      ``{region -> variant}`` to ``{region -> (variant, tile params)}``
      for variants that declared a ``TuningSpace`` at registration: the
      GA mutates/crosses tile points, staged adds a round-4 hill climb
      over the winner's tiles, exhaustive enumerates every valid point.
      Off (the default) reproduces the variant-only search bit-for-bit
      and keeps pre-tuning plan-cache keys unchanged.

    Measurement fidelity (NOT in the cache key — they change timing noise,
    never the search space):

    * ``warmup`` (int, 1) / ``reps`` (int, 5) — per-pattern timing runs;
      ``run_seconds`` is the median of ``reps``.

    Fault tolerance (also NOT in the cache key — they govern how the
    environment's failures are survived, never which pattern is best; see
    docs/fault-tolerance.md):

    * ``compile_timeout_s`` (float, 0.0) — wall ceiling per AOT compile
      under a watchdog; 0 disables.  Expiry is a transient
      ``CompileTimeout`` that gets a bounded retry.
    * ``run_timeout_s`` (float, 0.0) — wall ceiling per execution (first
      run, warmup, every timed rep); 0 disables.
    * ``max_retries`` (int, 2) / ``retry_backoff_s`` (float, 0.05) —
      bounded retry with exponential backoff for *transient* failures
      (timeouts, resource exhaustion, flaky devices).  Permanent failures
      (lowering errors, NaN/Inf output) never retry.
    * ``outlier_mad`` (float, 3.5) / ``remeasure`` (int, 2) — MAD-based
      outlier rejection over the timed reps: reps whose modified z-score
      exceeds the threshold are dropped, up to ``remeasure`` replacement
      reps run, and ``run_seconds`` is the median of the kept reps.
      ``outlier_mad=0`` disables.
    * ``quarantine_threshold`` (int, 2) — permanent failures strike the
      failed pattern's (region, variant[, tile]) genes; a gene with this
      many strikes is quarantined (strategies stop proposing it) and the
      strikes persist in the plan cache under ``measurement_key`` so
      future runs skip known-bad variants outright.

    Step-4 search strategy (core/strategies.py):

    * ``strategy`` (str, "staged")  — staged | genetic | surrogate |
      exhaustive | auto.
    * ``seed`` (int, 0)             — strategy RNG seed (GA determinism).
    * ``ga_population`` (int, 6)    — genomes per generation.
    * ``ga_generations`` (int, 4)   — generations (ledger hits free).
    * ``ga_crossover`` (float, 0.9) — uniform-crossover probability.
    * ``ga_mutation`` (float, 0.15) — per-gene mutation probability.
    * ``ga_tournament`` (int, 2)    — tournament size.
    * ``ga_elite`` (int, 1)         — elites carried over (re-measured
      free via the ledger).
    * ``ga_topk`` (int, 2)          — surrogate mode only: real
      measurements per generation; the rest of the population is scored
      by the roofline CostModel (core/cost_model.py).

    Verification executor (core/executor.py):

    * ``verify_workers`` (int, 1)   — thread-pool width for concurrent AOT
      compilation in Steps 3 and 4 (timed reps stay strictly serial at any
      width; the measured sequence and the selected pattern are identical
      for every value).  ``1`` is the fully serial pre-executor pipeline.
      Part of the plan-cache key so pipelined and serial plan provenance
      stay distinguishable.

    Example (the config is a frozen dataclass — derive variants with
    ``dataclasses.replace``):

    >>> from repro.core.planner import PlannerConfig
    >>> cfg = PlannerConfig(strategy="surrogate", max_measurements=8)
    >>> cfg.ga_topk
    2
    >>> import dataclasses
    >>> dataclasses.replace(cfg, ga_topk=3).ga_topk
    3
    >>> cfg.strategy
    'surrogate'
    """
    top_a: int = 5              # AI filter width (paper: 5)
    top_c: int = 3              # resource-efficiency filter width (paper: 3)
    max_measurements: int = 4   # d (paper: 4)
    resource_cap: float = 1.0   # summed vmem fraction cap for combinations
    unroll_b: int = 1           # kernel unroll knob (paper: 1)
    tune_tiles: bool = False    # search (variant, tile params) genes

    warmup: int = 1
    reps: int = 5
    # ---- fault tolerance (core/executor.py FaultPolicy; not in the key) ----
    compile_timeout_s: float = 0.0   # per-compile watchdog wall (0 = off)
    run_timeout_s: float = 0.0       # per-execution watchdog wall (0 = off)
    max_retries: int = 2             # bounded retries for transient failures
    retry_backoff_s: float = 0.05    # exponential-backoff base between tries
    outlier_mad: float = 3.5         # modified-z rep rejection (0 = off)
    remeasure: int = 2               # replacement reps after rejection
    quarantine_threshold: int = 2    # permanent-failure strikes per gene
    # ---- Step-4 search strategy (core/strategies.py) ----
    strategy: str = "staged"    # staged | genetic | surrogate | exhaustive | auto
    seed: int = 0               # strategy RNG seed (GA determinism)
    ga_population: int = 6      # genomes per generation
    ga_generations: int = 4     # generations (ledger hits don't spend d)
    ga_crossover: float = 0.9   # uniform-crossover probability
    ga_mutation: float = 0.15   # per-gene mutation probability
    ga_tournament: int = 2      # tournament size
    ga_elite: int = 1           # elites carried over (re-measured for free)
    ga_topk: int = 2            # surrogate: real measurements per generation
    # ---- verification executor (core/executor.py) ----
    verify_workers: int = 1     # concurrent AOT-compile threads (1 = serial)


def conditions_from_stats(stats: dict) -> dict:
    """Fold a ServeEngine windowed stats view (``engine.stats(window=N)``)
    into discrete measurement conditions for online replanning.

    The output is deliberately coarse — a plan-cache key ingredient
    (``OffloadableProgram.plan_extra``), not a telemetry dump: banding keeps
    neighboring windows of the same regime mapping to the same conditions
    (no key churn), while a real regime shift (dominant bucket, occupancy
    band, decode/prefill balance) re-opens the search.  Keys:

    * ``dominant_bucket`` — the prefill bucket with the most admissions in
      the window (ties favor the longer bucket; 0 when nothing admitted),
    * ``occupancy_band`` — mean slot occupancy in thirds: low / mid / high,
    * ``decode_prefill_band`` — ``floor(log2(1 + decode/prefill ratio))``,
      the workload-balance octave.

    Deterministic: equal stats give equal conditions."""
    hist = {int(b): int(c)
            for b, c in dict(stats.get("bucket_hist", {})).items()}
    dominant = (max(hist.items(), key=lambda kv: (kv[1], kv[0]))[0]
                if hist else 0)
    occ = float(stats.get("occupancy_mean", 0.0))
    occupancy_band = "low" if occ < 1 / 3 else ("mid" if occ < 2 / 3 else "high")
    ratio = max(float(stats.get("decode_prefill_ratio", 0.0)), 0.0)
    return {
        "dominant_bucket": dominant,
        "occupancy_band": occupancy_band,
        "decode_prefill_band": int(math.floor(math.log2(1.0 + ratio))),
    }


def _efficiency(analysis: RegionAnalysis,
                resources: ResourceEstimate | None) -> float:
    """The paper's resource efficiency: AI per unit of claimed resources.
    Single definition — both the ranking and the report read this."""
    if resources is None or not resources.lower_ok:
        return 0.0
    return analysis.arithmetic_intensity / max(
        resources.resource_fraction, 1e-6)


@dataclass
class VariantCandidate:
    """One (region, variant) offload destination candidate."""
    region: str
    variant: str
    analysis: RegionAnalysis
    resources: ResourceEstimate

    @property
    def efficiency(self) -> float:
        return _efficiency(self.analysis, self.resources)


@dataclass
class CandidateInfo:
    """Per-region analysis summary (Step 2 unit; Step 3 fans out to
    VariantCandidates, the best of which is mirrored here for reporting)."""
    region: str
    analysis: RegionAnalysis
    resources: ResourceEstimate | None = None      # best variant's estimate
    best_variant: str | None = None
    variant_estimates: dict[str, ResourceEstimate] = field(default_factory=dict)

    @property
    def efficiency(self) -> float:
        return _efficiency(self.analysis, self.resources)


@dataclass
class PlanReport:
    program: str
    source_loop_count: int
    jaxpr_loop_count: int
    candidates: list[CandidateInfo] = field(default_factory=list)
    ai_selected: list[str] = field(default_factory=list)       # after Step 2
    eff_selected: list[str] = field(default_factory=list)      # after Step 3
    eff_pairs: list[tuple[str, str]] = field(default_factory=list)
    baseline: Measurement | None = None
    measurements: list[Measurement] = field(default_factory=list)
    best_pattern: dict = field(default_factory=dict)
    speedup: float = 0.0
    best_seconds: float = 0.0          # winning measurement's own median
    skipped_combinations: list[str] = field(default_factory=list)
    from_cache: bool = False
    cache_key: str = ""
    strategy: str = "staged"           # which SearchStrategy produced this
    search_trace: list[dict] = field(default_factory=list)  # rounds/generations
    # cross-run measurement reuse: patterns served from plan-cache priming
    # (zero budget spent), and the size of the Step-3 survivor genome space
    # (what make_strategy("auto") keys its choice on)
    reused: list[Measurement] = field(default_factory=list)
    search_space: int = 0
    # pipelined-verification wall-clock accounting (core/executor.py):
    # verify_wall_s is the wall of the batched Step-4 verification phases
    # (compile + timed reps), compile_wall_s the portion the serial
    # pipeline was actually BLOCKED waiting on compiles — with workers > 1
    # it shrinks toward max-of-compiles per batch while the per-pattern
    # Measurement.compile_seconds (true compile durations) stay unchanged
    verify_workers: int = 1
    verify_wall_s: float = 0.0
    compile_wall_s: float = 0.0
    # the search's final CostModel calibration (export_state snapshot),
    # persisted next to the measurements so re-opened searches start from
    # calibrated deltas instead of the roofline seeds
    cost_model_state: dict = field(default_factory=dict)
    # fault-tolerance provenance: gene ids currently quarantined (filtered
    # from this search), and the full strike records persisted under
    # measurement_key so future runs skip known-bad variants
    quarantined: list[str] = field(default_factory=list)
    quarantine_records: list[dict] = field(default_factory=list)

    def best_impl(self) -> Impl:
        """The selected pattern as a dispatchable Impl."""
        return Impl(self.best_pattern)

    def summary(self) -> str:
        lines = [f"== offload plan: {self.program} =="
                 + ("  [served from plan cache]" if self.from_cache else "")]
        lines += [f"loops: source={self.source_loop_count} jaxpr={self.jaxpr_loop_count}",
                  f"search strategy: {self.strategy}",
                  f"AI top-a: {self.ai_selected}",
                  f"efficiency top-c: {self.eff_selected}"]
        if self.eff_pairs:
            lines.append("ranked destinations: "
                         + ", ".join(f"{r}={v}" for r, v in self.eff_pairs))
        for c in self.candidates:
            res = c.resources
            lines.append(
                f"  {c.region:18s} AI={c.analysis.arithmetic_intensity:10.2f} "
                f"flops={c.analysis.weighted_flops:.3e} "
                f"vmem_frac={res.resource_fraction if res else float('nan'):8.4f} "
                f"eff={c.efficiency:10.1f}"
                + (f" best_variant={c.best_variant}" if c.best_variant else ""))
        if self.baseline:
            lines.append(f"baseline (all-ref): {self.baseline.run_seconds*1e3:.2f} ms"
                         f"  (compile {self.baseline.compile_seconds*1e3:.0f} ms)")
        for m in self.measurements:
            lines.append(f"  pattern[{m.pattern}]: {m.run_seconds*1e3:.2f} ms"
                         f"  (compile {m.compile_seconds*1e3:.0f} ms)"
                         + (f"  [{m.attempts} attempts]" if m.attempts > 1 else "")
                         + ("" if m.ok else f"  FAILED [{m.failure_kind or '?'}]"
                            f" {m.error}"))
        if self.quarantined:
            lines.append("quarantined genes: " + ", ".join(self.quarantined))
        for m in self.reused:
            lines.append(f"  pattern[{m.pattern}]: {m.run_seconds*1e3:.2f} ms"
                         f"  [reused from plan cache, zero budget]")
        for t in self.search_trace:
            if "pairs" in t:          # cost-model pair-bias notes
                lines.append(f"  {t.get('stage', '?')}: " + "; ".join(
                    f"{'+'.join('='.join(g) for g in p['pair'])} "
                    f"{p['sign']} x{p['observations']} "
                    f"(mean {p['mean_rel_residual']:+.1%})"
                    for p in t["pairs"]))
                continue
            if "workers" in t:        # verification-executor accounting
                lines.append(
                    f"  {t.get('stage', '?')}: workers={t['workers']} "
                    f"batches={t.get('batches', 0)} "
                    f"compile_wall={t.get('compile_wall_s', 0.0)*1e3:.0f} ms "
                    f"(of {t.get('compile_seconds_total', 0.0)*1e3:.0f} ms "
                    f"compiled) verify_wall="
                    f"{t.get('verify_wall_s', 0.0)*1e3:.0f} ms "
                    f"cache_hits={t.get('compile_cache_hits', 0)}")
                continue
            # per-pattern timings are already listed above; the trace line
            # adds the stage grouping and the proposal count (which includes
            # free ledger hits, e.g. GA elites re-proposed across generations)
            n = len(t.get("patterns", []))
            line = (f"  {t.get('stage', '?')}: "
                    f"{n} proposal{'s' if n != 1 else ''}")
            if t.get("model_error") is not None:
                line += (f"  (surrogate error "
                         f"{t['model_error'] * 100:.1f}%)")
            lines.append(line)
        lines.append(f"best: {self.best_pattern}  speedup={self.speedup:.2f}x")
        return "\n".join(lines)


class AutoOffloader:
    def __init__(self, config: PlannerConfig = PlannerConfig(),
                 quarantine: "search.Quarantine | None" = None):
        self.config = config
        # offloader-lifetime compile memo: a pattern compiled once for a
        # (program, shapes) pair is never compiled again by this instance —
        # the cache-primed re-plan path (changed budget/strategy/variant
        # registry) re-verifies through warm executables
        self.compile_cache = CompileCache()
        # offloader-lifetime strike list.  An external instance may be
        # shared with a serving-side Replanner so a plan that faulted
        # mid-serve is filtered from every subsequent search; per-plan-run
        # records persisted in the cache merge into it on each plan().
        self.quarantine = (quarantine if quarantine is not None
                           else search.Quarantine(
                               threshold=config.quarantine_threshold))

    # ------------------------------------------------------------------
    def plan(self, program: OffloadableProgram,
             key: jax.Array | None = None,
             cache: "PlanCache | str | None" = None) -> PlanReport:
        """Plan ``program``: run the configured Step-4 search strategy, or
        serve the plan from ``cache``.

        Parameters
        ----------
        program:
            The ``OffloadableProgram`` to plan (regions + build + samples).
        key:
            PRNG key for ``program.sample_inputs`` (default
            ``jax.random.PRNGKey(0)``); does NOT affect the cache key.
        cache:
            A ``PlanCache``, a path, or None (no caching).  Three outcomes:

            * **hit** — an entry matches the full plan key (program shapes
              + variant registry + backend + config): the stored plan is
              returned with zero new measurements (``from_cache=True``);
            * **primed miss** — no plan-key match, but sibling entries
              measured under the same conditions (``measurement_cache_key``)
              donate their per-pattern measurements: the search re-runs,
              and every re-proposed known pattern is served from the
              ledger for free (``report.reused``);
            * **cold miss** — the full pipeline runs and the selection is
              stored (together with ALL its measurements) for both kinds
              of reuse above.

        Returns a ``PlanReport``; ``report.best_impl()`` is the
        dispatchable selected pattern.

        The whole call is the span ``planner.plan`` (attributes ``program``
        and ``from_cache``); a search opens one span per step under it:
        ``planner.analyze``, ``planner.intensity``, ``planner.resources``,
        ``planner.search`` and ``planner.select``.
        """
        with obs.span("planner.plan", program=program.name) as sp:
            store = resolve_cache(cache)
            ckey = (plan_cache_key(program, self.config)
                    if store is not None else "")
            if store is not None:
                entry = store.get(ckey)
                if entry is not None:
                    sp.set(from_cache=True)
                    return self._report_from_cache(program, ckey, entry)
            sp.set(from_cache=False)
            report = self._plan_measured(program, key, store=store)
            report.cache_key = ckey
            if store is not None and self._sound(report):
                store.put(ckey, self._cache_entry(report, program))
            return report

    @staticmethod
    def _sound(report: PlanReport) -> bool:
        """Only sound searches are worth freezing into the cache: a failed
        baseline or an all-patterns-failed round is likely transient (OOM,
        compile hiccup) and must be retried on the next plan() instead of
        being served forever.  An empty measurement list with a healthy
        baseline is legitimate (no destination fit the cap) and cacheable."""
        if report.baseline is None or not report.baseline.ok:
            return False
        if report.measurements and not any(m.ok for m in report.measurements):
            return False
        return True

    # ------------------------------------------------------------------
    def _plan_measured(self, program: OffloadableProgram,
                       key: jax.Array | None,
                       store: "PlanCache | None" = None) -> PlanReport:
        cfg = self.config
        key = key if key is not None else jax.random.PRNGKey(0)

        # ---- Step 1: code analysis ------------------------------------
        with obs.span("planner.analyze"):
            sample = program.sample_inputs(key)
            full_ref = program.build(Impl())
            try:
                jaxpr_loops = count_loops(full_ref, *sample)
            except Exception:  # noqa: BLE001 — census is advisory; a broken
                jaxpr_loops = 0  # all-ref build is recorded by the baseline
                                 # measurement below, not raised out of plan()
            report = PlanReport(program=program.name,
                                source_loop_count=program.source_loop_count,
                                jaxpr_loop_count=jaxpr_loops)

        # ---- Step 2: arithmetic-intensity filter ----------------------
        with obs.span("planner.intensity"):
            cands: list[CandidateInfo] = []
            for r in program.regions:
                ana = analyze_region(r.analysis_fn, *r.analysis_args, name=r.name)
                cands.append(CandidateInfo(region=r.name, analysis=ana))
            report.candidates = cands
            by_ai = sorted(cands, key=lambda c: -c.analysis.arithmetic_intensity)
            ai_set = [c.region for c in by_ai[:cfg.top_a]]
            report.ai_selected = ai_set

        # ---- Step 3: resource filter over (region, variant) pairs -----
        # the cheap lowering of EVERY (region, variant) pair fans out on the
        # verification executor — with verify_workers > 1 the per-pair
        # ``precompile`` calls run concurrently (order-preserving, so the
        # ranking below is identical at any worker count)
        policy = FaultPolicy(compile_timeout_s=cfg.compile_timeout_s,
                             run_timeout_s=cfg.run_timeout_s,
                             max_retries=cfg.max_retries,
                             retry_backoff_s=cfg.retry_backoff_s,
                             outlier_mad=cfg.outlier_mad,
                             remeasure=cfg.remeasure)
        executor = VerificationExecutor(workers=cfg.verify_workers,
                                        cache=self.compile_cache,
                                        policy=policy)
        # known-bad genes: the offloader-lifetime strike list, topped up
        # with records persisted by previous runs under the same
        # measurement conditions
        quarantine = self.quarantine
        mkey = measurement_cache_key(program) if store is not None else ""
        if store is not None:
            quarantine.load_records(store.quarantine_for(mkey))
        try:
            with obs.span("planner.resources"):
                region_map = {r.name: r for r in program.regions}
                pairs: list[VariantCandidate] = []
                lower_jobs: list[tuple] = []
                lower_meta: list[tuple] = []
                for c in cands:
                    if c.region not in ai_set:
                        continue
                    r = region_map[c.region]
                    for var, fn in offload_variants(c.region).items():
                        lower_jobs.append((c.region, var, fn, r.analysis_args,
                                           r.static_kwargs))
                        lower_meta.append((c, var))
                for (c, var), est in zip(
                        lower_meta,
                        precompile_many(lower_jobs, mapper=executor.map_concurrent)):
                    c.variant_estimates[var] = est
                    pairs.append(VariantCandidate(c.region, var, c.analysis, est))
                eligible = [p for p in pairs if p.resources.lower_ok
                            and p.resources.resource_fraction <= cfg.resource_cap]
                # quarantined (region, variant) pairs never re-enter the
                # ranking: their past permanent failures already cost budget
                eligible = [p for p in eligible
                            if not quarantine.is_quarantined(p.region, p.variant)]

                def rank_key(p: VariantCandidate):
                    # efficiency first; the region's declared deploy/measure
                    # preference breaks ties (equal AI + equal fraction is common
                    # for same-shaped variants)
                    r = region_map[p.region]
                    preferred = p.variant in (r.deploy_variant, r.measure_variant)
                    return (-p.efficiency, 0 if preferred else 1, p.variant)

                ranked = sorted(eligible, key=rank_key)

                # per-region variant ranking; top-c regions by their best pair
                variants_of: dict[str, list[VariantCandidate]] = {}
                for p in ranked:
                    variants_of.setdefault(p.region, []).append(p)
                eff_regions: list[str] = []
                for p in ranked:
                    if p.region not in eff_regions:
                        eff_regions.append(p.region)
                    if len(eff_regions) == cfg.top_c:
                        break
                report.eff_selected = eff_regions
                report.eff_pairs = [(p.region, p.variant) for p in ranked
                                    if p.region in eff_regions]
                for c in cands:                         # mirror best pair for reports
                    best = variants_of.get(c.region, [])
                    if best:
                        c.best_variant = best[0].variant
                        c.resources = best[0].resources
                    elif c.variant_estimates:           # all failed/over-cap: show one
                        c.resources = next(iter(c.variant_estimates.values()))

            # ---- Step 4: measured pattern search (pluggable strategy) -----
            with obs.span("planner.search"):
                # the all-ref baseline goes through the same fault policy as
                # every candidate: watchdogs when configured, bounded retry for
                # transients — an unlucky hiccup must not void the whole search
                report.baseline = measure_with_retry(
                    lambda: (search.time_callable(
                        full_ref, sample, warmup=cfg.warmup, reps=cfg.reps,
                        pattern="all-ref", impl=Impl(),
                        compile_timeout_s=policy.compile_timeout_s,
                        run_timeout_s=policy.run_timeout_s,
                        check_finite=policy.check_finite,
                        outlier_mad=policy.outlier_mad,
                        remeasure=policy.remeasure), True),
                    policy)

                def _job(impl) -> VerifyJob:
                    impl = Impl(impl)
                    return VerifyJob(key=compile_key(program.name, impl, sample),
                                     fn=program.build(impl), args=sample,
                                     pattern=impl.describe(), impl=dict(impl))

                def measure(impl: Impl) -> Measurement:
                    return executor.measure_one(_job(impl), warmup=cfg.warmup,
                                                reps=cfg.reps)

                def measure_batch(impls: list) -> list:
                    return executor.measure_batch([_job(i) for i in impls],
                                                  warmup=cfg.warmup, reps=cfg.reps)

                def prefetch(impls: list) -> None:
                    executor.prefetch([_job(i) for i in impls])

                ledger = MeasurementLedger(measure, budget=cfg.max_measurements,
                                           measure_batch_fn=measure_batch,
                                           prefetch_fn=prefetch,
                                           quarantine=quarantine)
                # cross-run reuse: sibling cache entries measured under the same
                # conditions donate their per-pattern measurements — a re-proposed
                # known pattern is served from the ledger and costs zero d
                primed: list[Measurement] = []
                if store is not None:
                    for m in store.measurements_for(mkey):
                        impl = Impl(m.get("impl", {}))
                        pm = Measurement(
                            pattern=str(m.get("pattern", impl.describe())),
                            compile_seconds=float(m.get("compile_seconds", 0.0)),
                            run_seconds=float(m.get("run_seconds", float("inf"))),
                            runs=[], ok=bool(m.get("ok", False)),
                            error=str(m.get("error", "")), impl=dict(impl),
                            first_run_seconds=float(m.get("first_run_seconds", 0.0)))
                        ledger.prime(impl, pm)
                        primed.append(pm)
                # the all-ref baseline pre-exists (the paper's running CPU system):
                # a strategy re-proposing it gets the measurement without spending d.
                # Primed AFTER the cache donations so this run's fresh baseline wins.
                ledger.prime(Impl(), report.baseline)

                def _bound_tuning(p: VariantCandidate):
                    # tile-parameter genes only when the config asks for them
                    # AND the variant declared a space; None keeps the
                    # variant-only trajectory bit-identical
                    if not cfg.tune_tiles:
                        return None
                    space = tuning_space(p.region, p.variant)
                    if space is None:
                        return None
                    return BoundTuningSpace(
                        space, tuple(region_map[p.region].analysis_args))

                state = SearchState(
                    regions=eff_regions,
                    ranked=[SearchCandidate(p.region, p.variant,
                                            p.resources.resource_fraction,
                                            p.efficiency,
                                            flops=p.analysis.flops,
                                            transcendentals=p.analysis.transcendentals,
                                            boundary_bytes=p.analysis.boundary_bytes,
                                            alignment=p.analysis.alignment,
                                            tuning=_bound_tuning(p))
                            for p in ranked if p.region in eff_regions],
                    resource_cap=cfg.resource_cap,
                    seed=cfg.seed,
                    baseline=report.baseline,
                    quarantine=quarantine)
                # the roofline surrogate, seeded from the Step-3 estimates and
                # pre-calibrated on everything already measured: the fresh baseline
                # (exact re-base), then the primed cross-run measurements —
                # single-gene patterns first, so their deltas are pinned exactly
                # before combined patterns distribute their residuals
                model = CostModel(candidates=state.ranked,
                                  baseline_seconds=report.baseline.run_seconds
                                  if report.baseline.ok else 0.0)
                # restore persisted calibration (deltas + pair-interaction
                # corrections) from sibling entries under the same measurement
                # conditions; this run's own observations below refine it
                if store is not None:
                    model.load_state(store.cost_model_for(mkey))
                if report.baseline.ok:
                    model.observe(Impl(), report.baseline.run_seconds)
                for m in sorted((p for p in primed if p.ok and p.mapping()),
                                key=lambda m: (len(m.mapping()), m.pattern)):
                    model.observe(Impl(m.mapping()), m.run_seconds)
                state.cost_model = model

                # |non-ref genome space| of the survivors — make_strategy("auto")
                # picks exhaustive/staged/surrogate from this.  A variant with
                # a bound TuningSpace contributes every valid tile point (the
                # bare default is one of them); without tuning each variant
                # counts once, exactly as before.
                space = 1
                for r in eff_regions:
                    n = 0
                    for c in state.variants_of(r):
                        n += (max(c.tuning.size(), 1)
                              if c.tuning is not None else 1)
                    space *= 1 + n
                report.search_space = max(space - 1, 0)
                strategy = make_strategy(cfg, space_size=report.search_space)
                strategy.run(state, ledger)
                executor.shutdown()     # sync final cache stats before reading them
                report.measurements = ledger.order       # budget-consuming, in order
                report.reused = [m for m in ledger.reused() if m.mapping()]
                report.quarantined = quarantine.blocked()
                report.quarantine_records = quarantine.to_records()
                report.strategy = strategy.name
                report.search_trace = state.trace
                report.skipped_combinations = state.skipped
                # cost-model residual-bias notes (ROADMAP "region interaction
                # terms"): pairs whose multi-gene observations stayed systematically
                # biased are surfaced so the surrogate's trust in composite
                # predictions is visible
                report.cost_model_state = model.export_state()
                bias = model.bias_notes()
                if bias:
                    report.search_trace.append(
                        {"stage": "cost-model pair bias", "pairs": bias})
                # pipelined-verification wall-clock accounting
                stats = executor.stats.as_dict()
                report.search_trace.append({"stage": "verification executor",
                                            **stats})
                report.verify_workers = cfg.verify_workers
                report.verify_wall_s = stats["verify_wall_s"]
                report.compile_wall_s = stats["compile_wall_s"]

            # ---- Step 5: select -------------------------------------------
            with obs.span("planner.select"):
                # over everything the strategy was served this run: fresh
                # measurements AND cross-run primed patterns it re-proposed
                base_ok = report.baseline.ok
                ok_measurements = [m for m in ledger.served
                                   if m.ok and m.mapping()]
                best = min(ok_measurements, key=lambda m: m.run_seconds,
                           default=None)
                if best is not None and (not base_ok
                                         or best.run_seconds < report.baseline.run_seconds):
                    report.best_pattern = best.mapping()
                    report.best_seconds = best.run_seconds
                    # a failed baseline gives no meaningful reference: still select
                    # the fastest working pattern, but never claim a speedup (and
                    # _sound() keeps this search out of the plan cache)
                    report.speedup = (report.baseline.run_seconds / best.run_seconds
                                      if base_ok else 1.0)
                else:
                    report.best_pattern = {}
                    report.best_seconds = (report.baseline.run_seconds
                                           if base_ok else 0.0)
                    report.speedup = 1.0
                return report
        finally:
            # shutdown is idempotent; the finally guards the pool and the
            # offloader-lifetime CompileCache against ANY exception from
            # Step 3 onward — an aborted plan must neither leak worker
            # threads nor leave a transiently-failed compile future to be
            # served as permanent on the next plan()
            executor.shutdown()

    # ------------------------------------------------------------------
    def _report_from_cache(self, program: OffloadableProgram, ckey: str,
                           entry: dict) -> PlanReport:
        baseline_s = float(entry.get("baseline_seconds", 0.0))
        report = PlanReport(
            program=program.name,
            source_loop_count=program.source_loop_count,
            jaxpr_loop_count=int(entry.get("jaxpr_loop_count", 0)),
            best_pattern=dict(entry.get("best_pattern", {})),
            speedup=float(entry.get("speedup", 1.0)),
            best_seconds=float(entry.get("best_seconds", 0.0)),
            from_cache=True,
            cache_key=ckey,
            strategy=str(entry.get("strategy", "staged")),
            verify_workers=int(entry.get("verify_workers", 1)),
        )
        report.baseline = Measurement("all-ref", 0.0, baseline_s, [],
                                      impl={})
        return report

    @staticmethod
    def _cache_entry(report: PlanReport, program: OffloadableProgram) -> dict:
        baseline_s = report.baseline.run_seconds if report.baseline else 0.0
        # persist EVERY ok per-pattern measurement (fresh + reused), not just
        # the winner: sibling searches with the same measurement_key prime
        # their ledgers from these.  Failed measurements are deliberately
        # dropped — a compile hiccup must be retried, not remembered.
        persisted = [
            {
                "pattern": m.pattern,
                "impl": m.mapping(),
                "run_seconds": m.run_seconds,
                "compile_seconds": m.compile_seconds,
                "first_run_seconds": m.first_run_seconds,
                "ok": m.ok,
                "error": m.error,
            }
            for m in list(report.measurements) + list(report.reused)
            if m.ok and m.mapping()
        ]
        return {
            "measurement_key": measurement_cache_key(program),
            "measurements": persisted,
            # cumulative gene strike records (see search.Quarantine):
            # sibling searches under the same measurement_key load these and
            # skip known-bad variants without re-paying their failures
            "quarantine": list(report.quarantine_records),
            # the calibrated surrogate state, keyed with the measurements it
            # was learned from (see PlanCache.cost_model_for)
            "cost_model": dict(report.cost_model_state),
            "program": report.program,
            "backend": jax.default_backend(),
            "best_pattern": dict(report.best_pattern),
            "pattern": Impl(report.best_pattern).describe(),
            "speedup": report.speedup,
            "baseline_seconds": baseline_s,
            # the winning measurement's own median — NOT baseline/speedup,
            # which drifts by division and is wrong when the failed-baseline
            # path clamps speedup to 1.0
            "best_seconds": report.best_seconds,
            "strategy": report.strategy,
            "jaxpr_loop_count": report.jaxpr_loop_count,
            "measured_patterns": [m.pattern for m in report.measurements],
            # provenance of the verification pipeline that produced the plan
            "verify_workers": report.verify_workers,
            "verify_wall_s": report.verify_wall_s,
            "compile_wall_s": report.compile_wall_s,
        }
