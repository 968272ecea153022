"""Verification-environment measurement (paper Step 4 executor).

The paper compiles each candidate pattern for the FPGA (~3 h) and runs the
app's sample benchmark.  Here a pattern compiles in seconds and runs on the
available backend; the *structure* (bounded number of measured patterns,
best-of-measured selection) is identical.

Compile time is measured with the AOT path —
``jax.jit(fn).lower(*args).compile()`` — so ``compile_seconds`` is the true
compilation cost and the first execution is reported separately
(``first_run_seconds``).  Compile cost is the paper's central constraint
(hours per FPGA pattern); folding the first run into it misreports exactly
the quantity the paper's budget ``d`` exists to bound.

The compile and run phases are split (:func:`aot_compile` +
``time_callable(..., precompiled=...)``) so a verification executor
(core/executor.py) can compile many candidate patterns concurrently and
hand each pre-built executable to the strictly *serial* timing phase —
``run_seconds`` medians are never taken while another pattern's timed reps
share the device.  The split also fixes the failure accounting: a pattern
whose compile succeeds but whose run fails still reports its true
``compile_seconds`` (the paper-central cost), and a failed compile reports
the time spent failing.

Timing uses ``time.perf_counter`` (monotonic, highest available resolution):
``time.time`` is subject to NTP slew / wall-clock adjustments and can make
``run_seconds`` jitter or even go negative across an adjustment.

``MeasurementLedger`` is the in-run analogue of the persistent plan cache:
search strategies propose offload patterns through it, a pattern re-proposed
within one plan run (e.g. a GA elite surviving into the next generation) is
served from the ledger, and only ledger *misses* consume the measurement
budget ``d``.  The ledger is thread-safe (compile workers may race on the
same pattern) and speaks both single (``measure``) and batched
(``measure_batch``) ask–tell, plus a free ``prefetch`` hint channel for
speculative compile-ahead.
"""
from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.regions import Impl, canonical_gene, gene_variant


@dataclass
class Measurement:
    pattern: str
    compile_seconds: float      # AOT compile only (lower + compile)
    run_seconds: float          # median of reps
    runs: list[float]
    ok: bool = True
    error: str = ""
    # structured offload pattern {region -> variant}; `pattern` is only its
    # human-readable rendering.  None for measurements taken before the
    # planner attached one (e.g. ad-hoc time_callable use).
    impl: dict | None = None
    first_run_seconds: float = 0.0   # first post-compile execution
    # wall-clock the (serial) verification pipeline was actually blocked
    # waiting for this pattern's compile.  Equals compile_seconds when the
    # compile ran inline; much smaller when a concurrent executor had the
    # executable warm before the timing phase reached this pattern.
    compile_wall_s: float = 0.0
    # fault-tolerance provenance.  `attempts` counts every try the retry
    # loop spent on this pattern (1 = first try succeeded); the compile
    # seconds burned by failed attempts are folded into compile_seconds /
    # compile_wall_s so retries are billed honestly.  On failure,
    # `failure_kind` is the classify_failure() verdict and `failure_phase`
    # says which half died ("compile" or "run").
    attempts: int = 1
    failure_kind: str = ""
    failure_phase: str = ""
    outliers_rejected: int = 0   # timed reps dropped by MAD rejection

    def mapping(self) -> dict:
        """The measured {region -> variant} mapping (empty = all-ref)."""
        return dict(self.impl) if self.impl else {}


@dataclass
class CompiledArtifact:
    """One AOT compile outcome: the executable (or the failure) plus the
    true compile duration.  Produced by :func:`aot_compile` — possibly on a
    worker thread — and consumed by ``time_callable(precompiled=...)`` on
    the serial timing thread."""
    compiled: object | None          # the AOT executable; None if it failed
    compile_seconds: float
    error: str = ""

    @property
    def ok(self) -> bool:
        return self.compiled is not None


def _block(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def aot_lower(fn, args) -> tuple:
    """Tracing/lowering half of the AOT path: ``jit -> lower``.  This is
    Python tracing — GIL-bound — so a concurrent executor runs it on the
    driver thread and ships only :func:`finish_compile` (the GIL-releasing
    XLA compile) to its worker pool.  Returns ``(lowered | None, seconds,
    error)`` and never raises.

    ``fn`` is traced through a fresh wrapper each call: JAX's trace cache
    makes a second trace of the same function wait for one in flight, so a
    retry would otherwise block behind a trace the watchdog abandoned."""
    t0 = time.perf_counter()
    try:
        lowered = jax.jit(functools.partial(fn)).lower(*args)
        return lowered, time.perf_counter() - t0, ""
    except Exception as e:  # noqa: BLE001 — a pattern failing = not a solution
        return None, time.perf_counter() - t0, f"{type(e).__name__}: {e}"


def finish_compile(lowered, lower_seconds: float = 0.0,
                   error: str = "") -> CompiledArtifact:
    """XLA-compile a lowered module (the GIL-releasing half — safe to run
    many concurrently on a thread pool).  ``compile_seconds`` on the
    artifact is the FULL AOT cost: the lowering seconds handed in plus the
    compile itself.  Never raises."""
    if lowered is None:
        return CompiledArtifact(None, lower_seconds, error)
    t0 = time.perf_counter()
    try:
        compiled = lowered.compile()
        return CompiledArtifact(
            compiled, lower_seconds + time.perf_counter() - t0)
    except Exception as e:  # noqa: BLE001 — a pattern failing = not a solution
        return CompiledArtifact(
            None, lower_seconds + time.perf_counter() - t0,
            f"{type(e).__name__}: {e}")


def aot_compile(fn, args) -> CompiledArtifact:
    """AOT-compile ``fn`` for ``args`` (``jit -> lower -> compile``) and
    time it.  Never raises: a failed lower/compile returns a non-``ok``
    artifact that still accounts the seconds spent failing — compile cost
    is the paper's central constraint even for rejected patterns."""
    return finish_compile(*aot_lower(fn, args))


# ---------------------------------------------------------------------------
# Fault tolerance: watchdog, failure classification, outlier rejection
# ---------------------------------------------------------------------------
# Error-message markers that make a failure *transient* — worth a bounded
# retry with backoff.  Everything else (lowering/type errors, non-finite
# outputs, injected permanent faults) is permanent: a retry cannot fix it
# and repeat offenders are quarantined instead.
TRANSIENT_MARKERS = (
    "WatchdogTimeout",
    "CompileTimeout",
    "RunTimeout",
    "/transient",                  # InjectedFault[kind/transient]
    "RESOURCE_EXHAUSTED",
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "OutOfMemory",
)


def classify_failure(error: str) -> str:
    """``"transient"`` or ``"permanent"`` for a measurement error string.

    Transient = the environment failed (timeout, resource exhaustion, a
    flaky device): retrying the identical measurement may succeed.
    Permanent = the *pattern* failed (it does not lower, types don't check,
    it produces NaN/Inf): retrying is wasted budget, so permanent failures
    strike the pattern's genes in the :class:`Quarantine` instead."""
    err = str(error or "")
    if not err:
        return "permanent"
    if "/permanent" in err or "NonFiniteOutput" in err:
        return "permanent"
    return ("transient" if any(m in err for m in TRANSIENT_MARKERS)
            else "permanent")


def watchdog_call(fn, args=(), *, timeout_s: float):
    """Run ``fn(*args)`` under a wall-clock watchdog.

    Returns ``(ok, value, error)``.  The work runs on a daemon thread
    joined with ``timeout_s``; on expiry the thread is *abandoned* (Python
    cannot kill a thread — a genuinely hung compile keeps its thread until
    process exit, which is exactly the trade a real verification
    environment makes when it gives up on a 3-hour HDL compile) and the
    error is ``WatchdogTimeout`` — classified transient, so the retry loop
    gets its bounded second chance."""
    box: dict = {}

    def target():
        try:
            box["value"] = fn(*args)
        except BaseException as e:  # noqa: BLE001 — reported to the caller
            box["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=target, daemon=True, name="measure-watchdog")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        return False, None, f"WatchdogTimeout: exceeded {timeout_s:.3f}s wall"
    if "error" in box:
        return False, None, box["error"]
    return True, box.get("value"), ""


def _mad_reject(runs: list, z: float) -> tuple[list, int]:
    """Split timed reps into (kept, n_rejected) by modified z-score:
    ``|x - median| / (1.4826 * MAD) > z`` rejects.  A zero MAD (at least
    half the reps identical) rejects nothing — the median is already
    robust there."""
    med = float(np.median(runs))
    mad = float(np.median([abs(x - med) for x in runs]))
    if mad <= 0.0:
        return list(runs), 0
    kept = [x for x in runs if abs(x - med) / (1.4826 * mad) <= z]
    return kept, len(runs) - len(kept)


def _nonfinite(tree) -> bool:
    """True when any inexact leaf of an output tree holds NaN/Inf."""
    for leaf in jax.tree.leaves(tree):
        arr = np.asarray(leaf)
        if (np.issubdtype(arr.dtype, np.inexact)
                and not np.all(np.isfinite(arr))):
            return True
    return False


class _RunFailure(RuntimeError):
    """Internal: a run-phase failure whose message is already formatted
    (the watchdog path) — the outer handler must not re-prefix it."""


def _call_blocked(compiled, args):
    """One fully-synchronous execution of an AOT executable."""
    out = compiled(*args)
    _block(out)
    return out


def time_callable(fn, args, *, warmup: int = 1, reps: int = 5,
                  pattern: str = "", impl: dict | None = None,
                  precompiled: CompiledArtifact | None = None,
                  compile_timeout_s: float = 0.0,
                  run_timeout_s: float = 0.0,
                  check_finite: bool = False,
                  outlier_mad: float = 0.0,
                  remeasure: int = 0) -> Measurement:
    """Measure one offload pattern: AOT compile (unless a ``precompiled``
    artifact is handed in), then first run, warmup, and ``reps`` timed
    executions; ``run_seconds`` is the median of the reps.

    The compile and run phases are accounted separately on BOTH the success
    and the failure paths: a run-phase failure still reports the (real)
    ``compile_seconds`` of its successful compile, and every failure is
    classified (``failure_kind``) and located (``failure_phase``).

    Fault-tolerance knobs (all off by default — the bare call is the exact
    historical behavior):

    * ``compile_timeout_s > 0`` runs the inline AOT compile under
      :func:`watchdog_call`; expiry is a transient ``CompileTimeout``.
    * ``run_timeout_s > 0`` runs *every* execution (first run, warmup, and
      each timed rep) under the watchdog; expiry is a transient
      ``RunTimeout``.  The watchdog thread adds microseconds of overhead to
      each rep — enable it when hangs are a real risk, not for free.
    * ``check_finite`` fails the measurement (permanent
      ``NonFiniteOutput``) when the first run produces NaN/Inf — a
      numerically-broken offload must never win on speed.
    * ``outlier_mad > 0`` rejects timed reps whose modified z-score exceeds
      the threshold (real-hardware noise), re-measures up to ``remeasure``
      replacement reps, and reports the median of the kept reps;
      ``runs`` keeps every raw rep and ``outliers_rejected`` the count.
    """
    impl = dict(impl) if impl is not None else None
    if precompiled is not None:
        art = precompiled
    elif compile_timeout_s and compile_timeout_s > 0:
        ok, art, err = watchdog_call(aot_compile, (fn, args),
                                     timeout_s=compile_timeout_s)
        if not ok:
            art = CompiledArtifact(None, compile_timeout_s,
                                   f"CompileTimeout: {err}")
    else:
        art = aot_compile(fn, args)
    if not art.ok:
        return Measurement(pattern, art.compile_seconds, float("inf"), [],
                           False, art.error, impl=impl,
                           compile_wall_s=art.compile_seconds,
                           failure_kind=classify_failure(art.error),
                           failure_phase="compile")

    def run_once():
        if run_timeout_s and run_timeout_s > 0:
            ok, out, err = watchdog_call(_call_blocked, (art.compiled, args),
                                         timeout_s=run_timeout_s)
            if not ok:
                raise _RunFailure(f"RunTimeout: {err}"
                                  if "WatchdogTimeout" in err else err)
            return out
        return _call_blocked(art.compiled, args)

    def run_failed(error: str) -> Measurement:
        # the compile SUCCEEDED and only the run failed: its compile cost is
        # real and must be accounted (previously misreported as 0.0)
        return Measurement(pattern, art.compile_seconds, float("inf"), [],
                           False, error, impl=impl,
                           compile_wall_s=art.compile_seconds,
                           failure_kind=classify_failure(error),
                           failure_phase="run")

    try:
        t0 = time.perf_counter()
        out = run_once()
        first_run_s = time.perf_counter() - t0
        if check_finite and _nonfinite(out):
            return run_failed("NonFiniteOutput: pattern produced NaN/Inf")
        for _ in range(max(warmup - 1, 0)):
            run_once()
        runs = []
        for _ in range(reps):
            t = time.perf_counter()
            run_once()
            runs.append(time.perf_counter() - t)
        rejected = 0
        kept = runs
        if outlier_mad and outlier_mad > 0 and len(runs) >= 3:
            kept, rejected = _mad_reject(runs, outlier_mad)
            # bounded re-measurement: replace (some of) the rejected reps,
            # then re-filter the full raw set once — no open-ended loop
            for _ in range(min(rejected, max(int(remeasure), 0))):
                t = time.perf_counter()
                run_once()
                runs.append(time.perf_counter() - t)
            if rejected:
                refiltered, rejected = _mad_reject(runs, outlier_mad)
                kept = refiltered if refiltered else kept
        return Measurement(pattern, art.compile_seconds,
                           float(np.median(kept)), runs, impl=impl,
                           first_run_seconds=first_run_s,
                           compile_wall_s=art.compile_seconds,
                           outliers_rejected=rejected)
    except _RunFailure as e:
        return run_failed(str(e))
    except Exception as e:  # noqa: BLE001 — a pattern failing = not a solution
        return run_failed(f"{type(e).__name__}: {e}")


# ---------------------------------------------------------------------------
# Measurement ledger — budget-aware dedup for the search strategies
# ---------------------------------------------------------------------------
def impl_key(impl) -> tuple:
    """Canonical hashable identity of an offload pattern: the sorted non-ref
    genes.  ``{a: ref, b: offload}`` and ``{b: offload}`` are the same
    program and must hit the same ledger entry.  Genes may carry tile
    params (``(variant, params)``); params equal to the variant's declared
    defaults canonicalize away (see :func:`repro.core.regions
    .canonical_gene`), so a defaulted-param gene and the bare variant — and
    any pre-tuning cache entry — share one key."""
    return tuple(sorted((r, canonical_gene(r, v))
                        for r, v in dict(impl).items()
                        if gene_variant(v) != "ref"))


class Quarantine:
    """Strike list for (region, variant[, tile]) genes that fail repeatedly.

    Gene identity is the canonical single-gene rendering
    (``Impl({region: gene}).describe()``), so a defaulted-tile gene and the
    bare variant share one record while distinct tile points are tracked
    separately — the same canonicalization the ledger key uses.

    ``record`` strikes every non-ref gene of a failed measurement (a failed
    multi-gene pattern can't name its culprit, so all its genes are
    suspects; a gene that also appears in succeeding patterns simply never
    accumulates enough strikes).  A gene reaching ``threshold`` strikes is
    quarantined: the planner filters it from the Step-3 ranking, strategies
    stop proposing it (:meth:`SearchState.gene_allowed`), and the
    replanner never re-offers a plan containing it.  Records round-trip
    through :class:`~repro.core.plan_cache.PlanCache` entries under
    ``measurement_key`` so future runs skip known-bad genes without
    re-paying their failures.  Transient failures are retried to success
    by the executor and never reach ``record`` — only permanent,
    retry-exhausted failures strike.
    """

    def __init__(self, threshold: int = 2):
        self.threshold = max(1, int(threshold))
        self._lock = threading.Lock()
        self._strikes: dict[str, int] = {}
        self._errors: dict[str, str] = {}

    @staticmethod
    def gene_id(region: str, gene) -> str:
        """Canonical persistent identity of one (region, gene)."""
        return Impl({region: gene}).describe()

    def record(self, m: Measurement) -> list[str]:
        """Strike the genes of a failed measurement; returns the gene ids
        that just crossed the quarantine threshold."""
        if m.ok:
            return []
        return self.record_failure(m.mapping(), m.error)

    def record_failure(self, impl, error: str) -> list[str]:
        """Strike every non-ref gene of ``impl`` directly (the serving-side
        feedback path, where no Measurement exists — e.g. a plan that
        faulted mid-serve)."""
        newly: list[str] = []
        with self._lock:
            for region, gene in sorted(dict(impl).items()):
                if gene_variant(gene) == "ref":
                    continue
                gid = self.gene_id(region, gene)
                n = self._strikes.get(gid, 0) + 1
                self._strikes[gid] = n
                self._errors[gid] = str(error)
                if n == self.threshold:
                    newly.append(gid)
        return newly

    def is_quarantined(self, region: str, gene) -> bool:
        gid = self.gene_id(region, gene)
        with self._lock:
            return self._strikes.get(gid, 0) >= self.threshold

    def allows(self, impl) -> bool:
        """True when no gene of the pattern is quarantined."""
        return not any(self.is_quarantined(r, g)
                       for r, g in dict(impl).items()
                       if gene_variant(g) != "ref")

    def blocked(self) -> list[str]:
        """Gene ids currently at/over the threshold, sorted."""
        with self._lock:
            return sorted(g for g, n in self._strikes.items()
                          if n >= self.threshold)

    def strikes(self) -> dict[str, int]:
        with self._lock:
            return dict(self._strikes)

    def to_records(self) -> list[dict]:
        """JSON-serializable strike records (persisted in cache entries)."""
        with self._lock:
            return [{"gene": g, "strikes": n,
                     "last_error": self._errors.get(g, "")}
                    for g, n in sorted(self._strikes.items())]

    def load_records(self, records) -> None:
        """Merge persisted records; the max strike count per gene wins
        (each persisted record is already a cumulative snapshot)."""
        for rec in records or ():
            if not isinstance(rec, dict):
                continue
            gene = rec.get("gene")
            try:
                n = int(rec.get("strikes", 0))
            except (TypeError, ValueError):
                continue
            if not isinstance(gene, str) or n <= 0:
                continue
            with self._lock:
                if n > self._strikes.get(gene, 0):
                    self._strikes[gene] = n
                    self._errors[gene] = str(rec.get("last_error", ""))


@dataclass
class MeasurementLedger:
    """In-run measurement memo with the budget attached.

    ``measure(impl)`` returns the cached Measurement on a hit (free), runs
    ``measure_fn`` and decrements ``budget`` on a miss, and returns ``None``
    once the budget is exhausted.  ``order`` is the measured (miss) sequence
    — exactly the patterns that consumed budget, in measurement order.

    ``measure_batch(impls)`` is the batched ask: every hit is served free,
    misses consume budget *in batch order* until it runs out (``None`` for
    the unaffordable tail), and the affordable misses are measured together
    through ``measure_batch_fn`` when one is wired (the concurrent
    verification executor: all compiles in flight at once, timed reps
    strictly serial).  Without a batch fn, misses fall back to sequential
    ``measure_fn`` calls — identical results, no pipelining.

    ``prime`` seeds an entry that never bills against ``d``: the all-ref
    baseline (the paper's pre-existing CPU system), and — since plan-cache
    entries persist *every* per-pattern measurement, not just the winner —
    measurements recovered from previous runs of the same program on the
    same backend (``AutoOffloader`` primes them on a cache miss, so a
    re-opened search re-proposing a known pattern costs zero ``d``).

    ``prefetch(impls)`` is a free hint — "these patterns may be proposed
    soon" — forwarded (ledger-missing subset only) to ``prefetch_fn`` so an
    executor can speculatively compile ahead.  It never measures, never
    spends budget, and is a no-op without a hook.

    ``served`` is every distinct Measurement handed to the strategy this
    run, hits and misses alike, in first-served order — the set the planner
    selects the winner from.  A primed entry the strategy never re-proposes
    stays out of ``served``: the current search vouches only for patterns
    it actually asked for.

    The ledger is thread-safe: concurrent ``measure`` calls on the same
    pattern collapse to one measurement (the losers wait and are served the
    winner's entry as hits), and budget accounting stays exact under races.
    """
    measure_fn: Callable
    budget: int
    measure_batch_fn: Optional[Callable] = None
    prefetch_fn: Optional[Callable] = None
    # failed (retry-exhausted) measurements strike their genes here, so the
    # strategies' quarantine filter sees new offenders mid-run
    quarantine: Optional[Quarantine] = None
    hits: int = 0
    misses: int = 0
    order: list[Measurement] = field(default_factory=list)
    served: list[Measurement] = field(default_factory=list)
    _entries: dict[tuple, Measurement] = field(default_factory=dict)
    _primed: set = field(default_factory=set)
    _served_keys: set = field(default_factory=set)
    _inflight: dict = field(default_factory=dict)   # key -> threading.Event
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def prime(self, impl, measurement: Measurement) -> None:
        """Record a measurement taken outside the budget (the all-ref
        baseline, or a measurement persisted by a previous plan run)."""
        k = impl_key(impl)
        with self._lock:
            self._entries[k] = measurement
            self._primed.add(k)

    def seen(self, impl) -> bool:
        with self._lock:
            return impl_key(impl) in self._entries

    def exhausted(self) -> bool:
        return self.budget <= 0

    def reused(self) -> list[Measurement]:
        """Primed (cross-run / baseline) measurements the strategy actually
        re-proposed this run — served for free."""
        return [m for m in self.served
                if impl_key(m.impl or {}) in self._primed]

    def failures(self) -> list[Measurement]:
        """Budget-consuming measurements that failed, in measurement order
        — the run's failure provenance (each carries ``attempts``,
        ``failure_kind``, ``failure_phase``, and the billed seconds)."""
        with self._lock:
            return [m for m in self.order if not m.ok]

    def _serve(self, key: tuple, m: Measurement) -> Measurement:
        # callers hold self._lock
        if key not in self._served_keys:
            self._served_keys.add(key)
            self.served.append(m)
        return m

    def measure(self, impl) -> Optional[Measurement]:
        k = impl_key(impl)
        while True:
            with self._lock:
                hit = self._entries.get(k)
                if hit is not None:
                    self.hits += 1
                    return self._serve(k, hit)
                ev = self._inflight.get(k)
                if ev is None:
                    if self.budget <= 0:
                        return None
                    self.budget -= 1
                    self.misses += 1
                    ev = threading.Event()
                    self._inflight[k] = ev
                    break
            # another thread is measuring this exact pattern: wait for its
            # entry instead of double-spending budget on a duplicate
            ev.wait()
        try:
            m = self.measure_fn(impl)
        except BaseException:
            # measure_fn must return failure Measurements, never raise; if
            # it does anyway (a test helper calling pytest.fail, a fault
            # injector blowing through the executor), release any waiters
            # AND refund the reserved budget before propagating — no entry
            # was stored, so a retry of the same pattern would otherwise
            # bill a second time for a measurement that never happened
            with self._lock:
                self.budget += 1
                self.misses -= 1
                self._inflight.pop(k, None)
            ev.set()
            raise
        with self._lock:
            self._entries[k] = m
            self.order.append(m)
            self._inflight.pop(k, None)
            res = self._serve(k, m)
        ev.set()
        if self.quarantine is not None and not m.ok:
            self.quarantine.record(m)
        return res

    def measure_batch(self, impls) -> list[Optional[Measurement]]:
        """Batched ask: one ``Optional[Measurement]`` per input, in order.
        Hits (including in-batch duplicates) are free; misses consume budget
        in batch order and are measured together via ``measure_batch_fn``
        when available, so their compiles can run concurrently while the
        timed reps stay strictly serial."""
        keys = [impl_key(i) for i in impls]
        to_measure: list[tuple] = []          # (key, impl) misses, batch order
        with self._lock:
            reserved = set()
            for k, impl in zip(keys, impls):
                if (k in self._entries or k in reserved
                        or k in self._inflight):
                    continue
                if self.budget <= 0:
                    continue
                self.budget -= 1
                self.misses += 1
                reserved.add(k)
                self._inflight[k] = threading.Event()
                to_measure.append((k, impl))
        measured_keys = {k for k, _ in to_measure}
        if to_measure:
            batch = [impl for _, impl in to_measure]
            try:
                if self.measure_batch_fn is not None:
                    ms = list(self.measure_batch_fn(batch))
                else:
                    ms = [self.measure_fn(impl) for impl in batch]
            except BaseException:
                # refund the whole reservation: nothing was stored, so the
                # strategy's retry of these patterns must not double-bill
                with self._lock:
                    for k, _ in to_measure:
                        self.budget += 1
                        self.misses -= 1
                        ev = self._inflight.pop(k, None)
                        if ev is not None:
                            ev.set()
                raise
            with self._lock:
                stored: set = set()
                for (k, _), m in zip(to_measure, ms):
                    self._entries[k] = m
                    self.order.append(m)
                    stored.add(k)
                    ev = self._inflight.pop(k, None)
                    if ev is not None:
                        ev.set()
                for k, _ in to_measure:
                    # a short batch_fn return: refund the unmeasured tail so
                    # its budget isn't leaked and no waiter deadlocks
                    if k not in stored:
                        self.budget += 1
                        self.misses -= 1
                        ev = self._inflight.pop(k, None)
                        if ev is not None:
                            ev.set()
            if self.quarantine is not None:
                for m in ms:
                    if m is not None and not m.ok:
                        self.quarantine.record(m)
        # patterns another thread is measuring right now: wait so the
        # assembly below can serve their entries instead of dropping them
        for k in set(keys) - measured_keys:
            with self._lock:
                ev = self._inflight.get(k)
            if ev is not None:
                ev.wait()
        out: list[Optional[Measurement]] = []
        with self._lock:
            first_seen: set = set()
            for k in keys:
                m = self._entries.get(k)
                if m is None:                 # unaffordable: budget ran out
                    out.append(None)
                    continue
                if not (k in measured_keys and k not in first_seen):
                    self.hits += 1            # pre-existing or in-batch dup
                first_seen.add(k)
                out.append(self._serve(k, m))
        return out

    def prefetch(self, impls) -> None:
        """Free compile-ahead hint.  Forwards the subset the ledger has no
        entry (or in-flight measurement) for to ``prefetch_fn``; never
        measures and never consumes budget."""
        if self.prefetch_fn is None:
            return
        with self._lock:
            fresh = [i for i in impls
                     if impl_key(i) not in self._entries
                     and impl_key(i) not in self._inflight]
        if fresh:
            self.prefetch_fn(fresh)
