"""Continuous-batching serving engine (slot-based, vLLM-style admission).

A fixed number of decode slots share one batched KV cache.  Each engine tick:
  1. install any pending plan generation (the online-replanning hot-swap
     point — see ``PlanGeneration``),
  2. admit queued requests into every free slot (bucketed single-sequence
     prefill, cache scattered into the slot),
  3. one batched decode step for every active slot,
  4. retire finished sequences (max_new_tokens reached) and free the slots.

The correctness contract (test-asserted): a request's tokens are identical
whether it runs alone or interleaved with arbitrary other requests — slot
isolation comes from per-slot cache rows, positions, and per-request sampling
keys (seed, rid, step).  Online replanning extends the contract: a plan
hot-swap between ticks never drops or re-queues a request, and (for patterns
with identical numerics) never changes a token.

Bucketed prefill: prompts are right-padded to power-of-two length buckets and
prefilled with a traced ``length`` scalar (``factory.make_bucketed_prefill_
step``), so the engine compiles one prefill per *bucket* instead of one per
distinct prompt length — the serving analogue of the per-pattern recompile
the offload-proposal paper (arXiv 2004.08548) warns naive placement pays.
``prefill_traces`` counts actual compilations for observability.

Decode ahead: the greedy tokens and the finite check are computed on the
device behind each step, so one small copy reaches the host per step.
When every slot decodes, none finishes and all are greedy, the next tick's
step is known before this one's tokens reach the host: it is dispatched
on the device's tokens at once, and the host's bookkeeping overlaps it.
The next tick uses it only if its inputs are the ones the tick would pass.

Admission control: ``submit()`` rejects requests whose prompt + frontend
prefix + max_new_tokens cannot fit the cache (the overflow used to silently
corrupt cache rows via the decode-step ``min(pos, ctx-1)`` slot clamp).

Graceful degradation: every tick-path plan call runs under a runtime guard
(``_plan_call``) — a kernel exception or non-finite logits rolls the engine
back to the last healthy ``PlanGeneration`` (all-ref as the terminal
fallback) and retries the same call, so in-flight requests are never dropped
or corrupted by a bad hot-swap.  ``canary_check`` lets a replanner validate
a candidate (finite + bit-equal logits on a synthetic batch) before
``offer_plan``; faulted plan keys are permanently refused re-installation.
See docs/fault-tolerance.md for the canary → swap → rollback state machine.

This runs the same ``prefill``/``decode_step`` the dry-run lowers, so it is
the serving layer for any assigned arch (GQA KV caches, rotating local
windows, SSM/RG-LRU states all behave as cache pytrees here).
"""
from __future__ import annotations

import threading
import time
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.regions import Impl
from repro.core.search import impl_key
from repro.models import factory as F
from repro.serving.sampling import GREEDY, SamplingParams, make_sampler

# per-tick event records retained for the windowed stats view; bounds the
# engine's memory on an infinite request stream
_EVENT_CAPACITY = 1024


class ServeIncompleteError(RuntimeError):
    """``run_to_completion`` ran out of ticks with work still in flight.

    Carries the structured partial result: ``finished`` (completed requests)
    and ``pending`` (rids still queued or mid-decode)."""

    def __init__(self, finished: list, pending: list[int], max_ticks: int):
        self.finished = finished
        self.pending = pending
        super().__init__(
            f"run_to_completion exhausted max_ticks={max_ticks} with "
            f"{len(pending)} request(s) unfinished (rids {pending}); "
            f"{len(finished)} finished")


class PlanFault(RuntimeError):
    """A serving plan misbehaved on the tick path: a kernel raised, or the
    plan produced non-finite logits.  The engine catches this internally to
    roll back to the last healthy generation; it only escapes when even the
    all-reference plan faults (nothing left to roll back to)."""


# rollback targets retained per engine: the newest N previously-healthy
# generations, newest last (older history adds nothing — all-ref is the
# terminal fallback anyway)
_FALLBACK_CAPACITY = 4


@dataclass
class Request:
    rid: int
    tokens: np.ndarray               # prompt [S]
    max_new_tokens: int
    sampling: SamplingParams = GREEDY
    frontend: Optional[np.ndarray] = None   # patch/frame embeddings (no batch dim)
    generated: list = field(default_factory=list)
    done: bool = False
    # ---- lifecycle stats (perf_counter seconds; -1 = not reached) ----
    submit_s: float = -1.0
    slot_s: float = -1.0             # assigned a free slot (prefill starts)
    admit_s: float = -1.0            # prefill finished, first token emitted
    finish_s: float = -1.0
    bucket: int = 0                  # padded prefill length
    admit_tick: int = -1             # engine tick that admitted the request
    plan_generation: int = 0         # plan generation at admission time

    @property
    def queue_wait_s(self) -> float:
        """Seconds between submit() and assignment to a free slot (excludes
        the request's own prefill — that is part of ttft_s)."""
        return self.slot_s - self.submit_s if self.slot_s >= 0 else -1.0

    @property
    def ttft_s(self) -> float:
        """Time to first token (queue wait + prefill + first sample)."""
        return self.admit_s - self.submit_s if self.admit_s >= 0 else -1.0

    @property
    def decode_tps(self) -> float:
        """Decode throughput for this request (tokens after the first)."""
        n = len(self.generated) - 1
        dt = self.finish_s - self.admit_s
        return n / dt if n > 0 and dt > 0 else 0.0


def _cache_batch_axis(path) -> int:
    """Stacked ('stack' subtree) cache leaves carry [layers, B, ...];
    unstacked ('tail') leaves carry [B, ...]."""
    top = str(getattr(path[0], "key", path[0]))
    return 1 if top == "stack" else 0


def cache_insert(full_cache, one_cache, slot: int):
    """Scatter a batch-1 cache into slot `slot` of the batched cache."""
    flat_full = jax.tree_util.tree_flatten_with_path(full_cache)
    flat_one = jax.tree_util.tree_flatten_with_path(one_cache)
    out = []
    for (path, leaf_full), (_, leaf_one) in zip(flat_full[0], flat_one[0]):
        ax = _cache_batch_axis(path)
        idx = [slice(None)] * leaf_full.ndim
        idx[ax] = slot
        src = jnp.take(leaf_one, 0, axis=ax)
        out.append(leaf_full.at[tuple(idx)].set(src.astype(leaf_full.dtype)))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(full_cache), out)


def _greedy_check(logits):
    """Each row's greedy token at its last position ([rows, 1], the shape
    a decode step takes its tokens in), and whether every logit is finite:
    the guard copies these to the host in place of the logits themselves."""
    return (jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32),
            jnp.all(jnp.isfinite(logits)))


@dataclass
class _Ahead:
    """A decode step dispatched one tick early, while the step before it
    still ran: what it was given and its outputs, still on the device.
    ``tokens`` is filled once the guard has read the earlier step's greedy
    tokens, which the early step took on the device."""
    gen: "PlanGeneration"
    params: object
    cache: object
    pos: np.ndarray
    out: tuple
    checked: tuple
    tokens: Optional[np.ndarray] = None

    def serves(self, gen, args) -> bool:
        """Whether this step is the one ``gen.decode(*args)`` would run."""
        params, cache, toks, pos = args
        return (gen is self.gen and params is self.params
                and cache is self.cache and self.tokens is not None
                and np.array_equal(toks, self.tokens)
                and np.array_equal(pos, self.pos))


def _tree_bytes(tree) -> int:
    """Bytes of every array in a pytree (a slot's cache rows: KV and
    recurrent state alike)."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


def _block(tree) -> None:
    """Wait for every device buffer in a pytree (warm-up barrier)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


@dataclass
class PlanGeneration:
    """One traced serving plan: the merged offload pattern plus the jitted
    prefill/decode entry points compiled for it.

    The engine serves exactly one generation at a time.  An online
    replanner builds the NEXT one off the tick path
    (``ServeEngine.prepare_plan`` — traces jitted and pre-warmed, safe on a
    background thread) and stages it with ``ServeEngine.offer_plan``.  The
    swap itself is a pointer assignment between ticks: ``step()`` installs
    the pending generation before admitting or decoding, so

    * no tick ever runs half-old half-new traces,
    * no tick blocks on search or compilation (both happened off-thread),
    * in-flight requests keep their KV cache rows — the cache layout
      depends only on (cfg, slots, ctx), never on the offload pattern,
    * a request's token stream does not depend on when (or whether) a
      swap landed, for patterns with identical numerics.

    ``generation`` is assigned by the engine when the generation is
    installed (the generation counter); ``key`` is the canonical pattern
    identity (``search.impl_key`` of the merged impl) — generations with
    equal keys share traces and a swap between them is a no-op.
    """
    impl: Impl                          # merged pattern the traces dispatch
    key: tuple                          # canonical identity (search.impl_key)
    prefill: Callable                   # jitted bucketed prefill
    decode: Callable                    # jitted batched decode step
    generation: int = 0                 # assigned at install time
    plan_seconds: Optional[float] = None  # planner's measured seconds, if any


class ServeEngine:
    """Continuous-batching serving engine — the single serving path.

    Public knobs (all constructor-only; none participate in the offload
    plan-cache key — serving shape is orthogonal to the planned pattern):

    * ``cfg`` (ModelConfig)  — architecture; ``cfg.reduced()`` for smoke
      runs.
    * ``params``             — model parameters (``factory.init_params``).
    * ``slots`` (int, 4)     — concurrent decode lanes sharing one batched
      KV cache.
    * ``ctx`` (int, 128)     — per-slot cache capacity; admission control
      rejects requests that cannot fit it.
    * ``seed`` (int, 0)      — sampling PRNG seed: the sampled token is a
      pure function of (seed, request id, step, logits row), so output is
      deterministic per seed and independent of slot placement / batch mix.
    * ``impl``               — offload pattern ({region -> variant}, e.g.
      the planner's ``PlanReport.best_impl()``); None = architectural
      defaults.  Planner patterns override the arch defaults per region.

    Online replanning (``serving/replan.py``) swaps the served pattern
    while requests are in flight: ``prepare_plan`` builds the new traces
    off-thread, ``offer_plan`` stages them, and ``step`` installs the swap
    between ticks under the ``plan_generation`` counter.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 ctx: int = 128, seed: int = 0, impl=None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.ctx = ctx
        self.seed = seed
        self._sample = jax.jit(make_sampler(seed))
        self._check = jax.jit(_greedy_check)
        self.prefill_traces = 0
        self.buckets_seen: set[int] = set()
        # (bucket, frontend signature) shapes actually prefilled — what
        # prepare_plan warms so a swapped-in generation compiles nothing
        # on the tick path
        self._prefill_shapes: set[tuple] = set()
        self.cache = F.init_cache(cfg, slots, ctx)
        self._ahead: Optional[_Ahead] = None   # next tick's decode, dispatched
        self.decoded_ahead = 0           # decode steps served from _ahead
        self._room_ahead: Optional[bool] = None  # read once, when first asked
        # dropless expert layers: the steps also return their routing
        # counters, copied with the logits and kept on the step's span
        self._counted = cfg.moe_dropless
        self.queue: deque[Request] = deque()
        self.active: list[Optional[Request]] = [None] * slots
        self.pos = np.zeros(slots, np.int32)          # next absolute position
        self.last_tok = np.zeros(slots, np.int32)
        # per-slot sampling state (mirrors the active request)
        self._rids = np.zeros(slots, np.int32)
        self._temps = np.zeros(slots, np.float32)
        self._top_ks = np.zeros(slots, np.int32)
        self.finished: list[Request] = []
        self.finished_total = 0          # lifetime count, survives drain
        self._next_rid = 0
        # ---- plan generations (online replanning) ----
        self.ticks = 0                   # completed step() calls
        self.plan_generation = 0         # bumped at every installed swap
        self.swaps = 0
        self.swap_ticks: list[int] = []  # tick number each swap landed before
        self._plan_lock = threading.Lock()
        self._pending_plan: Optional[PlanGeneration] = None
        self._trace_memo: dict[tuple, tuple] = {}
        self._warm_cache = None          # template cache for off-thread warms
        self._replanner = None
        self._events: deque[dict] = deque(maxlen=_EVENT_CAPACITY)
        # ---- fault tolerance (graceful degradation) ----
        self.rollbacks = 0               # faulted generations rolled back
        self.degraded = False            # serving a rollback, not the offer
        self.last_fault: Optional[str] = None
        self._fallbacks: list[PlanGeneration] = []   # healthy gens, newest last
        self._faulted_keys: set[tuple] = set()       # plan keys seen faulting
        # a generation is "healthy" once it has served a full tick without
        # faulting; only healthy generations become rollback targets
        self._gen_healthy = True
        self._gen = self._generation_for(impl)

    # ------------------------------------------------------------------
    # plan generations
    # ------------------------------------------------------------------
    def _generation_for(self, impl,
                        plan_seconds: Optional[float] = None) -> PlanGeneration:
        """Build (or reuse from the per-engine trace memo) the jitted
        prefill/decode pair for ``impl`` merged over the arch defaults.
        Thread-safe; does not install anything."""
        merged = Impl({**F.default_impl(self.cfg), **dict(impl or {})})
        key = impl_key(merged)
        with self._plan_lock:
            cached = self._trace_memo.get(key)
        if cached is None:
            raw_prefill = F.make_bucketed_prefill_step(
                self.cfg, impl=merged, ctx=self.ctx, counts=self._counted)

            def counted_prefill(params, batch, length):
                # body runs at trace time only: counts one compilation per
                # (bucket, frontend-structure) — the trace-count tests read
                # this; warm-up compiles on a background thread count too
                self.prefill_traces += 1
                return raw_prefill(params, batch, length)

            built = (jax.jit(counted_prefill),
                     jax.jit(F.make_serve_step(self.cfg, impl=merged,
                                               counts=self._counted)))
            with self._plan_lock:
                # two threads may have built concurrently: first one wins so
                # both use the same jitted objects (shared dispatch cache)
                cached = self._trace_memo.setdefault(key, built)
        return PlanGeneration(impl=merged, key=key, prefill=cached[0],
                              decode=cached[1], plan_seconds=plan_seconds)

    def prepare_plan(self, impl=None, *, plan_seconds: Optional[float] = None,
                     warm: bool = True) -> PlanGeneration:
        """Build the traces for ``impl`` WITHOUT installing them.

        Safe to call from a background thread while the engine keeps
        ticking: it touches no serving state.  With ``warm`` (default) the
        new decode step and every prefill shape the engine has served are
        executed once against a throwaway template cache, so the jit
        dispatch cache is hot and the post-swap tick pays zero compilation.
        The returned generation is staged with :meth:`offer_plan`."""
        gen = self._generation_for(impl, plan_seconds)
        if warm:
            self._warm(gen)
        return gen

    def _warm(self, gen: PlanGeneration) -> None:
        if self._warm_cache is None:
            self._warm_cache = F.init_cache(self.cfg, self.slots, self.ctx)
        toks = jnp.zeros((self.slots, 1), jnp.int32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        _block(gen.decode(self.params, self._warm_cache, toks, pos))
        for bucket, fe_sig in sorted(self._prefill_shapes,
                                     key=lambda t: (t[0], t[1] or ())):
            batch = {"tokens": jnp.zeros((1, bucket), jnp.int32)}
            if fe_sig is not None:
                key, shape, dtype = fe_sig
                batch[key] = jnp.zeros((1,) + tuple(shape), dtype)
            _block(gen.prefill(self.params, batch,
                               jnp.asarray(bucket, jnp.int32)))

    def offer_plan(self, prepared: PlanGeneration) -> None:
        """Stage ``prepared`` for installation at the next tick boundary.

        Thread-safe; the latest offer wins.  The engine installs it at the
        top of the next ``step()`` — never mid-tick — bumping
        ``plan_generation``.  Offering a generation whose canonical key
        equals the serving one is a no-op (no counter bump)."""
        with self._plan_lock:
            self._pending_plan = prepared

    def _install_pending(self) -> None:
        with self._plan_lock:
            prepared, self._pending_plan = self._pending_plan, None
        if prepared is None:
            return
        with obs.span("engine.install"):
            self._install(prepared)

    def _install(self, prepared: PlanGeneration) -> None:
        if prepared.key == self._gen.key:
            return
        if prepared.key in self._faulted_keys:
            return                       # never re-install a plan that faulted
        if self._gen_healthy:
            # keep the outgoing generation as a rollback target — it served
            # at least one full tick without faulting
            self._fallbacks = [g for g in self._fallbacks
                               if g.key != self._gen.key]
            self._fallbacks.append(self._gen)
            del self._fallbacks[:-_FALLBACK_CAPACITY]
        self._gen_healthy = False        # the incoming plan must earn trust
        self.degraded = False
        self.plan_generation += 1
        prepared.generation = self.plan_generation
        self._gen = prepared
        self.swaps += 1
        self.swap_ticks.append(self.ticks)

    # ------------------------------------------------------------------
    # fault tolerance: guarded plan calls, rollback, canary validation
    # ------------------------------------------------------------------
    def _all_ref_generation(self) -> PlanGeneration:
        """The terminal fallback: every region pinned to its loop-faithful
        ``ref`` variant (overriding any architectural offload defaults)."""
        return self._generation_for(
            Impl({r: "ref" for r in F.default_impl(self.cfg)}))

    def _plan_call(self, op: str, *args):
        """Run one plan entry point (``"prefill"`` or ``"decode"``) under the
        runtime guard.  A kernel exception or non-finite logits triggers a
        rollback to the last healthy generation and a retry of the same
        call, so the in-flight request never observes the fault.  Raises
        only when no rollback target remains (the all-reference plan itself
        is faulting).

        Returns ``(logits, cache, counters, greedy)``: the step's logits and
        cache on the device, a counted step's routing counters (its third
        output) or None, and each row's greedy token.  The finite check and
        the greedy tokens are computed on the device behind the step, so
        one small copy reaches the host per call.  The wait for the device
        (``engine.wait``) is timed apart from that copy and the check
        (``engine.guard``)."""
        while True:
            gen = self._gen
            ahead, self._ahead = self._ahead, None
            try:
                if op == "decode" and ahead is not None \
                        and ahead.serves(gen, args):
                    out, checked = ahead.out, ahead.checked
                    self.decoded_ahead += 1
                else:
                    out = getattr(gen, op)(*args)
                    checked = self._check(out[0])
                if op == "decode" and self._next_decode_known():
                    self._ahead = self._decode_ahead(gen, args, out, checked)
                with obs.span("engine.wait"):
                    jax.block_until_ready(checked)
                with obs.span("engine.guard"):
                    greedy, finite, *counters = jax.device_get(
                        (*checked, *out[2:3]))
                if not finite:
                    raise PlanFault(
                        f"{op} produced non-finite logits under plan "
                        f"{gen.impl.describe()!r}")
                if self._ahead is not None:
                    self._ahead.tokens = greedy
                return (*out[:2], counters[0] if counters else None,
                        greedy[:, 0])
            except Exception as err:  # noqa: BLE001 — every tick-path plan
                # failure routes through rollback, whatever its type
                self._ahead = None
                if not self._rollback(gen, op, err):
                    raise

    def _next_decode_known(self) -> bool:
        """Whether the next tick's decode step is known before this one's
        tokens reach the host: every slot decodes and none finishes now (so
        the next tick admits nothing and feeds every slot the token this
        step picks), every slot decodes greedily, and no plan waits to be
        installed."""
        return (all(r is not None
                    and len(r.generated) + 1 < r.max_new_tokens
                    for r in self.active)
                and not np.any(self._temps > 0.0)
                and self._pending_plan is None
                and self._has_room_ahead())

    def _has_room_ahead(self) -> bool:
        """Whether the device holds a second decode step in flight: one
        more cache and that step's temporaries, reckoned as two caches,
        beside the most it has held so far.  Read once, at the first tick
        that could run ahead (after the first prefills); a device that
        reports no limit has room."""
        if self._room_ahead is None:
            leaves = jax.tree_util.tree_leaves(self.cache)
            need = 2 * _tree_bytes(self.cache)
            room = []
            for dev in {d for leaf in leaves for d in leaf.devices()}:
                stats = dev.memory_stats() or {}
                if "bytes_limit" in stats:
                    room.append(stats["bytes_limit"]
                                - stats.get("peak_bytes_in_use", 0))
            self._room_ahead = not room or min(room) >= need
        return self._room_ahead

    def _decode_ahead(self, gen, args, out, checked) -> _Ahead:
        """Dispatch the next tick's decode step behind this one, on this
        step's cache and greedy tokens, so the device need not wait for the
        host between the two."""
        params, _, _, pos = args
        pos = np.asarray(pos) + 1
        nxt = gen.decode(params, out[1], checked[0], pos)
        return _Ahead(gen, params, out[1], pos, nxt, self._check(nxt[0]))

    def _rollback(self, failed: PlanGeneration, op: str,
                  err: Exception) -> bool:
        """Replace ``failed`` with the newest healthy fallback (all-ref as
        the terminal target).  Returns False when nothing is left to roll
        back to — the caller re-raises."""
        if failed is not self._gen:
            return True                  # already rolled past it: just retry
        self._faulted_keys.add(failed.key)
        target = None
        while self._fallbacks:
            cand = self._fallbacks.pop()
            if cand.key not in self._faulted_keys:
                target = cand
                break
        if target is None:
            target = self._all_ref_generation()
            if target.key == failed.key:
                return False             # the reference plan itself faulted
        self.plan_generation += 1
        target.generation = self.plan_generation
        self._gen = target
        self._gen_healthy = True         # fallbacks already earned trust
        self.rollbacks += 1
        self.degraded = True
        self.last_fault = f"{op}: {err}"
        with self._plan_lock:
            pending = self._pending_plan
            if pending is not None and pending.key in self._faulted_keys:
                self._pending_plan = None
        rp = self._replanner
        if rp is not None and hasattr(rp, "on_plan_fault"):
            rp.on_plan_fault(failed.impl, self.last_fault)
        return True

    def canary_check(self, prepared: PlanGeneration, *,
                     reference: Optional[PlanGeneration] = None
                     ) -> tuple[bool, str]:
        """Validate ``prepared`` on a synthetic batch BEFORE it may serve.

        Runs the candidate's decode step against a throwaway template cache
        (zero tokens/positions — the same shapes ``_warm`` exercises, so
        this piggybacks on warmed traces) and checks that it (a) does not
        raise, (b) produces finite logits, and (c) matches the reference
        generation's logits bit-for-bit on the same inputs (the serving
        plan by default) — the engine's correctness contract says patterns
        are numerics-identical, so any bit difference means a miscompiled
        or wrong kernel.  Returns ``(ok, reason)``.  Thread-safe off the
        tick path; touches no serving state."""
        ref = reference if reference is not None else self._gen
        if self._warm_cache is None:
            self._warm_cache = F.init_cache(self.cfg, self.slots, self.ctx)
        toks = jnp.zeros((self.slots, 1), jnp.int32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        try:
            logits = prepared.decode(self.params, self._warm_cache,
                                     toks, pos)[0]
            cand = np.asarray(logits)
        except Exception as err:  # noqa: BLE001 — any failure mode rejects
            return False, f"canary decode raised: {err}"
        if not np.all(np.isfinite(cand)):
            return False, "canary decode produced non-finite logits"
        if ref is not None and prepared.key != ref.key:
            try:
                ref_logits = ref.decode(self.params, self._warm_cache,
                                        toks, pos)[0]
                ref_arr = np.asarray(ref_logits)
            except Exception as err:  # noqa: BLE001 — a faulting reference
                # cannot veto the candidate; the finite check already passed
                return True, f"reference decode raised ({err}); accepted"
            if cand.shape != ref_arr.shape or not np.array_equal(cand, ref_arr):
                return False, ("canary logits differ bitwise from the "
                               "serving plan")
        return True, "ok"

    @property
    def plan_key(self) -> tuple:
        """Canonical identity of the serving pattern (``search.impl_key``)."""
        return self._gen.key

    @property
    def plan_impl(self) -> Impl:
        """The merged offload pattern currently serving (a copy)."""
        return Impl(dict(self._gen.impl))

    @property
    def plan_seconds(self) -> Optional[float]:
        """The serving plan's measured seconds (None when never measured,
        e.g. the constructor-installed pattern)."""
        return self._gen.plan_seconds

    def attach_replanner(self, replanner) -> None:
        """Hook a ``serving.replan.Replanner``: its ``on_tick(engine)`` runs
        after every tick (trigger evaluation only — search and trace
        building happen off the tick path)."""
        self._replanner = replanner
        attach = getattr(replanner, "attach", None)
        if attach is not None:
            attach(self)

    # ------------------------------------------------------------------
    def _request_n_front(self, frontend) -> int:
        """Frontend tokens prepended to the decoder sequence (paligemma
        patch embeddings).  Whisper frames feed the encoder, not the
        decoder prefix."""
        return self.cfg.n_front if frontend is not None else 0

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               sampling: Optional[SamplingParams] = None,
               frontend: Optional[np.ndarray] = None) -> int:
        """Queue a request; returns its request id (int).

        * ``prompt`` (1-D int32 array, required) — the prompt tokens; must
          be non-empty.
        * ``max_new_tokens`` (int, 16) — decode budget; generation stops at
          EOS or after this many tokens.
        * ``sampling`` (SamplingParams, greedy) — ``temperature`` 0 =
          greedy, ``top_k`` 0 = full vocabulary.
        * ``frontend`` (array, None) — non-text prefix for multimodal archs
          (patch embeddings / audio frames).

        Raises ValueError if the request cannot fit the cache: prompt +
        frontend prefix + max_new_tokens must be <= ctx (admission control
        — an overflow would silently overwrite the last cache slot and
        corrupt the sequence)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(f"prompt must be a non-empty 1-D token array, "
                             f"got shape {prompt.shape}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if self.cfg.encoder_layers and frontend is None:
            raise ValueError(f"{self.cfg.name} is an encoder-decoder arch: "
                             "submit() requires `frontend` frames")
        n_front = self._request_n_front(frontend)
        need = prompt.size + n_front + max_new_tokens
        if need > self.ctx:
            raise ValueError(
                f"request needs {need} cache slots (prompt {prompt.size} + "
                f"frontend {n_front} + max_new_tokens {max_new_tokens}) "
                f"but ctx={self.ctx}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens,
                      sampling=sampling or GREEDY, frontend=frontend)
        req.submit_s = time.perf_counter()
        self.queue.append(req)
        return rid

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.active)

    # ------------------------------------------------------------------
    def _sample_tokens(self, logits, rids, steps, temps, top_ks,
                       greedy=None) -> np.ndarray:
        """The next token of each row of ``logits`` ([rows, 1, vocab]):
        ``greedy`` (the guard's argmax, already on the host) where no row
        samples, else drawn on the device."""
        if not np.any(np.asarray(temps) > 0.0):
            # all-greedy tick (the default workload): skip the per-slot
            # sort + categorical work entirely
            if greedy is None:
                greedy = self._check(logits)[0][:, 0]
            return np.asarray(greedy, np.int32)
        return np.asarray(self._sample(
            logits[:, -1], jnp.asarray(rids, jnp.int32),
            jnp.asarray(steps, jnp.int32), jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32)), np.int32)

    @staticmethod
    def _note_routed(sp, out) -> None:
        """Keep a counted step's routing counters on its span: the (token,
        expert) pairs routed to the experts held here, and the held experts
        they reached, summed over the expert layers."""
        if len(out) > 2 and out[2] is not None:
            sp.set(moe_pairs=int(out[2][0]), moe_experts_hit=int(out[2][1]))

    def _retire(self, slot: int) -> None:
        req = self.active[slot]
        req.done = True
        req.finish_s = time.perf_counter()
        req.frontend = None          # only needed for prefill; don't pin the
        self.finished.append(req)    # patch/frame array for the engine's life
        self.finished_total += 1
        self.active[slot] = None
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0

    def _admit(self) -> list[tuple[int, int]]:
        """Admit queued requests into every free slot (multiple per tick).
        Returns the (bucket, prompt_len) pairs admitted this tick — the
        windowed stats view aggregates them."""
        admitted: list[tuple[int, int]] = []
        for slot in range(self.slots):
            if self.active[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            req.slot_s = time.perf_counter()
            n_front = self._request_n_front(req.frontend)
            n = req.tokens.size
            bucket = F.prefill_bucket(n, self.ctx - n_front)
            req.bucket = bucket
            req.admit_tick = self.ticks
            req.plan_generation = self.plan_generation
            self.buckets_seen.add(bucket)
            padded = np.zeros(bucket, np.int32)
            padded[:n] = req.tokens
            batch = {"tokens": jnp.asarray(padded[None, :])}
            fe_sig = None
            if req.frontend is not None:
                key = "patches" if self.cfg.frontend == "siglip_stub" else "frames"
                fe = jnp.asarray(req.frontend[None])
                batch[key] = fe
                fe_sig = (key, tuple(fe.shape[1:]), str(fe.dtype))
            self._prefill_shapes.add((bucket, fe_sig))
            with obs.span("engine.prefill", rid=req.rid, bucket=bucket,
                          prompt_len=n) as sp:
                out = self._plan_call(
                    "prefill", self.params, batch, jnp.asarray(n, jnp.int32))
                logits, one_cache = out[:2]
                self._note_routed(sp, out)
            with obs.span("engine.insert", bytes=_tree_bytes(one_cache)):
                self.cache = cache_insert(self.cache, one_cache, slot)
            with obs.span("engine.sample"):
                first = int(self._sample_tokens(
                    logits, [req.rid], [0], [req.sampling.temperature],
                    [req.sampling.top_k], *out[3:4])[0])
            req.generated.append(first)
            req.admit_s = time.perf_counter()
            self.active[slot] = req
            self.pos[slot] = n + n_front
            self.last_tok[slot] = first
            self._rids[slot] = req.rid
            self._temps[slot] = req.sampling.temperature
            self._top_ks[slot] = req.sampling.top_k
            admitted.append((bucket, n))
            if len(req.generated) >= req.max_new_tokens:
                self._retire(slot)      # single-token request: done at prefill
        return admitted

    def _tick_decode(self) -> int:
        """One batched decode step; returns the number of slots decoded."""
        decoding = sum(r is not None for r in self.active)
        if not decoding:
            return 0
        with obs.span("engine.decode", slots=decoding) as sp:
            # fresh host copies: the step may read them after this tick
            # has moved on, and a step dispatched ahead is matched on them
            toks = self.last_tok[:, None].copy()
            pos = self.pos.copy()
            # commit the cache only AFTER the guard: a faulting plan's
            # outputs (logits AND cache) are discarded whole, so a rollback
            # retries the step from the exact pre-tick state
            out = self._plan_call("decode", self.params, self.cache, toks, pos)
            logits, self.cache = out[:2]
            self._note_routed(sp, out)
            steps = np.asarray([len(r.generated) if r is not None else 0
                                for r in self.active], np.int32)
            with obs.span("engine.sample"):
                nxt = self._sample_tokens(logits, self._rids, steps,
                                          self._temps, self._top_ks, *out[3:4])
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[slot] += 1
            req.generated.append(int(nxt[slot]))
            self.last_tok[slot] = nxt[slot]
            if len(req.generated) >= req.max_new_tokens:
                self._retire(slot)
        return decoding

    def step(self) -> None:
        """One engine tick: install any pending plan (the hot-swap point —
        strictly between ticks), admit, decode, record the tick event, then
        let an attached replanner evaluate its triggers."""
        self.ticks += 1
        with obs.span("engine.tick", tick=self.ticks):
            self._tick()

    def _tick(self) -> None:
        self._install_pending()
        admitted = []
        if self.queue and any(r is None for r in self.active):
            with obs.span("engine.admit"):
                admitted = self._admit()
        decoded = self._tick_decode()
        # the serving generation survived a full tick: it is now a trusted
        # rollback target for future swaps
        self._gen_healthy = True
        self._events.append({
            "tick": self.ticks,
            "active": sum(r is not None for r in self.active),
            "queue": len(self.queue),
            "decode_tokens": decoded,
            "admitted": admitted,
        })
        if self._replanner is not None:
            self._replanner.on_tick(self)

    def run_to_completion(self, max_ticks: int = 10_000, *,
                          raise_incomplete: bool = True) -> list[Request]:
        """Drive the engine until idle.  If ``max_ticks`` expires with work
        still queued/active, raises ServeIncompleteError (which carries the
        structured partial result) — or, with ``raise_incomplete=False``,
        returns the finished list as-is (callers can inspect ``engine.busy``)."""
        ticks = 0
        while self.busy and ticks < max_ticks:
            self.step()
            ticks += 1
        if self.busy and raise_incomplete:
            pending = sorted([r.rid for r in self.queue]
                             + [r.rid for r in self.active if r is not None])
            raise ServeIncompleteError(
                sorted(self.finished, key=lambda r: r.rid), pending, max_ticks)
        return sorted(self.finished, key=lambda r: r.rid)

    def drain_finished(self) -> list[Request]:
        """Return and clear the finished list.  Long-lived engines serving a
        continuous stream should drain periodically — ``finished`` otherwise
        grows with every request ever served (``stats()`` aggregates only
        what is currently retained; ``finished_total`` and the windowed view
        survive draining)."""
        done, self.finished = sorted(self.finished, key=lambda r: r.rid), []
        return done

    # ------------------------------------------------------------------
    def _counts(self) -> dict:
        """Conserved lifecycle accounting, present in both stats views:
        ``requests_submitted == requests_finished_total + requests_pending
        + requests_active`` at every tick boundary (the harness asserts it)."""
        active = sum(r is not None for r in self.active)
        return {
            "requests_submitted": self._next_rid,
            "requests_pending": len(self.queue),
            "requests_active": active,
            "requests_finished_total": self.finished_total,
            "ticks": self.ticks,
            "plan_generation": self.plan_generation,
            "swaps": self.swaps,
            "rollbacks": self.rollbacks,
            "degraded": self.degraded,
            "slot_occupancy": active / self.slots if self.slots else 0.0,
        }

    def stats(self, window: Optional[int] = None) -> dict:
        """Serving statistics, in two views.

        ``stats()`` aggregates lifecycle stats over *finished* requests:
        ``requests_finished``, ``generated_tokens``, ``ttft_s_mean`` /
        ``ttft_s_p50`` (time to first token), ``queue_wait_s_mean``,
        ``decode_tps_mean`` (per-request decode tokens/sec), plus compile
        telemetry: ``prefill_traces`` (one per (bucket, frontend) shape)
        and ``buckets`` (sorted bucket lengths seen).

        ``stats(window=N)`` is the windowed in-flight view over the last N
        ticks — what a drift detector must read, since the finished-only
        aggregate is blind to a long-running regime until its requests
        complete.  Keys: ``bucket_hist`` (admissions per prefill bucket,
        including still-running requests), ``prompt_len_mean``,
        ``occupancy_mean`` (active slots / slots per tick),
        ``queue_depth_mean``, ``decode_tokens``, ``decode_prefill_ratio``
        (decode steps per admission), ``requests_admitted``,
        ``ticks_observed``.

        Both views carry the conserved counters (``requests_submitted``,
        ``requests_pending``, ``requests_active``,
        ``requests_finished_total``) and the replanning telemetry
        (``ticks``, ``plan_generation``, ``swaps``, ``slot_occupancy``).
        The windowed view is the measurement-conditions feed for online
        replanning (``core.planner.conditions_from_stats``)."""
        if window is not None:
            return self._stats_windowed(int(window))
        done = self.finished
        ttfts = [r.ttft_s for r in done if r.ttft_s >= 0]
        waits = [r.queue_wait_s for r in done if r.slot_s >= 0]
        tps = [r.decode_tps for r in done if r.decode_tps > 0]
        return {
            "requests_finished": len(done),
            "generated_tokens": sum(len(r.generated) for r in done),
            "ttft_s_mean": float(np.mean(ttfts)) if ttfts else 0.0,
            "ttft_s_p50": float(np.median(ttfts)) if ttfts else 0.0,
            "queue_wait_s_mean": float(np.mean(waits)) if waits else 0.0,
            "decode_tps_mean": float(np.mean(tps)) if tps else 0.0,
            "prefill_traces": self.prefill_traces,
            "buckets": sorted(self.buckets_seen),
            **self._counts(),
        }

    def _stats_windowed(self, window: int) -> dict:
        lo = self.ticks - max(window, 0)
        events = [e for e in self._events if e["tick"] > lo]
        buckets: Counter = Counter()
        lens: list[int] = []
        occ: list[float] = []
        qdepth: list[int] = []
        decode_tokens = 0
        for e in events:
            occ.append(e["active"] / self.slots if self.slots else 0.0)
            qdepth.append(e["queue"])
            decode_tokens += e["decode_tokens"]
            for bucket, plen in e["admitted"]:
                buckets[bucket] += 1
                lens.append(plen)
        admitted = len(lens)
        return {
            "window": window,
            "ticks_observed": len(events),
            "requests_admitted": admitted,
            "bucket_hist": dict(sorted(buckets.items())),
            "prompt_len_mean": float(np.mean(lens)) if lens else 0.0,
            "occupancy_mean": float(np.mean(occ)) if occ else 0.0,
            "queue_depth_mean": float(np.mean(qdepth)) if qdepth else 0.0,
            "decode_tokens": decode_tokens,
            "decode_prefill_ratio": decode_tokens / max(admitted, 1),
            **self._counts(),
        }
