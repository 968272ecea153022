"""The program's spans (``repro.obs``): parents, the ring's bound, window
reads, the profiler's host plane; the spans the engine and the planner
open; the ``region:variant`` scope that ``regions.dispatch`` puts on the
lowered program."""
import dataclasses
import glob
import sys
import threading
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.planner import AutoOffloader, PlannerConfig
from repro.core.plan_cache import PlanCache
from repro.core.program import OffloadableProgram, Region
from repro.core.regions import Impl, dispatch, register_variant, variants
from repro.models import factory as F
from repro.serving.engine import ServeEngine

KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def empty_ring():
    obs.reset()
    yield
    obs.reset()


def _by_name(spans) -> dict:
    out: dict = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


# ---------------------------------------------------------------------------
# repro.obs
# ---------------------------------------------------------------------------
def test_parent_is_the_span_open_around_it():
    with obs.span("a") as _:
        with obs.span("b", k=1):
            with obs.span("c"):
                pass
        with obs.span("d"):
            pass
    got = _by_name(obs.spans())
    a, b, c, d = (got[n][0] for n in "abcd")
    assert a.parent is None
    assert b.parent == a.id and d.parent == a.id and c.parent == b.id
    assert b.attrs == {"k": 1}
    assert a.start <= b.start <= c.start <= c.end <= b.end <= d.start <= a.end


def test_parent_does_not_cross_threads():
    """A span opened on another thread while one is open here is a root
    of its own thread; its children nest under it there."""
    def worker():
        with obs.span("w.outer"):
            with obs.span("w.inner"):
                pass

    with obs.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    got = _by_name(obs.spans())
    outer, inner = got["w.outer"][0], got["w.inner"][0]
    assert outer.parent is None
    assert inner.parent == outer.id
    assert got["main"][0].parent is None


def test_ring_keeps_the_newest_and_counts_what_it_dropped():
    ring = obs.Ring(capacity=3)
    for i in range(5):
        with ring.span(f"s{i}"):
            pass
    assert [s.name for s in ring.spans()] == ["s2", "s3", "s4"]
    assert ring.dropped() == 2
    ring.reset()
    assert ring.spans() == [] and ring.dropped() == 0
    assert obs.dropped() == 0          # the process's ring is another


def test_window_read_keeps_spans_that_start_in_it():
    with obs.span("before"):
        pass
    t0 = time.perf_counter()
    with obs.span("straddles"):
        time.sleep(0.01)
        t1 = time.perf_counter()
        with obs.span("inside"):
            pass
    with obs.span("after"):
        pass
    assert [s.name for s in obs.spans(t0, t1)] == ["straddles"]
    assert [s.name for s in obs.spans(t0)] == ["straddles", "inside", "after"]
    assert [s.name for s in obs.spans(until=t0)] == ["before"]


def test_span_records_when_the_block_raises():
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    with obs.span("next"):
        pass
    got = _by_name(obs.spans())
    assert got["next"][0].parent is None      # the stack was unwound


def test_late_attributes_reach_the_record():
    with obs.span("p", program="x") as sp:
        sp.set(from_cache=True)
    assert obs.spans()[0].attrs == {"program": "x", "from_cache": True}


def test_span_shows_in_the_profiler_host_plane(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("obs.test_span", rid=7):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    host = [p for p in data.planes if p.name == "/host:CPU"][0]
    events = [ev for line in host.lines for ev in line.events
              if ev.name == "obs.test_span"]
    assert len(events) == 1
    assert dict(events[0].stats)["rid"] == 7


def test_threads_lose_no_record_and_share_no_id():
    """More threads than cores, a short switch interval: every span is
    recorded once, with an id of its own and its own thread's parent."""
    per, n_threads = 300, 16
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def worker(k):
            for _ in range(per):
                with obs.span(f"t{k}"):
                    with obs.span(f"t{k}.child"):
                        pass
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    spans = obs.spans()
    assert len(spans) == 2 * per * n_threads
    assert len({s.id for s in spans}) == len(spans)
    names = {s.id: s.name for s in spans}
    for s in spans:
        if s.name.endswith(".child"):
            assert names[s.parent] == s.name[:-len(".child")]


# ---------------------------------------------------------------------------
# engine spans
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def ssm():
    cfg = dataclasses.replace(get_config("falcon-mamba-7b").reduced(),
                              dtype="float32")
    return cfg, F.init_params(cfg, KEY)


def _greedy_reference(cfg, params, prompt, n_new, ctx):
    """Greedy tokens from full forwards over prompt + tokens so far (no
    cache, no engine)."""
    prefill = jax.jit(F.make_prefill_step(cfg, ctx=ctx))
    seq = list(prompt)
    for _ in range(n_new):
        logits, _ = prefill(params, {"tokens": jnp.asarray([seq], jnp.int32)})
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def test_engine_spans_and_tokens(ssm):
    cfg, params = ssm
    ctx = 32
    prompts = [np.asarray(jax.random.randint(jax.random.fold_in(KEY, i),
                                             (n,), 0, cfg.vocab_size), np.int32)
               for i, n in enumerate((5, 9, 3))]
    eng = ServeEngine(cfg, params, slots=2, ctx=ctx)
    rids = [eng.submit(p, 3) for p in prompts]
    done = eng.run_to_completion()
    spans = obs.spans()
    got = _by_name(spans)
    by_id = {s.id: s for s in spans}

    # one prefill per request, with its rid, bucket and prompt length
    pre = sorted(got["engine.prefill"], key=lambda s: s.attrs["rid"])
    assert [s.attrs for s in pre] == [
        {"rid": r, "bucket": F.prefill_bucket(len(p), ctx), "prompt_len": len(p)}
        for r, p in zip(rids, prompts)]
    # one tick span per step, numbered; admit only on ticks that admitted
    assert [s.attrs["tick"] for s in got["engine.tick"]] == \
        list(range(1, eng.ticks + 1))
    admitting = {e["tick"] for e in eng._events if e["admitted"]}
    assert {by_id[s.parent].attrs["tick"] for s in got["engine.admit"]} == \
        admitting
    assert "engine.install" not in got
    # every wait lies under a prefill or a decode, and a guard follows it
    for s in got["engine.wait"]:
        assert by_id[s.parent].name in ("engine.prefill", "engine.decode")
    assert len(got["engine.guard"]) == len(got["engine.wait"])
    for s in got["engine.decode"]:
        assert 1 <= s.attrs["slots"] <= 2
    # tokens: those of a plain greedy forward
    for req, p in zip(done, prompts):
        assert req.generated == _greedy_reference(cfg, params, p, 3, ctx)


def test_engine_install_span_only_when_a_plan_is_pending(ssm):
    cfg, params = ssm
    eng = ServeEngine(cfg, params, slots=1, ctx=32)
    eng.submit(np.arange(4, dtype=np.int32), 3)
    eng.step()
    eng.offer_plan(eng.prepare_plan(Impl({"ssm_scan": "ref"}), warm=False))
    eng.step()
    eng.step()
    installs = _by_name(obs.spans()).get("engine.install", [])
    assert len(installs) == 1 and eng.swaps == 1


# ---------------------------------------------------------------------------
# planner spans
# ---------------------------------------------------------------------------
_tag = [0]


def _tiny_program():
    names = []
    for i in range(2):
        name = f"obs_{_tag[0]}_{i}"
        _tag[0] += 1
        names.append(name)
        register_variant(name, "ref")(lambda x: jnp.sin(x) * 2.0)
        register_variant(name, "offload")(lambda x: jnp.sin(x) * 2.0)

    def build(impl):
        def run(x):
            for nm in names:
                x = dispatch(nm, impl, x)
            return x
        return run

    abstract = (jax.ShapeDtypeStruct((128, 128), jnp.float32),)
    return OffloadableProgram(
        name="obs_tiny", regions=[Region(n, variants(n)["ref"], abstract)
                                  for n in names],
        build=build, sample_inputs=lambda k: (jax.random.normal(k, (128, 128)),),
        source_loop_count=2)


STEPS = ["planner.analyze", "planner.intensity", "planner.resources",
         "planner.search", "planner.select"]


@pytest.mark.parametrize("workers", [1, 2])
def test_planner_spans(workers, tmp_path):
    prog = _tiny_program()
    cfg = PlannerConfig(reps=2, warmup=0, verify_workers=workers)
    cache = PlanCache(tmp_path / "plans.json")
    rep = AutoOffloader(cfg).plan(prog, cache=cache)
    spans = obs.spans()
    got = _by_name(spans)
    plan = got["planner.plan"][0]
    assert plan.attrs == {"program": "obs_tiny", "from_cache": False}
    steps = [s for s in spans if s.parent == plan.id]
    assert [s.name for s in steps] == STEPS
    # Step 3 lowers every (region, variant) pair; Step 4 lowers, compiles
    # and times the baseline and every measured pattern
    executor = next(t for t in rep.search_trace
                    if t.get("stage") == "verification executor")
    compiled = executor["compiled"] if workers > 1 else len(rep.measurements)
    assert len(got["planner.compile"]) == 1 + compiled
    measured = Counter(s.attrs["pattern"] for s in got["planner.measure"])
    assert measured == Counter(["all-ref"] + [m.pattern for m in rep.measurements])
    assert all(s.attrs["reps"] == 2 for s in got["planner.measure"])
    resources = steps[2]
    step3 = sorted(s.attrs["pattern"] for s in got["planner.lower"]
                   if resources.start <= s.start <= resources.end)
    assert step3 == sorted(f"{r.name}=offload" for r in prog.regions)
    for s in got["planner.lower"] + got["planner.compile"]:
        assert plan.start <= s.start and s.end <= plan.end

    obs.reset()
    again = AutoOffloader(cfg).plan(prog, cache=cache)
    assert again.from_cache
    got = _by_name(obs.spans())
    assert got["planner.plan"][0].attrs["from_cache"] is True
    assert not set(STEPS) & set(got)


# ---------------------------------------------------------------------------
# region scopes
# ---------------------------------------------------------------------------
def test_dispatch_names_the_region_and_variant_in_locations():
    name = f"obs_scope_{_tag[0]}"
    _tag[0] += 1
    register_variant(name, "ref")(lambda x: jnp.sin(x) * 2.0)
    register_variant(name, "offload")(lambda x: jnp.cos(x) + 1.0)
    x = jnp.ones((8, 128))
    scoped = jax.jit(lambda x: dispatch(name, Impl({name: "offload"}), x)).lower(x)
    plain = jax.jit(lambda x: variants(name)["offload"](x)).lower(x)
    assert f"{name}:offload/cos" in scoped.as_text(debug_info=True)
    assert f"{name}:offload" not in plain.as_text(debug_info=True)
    # only the locations differ
    assert scoped.as_text() == plain.as_text()
