"""Planner behaviour tests — the paper's §3.3 pipeline invariants, plus
hypothesis property tests over synthetic programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.apps import mriq, tdfir
from repro.core.intensity import analyze_region, count_loops
from repro.core.planner import AutoOffloader, PlannerConfig
from repro.core.program import OffloadableProgram, Region
from repro.core.regions import Impl, dispatch, register_variant, variants
from repro.core.resources import VMEM_BUDGET, precompile


# ---------------------------------------------------------------------------
# Arithmetic-intensity analysis
# ---------------------------------------------------------------------------
def test_ai_counts_matmul_flops_exactly():
    f = lambda a, b: a @ b
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)   # lane-aligned dims
    ana = analyze_region(f, x, w)
    assert ana.flops == 2 * 64 * 128 * 128
    assert ana.boundary_bytes == 4 * (64 * 128 + 128 * 128 + 64 * 128)


def test_ai_counts_flops_inside_a_nested_jit():
    # an einsum (and silu) traced inside a jit appears as one `jit` eqn
    # whose sub-jaxpr holds the dot: its FLOPs must still be counted
    inner = jax.jit(lambda a, b: jax.nn.silu(jnp.einsum("ij,jk->ik", a, b)))
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ana = analyze_region(lambda a, b: inner(a, b), x, w)
    assert ana.flops >= 2 * 64 * 128 * 128
    assert "jit" not in ana.unclassified


def test_ai_multiplies_scan_trip_count():
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)   # lane-aligned
    ana = analyze_region(f, x)
    assert ana.flops == 7 * 2 * 128 * 128 * 128
    assert ana.loop_count == 1


def test_count_loops_nested():
    def f(x):
        def outer(c, _):
            def inner(d, _):
                return d + 1.0, None
            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, None
        y, _ = jax.lax.scan(outer, x, None, length=2)
        return y
    assert count_loops(f, jax.ShapeDtypeStruct((4,), jnp.float32)) == 2


def test_alignment_penalty_orders_misaligned_below_aligned():
    f = lambda a, b: a @ b
    aligned = analyze_region(f, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                             jax.ShapeDtypeStruct((128, 128), jnp.float32))
    tiny = analyze_region(f, jax.ShapeDtypeStruct((128, 7), jnp.float32),
                          jax.ShapeDtypeStruct((7, 128), jnp.float32))
    # per-flop discount of the RANKING metric: weighted_flops over true flops
    assert (tiny.weighted_flops / (2 * 128 * 7 * 128)
            < aligned.weighted_flops / (2 * 128**3))
    # raw counts stay undiscounted (roofline projections need true op counts)
    assert tiny.flops == 2 * 128 * 7 * 128
    assert tiny.alignment < 1.0 == aligned.alignment


def test_alignment_penalty_applies_to_transcendentals():
    """Regression: the penalty must discount the whole weighted total, not
    just flops — transcendental-heavy misaligned regions were under-ranked."""
    f = lambda a: jnp.sin(a)
    mis = analyze_region(f, jax.ShapeDtypeStruct((128, 7), jnp.float32))
    ali = analyze_region(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    assert mis.transcendentals == 128 * 7          # raw count preserved
    per_elem_mis = mis.weighted_flops / (128 * 7)
    per_elem_ali = ali.weighted_flops / (128 * 128)
    assert per_elem_mis < per_elem_ali


# ---------------------------------------------------------------------------
# Resource estimation
# ---------------------------------------------------------------------------
def test_precompile_reports_vmem_and_ops():
    f = lambda a, b: jax.nn.relu(a @ b)
    args = (jax.ShapeDtypeStruct((256, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 256), jnp.float32))
    est = precompile("dummy_region", "offload", f, args)
    assert est.lower_ok
    assert est.hlo_ops > 0
    assert 0 < est.vmem_bytes <= 8 * VMEM_BUDGET


def test_precompile_failure_is_recorded_not_raised():
    def bad(a):
        raise ValueError("no lowering for you")
    est = precompile("dummy", "offload", bad,
                     (jax.ShapeDtypeStruct((4,), jnp.float32),))
    assert not est.lower_ok
    assert est.resource_fraction == float("inf")


# ---------------------------------------------------------------------------
# Planner pipeline invariants on the paper apps
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("make", [tdfir.make_program, mriq.make_program])
def test_planner_respects_budgets(make):
    prog = make()
    cfg = PlannerConfig(reps=1, warmup=0)
    rep = AutoOffloader(cfg).plan(prog, jax.random.PRNGKey(0))
    assert len(rep.ai_selected) <= cfg.top_a
    assert len(rep.eff_selected) <= cfg.top_c
    assert len(rep.measurements) <= cfg.max_measurements
    assert rep.speedup >= 1.0          # never selects a slowdown
    assert rep.baseline is not None and rep.baseline.ok


def test_planner_ranks_hot_loop_first():
    rep = AutoOffloader(PlannerConfig(reps=1, warmup=0)).plan(
        tdfir.make_program(), jax.random.PRNGKey(0))
    assert rep.ai_selected[0] == "fir_bank"
    rep2 = AutoOffloader(PlannerConfig(reps=1, warmup=0)).plan(
        mriq.make_program(), jax.random.PRNGKey(0))
    assert rep2.ai_selected[0] == "compute_q"


def test_offload_variants_are_numerically_equivalent():
    """Every measured pattern must compute the same function."""
    key = jax.random.PRNGKey(1)
    for make in (tdfir.make_program, mriq.make_program):
        prog = make()
        sample = prog.sample_inputs(key)
        base = jax.jit(prog.build(Impl()))(*sample)
        for r in prog.regions:
            out = jax.jit(prog.build(Impl({r.name: "offload"})))(*sample)
            for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(out)):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Property tests: synthetic programs
# ---------------------------------------------------------------------------
_counter = [0]


def _make_synthetic_program(n_regions: int, fracs: list[float]):
    """Synthetic program with controllable per-region resource fractions."""
    names = []
    for i, frac in enumerate(fracs[:n_regions]):
        name = f"synth_{_counter[0]}_{i}"
        _counter[0] += 1
        names.append(name)
        register_variant(name, "ref")(lambda x: x * 2.0 + 1.0)
        register_variant(name, "offload")(lambda x: x * 2.0 + 1.0)

    def build(impl):
        def run(x):
            for nm in names:
                x = dispatch(nm, impl, x)
            return x
        return run

    regions = [Region(nm, variants(nm)["ref"],
                      (jax.ShapeDtypeStruct((128, 128), jnp.float32),),
                      deploy_variant="offload")
               for nm in names]
    return OffloadableProgram(
        name="synthetic", regions=regions, build=build,
        sample_inputs=lambda k: (jax.random.normal(k, (128, 128)),),
        source_loop_count=n_regions)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 6), a=st.integers(1, 5), c=st.integers(1, 3),
       d=st.integers(1, 4))
def test_planner_budget_properties(n, a, c, d):
    prog = _make_synthetic_program(n, [0.01] * n)
    cfg = PlannerConfig(top_a=a, top_c=c, max_measurements=d, reps=1, warmup=0)
    rep = AutoOffloader(cfg).plan(prog, jax.random.PRNGKey(0))
    assert len(rep.ai_selected) <= min(a, n)
    assert len(rep.eff_selected) <= min(c, a, n)
    assert len(rep.measurements) <= d
    assert rep.speedup >= 1.0


@settings(max_examples=6, deadline=None)
@given(vals=st.lists(st.floats(0.4, 0.9), min_size=2, max_size=3))
def test_combinations_respect_resource_cap(vals):
    """Combinations whose summed vmem fraction exceeds the cap are skipped."""
    from repro.core import resources as RES

    prog = _make_synthetic_program(len(vals), vals)
    for r, frac in zip(prog.regions, vals):
        RES.register_vmem_estimator(r.name, "offload")(
            (lambda fr: lambda *a: fr * RES.VMEM_BUDGET)(frac))
    cfg = PlannerConfig(top_a=5, top_c=3, max_measurements=10, reps=1, warmup=0,
                        resource_cap=1.0)
    rep = AutoOffloader(cfg).plan(prog, jax.random.PRNGKey(0))
    for m in rep.measurements:
        if m.pattern == "all-ref" or "+" not in m.pattern:
            continue
        combo = [kv.split("=")[0] for kv in m.pattern.split("+")]
        total = sum(v for r, v in zip([r.name for r in prog.regions], vals)
                    if r in combo)
        assert total <= cfg.resource_cap + 1e-9


# ---------------------------------------------------------------------------
# Impl / regions plumbing
# ---------------------------------------------------------------------------
def test_impl_describe_roundtrip():
    impl = Impl({"a": "offload", "b": "pallas"})
    assert impl.describe() == "a=offload+b=pallas"
    assert Impl().describe() == "all-ref"


def test_dispatch_unknown_variant_raises():
    with pytest.raises(KeyError):
        dispatch("attn_core", Impl({"attn_core": "nope"}), None, None, None)


# ---------------------------------------------------------------------------
# Mixed-destination pattern search (arXiv 2011.12431 extension)
# ---------------------------------------------------------------------------
def _slow_ref(x):
    """Loop-faithful stand-in: 400 sequential transcendental sweeps, so any
    vectorized variant wins by orders of magnitude (keeps timing asserts
    robust on a loaded CI box)."""
    def body(i, acc):
        return acc + 1e-6 * jnp.sin(acc * 1e-3)
    return jax.lax.fori_loop(0, 400, body, x)


def _mixed_program(tag: str):
    """Two regions; region a has TWO offload destinations (fast > offload by
    pinned resource fractions), region b has one."""
    from repro.core import resources as RES

    a, b = f"{tag}_a", f"{tag}_b"
    register_variant(a, "ref")(_slow_ref)
    register_variant(a, "offload")(lambda x: x * 1.0000001)
    register_variant(a, "fast")(lambda x: x + 1e-7)
    register_variant(b, "ref")(_slow_ref)
    register_variant(b, "offload")(lambda x: x - 1e-7)
    RES.register_vmem_estimator(a, "fast")(lambda *ar: 0.001 * RES.VMEM_BUDGET)
    RES.register_vmem_estimator(a, "offload")(lambda *ar: 0.5 * RES.VMEM_BUDGET)
    RES.register_vmem_estimator(b, "offload")(lambda *ar: 0.01 * RES.VMEM_BUDGET)

    def build(impl):
        def run(x):
            x = dispatch(a, impl, x)
            return dispatch(b, impl, x)
        return run

    abstract = (jax.ShapeDtypeStruct((128, 128), jnp.float32),)
    regions = [Region(a, variants(a)["ref"], abstract),
               Region(b, variants(b)["ref"], abstract)]
    prog = OffloadableProgram(
        name=f"mixed_{tag}", regions=regions, build=build,
        sample_inputs=lambda k: (jax.random.normal(k, (128, 128)),),
        source_loop_count=2)
    return prog, a, b


def test_mixed_destination_pattern_measured_and_selected():
    name = f"mix_{_counter[0]}"
    _counter[0] += 1
    prog, a, b = _mixed_program(name)
    cfg = PlannerConfig(top_a=5, top_c=3, max_measurements=6, reps=3, warmup=0)
    rep = AutoOffloader(cfg).plan(prog, jax.random.PRNGKey(0))

    # Step 3 ranked every (region, variant) destination, best first
    assert (a, "fast") in rep.eff_pairs and (a, "offload") in rep.eff_pairs
    assert rep.eff_pairs.index((a, "fast")) < rep.eff_pairs.index((a, "offload"))

    # round 1 measured each region's best destination singly
    mappings = [m.mapping() for m in rep.measurements]
    assert {a: "fast"} in mappings
    assert {b: "offload"} in mappings
    # round 2 measured a MIXED cross-region combination (variants differ)
    assert {a: "fast", b: "offload"} in mappings
    # round 3 spent leftover budget on the runner-up destination
    assert {a: "offload"} in mappings
    # both refs are slow loops: the mixed combination wins outright
    assert rep.best_pattern == {a: "fast", b: "offload"}
    assert rep.speedup > 1.0


def test_best_pattern_is_structured_mapping_of_winner():
    """best_pattern must equal the winning Measurement's own Impl — no
    string re-parsing (regression for the pattern.split('+') round-trip)."""
    name = f"mixw_{_counter[0]}"
    _counter[0] += 1
    prog, a, b = _mixed_program(name)
    rep = AutoOffloader(PlannerConfig(max_measurements=6, reps=3,
                                      warmup=0)).plan(prog, jax.random.PRNGKey(0))
    ok = [m for m in rep.measurements if m.ok]
    best = min(ok, key=lambda m: m.run_seconds)
    if best.run_seconds < rep.baseline.run_seconds:
        assert rep.best_pattern == best.mapping()
    else:
        assert rep.best_pattern == {}
    # every measurement carries its structured pattern end-to-end
    for m in rep.measurements:
        assert m.impl is not None
        assert m.pattern == Impl(m.impl).describe()


def test_failed_baseline_blocks_round2_combinations():
    """Regression: a failed baseline measures as run_seconds=inf, which used
    to promote EVERY ok round-1 measurement to 'winner' — round 2 then
    measured cross-region combinations against a meaningless reference.
    With the guard on report.baseline.ok, no combination is measured, the
    fastest working single pattern is still selected, and no speedup is
    claimed."""
    tag = f"nobase_{_counter[0]}"
    _counter[0] += 1
    a, b = f"{tag}_a", f"{tag}_b"
    for nm in (a, b):
        register_variant(nm, "ref")(lambda x: x * 2.0 + 1.0)
        register_variant(nm, "offload")(lambda x: x * 2.0 + 1.0)

    def build(impl):
        if not impl:                # the all-ref baseline build is broken
            def boom(x):
                raise RuntimeError("baseline build broken")
            return boom

        def run(x):
            x = dispatch(a, impl, x)
            return dispatch(b, impl, x)
        return run

    abstract = (jax.ShapeDtypeStruct((128, 128), jnp.float32),)
    prog = OffloadableProgram(
        name=tag, regions=[Region(a, lambda x: x * 2.0 + 1.0, abstract),
                           Region(b, lambda x: x * 2.0 + 1.0, abstract)],
        build=build,
        sample_inputs=lambda k: (jax.random.normal(k, (128, 128)),),
        source_loop_count=2)
    rep = AutoOffloader(PlannerConfig(reps=1, warmup=0,
                                      max_measurements=6)).plan(
        prog, jax.random.PRNGKey(0))
    assert rep.baseline is not None and not rep.baseline.ok
    # both singles measured ok, but NO cross-region combination was built
    ok_single = [m for m in rep.measurements if m.ok]
    assert len(ok_single) >= 2
    assert all(len(m.mapping()) <= 1 for m in rep.measurements)
    # the fastest working pattern is still selected, with no speedup claim
    assert len(rep.best_pattern) == 1
    assert rep.speedup == 1.0
    assert not AutoOffloader._sound(rep)        # and it must never be cached


def test_failing_variant_is_never_selected():
    """A variant whose lowering fails (lower_ok=False) must be excluded
    from ranking, measurement, and selection."""
    name = f"fail_{_counter[0]}"
    _counter[0] += 1
    register_variant(name, "ref")(_slow_ref)
    register_variant(name, "offload")(lambda x: x * 2.0)

    @register_variant(name, "pallas")
    def _bad(x):
        raise RuntimeError("no pallas lowering on this backend")

    def build(impl):
        def run(x):
            return dispatch(name, impl, x)
        return run

    prog = OffloadableProgram(
        name="failvar",
        regions=[Region(name, variants(name)["ref"],
                        (jax.ShapeDtypeStruct((128, 128), jnp.float32),))],
        build=build,
        sample_inputs=lambda k: (jax.random.normal(k, (128, 128)),),
        source_loop_count=1)
    rep = AutoOffloader(PlannerConfig(reps=1, warmup=0,
                                      max_measurements=4)).plan(
        prog, jax.random.PRNGKey(0))
    assert (name, "pallas") not in rep.eff_pairs
    assert all(m.mapping().get(name) != "pallas" for m in rep.measurements)
    assert rep.best_pattern.get(name) != "pallas"
    cand = next(c for c in rep.candidates if c.region == name)
    assert not cand.variant_estimates["pallas"].lower_ok
    assert cand.variant_estimates["offload"].lower_ok


# ---------------------------------------------------------------------------
# Beyond-paper: block-level planning over an assigned arch (paper §6 future
# work: offload of larger functional blocks)
# ---------------------------------------------------------------------------
def test_block_level_planning_on_ssm_arch():
    from repro.models.offload_program import make_lm_program

    prog = make_lm_program("falcon-mamba-7b", batch=1, seq=32)
    rep = AutoOffloader(PlannerConfig(reps=1, warmup=0)).plan(
        prog, jax.random.PRNGKey(0))
    # the SSM scan is the arch's hot region: it tops the AI ranking and every
    # registered destination is precompiled in the mixed-destination Step 3
    assert rep.ai_selected[0] == "ssm_scan"
    cand = next(c for c in rep.candidates if c.region == "ssm_scan")
    assert set(cand.variant_estimates) >= {"offload", "seq", "pallas"}
    if rep.eff_selected:
        # some destination fits this backend: the hot region leads survivors
        assert "ssm_scan" in rep.eff_selected
    else:
        # no destination is placeable here (the Pallas kernel cannot lower on
        # this container and the XLA variants' chunk working set exceeds the
        # VMEM cap at full shapes): the planner must fall back to all-ref
        # rather than select an overweight or unloadable variant
        assert all(not est.lower_ok
                   or est.resource_fraction > PlannerConfig().resource_cap
                   for est in cand.variant_estimates.values())
        assert rep.best_pattern == {}
        assert rep.speedup == 1.0
    assert rep.baseline is not None and rep.baseline.ok
