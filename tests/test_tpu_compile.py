"""Compiles of the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles each kernel at
its real size for one chip of a ``v5e:2x2`` topology that is described, not
attached, and refuses what the chip's compiler would refuse (tiling, VMEM,
unsupported primitives).  Every compiled program must hold a Mosaic kernel
(``tpu_custom_call``).  The topology is described inside a fixture, never at
import, so that every test worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import functools
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.paper_apps import MRIQ_FULL, TDFIR_FULL
from repro.kernels.fir import fir_filter_bank
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mriq import mriq_compute_q
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssm_scan import Factored, ssm_scan

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:          # the benchmark's configuration files
    sys.path.insert(0, str(ROOT))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_fir_compiles_at_hpec_set1(one_chip):
    m, k, n = TDFIR_FULL.n_banks, TDFIR_FULL.n_taps, TDFIR_FULL.n_samples
    _compile(lambda x, h: fir_filter_bank(x, h, interpret=False),
             [((m, n), jnp.complex64), ((m, k), jnp.complex64)], one_chip)


def test_fir_compiles_with_lane_blocked_banks(one_chip):
    # 256 banks: two 128-lane bank blocks, short sample blocks, unrolled taps
    _compile(lambda x, h: fir_filter_bank(x, h, block_n=128, tap_unroll=4,
                                          interpret=False),
             [((256, 4096), jnp.complex64), ((256, 128), jnp.complex64)],
             one_chip)


def test_mriq_compiles_at_parboil_large(one_chip):
    fx = ((MRIQ_FULL.num_x,), jnp.float32)
    fk = ((MRIQ_FULL.num_k,), jnp.float32)
    fn = lambda *a: mriq_compute_q(*a, interpret=False)  # noqa: E731
    _compile(fn, [fx, fx, fx, fk, fk, fk, fk], one_chip)
    # the VPU alone does the arithmetic, in float32: no contraction (an MXU
    # pass), no sin or cos of its own range reduction, no bfloat16
    jaxpr = jax.make_jaxpr(fn)(*[jax.ShapeDtypeStruct(*s)
                                 for s in [fx, fx, fx, fk, fk, fk, fk]])
    prims, dtypes = _walk(jaxpr.jaxpr)
    assert "pallas_call" in prims
    assert not prims & {"dot_general", "sin", "cos"}
    assert jnp.bfloat16 not in dtypes


def _walk(jaxpr):
    """Primitive names and value dtypes of ``jaxpr`` and every jaxpr
    nested in its equations' parameters (a Pallas kernel's among them)."""
    prims, dtypes = set(), set()

    def walk(j):
        for eqn in j.eqns:
            prims.add(eqn.primitive.name)
            dtypes.update(v.aval.dtype for v in eqn.outvars
                          if hasattr(v.aval, "dtype"))
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr)
    return prims, dtypes


def test_mriq_round_one_pattern_keeps_q_in_vmem(one_chip, monkeypatch):
    """The staged search's round 1 times ``compute_q: pallas`` beside the
    loop-faithful checksum, a scalar loop over Q.  Where Q lies in HBM that
    loop reads each element from HBM, the pattern measures slower than the
    all-ref baseline and the kernel is never selected: the loop's Q operands
    must be in VMEM (memory space S(1))."""
    from repro.apps import mriq
    from repro.core.regions import Impl

    monkeypatch.setattr(mriq, "mriq_compute_q", functools.partial(
        mriq_compute_q, interpret=False))
    prog = mriq.make_program(MRIQ_FULL, MRIQ_FULL)
    fx = ((MRIQ_FULL.num_x,), jnp.float32)
    fk = ((MRIQ_FULL.num_k,), jnp.float32)
    text = _compile(prog.build(Impl({"compute_q": "pallas"})),
                    [fx, fx, fx, fk, fk, fk, fk, fk], one_chip).as_text()
    loops = [line.split(" while(")[0] for line in text.splitlines()
             if " while(" in line and "mriq_check:ref/while" in line]
    assert loops
    q = re.compile(rf"f32\[{MRIQ_FULL.num_x}\]{{[^}}]*}}")
    layouts = [m for loop in loops for m in q.findall(loop)]
    assert len(layouts) >= 2 and all("S(1)" in s for s in layouts), layouts


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ssm_scan_compiles_at_falcon_mamba_widths(one_chip, dtype):
    cfg = get_config("falcon-mamba-7b")
    d, n, s = cfg.d_inner, cfg.ssm_state, 128
    _compile(lambda a, bx, c, h0: ssm_scan(a, bx, c, h0, interpret=False),
             [((1, s, d, n), dtype), ((1, s, d, n), dtype),
              ((1, s, n), dtype), ((1, d, n), jnp.float32)], one_chip)


def test_fused_ssm_scan_compiles_at_falcon_mamba_widths(one_chip):
    from repro.models.ssm import SEQ_TILES
    cfg = get_config("falcon-mamba-7b")
    d, n, s = cfg.d_inner, cfg.ssm_state, 512
    bc, tc = SEQ_TILES
    bf16, f32 = jnp.bfloat16, jnp.float32
    _compile(lambda dt, x, a, b, c, h0: ssm_scan(
                 Factored(dt, x, a, b), None, c, h0, block_c=bc,
                 time_chunk=tc, interpret=False),
             [((1, s, d), bf16), ((1, s, d), bf16), ((d, n), f32),
              ((1, s, n), bf16), ((1, s, n), bf16), ((1, d, n), f32)],
             one_chip)


def _prefill_hlo(impl, s, sharding):
    """Optimised HLO of the reduced falcon-mamba bucketed prefill (S=s)
    under ``impl``, compiled for the described chip."""
    from repro.core.regions import Impl
    from repro.models import factory as F

    cfg = get_config("falcon-mamba-7b").reduced()
    params = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding),
        jax.eval_shape(lambda: F.init_params(cfg, jax.random.PRNGKey(0))))
    step = F.make_bucketed_prefill_step(cfg, impl=Impl(impl), ctx=2 * s)
    args = ({"tokens": jax.ShapeDtypeStruct((1, s), jnp.int32,
                                            sharding=sharding)},
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding))
    text = jax.jit(step).lower(params, *args).compile().as_text()
    return cfg, text


def test_bucketed_prefill_never_builds_the_expanded_scan_inputs(
        one_chip, monkeypatch):
    """Under ``ssm_scan=seq`` the prefill holds the Mosaic kernel and no
    [1, S, d_inner, N] value; under ``ref`` (expanded by XLA) it holds
    them, so the pattern can see them."""
    import repro.kernels.ssm_scan as K

    # the chip's branch: this process's backend is the CPU
    monkeypatch.setattr(K, "resolve_interpret", lambda interpret: False)
    s = 40
    K.ssm_scan.clear_cache()
    try:
        cfg, seq = _prefill_hlo({"ssm_scan": "seq"}, s, one_chip)
    finally:
        K.ssm_scan.clear_cache()
    _, ref = _prefill_hlo({"ssm_scan": "ref"}, s, one_chip)
    expanded = re.compile(rf"\[1,{s},{cfg.d_inner},{cfg.ssm_state}\]")
    assert "tpu_custom_call" in seq
    assert not expanded.search(seq)
    assert expanded.search(ref)


def test_rglru_scan_compiles_at_recurrentgemma_width(one_chip):
    d = get_config("recurrentgemma-2b").rglru_d_rnn
    _compile(lambda a, b, h0: rglru_scan(a, b, h0, interpret=False),
             [((1, 256, d), jnp.bfloat16), ((1, 256, d), jnp.bfloat16),
              ((1, d), jnp.float32)], one_chip)


def test_flash_attention_compiles_at_head_dim_128(one_chip):
    cfg = get_config("mistral-nemo-12b")
    hd, s = cfg.head_dim, 4096
    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
             [((1, cfg.num_heads, s, hd), jnp.bfloat16),
              ((1, cfg.num_kv_heads, s, hd), jnp.bfloat16),
              ((1, cfg.num_kv_heads, s, hd), jnp.bfloat16)], one_chip)


@pytest.mark.parametrize("rows,tm", [(8192, 512), (160, 16)])
def test_grouped_expert_kernels_compile_at_jamba_widths(one_chip, rows, tm):
    """The dropless expert layer's two kernels at jamba2-mini's published
    widths (4,096 -> 14,336 -> 4,096), 8 held experts: a 4,096-token
    prefill's 8,192 pairs and a 16-slot decode step's rows.  The kernel is
    the custom call named ``moe_gmm`` that the benchmark reads."""
    from repro.kernels.moe_gmm import down, gate_up
    cfg = get_config("jamba2-mini")
    d, f, g = cfg.d_model, cfg.moe_d_ff, 8
    bf16, i32 = jnp.bfloat16, jnp.int32
    meta = [((rows // tm,), i32), ((1,), i32)]
    up = _compile(lambda x, wg, wu, t, n: gate_up(x, wg, wu, t, n, tm=tm,
                                                  interpret=False),
                  [((rows, d), bf16), ((g, d, f), bf16), ((g, d, f), bf16),
                   *meta], one_chip)
    dn = _compile(lambda h, wd, t, n: down(h, wd, t, n, tm=tm,
                                           interpret=False),
                  [((rows, f), bf16), ((g, f, d), bf16), *meta], one_chip)
    for compiled in (up, dn):
        assert re.search(r"%moe_gmm(\.\d+)? = .*custom-call",
                         compiled.as_text())


def test_jamba_cut_prefill_at_4096_compiles_within_the_chip(one_chip,
                                                            monkeypatch):
    """The benchmark's jamba2-mini cut (one period, 8 of 16 experts, the
    vocabulary slice) through its served plan's bucketed prefill at the
    largest bucket: compiled with both Mosaic kernels, and its weights,
    the 16-slot cache and the step's temporaries fit the chip's 16 GB."""
    import repro.kernels.moe_gmm as G
    import repro.kernels.ssm_scan as K
    from bench.harness import load_module
    from repro.core.regions import Impl
    from repro.models import factory as F

    for mod in (G, K):
        monkeypatch.setattr(mod, "resolve_interpret", lambda interpret: False)
    for fn in (G.gate_up, G.down, K.ssm_scan):
        fn.clear_cache()
    counts = load_module(ROOT / "bench/configs/jamba2-mini.py")
    conf = counts.CONF
    cfg = counts.model_config(conf)
    ctx = conf["serving"]["ctx"]

    def placed(tree):
        return jax.tree.map(lambda v: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=one_chip), tree)
    step = F.make_bucketed_prefill_step(cfg, Impl(conf["serving"]["plan"]),
                                        ctx=ctx, counts=True)
    try:
        compiled = jax.jit(step).lower(
            placed(F.abstract_params(cfg)),
            {"tokens": jax.ShapeDtypeStruct((1, 4096), jnp.int32,
                                            sharding=one_chip)},
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    finally:
        for fn in (G.gate_up, G.down, K.ssm_scan):
            fn.clear_cache()
    text = compiled.as_text()
    assert "moe_gmm" in text and "ssm_scan" in text
    need = (counts.weight_bytes(conf)
            + conf["serving"]["slots"] * counts.cache_bytes_per_slot(conf, ctx)
            + compiled.memory_analysis().temp_size_in_bytes)
    assert need < 16e9
