"""jamba2-mini at a small size on the CPU, on seeded random weights:
served through ``ServeEngine`` against the plain reference
(``bench/references/jamba2-mini.py``), the dropless expert region's two
variants against each other, the held experts' shares against the uncut
layer, routing that looks at one token alone, and falcon-mamba's block
unchanged with the new fields off."""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.regions import Impl, dispatch
from repro.models import factory as F

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench.harness import load_module  # noqa: E402

COUNTS = load_module(ROOT / "bench/configs/jamba2-mini.py")
REF = load_module(ROOT / "bench/references/jamba2-mini.py")

# one 8-layer period at d_model 64, 16 experts routed with 8 held, vocab 256
SMALL = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "mamba_d_state": 16, "mamba_dt_rank": 8, "vocab_size": 256,
         "num_experts": 8, "router_experts": 16, "experts_first": 0,
         "dtype": "float32",
         "serving": {"slots": 3, "ctx": 96,
                     "plan": {"ssm_scan": "seq", "moe_dropless": "gmm"}}}


def small_conf(**changes) -> dict:
    conf = json.loads((ROOT / "bench/configs/jamba2-mini.json").read_text())
    conf.update(SMALL, **changes)
    return conf


@functools.lru_cache(maxsize=None)
def _model(first: int = 0, held: int = 8, dtype: str = "float32"):
    conf = small_conf(experts_first=first, num_experts=held, dtype=dtype)
    cfg = COUNTS.model_config(conf)
    return conf, cfg, COUNTS.init_weights(cfg, jax.random.PRNGKey(7))


def _serve(conf, cfg, weights, prompts, new_tokens):
    """Serve ``prompts`` through the engine (bucketed prefill, the cache
    insert, batched decode over its slots); returns, per request, its
    tokens and the logits each was sampled from."""
    from repro.serving.engine import ServeEngine
    serving = conf["serving"]
    engine = ServeEngine(cfg, weights, slots=serving["slots"],
                         ctx=serving["ctx"], impl=Impl(serving["plan"]))
    seen: dict = {}
    sample = engine._sample_tokens

    def record(logits, rids, steps, temps, top_ks, *greedy):
        out = sample(logits, rids, steps, temps, top_ks, *greedy)
        for row, (rid, step) in enumerate(zip(rids, steps)):
            if rid in pending and step == len(seen.get(int(rid), [])):
                seen.setdefault(int(rid), []).append(
                    np.asarray(logits[row, -1]))
        return out

    engine._sample_tokens = record
    pending = {engine.submit(p, new_tokens) for p in prompts}
    done = engine.run_to_completion()
    return [(np.asarray(r.generated, np.int32), np.stack(seen[r.rid]))
            for r in done]


def _logit_error(got, want) -> float:
    """Largest logit difference over the spread of the reference's logits."""
    return float(np.max(np.abs(got - want)) / np.ptp(want))


def test_served_logits_match_the_reference(monkeypatch):
    """Prefill then decoding through the engine's cache (two prompt
    buckets, three slots, requests admitted while others decode) against
    the reference's full forward.  The program runs in float32 here, but
    its output head, as for every model, takes its input rounded to
    bfloat16 (``layers.unembed``): that rounding alone moves the logits by
    up to 1.1e-3 of their spread, so the reference is given the same
    rounding of the head's input.  Then the two agree to 1e-4 of the
    spread (measured: exactly, the body's float32 rounding stays below the
    head input's bfloat16 step), and the reference with bfloat16 products,
    which lies 2.9e-3 or more away, fails."""
    conf, cfg, w = _model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, conf["vocab_size"], n).astype(np.int32)
               for n in (5, 17, 30, 9)]
    served = _serve(conf, cfg, w, prompts, 6)
    seqs = [(p, toks) for p, (toks, _) in zip(prompts, served)]
    norm = REF._final_norm
    monkeypatch.setattr(REF, "_final_norm", lambda x, ln, eps: norm(
        x, ln, eps=eps).astype(jnp.bfloat16).astype(jnp.float32))
    kw = dict(length=48, served_max=6)
    ref = REF.served_logits(w, conf, seqs, **kw)
    low = REF.served_logits(w, conf, seqs, precision="bfloat16", **kw)
    for (_, got), want, rough in zip(served, ref, low):
        assert _logit_error(got, want) < 1e-4
        assert _logit_error(rough, want) > 1e-3
    assert float(REF.served_gaps(ref, seqs).max()) < 1e-4


def test_reference_control_is_far_from_the_reference():
    """The float8 control, the chip check's step below bfloat16, lies far
    outside the served tokens' reach at this size too."""
    conf, cfg, w = _model()
    rng = np.random.default_rng(1)
    seqs = [(rng.integers(0, 256, 12).astype(np.int32),
             rng.integers(0, 256, 4).astype(np.int32))]
    ref = REF.served_logits(w, conf, seqs, length=16, served_max=4)
    low = REF.served_logits(w, conf, seqs, length=16, served_max=4,
                            **REF.CONTROL)
    assert _logit_error(low[0], ref[0]) > 1e-2


def _expert_params(held: int, first: int, d=64, f=128, routed=16, key=3):
    """Weights of one expert layer: the router over ``routed`` experts and
    the stacks of experts [first, first + held) of a layer of ``routed``."""
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    full = {"router": jax.random.normal(ks[0], (d, routed)) / d ** 0.5,
            "w_gate": jax.random.normal(ks[1], (routed, d, f)) / d ** 0.5,
            "w_up": jax.random.normal(ks[2], (routed, d, f)) / d ** 0.5,
            "w_down": jax.random.normal(ks[3], (routed, f, d)) / f ** 0.5}
    return {k: (v if k == "router" else v[first:first + held])
            for k, v in full.items()}


def _region(variant, x, params, first=0, k=2, valid=None):
    return dispatch("moe_dropless", Impl({"moe_dropless": variant}), x,
                    params, k=k, first=first, renormalize=False, valid=valid)


@pytest.mark.parametrize("variant", ["ref", "gmm"])
def test_held_shares_add_up_to_the_uncut_layer(variant):
    """Experts 0-7 held on one chip and 8-15 on another: their outputs add
    up to the layer that holds all 16 (float32; rounding only)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 64))
    lo, c_lo = _region(variant, x, _expert_params(8, 0), first=0)
    hi, c_hi = _region(variant, x, _expert_params(8, 8), first=8)
    whole, c_all = _region(variant, x, _expert_params(16, 0), first=0)
    np.testing.assert_allclose(lo + hi, whole, rtol=1e-5, atol=1e-5)
    assert int(c_lo[0] + c_hi[0]) == int(c_all[0]) == 80
    assert int(c_lo[1]) <= 8 and int(c_all[1]) <= 16


def test_reference_held_shares_add_up_to_the_uncut_layer():
    """The reference's expert layer, held 0-7 plus held 8-15, is its layer
    over all 16 experts."""
    conf = small_conf()
    p16 = {k: v[None] for k, v in _expert_params(16, 0).items()}
    p16["ln"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (1, 64))

    def share(first, held):
        p = {k: (v if k in ("router", "ln") else v[:, first:first + held])
             for k, v in p16.items()}
        c = dict(conf, experts_first=first)
        x = jax.random.normal(jax.random.PRNGKey(6), (24, 64))
        return REF._experts(p, x, c, 1e-6, None) - x

    np.testing.assert_allclose(share(0, 8) + share(8, 8), share(0, 16),
                               rtol=1e-5, atol=1e-5)


def _skewed_router(d=64, routed=16, picks=(2, 5)):
    """A router whose top two are experts ``picks`` for every token."""
    w = np.zeros((d, routed), np.float32)
    w[:, picks[0]], w[:, picks[1]] = 1.0, 0.5
    return jnp.asarray(w)


@pytest.mark.parametrize("tokens,skewed", [(1, False), (16, False),
                                           (37, False), (256, False),
                                           (64, True)])
def test_plain_and_grouped_variants_agree(tokens, skewed):
    """Dense-over-held (``ref``) and sorted grouped kernels (``gmm``) give
    one result, at decode- and prefill-like sizes and where every pick of
    every token lands on the same two experts (each group then holds all
    the tokens: nothing is dropped)."""
    params = _expert_params(8, 0)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 64))
    if skewed:
        params["router"] = _skewed_router()
        x = jnp.abs(x)                   # positive rows: the picks hold
    a, ca = _region("ref", x, params)
    b, cb = _region("gmm", x, params)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(ca, cb)
    if skewed:
        assert (int(cb[0]), int(cb[1])) == (2 * tokens, 2)


@pytest.mark.parametrize("variant", ["ref", "gmm"])
def test_a_tokens_output_depends_on_that_token_alone(variant):
    """Neither batch-mates nor later tokens move a token's experts or its
    output; tokens marked not valid (bucket padding) go to no expert."""
    params = _expert_params(8, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (48, 64))
    whole, _ = _region(variant, x, params)
    prefix, _ = _region(variant, x[:20], params)
    mates = x.at[20:].set(jax.random.normal(jax.random.PRNGKey(2), (28, 64)))
    other, _ = _region(variant, mates, params)
    np.testing.assert_allclose(prefix, whole[:20], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(other[:20], whole[:20], rtol=1e-6, atol=1e-6)
    valid = jnp.arange(48) < 20
    masked, counts = _region(variant, x, params, valid=valid)
    np.testing.assert_allclose(masked[:20], whole[:20], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(masked[20:], 0.0)
    _, c20 = _region(variant, x[:20], params)
    np.testing.assert_array_equal(counts, c20)


def test_bucket_padding_leaves_kv_and_mamba_state_exact():
    """A prompt padded to its bucket leaves the attention layer's KV cache
    and every Mamba state as the unpadded prompt does, and the same
    logits and routing counters."""
    conf, cfg, w = _model()
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 256, 11),
                       jnp.int32)[None]
    impl = Impl(conf["serving"]["plan"])
    step = jax.jit(F.make_bucketed_prefill_step(cfg, impl, ctx=32,
                                                counts=True))
    exact = step(w, {"tokens": toks}, jnp.int32(11))
    padded = step(w, {"tokens": jnp.pad(toks, ((0, 0), (0, 5)))},
                  jnp.int32(11))
    np.testing.assert_allclose(padded[0], exact[0], rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(padded[1]), jax.tree.leaves(exact[1])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(padded[2], exact[2])
    # 11 tokens x top-2 x 4 expert layers, about half on the 8 held of 16
    assert 0 < int(exact[2][0]) < 11 * 2 * 4


def test_engine_counts_routed_pairs_on_its_spans():
    """The engine keeps each step's routing counters on its span, and the
    bytes of a slot's cache on ``engine.insert``."""
    from repro import obs
    conf, cfg, w = _model()
    prompt = np.arange(1, 12, dtype=np.int32)
    step = F.make_bucketed_prefill_step(cfg, Impl(conf["serving"]["plan"]),
                                        ctx=32, counts=True)
    want = step(w, {"tokens": jnp.asarray(prompt)[None]}, jnp.int32(11))[2]
    obs.reset()
    _serve(conf, cfg, w, [prompt], 3)
    spans = obs.spans()
    pre = [s.attrs for s in spans if s.name == "engine.prefill"]
    dec = [s.attrs for s in spans if s.name == "engine.decode"]
    assert (pre[0]["moe_pairs"], pre[0]["moe_experts_hit"]) == tuple(
        int(v) for v in want)
    # every slot decodes, the empty ones too: 3 slots x top-2 x 4 layers
    assert len(dec) == 2
    assert all(0 < a["moe_pairs"] <= 3 * 2 * 4 for a in dec)
    assert all(0 < a["moe_experts_hit"] <= min(a["moe_pairs"], 4 * 8)
               for a in pre + dec)
    ins = [s.attrs["bytes"] for s in spans if s.name == "engine.insert"]
    one_slot = F.abstract_cache(cfg, 1, conf["serving"]["ctx"])
    assert ins == [sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(one_slot))]


# The falcon-mamba block as it stood before the new fields: its parameter
# layout and the lowered program of its reduced block (seq plan, bucketed).
FALCON_TEMPLATE_SHA = "a5f950bfadf348f1"
FALCON_BLOCK_SHA = "799a5dfe8a19b621"


def test_falcon_mamba_tree_and_block_unchanged():
    from repro.models import ssm
    leaves = jax.tree_util.tree_flatten_with_path(
        F.template(get_config("falcon-mamba-7b")),
        is_leaf=lambda s: hasattr(s, "init"))[0]
    tree = repr([(jax.tree_util.keystr(k), v.shape, v.dtype, v.init)
                 for k, v in leaves])
    assert hashlib.sha256(tree.encode()).hexdigest()[:16] == FALCON_TEMPLATE_SHA
    cfg = get_config("falcon-mamba-7b").reduced()
    params = F.init_params(cfg, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["stack"]["l0"]["ssm"])
    x = jnp.zeros((1, 16, cfg.d_model), jnp.bfloat16)
    text = jax.jit(functools.partial(
        ssm.mamba_block, cfg=cfg, impl=Impl({"ssm_scan": "seq"}))
    ).lower(p, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == FALCON_BLOCK_SHA


def test_jamba_layout_follows_the_published_offsets():
    cfg = get_config("jamba2-mini")
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [4, 12, 20, 28]
    assert [i for i, f in enumerate(ffns) if f == "moe"] == list(range(1, 32, 2))
    assert F.default_impl(cfg) == {"ssm_scan": "seq"}
    # about 52B parameters, 12B active (the family's published sizes)
    assert 51e9 < cfg.param_count() < 53e9
    assert 11.5e9 < cfg.active_param_count() < 12.5e9
    held = dataclasses.replace(cfg, experts_held=8)
    per_expert = 3 * 4096 * 14336
    assert cfg.param_count() - held.param_count() == 16 * 8 * per_expert
