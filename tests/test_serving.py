"""Continuous-batching engine: slot isolation, admission control, bucketed
prefill, sampling determinism, and lifecycle stats."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import factory as F
from repro.serving.engine import ServeEngine, ServeIncompleteError
from repro.serving.sampling import SamplingParams

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("qwen2-72b").reduced(),
                              dtype="float32")
    params = F.init_params(cfg, KEY)
    return cfg, params


def _prompts(cfg, n):
    return [np.asarray(jax.random.randint(jax.random.fold_in(KEY, i),
                                          (6 + i,), 0, cfg.vocab_size))
            for i in range(n)]


def test_continuous_batching_matches_solo(setup):
    cfg, params = setup
    prompts = _prompts(cfg, 5)
    solo = []
    for p in prompts:
        eng = ServeEngine(cfg, params, slots=1, ctx=32)
        eng.submit(p, max_new_tokens=5)
        solo.append(eng.run_to_completion()[0].generated)

    eng = ServeEngine(cfg, params, slots=3, ctx=32)
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    done = eng.run_to_completion()
    assert len(done) == 5
    for req, ref in zip(done, solo):
        assert req.generated == ref


def test_more_requests_than_slots_all_complete(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=2, ctx=32)
    rids = [eng.submit(p, max_new_tokens=3) for p in _prompts(cfg, 6)]
    done = eng.run_to_completion()
    assert sorted(r.rid for r in done) == rids
    assert all(len(r.generated) == 3 for r in done)


def test_engine_idle_after_completion(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=2, ctx=32)
    eng.submit(_prompts(cfg, 1)[0], max_new_tokens=2)
    eng.run_to_completion()
    assert not eng.busy
    assert all(s is None for s in eng.active)


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------
def test_submit_rejects_ctx_overflow(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=1, ctx=32)
    prompt = np.zeros(30, np.int32)
    with pytest.raises(ValueError, match="cache slots"):
        eng.submit(prompt, max_new_tokens=5)        # 30 + 5 > 32
    eng.submit(prompt, max_new_tokens=2)            # 30 + 2 <= 32 admits
    assert len(eng.run_to_completion()[0].generated) == 2


def test_submit_validates_inputs(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=1, ctx=32)
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(np.zeros(0, np.int32))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros(4, np.int32), max_new_tokens=0)


# ---------------------------------------------------------------------------
# Bucketed prefill
# ---------------------------------------------------------------------------
def test_prefill_compiles_once_per_bucket(setup):
    """Across >= 6 distinct prompt lengths the engine must compile one
    prefill per power-of-two bucket, not one per length (the trace counter
    increments exactly when the jitted prefill's python body re-runs)."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=3, ctx=64)
    lengths = (5, 6, 7, 9, 12, 15)                  # buckets: 8 and 16
    for i, n in enumerate(lengths):
        eng.submit(np.asarray(jax.random.randint(
            jax.random.fold_in(KEY, 100 + i), (n,), 0, cfg.vocab_size)),
            max_new_tokens=2)
    done = eng.run_to_completion()
    assert len(done) == len(lengths)
    assert eng.buckets_seen == {8, 16}
    assert eng.prefill_traces == 2                  # one per bucket
    # a repeat request in a seen bucket must not retrace
    eng.submit(np.zeros(10, np.int32), max_new_tokens=2)
    eng.run_to_completion()
    assert eng.prefill_traces == 2


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "recurrentgemma-2b"])
def test_bucketed_prefill_matches_unpadded(arch):
    """Length masking must make the padded prefill bit-exact for the real
    tokens: logits at the last real position AND every cache leaf (KV slots,
    conv trailing context, recurrent states) equal the unpadded prefill.
    Parametrized over the recurrent families — attention exactness is
    already pinned by test_continuous_batching_matches_solo."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    params = F.init_params(cfg, KEY)
    ctx = 32
    exact = jax.jit(F.make_prefill_step(cfg, ctx=ctx))
    bucketed = jax.jit(F.make_bucketed_prefill_step(cfg, ctx=ctx))
    for n in (5, 11):
        toks = np.asarray(jax.random.randint(jax.random.fold_in(KEY, n),
                                             (n,), 0, cfg.vocab_size), np.int32)
        lg_e, cache_e = exact(params, {"tokens": jnp.asarray(toks[None])})
        bucket = F.prefill_bucket(n, ctx)
        padded = np.zeros(bucket, np.int32)
        padded[:n] = toks
        lg_b, cache_b = bucketed(params, {"tokens": jnp.asarray(padded[None])},
                                 jnp.asarray(n, jnp.int32))
        np.testing.assert_allclose(np.asarray(lg_e), np.asarray(lg_b),
                                   rtol=2e-5, atol=2e-5)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(cache_e)[0],
                jax.tree_util.tree_flatten_with_path(cache_b)[0]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5,
                err_msg=f"{arch} n={n} {jax.tree_util.keystr(path)}")


def test_bucketed_prefill_matches_unpadded_windowed_wraparound():
    """Pin the rotation branch of the bucketed KV gather: with
    attn_window < prompt length < bucket, slot j holds the newest valid
    position p ≡ j (mod window) — the non-trivial case of
    p_j = length-1-((length-1-j) % size)."""
    cfg = dataclasses.replace(get_config("recurrentgemma-2b").reduced(),
                              attn_window=8, dtype="float32")
    params = F.init_params(cfg, KEY)
    ctx = 32
    exact = jax.jit(F.make_prefill_step(cfg, ctx=ctx))
    bucketed = jax.jit(F.make_bucketed_prefill_step(cfg, ctx=ctx))
    n = 11                                          # window 8 < 11 < bucket 16
    toks = np.asarray(jax.random.randint(KEY, (n,), 0, cfg.vocab_size),
                      np.int32)
    lg_e, cache_e = exact(params, {"tokens": jnp.asarray(toks[None])})
    padded = np.zeros(F.prefill_bucket(n, ctx), np.int32)
    padded[:n] = toks
    lg_b, cache_b = bucketed(params, {"tokens": jnp.asarray(padded[None])},
                             jnp.asarray(n, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_e), np.asarray(lg_b),
                               rtol=2e-5, atol=2e-5)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(cache_e)[0],
            jax.tree_util.tree_flatten_with_path(cache_b)[0]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def test_prefill_bucket_helper():
    assert F.prefill_bucket(1, 64) == F.PREFILL_BUCKET_MIN
    assert F.prefill_bucket(8, 64) == 8
    assert F.prefill_bucket(9, 64) == 16
    assert F.prefill_bucket(33, 64) == 64
    assert F.prefill_bucket(33, 40) == 40           # capped at cache capacity
    with pytest.raises(ValueError):
        F.prefill_bucket(41, 40)


# ---------------------------------------------------------------------------
# run_to_completion timeout
# ---------------------------------------------------------------------------
def test_run_to_completion_raises_when_incomplete(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=1, ctx=32)
    rids = [eng.submit(p, max_new_tokens=4) for p in _prompts(cfg, 2)]
    with pytest.raises(ServeIncompleteError) as ei:
        eng.run_to_completion(max_ticks=2)
    assert ei.value.pending                          # structured partial result
    assert set(ei.value.pending) <= set(rids)
    assert all(r.done for r in ei.value.finished)
    # the engine state is intact: draining afterwards completes everything
    done = eng.run_to_completion()
    assert sorted(r.rid for r in done) == rids
    # opt-out returns the partial list instead of raising
    eng2 = ServeEngine(cfg, params, slots=1, ctx=32)
    eng2.submit(_prompts(cfg, 1)[0], max_new_tokens=4)
    assert eng2.run_to_completion(max_ticks=1, raise_incomplete=False) == []
    assert eng2.busy


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------
def _run_sampled(cfg, params, seed, sampling, max_new=5):
    eng = ServeEngine(cfg, params, slots=1, ctx=32, seed=seed)
    eng.submit(_prompts(cfg, 1)[0], max_new_tokens=max_new, sampling=sampling)
    return eng.run_to_completion()[0].generated


def test_sampling_seed_determinism(setup):
    """Same engine seed => identical sampled tokens; different seed =>
    different tokens at temperature > 0 (the previously-dead `seed` arg)."""
    cfg, params = setup
    sp = SamplingParams(temperature=1.0)
    a1 = _run_sampled(cfg, params, seed=0, sampling=sp)
    a2 = _run_sampled(cfg, params, seed=0, sampling=sp)
    b = _run_sampled(cfg, params, seed=1, sampling=sp)
    assert a1 == a2
    assert a1 != b


def test_top_k_one_equals_greedy(setup):
    """top_k=1 collapses temperature sampling onto the argmax path."""
    cfg, params = setup
    greedy = _run_sampled(cfg, params, seed=0, sampling=SamplingParams())
    topk1 = _run_sampled(cfg, params, seed=0,
                         sampling=SamplingParams(temperature=1.0, top_k=1))
    assert topk1 == greedy


def test_sampling_params_validate():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)


# ---------------------------------------------------------------------------
# Lifecycle stats
# ---------------------------------------------------------------------------
def _full_slots_run(cfg, params, ahead: bool, sampling=None):
    """Six requests over three slots, every slot busy for several ticks;
    with ``ahead`` False the engine never dispatches a decode early."""
    eng = ServeEngine(cfg, params, slots=3, ctx=32)
    if not ahead:
        eng._next_decode_known = lambda: False
    for i, p in enumerate(_prompts(cfg, 6)):
        eng.submit(p, max_new_tokens=6 + 2 * i,
                   **({"sampling": sampling} if sampling else {}))
    return eng, {r.rid: r.generated for r in eng.run_to_completion()}


def test_decode_dispatched_ahead_gives_the_same_tokens(setup):
    """With every slot decoding and none finishing, the next tick's step is
    dispatched before this one's tokens reach the host; the tokens are
    those of an engine that always waits."""
    cfg, params = setup
    eng, early = _full_slots_run(cfg, params, ahead=True)
    _, waited = _full_slots_run(cfg, params, ahead=False)
    assert eng.decoded_ahead > 0
    assert early == waited


def test_no_decode_dispatched_ahead_when_sampling_or_a_plan_waits(setup):
    """Nor where the device lacks room for a second step's cache."""
    cfg, params = setup
    eng, _ = _full_slots_run(cfg, params, ahead=True,
                             sampling=SamplingParams(temperature=0.8))
    assert eng.decoded_ahead == 0
    eng = ServeEngine(cfg, params, slots=3, ctx=32)
    eng._room_ahead = False
    for p in _prompts(cfg, 3):
        eng.submit(p, max_new_tokens=8)
    eng.run_to_completion()
    assert eng.decoded_ahead == 0
    eng = ServeEngine(cfg, params, slots=2, ctx=32)
    for p in _prompts(cfg, 2):
        eng.submit(p, max_new_tokens=8)
    eng.step()
    eng.offer_plan(eng.prepare_plan(eng.plan_impl, warm=False))
    assert not eng._next_decode_known()


def test_request_stats_populated(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=1, ctx=32)
    for p in _prompts(cfg, 2):
        eng.submit(p, max_new_tokens=4)
    done = eng.run_to_completion()
    for r in done:
        assert r.finish_s >= r.admit_s >= r.slot_s >= r.submit_s > 0
        assert r.ttft_s > 0
        assert 0 <= r.queue_wait_s < r.ttft_s   # ttft adds the prefill itself
        assert r.decode_tps > 0
        assert r.bucket >= r.tokens.size
    # the second request waited for the first to release the only slot
    assert done[1].queue_wait_s > done[0].queue_wait_s
    s = eng.stats()
    assert s["requests_finished"] == 2
    assert s["generated_tokens"] == 8
    assert s["ttft_s_mean"] > 0 and s["ttft_s_p50"] > 0
    assert s["decode_tps_mean"] > 0
    assert s["prefill_traces"] >= 1
    assert s["buckets"] == sorted(eng.buckets_seen)
