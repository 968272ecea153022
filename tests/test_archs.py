"""Per-arch smoke tests (assignment deliverable f): every assigned
architecture instantiates a REDUCED same-family config and runs one forward +
one train step on CPU, asserting output shapes and finiteness; decode paths
are checked for exact consistency with the full forward in fp32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, all_configs, get_config
from repro.configs.base import BONUS_ARCH_IDS

ALL_ARCHS = ARCH_IDS + BONUS_ARCH_IDS
from repro.models import factory as F

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def configs():
    return all_configs()


def test_init_params_values_unchanged_on_reduced_config():
    """The per-leaf jitted draw gives bit-for-bit the values of the eager
    draw it replaced (float32 normal, times scale, over sqrt(fan_in), then
    the cast) on a reduced config."""
    from repro.models.params import _is_spec

    cfg = get_config("falcon-mamba-7b").reduced()
    template = F.template(cfg)
    specs, _ = jax.tree.flatten(template, is_leaf=_is_spec)
    keys = jax.random.split(KEY, len(specs))
    got = jax.tree.leaves(F.init_params(cfg, KEY))
    checked = 0
    for k, s, arr in zip(keys, specs, got):
        if s.init not in ("normal", "scaled"):
            continue
        if s.init == "scaled":
            fan_in = s.shape[0] if len(s.shape) >= 2 else int(np.prod(s.shape))
            want = jax.random.normal(k, s.shape, jnp.float32) / np.sqrt(fan_in)
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            want = (jax.random.normal(k, s.shape, jnp.float32) * s.scale
                    / np.sqrt(fan_in))
        np.testing.assert_array_equal(np.asarray(arr),
                                      np.asarray(want.astype(s.dtype)))
        checked += 1
    assert checked >= 5


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_and_loss(arch, configs):
    cfg = configs[arch].reduced()
    params = F.init_params(cfg, KEY)
    batch = F.synthetic_batch(cfg, 2, 16, KEY)
    logits = F.make_forward(cfg)(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss = F.make_loss(cfg)(params, batch)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step(arch, configs):
    from repro.parallel.rules import ParallelismConfig
    from repro.runtime import steps as RS

    cfg = configs[arch].reduced()
    pcfg = ParallelismConfig(remat="none", microbatch=1)
    step = RS.make_train_step(cfg, pcfg)
    state = RS.init_train_state(cfg, KEY)
    batch = F.synthetic_batch(cfg, 2, 16, KEY)
    new_state, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(new_state["step"]) == 1


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_decode_matches_forward_fp32(arch, configs):
    # MoE note: token-choice capacity depends on how many tokens compete, so
    # decode (1 token) == forward (full batch) only when capacity never
    # binds — lift capacity_factor for the parity check.
    cfg = dataclasses.replace(configs[arch].reduced(), dtype="float32",
                              capacity_factor=16.0)
    params = F.init_params(cfg, KEY)
    s = 12
    batch = F.synthetic_batch(cfg, 2, s, KEY)
    logits_full = F.make_forward(cfg)(params, batch)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :s - 1]
    n_front = cfg.frontend_seq if cfg.frontend == "siglip_stub" else 0
    _, cache = F.make_prefill_step(cfg, ctx=s + n_front)(params, pre)
    pos = jnp.full((2,), s - 1 + n_front, jnp.int32)
    lg_dec, _ = F.make_serve_step(cfg)(params, cache, batch["tokens"][:, s - 1:s],
                                       pos)
    a = np.asarray(lg_dec[:, 0], np.float32)
    b = np.asarray(logits_full[:, s - 1], np.float32)
    np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_microbatched_grad_accumulation_matches(arch, configs):
    """grad accumulation (k=2) must give (near-)identical loss metrics.
    (MoE: capacity binds per routing group, and microbatching changes the
    group size — lift capacity so semantics match across k.)"""
    from repro.parallel.rules import ParallelismConfig
    from repro.runtime import steps as RS

    cfg = dataclasses.replace(configs[arch].reduced(), dtype="float32",
                              capacity_factor=16.0)
    batch = F.synthetic_batch(cfg, 4, 16, KEY)
    losses = {}
    for k in (1, 2):
        pcfg = ParallelismConfig(remat="none", microbatch=k)
        step = RS.make_train_step(cfg, pcfg)
        state = RS.init_train_state(cfg, KEY)
        _, metrics = jax.jit(step)(state, batch)
        losses[k] = float(metrics["loss"])
    assert abs(losses[1] - losses[2]) < 5e-4, losses


def test_param_counts_match_published():
    """Analytic parameter counts should land on the published sizes."""
    expected = {
        "mistral-nemo-12b": (12.0e9, 12.5e9),
        "phi3-medium-14b": (13.5e9, 15.0e9),
        "qwen2-72b": (72.0e9, 73.5e9),
        "deepseek-67b": (67.0e9, 68.0e9),
        "kimi-k2-1t-a32b": (1.00e12, 1.07e12),
        "arctic-480b": (4.6e11, 4.9e11),
        "falcon-mamba-7b": (7.0e9, 7.6e9),
        "recurrentgemma-2b": (2.5e9, 2.9e9),
        "paligemma-3b": (2.4e9, 2.7e9),        # backbone only (stub frontend)
        "whisper-small": (2.4e8, 3.5e8),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"


def test_moe_active_params():
    kimi = get_config("kimi-k2-1t-a32b")
    assert kimi.active_param_count() < 0.05 * kimi.param_count()
    arctic = get_config("arctic-480b")
    assert arctic.active_param_count() < 0.1 * arctic.param_count()


def test_remat_policies_forward_equal():
    cfg = dataclasses.replace(get_config("qwen2-72b").reduced(), dtype="float32")
    params = F.init_params(cfg, KEY)
    batch = F.synthetic_batch(cfg, 2, 16, KEY)
    base = None
    for remat in ("none", "dots", "full"):
        loss = F.make_loss(cfg, remat=remat)(params, batch)
        if base is None:
            base = float(loss)
        else:
            assert abs(float(loss) - base) < 1e-5
