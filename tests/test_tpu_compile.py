"""Compiles of the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles each kernel at
its real size for one chip of a ``v5e:2x2`` topology that is described, not
attached, and refuses what the chip's compiler would refuse (tiling, VMEM,
unsupported primitives).  Every compiled program must hold a Mosaic kernel
(``tpu_custom_call``).  The topology is described inside a fixture, never at
import, so that every test worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import os

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
from repro.kernels.ssm_scan import ssm_scan


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
    _compile(lambda *a: mriq_compute_q(*a, interpret=False),
             [fx, fx, fx, fk, fk, fk, fk], one_chip)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ssm_scan_compiles_at_falcon_mamba_widths(one_chip, dtype):
    cfg = get_config("falcon-mamba-7b")
    d, n, s = cfg.d_inner, cfg.ssm_state, 128
    _compile(lambda a, bx, c, h0: ssm_scan(a, bx, c, h0, interpret=False),
             [((1, s, d, n), dtype), ((1, s, d, n), dtype),
              ((1, s, n), dtype), ((1, d, n), jnp.float32)], one_chip)


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
