"""Public jit'd kernel wrappers + registration of the `pallas` region
variants for the offload planner.

The kernels pick their execution mode from the backend (see
``repro.kernels.resolve_interpret``): compiled by Mosaic on a TPU,
interpreted elsewhere.

Tile knobs are exposed uniformly with a ``0`` sentinel meaning "auto from
shape" (the pre-tuning heuristic, and each knob's declared TuningSpace
default — so a bare variant gene and an explicit all-zero tile point are
the same gene).  Nonzero knobs are clamped to the nearest legal divisor
(legality itself lives in the TuningSpace predicates): the autotuner may
propose any point and still gets a correct, measurable kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.regions import TuningSpace, register_variant
from repro.kernels.decode_attention import decode_attention
from repro.kernels.fir import fir_filter_bank, largest_divisor
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mriq import mriq_compute_q
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssm_scan import ssm_scan


def _dim(args, idx: int, axis: int):
    """Shape dimension of an abstract region arg, or None when the
    validity query is unbound (args absent or shaped differently)."""
    try:
        return args[idx].shape[axis]
    except (TypeError, IndexError, AttributeError):
        return None


def _divides(knob: int, dim) -> bool:
    return knob == 0 or dim is None or (knob <= dim and dim % knob == 0)


def _attn_tile_ok(p, args) -> bool:
    return (_divides(p["block_q"], _dim(args, 0, 2))
            and _divides(p["block_k"], _dim(args, 1, 2)))


def _rglru_tile_ok(p, args) -> bool:
    return (_divides(p["block_c"], _dim(args, 0, 2))
            and _divides(p["time_chunk"], _dim(args, 0, 1)))


def _ssm_tile_ok(p, args) -> bool:
    return (_divides(p["block_c"], _dim(args, 0, 2))
            and _divides(p["time_chunk"], _dim(args, 0, 1)))


# ---------------------------------------------------------------------------
# Model-region pallas variants
# ---------------------------------------------------------------------------
@register_variant("attn_core", "pallas", tuning=TuningSpace(
    axes={"block_q": (0, 128, 256, 512), "block_k": (0, 128, 256, 512, 1024)},
    validity=_attn_tile_ok))
def attn_core_pallas(q, k, v, *, causal=True, window=0,
                     block_q=0, block_k=0):
    s, sk = q.shape[2], k.shape[2]
    bq = (largest_divisor(s, block_q) if block_q
          else 256 if s % 256 == 0 else (s if s <= 256 else 8))
    bk = (largest_divisor(sk, block_k) if block_k
          else 512 if sk % 512 == 0 else (sk if sk <= 512 else 8))
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=bq, block_k=bk)


@register_variant("rglru_scan", "pallas", tuning=TuningSpace(
    axes={"block_c": (0, 64, 128, 256), "time_chunk": (0, 64, 128, 256)},
    validity=_rglru_tile_ok))
def rglru_scan_pallas(a, b, h0, *, block_c=0, time_chunk=0):
    bc = (largest_divisor(a.shape[-1], block_c) if block_c
          else 128 if a.shape[-1] % 128 == 0 else a.shape[-1])
    tc = (largest_divisor(a.shape[1], time_chunk) if time_chunk
          else 128 if a.shape[1] % 128 == 0 else a.shape[1])
    return rglru_scan(a, b, h0, block_c=bc, time_chunk=tc)


@register_variant("ssm_scan", "pallas", tuning=TuningSpace(
    axes={"block_c": (0, 64, 128, 256), "time_chunk": (0, 32, 64, 128)},
    validity=_ssm_tile_ok))
def ssm_scan_pallas(a, bx, c, h0, *, block_c=0, time_chunk=0):
    bc = (largest_divisor(a.shape[2], block_c) if block_c
          else 128 if a.shape[2] % 128 == 0 else a.shape[2])
    tc = (largest_divisor(a.shape[1], time_chunk) if time_chunk
          else 64 if a.shape[1] % 64 == 0 else a.shape[1])
    return ssm_scan(a, bx, c, h0, block_c=bc, time_chunk=tc)


@register_variant("rmsnorm", "pallas")
def rmsnorm_pallas(x, w, eps=1e-6):
    return rmsnorm(x, w, eps=eps)


@register_variant("decode_attn", "ref")
def decode_attn_ref(q, k_cache, v_cache, slot_pos, cur_pos, *, window=0):
    """Loop-faithful decode-attention oracle: dense masked softmax over the
    whole KV cache.  The planner's host-side baseline for the decode-attn
    region (the pallas kernel computes exactly this, block-streamed)."""
    b, hq, _, d = q.shape
    _, hkv, s, _ = k_cache.shape
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg,
                        k_cache.astype(jnp.float32)) / jnp.sqrt(
                            jnp.float32(d))
    valid = (slot_pos >= 0) & (slot_pos <= cur_pos[:, None])
    if window:
        valid &= slot_pos > cur_pos[:, None] - window
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


@register_variant("decode_attn", "pallas", tuning=TuningSpace(
    axes={"block_k": (0, 128, 256, 512, 1024)}))
def decode_attn_pallas(q, k_cache, v_cache, slot_pos, cur_pos, *,
                       window=0, block_k=0):
    s = k_cache.shape[2]
    bk = (block_k if block_k
          else 512 if s % 512 == 0 else (128 if s % 128 == 0 else s))
    # the kernel itself clamps block_k to s and pads the cache to a
    # multiple, so every proposed point is legal (no validity predicate)
    return decode_attention(q, k_cache, v_cache, slot_pos, cur_pos,
                            window=window, block_k=bk)


__all__ = ["decode_attention", "fir_filter_bank", "flash_attention",
           "mriq_compute_q", "rglru_scan", "rmsnorm", "ssm_scan"]
