"""Decode-attention Pallas kernel: one query token vs a long KV cache.

The roofline shows every decode cell is memory-bound: the step streams the
KV cache once.  This kernel makes that streaming optimal — grid over
(batch*kv_heads, cache blocks) with the online-softmax partials accumulated
in VMEM scratch across cache blocks; invalid / out-of-window slots are
masked via the slot-position plane (supports the rotating local-attention
cache).  GQA: all G query heads of a kv head ride in one block so the cache
block is read ONCE for the whole group (the G× reuse is exactly the GQA
bandwidth win).

VMEM per step: bk*(D + 1) cache floats + G*D accumulators
~= 512*129*4 + 8*128*4 ~= 270 KB at the defaults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, sp_ref, pos_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_k: int, window: int,
                   scale: float):
    jb = pl.program_id(1)

    @pl.when(jb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale              # [G, D]
    k = k_ref[0].astype(jnp.float32)                      # [bk, D]
    v = v_ref[0].astype(jnp.float32)
    sp = sp_ref[0]                                        # [bk] slot positions
    pos = pos_ref[0, 0]                                   # scalar current pos

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # [G, bk]
    valid = (sp >= 0) & (sp <= pos)
    if window:
        valid &= sp > pos - window
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[...]                                   # [G]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(jb == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, slot_pos, cur_pos, *,
                     window: int = 0, block_k: int = 512,
                     interpret: bool | None = None):
    """q: [B, Hq, 1, D]; k/v_cache: [B, Hkv, S, D]; slot_pos: [B, S] int32;
    cur_pos: [B] int32.  Returns [B, Hq, 1, D]."""
    b, hq, _, d = q.shape
    _, hkv, s, _ = k_cache.shape
    g = hq // hkv
    block_k = min(block_k, s)
    pad = (-s) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, pad)), constant_values=-1)
    sp = s + pad

    qg = q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    kf = k_cache.reshape(b * hkv, sp, d)
    vf = v_cache.reshape(b * hkv, sp, d)
    spf = jnp.repeat(slot_pos[:, None, :], hkv, axis=1).reshape(b * hkv, sp)
    posf = jnp.repeat(cur_pos[:, None], hkv, axis=1).reshape(b * hkv, 1)

    grid = (b * hkv, sp // block_k)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k, window=window,
                          scale=1.0 / np.sqrt(d)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, d), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, block_k), lambda h, j: (h, j)),
            pl.BlockSpec((1, 1), lambda h, j: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda h, j: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),      # m (running max)
            pltpu.VMEM((g,), jnp.float32),      # l (normalizer)
            pltpu.VMEM((g, d), jnp.float32),    # acc
        ],
        interpret=resolve_interpret(interpret),
    )(qg, kf, vf, spf, posf)
    return out.reshape(b, hq, 1, d)
