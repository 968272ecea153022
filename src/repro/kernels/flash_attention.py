"""Flash attention Pallas kernel (causal / sliding-window, GQA).

Grid: (batch * q_heads, num_q_blocks); the kv-block loop runs inside the
kernel with the online-softmax running max / normalizer / accumulator held in
VMEM.  GQA is expressed in the k/v BlockSpec index maps (q head h reads kv
head h // group).  VMEM per step at the defaults (bq=256, bk=512, d<=256):
q 256*256*4 + k/v 2*512*256*4 + acc 256*256*4 ~= 1.8 MB.

This is the deploy target for the model's "attn_core" region; the planner's
`pallas` variant.  Forward-only (inference / offload use); training uses the
XLA path (see DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, seq_len: int, block_q: int,
                  block_k: int, causal: bool, window: int, scale: float):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                   # [bq, d]
    d = q.shape[-1]
    q_pos = iq * block_q + jax.lax.iota(jnp.int32, block_q)

    num_kb = seq_len // block_k
    if causal:
        # only kv blocks that intersect the causal triangle for this q block
        last_kb = (iq + 1) * block_q
        num_live = (last_kb + block_k - 1) // block_k
    else:
        num_live = num_kb

    def body(ik, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ik * block_k, block_k), :]
        v = v_ref[0, pl.ds(ik * block_k, block_k), :]
        k_pos = ik * block_k + jax.lax.iota(jnp.int32, block_k)
        s = jnp.dot(q, k.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)        # [bq, bk]
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_live, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, block_q: int = 256,
                    block_k: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """q: [B, Hq, S, D]; k/v: [B, Hkv, S, D] -> [B, Hq, S, D]."""
    b, hq, s, d = q.shape
    _, hkv, sk, _ = k.shape
    assert s == sk, "self-attention kernel (prefill); decode uses XLA path"
    assert hq % hkv == 0
    group = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)

    qf = q.reshape(b * hq, s, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    grid = (b * hq, s // block_q)

    def kv_map(h, iq):
        # flat q index h = bi * hq + qh ; kv row = bi * hkv + qh // group
        bi = h // hq
        qh = h % hq
        return (bi * hkv + qh // group, 0, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, seq_len=s, block_q=block_q,
                          block_k=block_k, causal=causal, window=window,
                          scale=1.0 / np.sqrt(d)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, iq: (h, iq, 0)),
            pl.BlockSpec((1, s, d), kv_map),
            pl.BlockSpec((1, s, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, iq: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, hq, s, d)
