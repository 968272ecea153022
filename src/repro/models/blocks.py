"""Per-layer block templates + apply functions for every assigned family.

Each block kind provides:
  * ``<kind>_template(cfg)``  — ParamSpec tree (single layer, unstacked)
  * ``<kind>_apply(...)``     — full-sequence forward (train / prefill)
  * ``<kind>_decode(...)``    — single-token forward with cache
  * ``<kind>_cache_template(cfg, batch, ctx)`` — cache ParamSpec tree

Blocks route their hot loops through ``repro.core.regions.dispatch`` so the
offload planner can swap implementations (the paper's loop-statement offload).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL_ATTN, RGLRU, SSM, ModelConfig
from repro.core.regions import dispatch, register_variant
from repro.parallel.ctx import constrain, heads_shardable
from repro.models import layers as L
from repro.models import moe as _moe  # noqa: F401  (registers moe_ffn variants)
from repro.models import rglru as RG
from repro.models import ssm as SS
from repro.models.params import spec

# ---------------------------------------------------------------------------
# attn_core region variants
# ---------------------------------------------------------------------------
register_variant("attn_core", "ref")(
    lambda q, k, v, **kw: L.chunked_attention(q, k, v, q_chunk=512, k_chunk=1024, **kw))
register_variant("attn_core", "offload")(
    lambda q, k, v, **kw: L.chunked_attention(q, k, v, q_chunk=1024, k_chunk=2048, **kw))


@register_variant("mlp_core", "ref")
def _mlp_ref(x, w_gate, w_up, w_down):
    return L.swiglu(x, w_gate, w_up, w_down)


@register_variant("mlp_core", "offload")
def _mlp_offload(x, w_gate, w_up, w_down):
    # fused formulation: single concatenated matmul then split (one HBM pass
    # over x; what a fused Pallas MLP kernel computes)
    w_cat = jnp.concatenate([w_gate, w_up], axis=1)
    h = x @ w_cat
    g, u = jnp.split(h, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_down


@register_variant("mlp_gelu", "ref")
def _mlp_gelu_ref(x, w_up, b_up, w_down, b_down):
    return L.gelu_mlp(x, w_up, b_up, w_down, b_down)


@register_variant("mlp_gelu", "offload")
def _mlp_gelu_offload(x, w_up, b_up, w_down, b_down):
    # one-pass formulation with f32 activation accumulation (what a fused
    # Pallas gelu-MLP kernel computes between HBM reads)
    h = jnp.dot(x, w_up, preferred_element_type=jnp.float32) + b_up
    g = jax.nn.gelu(h).astype(x.dtype)
    return (g @ w_down + b_down).astype(x.dtype)


@register_variant("conv_stem", "ref")
def _conv_stem_ref(x, w, b, *, stride=1):
    # x: [B, W, Cin]; w: [K, Cin, Cout] (whisper's k=3 conv1d stem layer)
    h = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride,), padding="SAME",
        dimension_numbers=("NHC", "HIO", "NHC"))
    return jax.nn.gelu(h + b)


@register_variant("conv_stem", "offload")
def _conv_stem_offload(x, w, b, *, stride=1):
    # im2col formulation: gather the K strided windows and run ONE matmul —
    # the layout a systolic offload target wants (conv as dense GEMM)
    k, cin, cout = w.shape
    win = x.shape[1]
    out_w = -(-win // stride)
    pad_total = max((out_w - 1) * stride + k - win, 0)
    lo = pad_total // 2
    xp = jnp.pad(x, ((0, 0), (lo, pad_total - lo), (0, 0)))
    span = (out_w - 1) * stride + 1
    cols = jnp.concatenate([xp[:, i:i + span:stride, :] for i in range(k)],
                           axis=-1)                     # [B, out_w, K*Cin]
    h = cols @ w.reshape(k * cin, cout)
    return jax.nn.gelu(h + b)


# ---------------------------------------------------------------------------
# Attention block
# ---------------------------------------------------------------------------
def attn_template(cfg: ModelConfig, cross: bool = False) -> dict:
    d, hq, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    t = {
        "ln": spec([d], ("embed",), "zeros"),
        "wq": spec([d, hq * hd], ("embed", "qkv")),
        "wk": spec([d, hkv * hd], ("embed", "kv_qkv")),
        "wv": spec([d, hkv * hd], ("embed", "kv_qkv")),
        "wo": spec([hq * hd, d], ("qkv", "embed"), "scaled"),
    }
    if cfg.qkv_bias:
        t["bq"] = spec([hq * hd], ("qkv",), "zeros")
        t["bk"] = spec([hkv * hd], ("kv_qkv",), "zeros")
        t["bv"] = spec([hkv * hd], ("kv_qkv",), "zeros")
    return t


def _split_heads(x, n_heads, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)   # [B, H, S, hd]


def _rope(x, positions, cfg: ModelConfig):
    """Rotary embedding at ``positions``, or ``x`` unchanged where the
    architecture gives attention no positions (``cfg.use_rope`` off)."""
    return L.apply_rope(x, positions, cfg.rope_theta) if cfg.use_rope else x


def _merge_heads(x):
    b, h, s, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def _qkv(p, h, kv_src, cfg):
    hd = cfg.resolved_head_dim
    q = h @ p["wq"]
    k = kv_src @ p["wk"]
    v = kv_src @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (_split_heads(q, cfg.num_heads, hd),
            _split_heads(k, cfg.num_kv_heads, hd),
            _split_heads(v, cfg.num_kv_heads, hd))


def attn_apply(p, x, *, cfg: ModelConfig, positions, impl=None, causal=True,
               window=0, kv_src=None, kv_positions=None, return_kv=False):
    """Full-sequence attention block with pre-norm residual.

    x: [B, S, D]; positions: [B, S] absolute positions.
    kv_src: encoder output for cross-attention (else self)."""
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    src = h if kv_src is None else kv_src
    q, k, v = _qkv(p, h, src, cfg)
    kpos = positions if kv_positions is None else kv_positions
    q = _rope(q, positions[:, None, :], cfg)
    k = _rope(k, kpos[:, None, :], cfg)
    # Query heads shard over 'model' when divisible (qwen2 64H); otherwise
    # fall back to sequence-parallel queries (phi3 40H / arctic 56H /
    # whisper 12H on a 16-way axis would otherwise replicate the S^2 work on
    # every model shard).  K/V shard on kv_heads only when divisible; a
    # replicated K/V is the standard GQA trade (kv=8 < 16).
    q_axes = (("batch", "heads", None, None) if heads_shardable(cfg.num_heads)
              else ("batch", None, "act_seq", None))
    kv_axes = (("batch", "kv_heads", None, None)
               if heads_shardable(cfg.num_kv_heads)
               else ("batch", None, None, None))
    q = constrain(q, q_axes)
    k = constrain(k, kv_axes)
    v = constrain(v, kv_axes)
    out = dispatch("attn_core", impl, q, k, v, causal=causal, window=window)
    out = constrain(out, q_axes)
    out = _merge_heads(out) @ p["wo"]
    res = x + out.astype(x.dtype)
    if return_kv:
        return res, (k, v)
    return res


def attn_cache_template(cfg: ModelConfig, batch: int, ctx: int, window: int = 0) -> dict:
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    s = min(ctx, window) if window else ctx
    return {
        "k": spec([batch, hkv, s, hd], ("batch", "kv_heads", "ctx", None), "zeros"),
        "v": spec([batch, hkv, s, hd], ("batch", "kv_heads", "ctx", None), "zeros"),
        "slot_pos": spec([batch, s], ("batch", "ctx"), "neg_ones_i32", dtype="int32"),
    }


def attn_decode(p, x, cache, *, cfg: ModelConfig, pos, impl=None, window=0,
                cross_kv=None):
    """x: [B, 1, D]; pos: [B] absolute position of this token."""
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    if cross_kv is not None:
        k_cache, v_cache, slot_pos = cross_kv
        hd = cfg.resolved_head_dim
        q = _split_heads(h @ p["wq"] + (p["bq"] if "bq" in p else 0.0),
                         cfg.num_heads, hd)
        q = _rope(q, pos[:, None, None], cfg)
        out = L.decode_attention(q, k_cache, v_cache, slot_pos,
                                 jnp.full_like(pos, 2**30), window=0)
        out = _merge_heads(out) @ p["wo"]
        return x + out.astype(x.dtype), cache
    q, k_new, v_new = _qkv(p, h, h, cfg)
    q = _rope(q, pos[:, None, None], cfg)
    k_new = _rope(k_new, pos[:, None, None], cfg)
    k_c, v_c, sp = L.cache_update(cache["k"], cache["v"], cache["slot_pos"],
                                  k_new, v_new, pos, window=window)
    out = L.decode_attention(q, k_c, v_c, sp, pos, window=window)
    out = _merge_heads(out) @ p["wo"]
    return x + out.astype(x.dtype), {"k": k_c, "v": v_c, "slot_pos": sp}


def attn_prefill_cache(p, x, *, cfg: ModelConfig, positions, window=0, ctx=None,
                       length=None):
    """Compute the KV cache contents after a prefill of x ([B, S, D] normed
    input is recomputed here).  Returns the cache dict.

    ``length`` (traced scalar): only positions < length are real (bucketed
    prefill right-pads the sequence).  Slot j then holds the newest valid
    position p with p % size == j (the same slot discipline cache_update uses
    at decode), and unfilled slots are zeroed with slot_pos = -1 so
    decode_attention masks them."""
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    _, k, v = _qkv(p, h, h, cfg)
    k = _rope(k, positions[:, None, :], cfg)
    b, hkv, s, hd = k.shape
    size = min(ctx or s, window) if window else (ctx or s)
    if length is not None:
        # slot j <- newest position p < length with p ≡ j (mod size); this is
        # one formula for both the full cache (p = j when j < length) and the
        # rotating window (the last `size` valid positions at p % size).
        j = jnp.arange(size)
        p_j = length - 1 - ((length - 1 - j) % size)           # [size]
        valid = p_j >= 0
        gather = jnp.clip(p_j, 0, s - 1)
        kc = jnp.take(k, gather, axis=2)
        vc = jnp.take(v, gather, axis=2)
        m = valid[None, None, :, None]
        kc = jnp.where(m, kc, jnp.zeros((), kc.dtype))
        vc = jnp.where(m, vc, jnp.zeros((), vc.dtype))
        sp = jnp.broadcast_to(jnp.where(valid, p_j, -1)[None, :], (b, size))
        return {"k": kc, "v": vc, "slot_pos": sp.astype(jnp.int32)}
    if window and s > size:
        # keep last `size` positions at slots pos % size
        keep_pos = positions[:, -size:]                        # [B, size]
        slots = keep_pos % size
        kc = jnp.zeros((b, hkv, size, hd), k.dtype)
        vc = jnp.zeros((b, hkv, size, hd), v.dtype)
        sp = jnp.full((b, size), -1, jnp.int32)
        bi = jnp.arange(b)[:, None]
        kc = kc.at[bi, :, slots].set(k[:, :, -size:].transpose(0, 2, 1, 3))
        vc = vc.at[bi, :, slots].set(v[:, :, -size:].transpose(0, 2, 1, 3))
        sp = sp.at[bi, slots].set(keep_pos)
    else:
        pad = size - s
        kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        sp = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    return {"k": kc, "v": vc, "slot_pos": sp.astype(jnp.int32)}


# ---------------------------------------------------------------------------
# Dense MLP block
# ---------------------------------------------------------------------------
def mlp_template(cfg: ModelConfig, d_ff: Optional[int] = None, gelu: bool = False) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    t = {"ln": spec([d], ("embed",), "zeros")}
    if gelu:
        t.update(w_up=spec([d, f], ("embed", "mlp")), b_up=spec([f], ("mlp",), "zeros"),
                 w_down=spec([f, d], ("mlp", "embed"), "scaled"),
                 b_down=spec([d], ("embed",), "zeros"))
    else:
        t.update(w_gate=spec([d, f], ("embed", "mlp")),
                 w_up=spec([d, f], ("embed", "mlp")),
                 w_down=spec([f, d], ("mlp", "embed"), "scaled"))
    return t


def mlp_apply(p, x, *, cfg: ModelConfig, impl=None):
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    if "w_gate" in p:
        out = dispatch("mlp_core", impl, h, p["w_gate"], p["w_up"], p["w_down"])
    else:
        out = dispatch("mlp_gelu", impl, h, p["w_up"], p["b_up"],
                       p["w_down"], p["b_down"])
    return x + out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE block
# ---------------------------------------------------------------------------
def moe_template(cfg: ModelConfig) -> dict:
    """The router spans all ``num_experts``; the expert stacks hold only the
    experts held here (``resolved_experts_held``)."""
    d, e, held = cfg.d_model, cfg.num_experts, cfg.resolved_experts_held
    f = cfg.moe_d_ff or cfg.d_ff
    t = {
        "ln": spec([d], ("embed",), "zeros"),
        "router": spec([d, e], ("embed", "experts")),
        "w_gate": spec([held, d, f], ("experts", "embed", "expert_mlp")),
        "w_up": spec([held, d, f], ("experts", "embed", "expert_mlp")),
        "w_down": spec([held, f, d], ("experts", "expert_mlp", "embed"), "scaled"),
    }
    if cfg.dense_residual_d_ff:
        t["dense"] = {k: v for k, v in
                      mlp_template(cfg, d_ff=cfg.dense_residual_d_ff).items()
                      if k != "ln"}
    return t


def moe_apply(p, x, *, cfg: ModelConfig, impl=None, length=None):
    """Returns (x + the experts' output, counts): ``counts`` int32 [2] are
    the (token, expert) pairs routed to the experts held here and the held
    experts they reached, under ``cfg.moe_dropless`` (else None).
    ``length`` (traced scalar, dropless only): positions >= length are
    bucket padding, routed to no expert."""
    b, s, d = x.shape
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    flat = h.reshape(b * s, d)
    experts = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    counts = None
    if cfg.moe_dropless:
        valid = None
        if length is not None:
            valid = jnp.broadcast_to(jnp.arange(s) < length, (b, s)).reshape(-1)
        moe_out, counts = dispatch(
            "moe_dropless", impl, flat, experts, k=cfg.experts_per_token,
            first=cfg.experts_first, renormalize=cfg.moe_renormalize,
            valid=valid)
    else:
        moe_out = dispatch("moe_ffn", impl, flat, experts,
                           num_experts=cfg.num_experts,
                           k=cfg.experts_per_token,
                           capacity_factor=cfg.capacity_factor,
                           inner_impl=impl)
    out = moe_out.reshape(b, s, d)
    if "dense" in p:
        dp = p["dense"]
        out = out + L.swiglu(h, dp["w_gate"], dp["w_up"], dp["w_down"]).astype(x.dtype)
    return x + out.astype(x.dtype), counts


# ---------------------------------------------------------------------------
# Mamba (SSM) block
# ---------------------------------------------------------------------------
def ssm_template(cfg: ModelConfig) -> dict:
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    dtr, k = cfg.resolved_dt_rank, cfg.ssm_conv
    t = {
        "ln": spec([d], ("embed",), "zeros"),
        "w_in": spec([d, 2 * di], ("embed", "inner2")),
        "conv_w": spec([k, di], (None, "inner"), "normal", scale=0.3),
        "w_dbc": spec([di, dtr + 2 * n], ("inner", None)),
        "w_dt": spec([dtr, di], (None, "inner")),
        "dt_bias": spec([di], ("inner",), "zeros"),
        "a_log": spec([di, n], ("inner", None), "a_log", dtype="float32"),
        "d_skip": spec([di], ("inner",), "ones"),
        "w_out": spec([di, d], ("inner", "embed"), "scaled"),
    }
    if cfg.ssm_conv_bias:
        t["conv_b"] = spec([di], ("inner",), "zeros")
    if cfg.ssm_dt_norm:
        t["dt_ln"] = spec([dtr], (None,), "zeros")
        t["b_ln"] = spec([n], (None,), "zeros")
        t["c_ln"] = spec([n], (None,), "zeros")
    return t


def ssm_cache_template(cfg: ModelConfig, batch: int) -> dict:
    di, n, k = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    return {
        "conv": spec([batch, k - 1, di], ("batch", None, "inner"), "zeros"),
        "h": spec([batch, di, n], ("batch", "inner", None), "zeros", dtype="float32"),
    }


def ssm_apply(p, x, *, cfg: ModelConfig, impl=None, state=None, length=None):
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    out, new_state = SS.mamba_block(p, h, cfg=cfg, impl=impl, state=state,
                                    length=length)
    return x + out, new_state


def ssm_decode(p, x, cache, *, cfg: ModelConfig, pos=None, impl=None):
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    out, new_state = SS.mamba_decode_step(p, h, cache, cfg=cfg, impl=impl)
    return x + out, new_state


# ---------------------------------------------------------------------------
# RG-LRU block
# ---------------------------------------------------------------------------
def rglru_template(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    dr = cfg.rglru_d_rnn or d
    g = 8 if dr % 8 == 0 else 1
    k = cfg.ssm_conv
    return {
        "ln": spec([d], ("embed",), "zeros"),
        "w_branch": spec([d, dr], ("embed", "rnn")),
        "w_gate": spec([d, dr], ("embed", "rnn")),
        "conv_w": spec([k, dr], (None, "rnn"), "normal", scale=0.3),
        "w_a": spec([g, dr // g, dr // g], (None, None, None), "normal", scale=0.3),
        "w_x": spec([g, dr // g, dr // g], (None, None, None), "normal", scale=0.3),
        "lam": spec([dr], ("rnn",), "ones"),
        "w_out": spec([dr, d], ("rnn", "embed"), "scaled"),
    }


def rglru_cache_template(cfg: ModelConfig, batch: int) -> dict:
    dr = cfg.rglru_d_rnn or cfg.d_model
    k = cfg.ssm_conv
    return {
        "conv": spec([batch, k - 1, dr], ("batch", None, "rnn"), "zeros"),
        "h": spec([batch, dr], ("batch", "rnn"), "zeros", dtype="float32"),
    }


def rglru_apply(p, x, *, cfg: ModelConfig, impl=None, state=None, length=None):
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    out, new_state = RG.rglru_block(p, h, cfg=cfg, impl=impl, state=state,
                                    length=length)
    return x + out, new_state


def rglru_decode(p, x, cache, *, cfg: ModelConfig, pos=None, impl=None):
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    out, new_state = RG.rglru_decode_step(p, h, cache, cfg=cfg, impl=impl)
    return x + out, new_state
