"""Model assembly: template construction, train forward, prefill, decode.

Scan-over-layers everywhere: per-layer params are stacked along a leading
``layers`` axis and the layer body is traced ONCE regardless of depth, so the
dry-run HLO for 95-layer deepseek is the same size as for the 2-layer smoke
config.

Hybrid archs (recurrentgemma) repeat a block *pattern*; we scan over whole
pattern repetitions ("units") and apply the non-multiple tail unstacked:
26 layers of (RGLRU, RGLRU, LOCAL) = scan over 8 units + 2-layer tail.
A layer's feed-forward kind (``cfg.ffn_kinds``) follows its position, so
it repeats with the unit too (jamba: experts on the odd layers of each
8-layer period, whatever their mixer).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, LOCAL_ATTN, MLP, MOE, RGLRU, SSM, ModelConfig
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.params import spec, stack_tree


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------
def layer_plan(cfg: ModelConfig) -> tuple[tuple[str, ...], int, tuple[str, ...]]:
    """Returns (unit_kinds, reps, tail_kinds)."""
    kinds = cfg.layer_kinds()
    if cfg.layer_pattern:
        m = len(cfg.layer_pattern)
        reps = len(kinds) // m
        return tuple(cfg.layer_pattern), reps, tuple(kinds[reps * m:])
    return (kinds[0],), len(kinds), ()


def layer_ffns(cfg: ModelConfig) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """Feed-forward kinds of the unit's layers and of the tail's, laid out
    as :func:`layer_plan` lays out the mixers; every unit must repeat the
    first one's."""
    unit_kinds, reps, tail_kinds = layer_plan(cfg)
    ffns = cfg.ffn_kinds()
    m = len(unit_kinds)
    unit = tuple(ffns[:m])
    if any(tuple(ffns[r * m:(r + 1) * m]) != unit for r in range(reps)):
        raise ValueError(f"{cfg.name}: the feed-forward kinds do not repeat "
                         f"with the {m}-layer unit")
    return unit, tuple(ffns[reps * m:])


def layer_template(cfg: ModelConfig, kind: str, ffn: str) -> dict:
    t: dict = {}
    if kind in (ATTN, LOCAL_ATTN):
        t["attn"] = B.attn_template(cfg)
        if cfg.cross_attention:
            t["xattn"] = B.attn_template(cfg)
    elif kind == RGLRU:
        t["rglru"] = B.rglru_template(cfg)
    elif kind == SSM:
        t["ssm"] = B.ssm_template(cfg)
    else:
        raise ValueError(kind)
    if ffn == MOE:
        t["ffn"] = B.moe_template(cfg)
    elif ffn == MLP:
        t["ffn"] = B.mlp_template(cfg, gelu=(cfg.family == "audio"))
    return t


def model_template(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    t: dict = {
        "embed": spec([cfg.vocab_size, d], ("vocab", "embed"), scale=1.0),
        "final_ln": spec([d], ("embed",), "zeros"),
    }
    if not cfg.tie_embeddings:
        t["unembed"] = spec([d, cfg.vocab_size], ("embed", "vocab"))
    if cfg.frontend != "none":
        if cfg.conv_stem:
            # whisper-style 2-conv stem: k=3 stride 1 (mel -> d) then k=3
            # stride 2 (d -> d, halves the frame count to encoder_seq)
            t["stem"] = {
                "w1": spec([3, cfg.frontend_dim, d], (None, "frontend", "embed")),
                "b1": spec([d], ("embed",), "zeros"),
                "w2": spec([3, d, d], (None, None, "embed")),
                "b2": spec([d], ("embed",), "zeros"),
            }
        else:
            t["w_front"] = spec([cfg.frontend_dim, d], ("frontend", "embed"))
    if cfg.encoder_layers:
        enc_unit = {"attn": B.attn_template(cfg),
                    "ffn": B.mlp_template(cfg, gelu=True)}
        t["encoder"] = {"stack": stack_tree(cfg.encoder_layers, enc_unit),
                        "ln": spec([d], ("embed",), "zeros")}
    unit_kinds, reps, tail_kinds = layer_plan(cfg)
    unit_ffns, tail_ffns = layer_ffns(cfg)
    unit = {f"l{i}": layer_template(cfg, k, f)
            for i, (k, f) in enumerate(zip(unit_kinds, unit_ffns))}
    t["stack"] = stack_tree(reps, unit)
    if tail_kinds:
        t["tail"] = {f"l{i}": layer_template(cfg, k, f)
                     for i, (k, f) in enumerate(zip(tail_kinds, tail_ffns))}
    if cfg.dtype != "bfloat16":
        import dataclasses as _dc
        from repro.models.params import ParamSpec

        def _cast(s):
            if s.dtype == "bfloat16":
                return _dc.replace(s, dtype=cfg.dtype)
            return s
        t = jax.tree.map(_cast, t, is_leaf=lambda x: isinstance(x, ParamSpec))
    return t


# ---------------------------------------------------------------------------
# Cache templates
# ---------------------------------------------------------------------------
def layer_cache_template(cfg: ModelConfig, kind: str, batch: int, ctx: int) -> dict:
    t: dict = {}
    if kind == ATTN:
        t["attn"] = B.attn_cache_template(cfg, batch, ctx)
        if cfg.cross_attention:
            hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            t["xkv"] = {
                "k": spec([batch, hkv, cfg.encoder_seq, hd],
                          ("batch", "kv_heads", None, None), "zeros"),
                "v": spec([batch, hkv, cfg.encoder_seq, hd],
                          ("batch", "kv_heads", None, None), "zeros"),
            }
    elif kind == LOCAL_ATTN:
        t["attn"] = B.attn_cache_template(cfg, batch, ctx, window=cfg.attn_window)
    elif kind == RGLRU:
        t["rglru"] = B.rglru_cache_template(cfg, batch)
    elif kind == SSM:
        t["ssm"] = B.ssm_cache_template(cfg, batch)
    return t


def cache_template(cfg: ModelConfig, batch: int, ctx: int) -> dict:
    unit_kinds, reps, tail_kinds = layer_plan(cfg)
    unit = {f"l{i}": layer_cache_template(cfg, k, batch, ctx)
            for i, k in enumerate(unit_kinds)}
    t = {"stack": stack_tree(reps, unit)}
    if tail_kinds:
        t["tail"] = {f"l{i}": layer_cache_template(cfg, k, batch, ctx)
                     for i, k in enumerate(tail_kinds)}
    if cfg.dtype != "bfloat16":
        import dataclasses as _dc
        from repro.models.params import ParamSpec

        def _cast(s):
            if s.dtype == "bfloat16":
                return _dc.replace(s, dtype=cfg.dtype)
            return s
        t = jax.tree.map(_cast, t, is_leaf=lambda x: isinstance(x, ParamSpec))
    return t


# ---------------------------------------------------------------------------
# Unit application (one pattern repetition)
# ---------------------------------------------------------------------------
def _apply_ffn(p, x, *, cfg, ffn, impl, counts, length=None):
    """The layer's feed-forward block.  Returns (x, counts): a dropless
    expert layer adds its routing counters (int32 [2]) to ``counts``.
    ``length``: as in :func:`prefill` (padding is routed to no expert)."""
    if ffn == MOE:
        x, c = B.moe_apply(p["ffn"], x, cfg=cfg, impl=impl, length=length)
        if c is not None:
            counts = c if counts is None else counts + c
    elif ffn == MLP:
        x = B.mlp_apply(p["ffn"], x, cfg=cfg, impl=impl)
    return x, counts


def _apply_unit_seq(unit_params, x, *, cfg, kinds, ffns, positions, impl,
                    enc_out, enc_positions):
    """Full-sequence unit forward (no cache).  Returns x."""
    for i, kind in enumerate(kinds):
        p = unit_params[f"l{i}"]
        if kind in (ATTN, LOCAL_ATTN):
            window = cfg.attn_window if kind == LOCAL_ATTN else 0
            x = B.attn_apply(p["attn"], x, cfg=cfg, positions=positions,
                             impl=impl, causal=True, window=window)
            if cfg.cross_attention:
                x = B.attn_apply(p["xattn"], x, cfg=cfg, positions=positions,
                                 impl=impl, causal=False, kv_src=enc_out,
                                 kv_positions=enc_positions)
        elif kind == RGLRU:
            x, _ = B.rglru_apply(p["rglru"], x, cfg=cfg, impl=impl)
        elif kind == SSM:
            x, _ = B.ssm_apply(p["ssm"], x, cfg=cfg, impl=impl)
        x, _ = _apply_ffn(p, x, cfg=cfg, ffn=ffns[i], impl=impl, counts=None)
    return x


def _apply_unit_seq_exact(unit_params, x, *, cfg, kinds, ffns, positions,
                          impl, enc_out, enc_positions, ctx, length=None):
    """Like _apply_unit_seq but computes the attention caches from the exact
    pre-block residual stream (used by prefill).  ``length`` (traced scalar):
    positions >= length are right-padding (bucketed prefill) — attention is
    already exact under a causal mask, so padding only has to be masked out
    of the KV caches and the recurrent state updates.  Returns (x, cache,
    counts): the unit's dropless routing counters, or None."""
    cache_out: dict = {}
    counts = None
    for i, kind in enumerate(kinds):
        p = unit_params[f"l{i}"]
        c: dict = {}
        if kind in (ATTN, LOCAL_ATTN):
            window = cfg.attn_window if kind == LOCAL_ATTN else 0
            c["attn"] = B.attn_prefill_cache(p["attn"], x, cfg=cfg,
                                             positions=positions, window=window,
                                             ctx=ctx, length=length)
            x = B.attn_apply(p["attn"], x, cfg=cfg, positions=positions,
                             impl=impl, causal=True, window=window)
            if cfg.cross_attention:
                h = L.rms_norm(x, p["xattn"]["ln"], cfg.norm_eps)
                _, xk, xv = B._qkv(p["xattn"], h, enc_out, cfg)
                xk = L.apply_rope(xk, enc_positions[:, None, :], cfg.rope_theta)
                c["xkv"] = {"k": xk, "v": xv}
                x = B.attn_apply(p["xattn"], x, cfg=cfg, positions=positions,
                                 impl=impl, causal=False, kv_src=enc_out,
                                 kv_positions=enc_positions)
        elif kind == RGLRU:
            x, st = B.rglru_apply(p["rglru"], x, cfg=cfg, impl=impl,
                                  length=length)
            c["rglru"] = st
        elif kind == SSM:
            x, st = B.ssm_apply(p["ssm"], x, cfg=cfg, impl=impl, length=length)
            c["ssm"] = st
        x, counts = _apply_ffn(p, x, cfg=cfg, ffn=ffns[i], impl=impl,
                               counts=counts, length=length)
        cache_out[f"l{i}"] = c
    return x, cache_out, counts


def _apply_unit_decode(unit_params, unit_cache, x, *, cfg, kinds, ffns, pos,
                       impl):
    """Single-token unit forward.  Returns (x, new_unit_cache, counts)."""
    new_cache: dict = {}
    counts = None
    for i, kind in enumerate(kinds):
        p = unit_params[f"l{i}"]
        c = unit_cache[f"l{i}"]
        nc: dict = {}
        if kind in (ATTN, LOCAL_ATTN):
            window = cfg.attn_window if kind == LOCAL_ATTN else 0
            x, nc["attn"] = B.attn_decode(p["attn"], x, c["attn"], cfg=cfg,
                                          pos=pos, impl=impl, window=window)
            if cfg.cross_attention:
                enc_sp = jnp.broadcast_to(
                    jnp.arange(cfg.encoder_seq, dtype=jnp.int32)[None],
                    (x.shape[0], cfg.encoder_seq))
                x, _ = B.attn_decode(
                    p["xattn"], x, None, cfg=cfg, pos=pos, impl=impl,
                    cross_kv=(c["xkv"]["k"], c["xkv"]["v"], enc_sp))
                nc["xkv"] = c["xkv"]
        elif kind == RGLRU:
            x, nc["rglru"] = B.rglru_decode(p["rglru"], x, c["rglru"], cfg=cfg,
                                            impl=impl)
        elif kind == SSM:
            x, nc["ssm"] = B.ssm_decode(p["ssm"], x, c["ssm"], cfg=cfg, impl=impl)
        x, counts = _apply_ffn(p, x, cfg=cfg, ffn=ffns[i], impl=impl,
                               counts=counts)
        new_cache[f"l{i}"] = nc
    return x, new_cache, counts


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------
def encode(params, frames, *, cfg, impl=None):
    """frames: [B, S_frames, frontend_dim] -> [B, S_enc, D].

    With ``cfg.conv_stem`` the frames pass through whisper's two k=3 conv1d
    layers (stride 1 then stride 2, gelu after each) so S_enc = S_frames/2;
    otherwise a single linear projection with S_enc = S_frames."""
    if "stem" in params:
        from repro.core.regions import dispatch
        st = params["stem"]
        x = frames.astype(st["w1"].dtype)    # conv needs matching dtypes
        x = dispatch("conv_stem", impl, x, st["w1"], st["b1"], stride=1)
        x = dispatch("conv_stem", impl, x.astype(st["w2"].dtype),
                     st["w2"], st["b2"], stride=2)
        x = x.astype(jnp.dtype(cfg.dtype))
    else:
        x = (frames @ params["w_front"]).astype(jnp.dtype(cfg.dtype))
    pos = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32)[None],
                           x.shape[:2])

    def body(carry, p):
        h = B.attn_apply(p["attn"], carry, cfg=cfg, positions=pos, impl=impl,
                         causal=False)
        h = B.mlp_apply(p["ffn"], h, cfg=cfg, impl=impl)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["stack"])
    return L.rms_norm(x, params["encoder"]["ln"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
def _embed_inputs(params, cfg, tokens, frontend_emb):
    d = cfg.d_model
    x = L.embed(tokens, params["embed"]) * math.sqrt(d)
    x = x.astype(jnp.dtype(cfg.dtype))
    n_front = 0
    if cfg.frontend == "siglip_stub" and frontend_emb is not None:
        fe = (frontend_emb @ params["w_front"]).astype(x.dtype)
        x = jnp.concatenate([fe, x], axis=1)
        n_front = fe.shape[1]
    return x, n_front


def _maybe_remat(fn, remat: str):
    if remat in ("none", "2level"):
        return fn
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)   # 'full': save nothing


def _closest_divisor(n: int, target: int) -> int:
    best = 1
    for d in range(1, n + 1):
        if n % d == 0 and abs(d - target) < abs(best - target):
            best = d
    return best


def _scan_stack(unit_body, x, stack, remat: str):
    """Scan over the layer stack; remat='2level' uses sqrt(L) segment
    checkpointing (outer scan over G groups, inner CHECKPOINTED scan over
    L/G layers) so the saved residuals are G layer-boundary activations
    instead of L — the memory lever that lets big-model cells drop their
    gradient-accumulation factor (EXPERIMENTS.md §Perf, kimi iteration)."""
    if remat != "2level":
        x, _ = jax.lax.scan(_maybe_remat(unit_body, remat), x, stack)
        return x
    reps = jax.tree.leaves(stack)[0].shape[0]
    g = _closest_divisor(reps, int(np.sqrt(reps)) or 1)
    grouped = jax.tree.map(
        lambda t: t.reshape((g, reps // g) + t.shape[1:]), stack)
    # inner units keep the dots policy (attention/MLP internals rematted);
    # the outer checkpoint drops the inner layer-boundary residuals too.
    inner_body = jax.checkpoint(
        unit_body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    @jax.checkpoint
    def group_body(carry, group_params):
        out, _ = jax.lax.scan(inner_body, carry, group_params)
        return out, None

    x, _ = jax.lax.scan(group_body, x, grouped)
    return x


def forward(params, tokens, *, cfg: ModelConfig, impl=None, frontend_emb=None,
            remat: str = "none"):
    """Training/scoring forward.  Returns logits [B, S(+front), vocab]."""
    x, n_front = _embed_inputs(params, cfg, tokens, frontend_emb)
    bsz, s_tot = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s_tot, dtype=jnp.int32)[None],
                                 (bsz, s_tot))
    enc_out = enc_pos = None
    if cfg.encoder_layers:
        enc_out = encode(params, frontend_emb, cfg=cfg, impl=impl)
        enc_pos = jnp.broadcast_to(
            jnp.arange(cfg.encoder_seq, dtype=jnp.int32)[None],
            (bsz, enc_out.shape[1]))

    unit_kinds, reps, tail_kinds = layer_plan(cfg)
    unit_ffns, tail_ffns = layer_ffns(cfg)

    def unit_body(carry, unit_params):
        out = _apply_unit_seq(unit_params, carry, cfg=cfg, kinds=unit_kinds,
                              ffns=unit_ffns, positions=positions, impl=impl,
                              enc_out=enc_out, enc_positions=enc_pos)
        return out, None

    x = _scan_stack(unit_body, x, params["stack"], remat)
    if tail_kinds:
        x = _apply_unit_seq(params["tail"], x, cfg=cfg, kinds=tail_kinds,
                            ffns=tail_ffns, positions=positions, impl=impl,
                            enc_out=enc_out, enc_positions=enc_pos)
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    if n_front:
        x = x[:, n_front:]
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return L.unembed(x, table, cfg.tie_embeddings)


def loss_fn(params, batch, *, cfg: ModelConfig, impl=None, remat: str = "none"):
    """Next-token cross-entropy.  batch: {'tokens', optional 'frames'/'patches'}."""
    tokens = batch["tokens"]
    fe = batch.get("patches", batch.get("frames"))
    logits = forward(params, tokens, cfg=cfg, impl=impl, frontend_emb=fe,
                     remat=remat)
    labels = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _total(counts) -> jax.Array:
    """The routing counters summed over the units (zeros where none)."""
    if counts is None:
        return jnp.zeros(2, jnp.int32)
    return counts.reshape(-1, 2).sum(0)


def prefill(params, tokens, *, cfg: ModelConfig, impl=None, frontend_emb=None,
            ctx: Optional[int] = None, length=None, counts: bool = False):
    """Prefill: forward + exact KV/state caches.  Returns (logits_last, cache),
    and with ``counts`` a third output: the dropless expert layers' routing
    counters summed over the layers, int32 [2] (pairs routed to the experts
    held here, held experts reached; zeros for a model without them).

    ctx: cache capacity (>= prompt length); defaults to prompt length.
    length: traced scalar count of REAL prompt tokens when ``tokens`` is
    right-padded to a bucket (serving-engine bucketed prefill).  The returned
    logits are then taken at the last real position and the caches are masked
    so they are identical to an unpadded prefill of ``length`` tokens (for
    token-routed MoE layers identity holds per bucket — routing capacity sees
    the padded length; dropless layers route the real tokens only).  None =
    every token is real (existing behavior)."""
    x, n_front = _embed_inputs(params, cfg, tokens, frontend_emb)
    bsz, s_tot = x.shape[:2]
    ctx = max(ctx or s_tot, s_tot)   # frontend prefix counts toward capacity
    # the frontend prefix is always real: valid positions are [0, n_front+length)
    valid = None if length is None else length + n_front
    positions = jnp.broadcast_to(jnp.arange(s_tot, dtype=jnp.int32)[None],
                                 (bsz, s_tot))
    enc_out = enc_pos = None
    if cfg.encoder_layers:
        enc_out = encode(params, frontend_emb, cfg=cfg, impl=impl)
        enc_pos = jnp.broadcast_to(
            jnp.arange(cfg.encoder_seq, dtype=jnp.int32)[None],
            (bsz, enc_out.shape[1]))
    unit_kinds, reps, tail_kinds = layer_plan(cfg)
    unit_ffns, tail_ffns = layer_ffns(cfg)

    def unit_body(carry, unit_params):
        out, c, n = _apply_unit_seq_exact(unit_params, carry, cfg=cfg,
                                          kinds=unit_kinds, ffns=unit_ffns,
                                          positions=positions, impl=impl,
                                          enc_out=enc_out,
                                          enc_positions=enc_pos, ctx=ctx,
                                          length=valid)
        return out, (c, n)

    x, (stack_cache, routed) = jax.lax.scan(unit_body, x, params["stack"])
    cache = {"stack": stack_cache}
    if tail_kinds:
        x, tail_cache, n = _apply_unit_seq_exact(
            params["tail"], x, cfg=cfg, kinds=tail_kinds, ffns=tail_ffns,
            positions=positions, impl=impl, enc_out=enc_out,
            enc_positions=enc_pos, ctx=ctx, length=valid)
        cache["tail"] = tail_cache
        if n is not None:
            routed = n if routed is None else _total(routed) + n
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    if valid is None:
        x_last = x[:, -1:]
    else:
        x_last = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
    logits_last = L.unembed(x_last, table, cfg.tie_embeddings)
    if counts:
        return logits_last, cache, _total(routed)
    return logits_last, cache


def decode_step(params, cache, tokens, pos, *, cfg: ModelConfig, impl=None,
                counts: bool = False):
    """One decode step.  tokens: [B, 1] int32; pos: [B] int32 absolute
    position of this token.  Returns (logits [B, 1, V], new_cache), and with
    ``counts`` the routing counters as :func:`prefill` gives them."""
    x = L.embed(tokens, params["embed"]) * math.sqrt(cfg.d_model)
    x = x.astype(jnp.dtype(cfg.dtype))
    unit_kinds, reps, tail_kinds = layer_plan(cfg)
    unit_ffns, tail_ffns = layer_ffns(cfg)

    def unit_body(carry, scanned):
        unit_params, unit_cache = scanned
        out, nc, n = _apply_unit_decode(unit_params, unit_cache, carry,
                                        cfg=cfg, kinds=unit_kinds,
                                        ffns=unit_ffns, pos=pos, impl=impl)
        return out, (nc, n)

    x, (new_stack, routed) = jax.lax.scan(
        unit_body, x, (params["stack"], cache["stack"]))
    new_cache = {"stack": new_stack}
    if tail_kinds:
        x, nc, n = _apply_unit_decode(params["tail"], cache["tail"], x,
                                      cfg=cfg, kinds=tail_kinds,
                                      ffns=tail_ffns, pos=pos, impl=impl)
        new_cache["tail"] = nc
        if n is not None:
            routed = n if routed is None else _total(routed) + n
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = L.unembed(x, table, cfg.tie_embeddings)
    if counts:
        return logits, new_cache, _total(routed)
    return logits, new_cache
