"""Plain float32 reference of jamba2-mini as served (one 8-layer period of
AI21 Jamba Mini, 8 of its 16 experts held, a vocabulary slice).

Written from the equations, in ``jax.numpy`` with every product at
``Precision.HIGHEST``, one layer, one sequence and one expert at a time so
that it fits beside the served bfloat16 weights.  It imports nothing of the
program; it reads the weights the benchmark drew, by their names (each
layer's leaves carry a leading axis of 1, the one period):

    embed [V, D]; final_ln [D]; unembed [D, V]
    stack/l<i>/ssm/{ln, w_in, conv_w, conv_b, w_dbc, dt_ln, b_ln, c_ln,
                    w_dt, dt_bias, a_log, d_skip, w_out}     Mamba layers
    stack/l<i>/attn/{ln, wq, wk, wv, wo}                      attention layer
    stack/l<i>/ffn/{ln, w_gate, w_up, w_down}                 dense MLP
    stack/l<i>/ffn/{ln, router [D, E], w_gate [H, D, F], w_up [H, D, F],
                    w_down [H, F, D]}                         expert layer

Layer i is attention where i % attn_layer_period == attn_layer_offset and
Mamba otherwise; its feed-forward block is the expert layer where
i % expert_layer_period == expert_layer_offset and the dense MLP otherwise.
With x the residual stream (the embedding times sqrt(D)) and
rms(v, w) = v / sqrt(mean(v^2) + eps) * (1 + w):

    Mamba:  h = rms(x, ln);  xi, z = split(h @ w_in)
            xi = silu(causal depthwise conv of xi with conv_w, plus conv_b)
            dt, B, C = split(xi @ w_dbc, [R, R + N])
            dt = rms(dt, dt_ln);  B = rms(B, b_ln);  C = rms(C, c_ln)
            dt = softplus(dt @ w_dt + dt_bias);  A = -exp(a_log)
            s_t = exp(dt_t A) * s_{t-1} + (dt_t xi_t) B_t;  y_t = s_t . C_t
            x = x + ((y + D * xi) * silu(z)) @ w_out
    attention (no positional encoding; GQA, query head j reads KV head
            j // (Hq / Hkv)):  h = rms(x, ln)
            x = x + softmax_causal(q k^T / sqrt(hd)) v @ wo,
            q, k, v = h @ wq, h @ wk, h @ wv
    dense MLP: h = rms(x, ln);  x = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down
    experts: h = rms(x, ln);  p = softmax(h @ router) over all E routed
            experts (float32);  (g_1, e_1), (g_2, e_2) = its top 2, the
            weights NOT renormalised;  x = x + sum_j [first <= e_j <
            first + H] g_j * (silu(h @ w_gate[e']) * (h @ w_up[e'])) @
            w_down[e'], e' = e_j - first: the held experts' share only

and logits = rms(x, final_ln) @ unembed, over the vocabulary slice.

``precision="fp8"`` is the control (``CONTROL``): every matrix product
takes its weight and its input rounded to float8 e4m3 (scaled per output
column and per row), the step below the configuration's bfloat16.
``precision="bfloat16"`` rounds them to bfloat16 instead: the step below
float32, for a program run in float32 (the tests on the CPU).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0
# options of ``served_logits`` that make it the control
CONTROL = {"precision": "fp8"}
# query positions per block of the attention's scores
Q_BLOCK = 256


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, low):
    """x @ w in float32, the operands first rounded as ``low`` says:
    None (not), "fp8" or "bf16"."""
    w = w.astype(jnp.float32)
    if low == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    elif low == "bf16":
        x, w = (v.astype(jnp.bfloat16).astype(jnp.float32) for v in (x, w))
    return jnp.dot(x, w, precision=HI)


def _period(p):
    """A layer's leaves without their leading period axis of 1 (inside a
    jit, where dropping it copies nothing)."""
    return jax.tree.map(lambda a: a[0], p)


def _rms(x, w, eps):
    w = w.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


@partial(jax.jit, static_argnames=("eps", "low"))
def _mamba(p, x, *, eps: float, low):
    """One Mamba layer over one sequence: x [T, D] float32 -> [T, D]."""
    p = _period(p)
    di, n = p["a_log"].shape
    r = p["w_dt"].shape[0]
    k = p["conv_w"].shape[0]
    conv_w = p["conv_w"].astype(jnp.float32)
    h = _rms(x, p["ln"], eps)
    xz = _mm(h, p["w_in"], low)
    xi, z = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((k - 1, di), jnp.float32), xi], 0)
    xi = sum(xp[i:i + xi.shape[0]] * conv_w[i] for i in range(k))
    xi = jax.nn.silu(xi + p["conv_b"].astype(jnp.float32))
    dbc = _mm(xi, p["w_dbc"], low)
    dt = _rms(dbc[:, :r], p["dt_ln"], eps)
    bm = _rms(dbc[:, r:r + n], p["b_ln"], eps)
    cm = _rms(dbc[:, r + n:], p["c_ln"], eps)
    dt = jax.nn.softplus(_mm(dt, p["w_dt"], low)
                         + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    def step(s, inp):
        dt_t, x_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t[None]
        return s, jnp.sum(s * c_t[None], -1)

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32), (dt, xi, bm, cm))
    y = (y + p["d_skip"].astype(jnp.float32) * xi) * jax.nn.silu(z)
    return x + _mm(y, p["w_out"], low)


@partial(jax.jit, static_argnames=("eps", "low", "heads", "kv_heads"))
def _attention(p, x, *, eps: float, low, heads: int, kv_heads: int):
    """The attention layer over one sequence, causal, without positions;
    the scores are computed Q_BLOCK queries at a time."""
    p = _period(p)
    t, _ = x.shape
    h = _rms(x, p["ln"], eps)
    hd = p["wq"].shape[1] // heads
    g = heads // kv_heads
    q = _mm(h, p["wq"], low).reshape(t, kv_heads, g, hd) / np.sqrt(hd)
    k = _mm(h, p["wk"], low).reshape(t, kv_heads, hd)
    v = _mm(h, p["wv"], low).reshape(t, kv_heads, hd)
    block = Q_BLOCK if t % Q_BLOCK == 0 else t

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI)
        mask = (jnp.arange(t)[None, :]
                <= (i * block + jnp.arange(block))[:, None])
        s = jnp.where(mask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", w, v, precision=HI)

    out = jax.lax.map(rows, jnp.arange(t // block)).reshape(t, heads * hd)
    return x + _mm(out, p["wo"], low)


@partial(jax.jit, static_argnames=("eps", "low"))
def _mlp(p, x, *, eps: float, low):
    p = _period(p)
    h = _rms(x, p["ln"], eps)
    return x + _mm(jax.nn.silu(_mm(h, p["w_gate"], low))
                   * _mm(h, p["w_up"], low), p["w_down"], low)


@partial(jax.jit, static_argnames=("eps", "low", "k"))
def _route(p, x, *, eps: float, low, k: int):
    """The expert layer's input and each token's top-k (weight, expert id)
    of a float32 softmax over every routed expert, not renormalised."""
    p = _period(p)
    h = _rms(x, p["ln"], eps)
    gate, idx = jax.lax.top_k(jax.nn.softmax(_mm(h, p["router"], low), -1), k)
    return h, gate, idx


@partial(jax.jit, static_argnames=("low",))
def _expert(p, h, gate, idx, held, first, *, low):
    """Held expert ``held`` (expert id ``first + held``): its SwiGLU output,
    weighted by each token's gate for it (0 where the token did not pick
    it).  Only this expert's weights are read and widened."""
    w_gate, w_up, w_down = (p[name][0, held]
                            for name in ("w_gate", "w_up", "w_down"))
    weight = jnp.sum(jnp.where(idx == first + held, gate, 0.0), -1)
    y = _mm(jax.nn.silu(_mm(h, w_gate, low)) * _mm(h, w_up, low), w_down, low)
    return weight[:, None] * y


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, final_ln, *, eps: float):
    return _rms(x, final_ln, eps)


@partial(jax.jit, static_argnames=("low",))
def _head(xn, w, *, low):
    return _mm(xn, w, low)


def _experts(p, x, conf, eps, low):
    h, gate, idx = _route(p, x, eps=eps, low=low,
                          k=conf["num_experts_per_tok"])
    acc = x
    for e in range(p["w_gate"].shape[1]):
        acc = acc + _expert(p, h, gate, idx, np.int32(e),
                            np.int32(conf["experts_first"]), low=low)
    return acc


def served_logits(weights, conf: dict, sequences, *, length: int,
                  served_max: int,
                  precision: str = "float32") -> list[np.ndarray]:
    """Logits at every served position of each sequence.

    ``sequences`` holds (prompt ids, served ids) pairs; the model reads the
    prompt and every served token but the last, right-padded to
    ``length`` (causal, so padding changes nothing before it); every
    sequence has at most ``served_max`` served tokens.  The shapes are
    fixed by the two, so each step compiles once.  Returns, per sequence,
    float32 logits [len(served), V]: row i is the prediction of served
    token i."""
    low = {"float32": None, "bfloat16": "bf16", "fp8": "fp8"}[precision]
    eps = float(conf["rms_norm_eps"])
    d = conf["hidden_size"]
    with jax.default_matmul_precision("highest"):
        xs = []
        for prompt, served in sequences:
            ids = np.zeros(length, np.int32)
            seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            ids[:seq.size] = seq
            xs.append(jnp.take(weights["embed"], jnp.asarray(ids), axis=0)
                      .astype(jnp.float32) * np.float32(np.sqrt(d)))
        stack = weights["stack"]
        for i in range(conf["num_hidden_layers"]):
            layer = stack[f"l{i}"]
            if i % conf["attn_layer_period"] == conf["attn_layer_offset"]:
                xs = [_attention(layer["attn"], x, eps=eps, low=low,
                                 heads=conf["num_attention_heads"],
                                 kv_heads=conf["num_key_value_heads"])
                      for x in xs]
            else:
                xs = [_mamba(layer["ssm"], x, eps=eps, low=low) for x in xs]
            if i % conf["expert_layer_period"] == conf["expert_layer_offset"]:
                xs = [_experts(layer["ffn"], x, conf, eps, low) for x in xs]
            else:
                xs = [_mlp(layer["ffn"], x, eps=eps, low=low) for x in xs]
        out = []
        for (prompt, served), x in zip(sequences, xs):
            idx = np.minimum(np.arange(served_max) + prompt.size - 1,
                             length - 1)
            xn = _final_norm(jnp.take(x, jnp.asarray(idx), axis=0),
                             weights["final_ln"], eps=eps)
            logits = np.asarray(_head(xn, weights["unembed"], low=low))
            out.append(logits[:served.size])
        return out


def served_gaps(ref_logits: list[np.ndarray], sequences) -> np.ndarray:
    """For every served token, how far its reference logit lies below the
    reference's best at that position (0 where they agree)."""
    gaps = [lg.max(-1) - lg[np.arange(served.size), served]
            for lg, (_, served) in zip(ref_logits, sequences)]
    return np.concatenate(gaps) if gaps else np.zeros(0)
