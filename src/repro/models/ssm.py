"""Mamba-1 selective-state-space block (falcon-mamba-7b).

Recurrence per channel c and state n:
    h_t = exp(dt_t * A[c,n]) * h_{t-1} + dt_t * B_t[n] * x_t[c]
    y_t = sum_n C_t[n] * h_t[c,n] + D[c] * x_t[c]

Training/prefill uses a *chunked associative scan*: ``lax.scan`` over chunks
of the sequence carrying h, with ``lax.associative_scan`` inside each chunk.
This bounds the [B, chunk, D, N] working set (the full [B, S, D, N] tensor at
S=4k, D=8k, N=16 would be >1 TB fp32 per pod) while keeping O(log chunk)
sequential depth inside the chunk.  TPU adaptation note: on FPGA the paper's
offload target is the loop nest; here the offload target is this scan region,
and the Pallas kernel (`kernels/ssm_scan.py`) tiles channels into VMEM.

``mamba_block`` hands the region the factored form (dt, x, A, B: see
``kernels.ssm_scan.Factored``), never the [B, S, D, N] decay and input;
each variant builds those where it needs them, the ``seq`` kernel one step
at a time in VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.regions import register_variant
from repro.kernels.ssm_scan import Factored
from repro.kernels.ssm_scan import ssm_scan as ssm_scan_kernel
from repro.models.layers import rms_norm
from repro.parallel import ctx as parallel_ctx


# ---------------------------------------------------------------------------
# Depthwise causal conv (kernel size K, shift-and-add formulation)
# ---------------------------------------------------------------------------
def causal_depthwise_conv(x: jax.Array, w: jax.Array, state: jax.Array | None = None,
                          length: jax.Array | None = None,
                          bias: jax.Array | None = None):
    """x: [B, S, D]; w: [K, D]; state: [B, K-1, D] trailing context or None;
    bias: [D] added to every output, or None.

    ``length`` (traced scalar): only the first ``length`` positions of x are
    real — the returned state is then the K-1 inputs *ending at* position
    ``length`` (bucketed prefill right-pads x, and the trailing context must
    not contain padding).  None = all S positions are real.

    Returns (y [B, S, D], new_state [B, K-1, D])."""
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)                  # [B, S+K-1, D]
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    if bias is not None:
        y = y + bias
    if k <= 1:
        new_state = jnp.zeros_like(state)
    elif length is None:
        new_state = xp[:, -(k - 1):]
    else:
        # inputs at positions [length-(K-1), length) = xp[length : length+K-1]
        new_state = jax.lax.dynamic_slice_in_dim(xp, length, k - 1, axis=1)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Selective scan (region: "ssm_scan")
# ---------------------------------------------------------------------------
def _arrays(a, bx):
    """The (a, bx) arrays of either input form of the region."""
    return a.expand() if isinstance(a, Factored) else (a, bx)


def _assoc_combine(l, r):
    a_l, b_l = l
    a_r, b_r = r
    return a_l * a_r, b_l * a_r + b_r


@register_variant("ssm_scan", "ref")
def ssm_scan_ref(a: jax.Array, bx: jax.Array, c: jax.Array, h0: jax.Array,
                 chunk: int = 256):
    """a, bx: [B, S, D, N] (decay and input), or ``a`` a
    :class:`~repro.kernels.ssm_scan.Factored` and ``bx`` None (expanded
    here); c: [B, S, N]; h0: [B, D, N].

    Returns (y [B, S, D], h_final [B, D, N])."""
    a, bx = _arrays(a, bx)
    b, s, d, n = a.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
        bx = jnp.pad(bx, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk
    a = a.reshape(b, nc, chunk, d, n)
    bx = bx.reshape(b, nc, chunk, d, n)
    c = c.reshape(b, nc, chunk, n)

    def chunk_body(h, inp):
        a_c, bx_c, c_c = inp                                   # [B, chunk, D, N]
        cum_a, cum_b = jax.lax.associative_scan(_assoc_combine, (a_c, bx_c), axis=1)
        h_t = cum_a * h[:, None] + cum_b                       # [B, chunk, D, N]
        y_c = jnp.einsum("btdn,btn->btd", h_t, c_c)
        return h_t[:, -1], y_c

    # scan over chunks: move chunk axis first
    a_s = jnp.moveaxis(a, 1, 0)
    bx_s = jnp.moveaxis(bx, 1, 0)
    c_s = jnp.moveaxis(c, 1, 0)
    h_f, ys = jax.lax.scan(chunk_body, h0.astype(a.dtype), (a_s, bx_s, c_s))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * chunk, d)[:, :s]
    return y, h_f


@register_variant("ssm_scan", "offload")
def ssm_scan_offload(a, bx, c, h0, chunk: int = 512):
    """Same math, larger chunks + fp32 state accumulation (the restructuring
    the Pallas kernel implements: fewer carries, MXU-aligned einsum)."""
    a, bx = _arrays(a, bx)
    return ssm_scan_ref(a.astype(jnp.float32), bx.astype(jnp.float32),
                        c.astype(jnp.float32), h0, chunk=chunk)


@register_variant("ssm_scan", "seq")
def ssm_scan_seq_chunked(a, bx, c, h0, chunk: int = 256):
    """Time-SEQUENTIAL scan: one step after another, the state carried.

    Where its inputs are built depends on the form it is given.  Given the
    factored form (what ``mamba_block`` hands it), it runs the Pallas
    kernel, which discretises each step in registers and keeps the state
    in VMEM (:func:`_seq_factored`; under a mesh, whose partitioner cannot
    split a kernel, it expands the form and takes the XLA path).  Given
    arrays, it is a chunked XLA scan: the associative-scan formulation
    streams O(S log chunk) bytes of slice/concat intermediates per level;
    this reads each element exactly once per pass (perf iteration
    'falcon-mamba A1', EXPERIMENTS.md §Perf).  Outer scan carries h across
    chunks (checkpointed, so backward recomputes within-chunk states from
    the chunk-boundary h instead of storing [B, S, D, N] residuals)."""
    if isinstance(a, Factored):
        if parallel_ctx.current() is None:
            return _seq_factored(a, c, h0)
        a, bx = a.expand()
    b, s, d, n = a.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
        bx = jnp.pad(bx, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk
    a_s = jnp.moveaxis(a.reshape(b, nc, chunk, d, n), 1, 0)
    bx_s = jnp.moveaxis(bx.reshape(b, nc, chunk, d, n), 1, 0)
    c_s = jnp.moveaxis(c.reshape(b, nc, chunk, n), 1, 0)

    @jax.checkpoint
    def chunk_body(h, inp):
        a_c, bx_c, c_c = inp                       # [B, chunk, D, N]

        def step(hh, t_inp):
            a_t, bx_t, c_t = t_inp                 # [B, D, N], [B, N]
            hh = a_t * hh + bx_t
            y_t = jnp.einsum("bdn,bn->bd", hh, c_t)
            return hh, y_t

        h, ys = jax.lax.scan(step, h,
                             (jnp.moveaxis(a_c, 1, 0),
                              jnp.moveaxis(bx_c, 1, 0),
                              jnp.moveaxis(c_c, 1, 0)))
        return h, jnp.moveaxis(ys, 0, 1)           # [B, chunk, D]

    h_f, ys = jax.lax.scan(chunk_body, h0.astype(a.dtype), (a_s, bx_s, c_s))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * chunk, d)[:, :s]
    return y, h_f


# (block_c, time_chunk) of the seq variant's kernel, the fastest of a sweep
# at buckets 128 and 512 on a TPU v5e (PERF.md, section 5)
SEQ_TILES = (1024, 128)


@jax.custom_vjp
def _seq_factored(f: Factored, c, h0):
    """The seq schedule as one Pallas kernel over the factored form: the
    [B, S, D, N] decay and input never reach HBM.  Differentiated through
    the chunked XLA scan on the expanded form (recomputed in backward)."""
    block_c, time_chunk = SEQ_TILES
    d = f.dt.shape[-1]
    return ssm_scan_kernel(f, None, c, h0, time_chunk=time_chunk,
                           block_c=block_c if d % block_c == 0 else d)


def _seq_factored_fwd(f, c, h0):
    return _seq_factored(f, c, h0), (f, c, h0)


def _seq_factored_bwd(res, g):
    def xla(f, c, h0):
        y, h_f = ssm_scan_seq_chunked(*f.expand(), c, h0)
        return y.astype(g[0].dtype), h_f.astype(g[1].dtype)
    return jax.vjp(xla, *res)[1](g)


_seq_factored.defvjp(_seq_factored_fwd, _seq_factored_bwd)


def ssm_decode_step(a, bx, c, h):
    """Single-token recurrence.  a, bx: [B, D, N]; c: [B, N]; h: [B, D, N]."""
    h_new = a * h + bx
    y = jnp.einsum("bdn,bn->bd", h_new, c)
    return y, h_new


# ---------------------------------------------------------------------------
# Full Mamba block
# ---------------------------------------------------------------------------
def _dt_b_c(params, xi, cfg):
    """dt (after dt_proj and softplus), B and C of the mixer input ``xi``.
    Under ``cfg.ssm_dt_norm`` dt (before dt_proj), B and C are first
    RMS-normalised with their learned weights (jamba)."""
    dbc = xi @ params["w_dbc"]                                 # [B, S, dt_rank + 2N]
    dtr = cfg.resolved_dt_rank
    dt, bmat, cmat = jnp.split(dbc, [dtr, dtr + cfg.ssm_state], axis=-1)
    if cfg.ssm_dt_norm:
        dt = rms_norm(dt, params["dt_ln"], cfg.norm_eps)
        bmat = rms_norm(bmat, params["b_ln"], cfg.norm_eps)
        cmat = rms_norm(cmat, params["c_ln"], cfg.norm_eps)
    dt = jax.nn.softplus(dt @ params["w_dt"] + params["dt_bias"])   # [B, S, Di]
    return dt, bmat, cmat


def mamba_block(params, x, *, cfg, impl=None, state=None, length=None):
    """x: [B, S, D_model].  state: None (train) or dict(conv, h) for decode-
    style stateful prefill.  ``length`` (traced scalar): positions >= length
    are right-padding — their dt is zeroed, which makes their recurrence
    steps the identity (a=1, bx=0), so the final state is exactly the state
    after ``length`` real tokens (bucketed prefill).  Returns (y, new_state)."""
    from repro.core.regions import dispatch

    b, s, _ = x.shape
    di, n = cfg.d_inner, cfg.ssm_state
    xz = x @ params["w_in"]                                    # [B, S, 2*Di]
    xi, z = jnp.split(xz, 2, axis=-1)
    conv_state = None if state is None else state["conv"]
    xi, new_conv = causal_depthwise_conv(xi, params["conv_w"], conv_state,
                                         length=length,
                                         bias=params.get("conv_b"))
    xi = jax.nn.silu(xi)

    # input-dependent dt, B, C
    dt, bmat, cmat = _dt_b_c(params, xi, cfg)
    a_log = -jnp.exp(params["a_log"].astype(jnp.float32))      # [Di, N]

    if length is not None:
        # dt = 0 makes a padded step the identity: a = exp(0) = 1, bx = 0
        dt = jnp.where((jnp.arange(s) < length)[None, :, None], dt, 0)
    from repro.parallel.ctx import constrain
    inner = ("batch", None, "inner")
    # the factored form: each variant builds a = exp(dt * A) and
    # bx = dt * x * B [B, S, Di, N] where it needs them
    factors = Factored(constrain(dt, inner), constrain(xi, inner), a_log, bmat)
    h0 = (jnp.zeros((b, di, n), jnp.float32) if state is None
          else state["h"].astype(jnp.float32))
    y, h_f = dispatch("ssm_scan", impl, factors, None, cmat.astype(x.dtype),
                      h0)
    y = y + xi * params["d_skip"]
    y = y * jax.nn.silu(z)
    out = y @ params["w_out"]
    new_state = {"conv": new_conv, "h": h_f.astype(jnp.float32)}
    return out.astype(x.dtype), new_state


def mamba_decode_step(params, x, state, *, cfg, impl=None):
    """x: [B, 1, D_model]; state: dict(conv [B, K-1, Di], h [B, Di, N])."""
    xz = x @ params["w_in"]
    xi, z = jnp.split(xz, 2, axis=-1)                          # [B, 1, Di]
    xi, new_conv = causal_depthwise_conv(xi, params["conv_w"], state["conv"],
                                         bias=params.get("conv_b"))
    xi = jax.nn.silu(xi)

    dt, bmat, cmat = _dt_b_c(params, xi, cfg)
    a_log = -jnp.exp(params["a_log"].astype(jnp.float32))

    a = jnp.exp(dt[:, 0, :, None].astype(jnp.float32) * a_log)     # [B, Di, N]
    bx = (dt * xi)[:, 0, :, None] * bmat[:, 0, None, :]        # [B, Di, N]
    y, h_new = ssm_decode_step(a.astype(jnp.float32), bx.astype(jnp.float32),
                               cmat[:, 0].astype(jnp.float32), state["h"])
    y = y[:, None, :].astype(x.dtype) + xi * params["d_skip"]
    y = y * jax.nn.silu(z)
    out = y @ params["w_out"]
    return out.astype(x.dtype), {"conv": new_conv, "h": h_new}
