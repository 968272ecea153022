"""tdFIR Pallas kernel — the paper's first evaluation app (HPEC challenge).

Complex FIR filter bank: for bank m, output sample n:
    y[m, n] = sum_k h[m, k] * x[m, n - k]      (complex MAC, x[<0] = 0)

TPU adaptation of the paper's FPGA offload.  The kernel works on the
transposed layout — samples on sublanes, banks on lanes — so every block
meets the (8, 128) tiling: banks are blocked by 128 lanes (or all of them
when there are fewer), samples by ``block_n`` rows.  Each grid step reads a
window of ``block_n + halo`` input rows (``halo`` = K-1 rounded up to 8),
overlapping its predecessor by the halo; tap k then multiplies the window
rows ``[halo - k, halo - k + block_n)`` by the bank-wise tap row ``h[k]``
on the VPU.  The paper's loop-unroll knob ``b`` maps to ``tap_unroll``
(taps processed per fori_loop step).

Complex numbers are carried as separate re/im planes (TPU has no complex
vector unit; 4 real MACs per complex MAC, 8 flops — same count the paper's
AI analysis uses).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def largest_divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is <= ``cap`` (>= 1).  Used to
    clamp proposed tile knobs to legal values: the autotuner may propose
    any point, and legality lives in the TuningSpace predicate — the
    kernel itself must degrade gracefully, never assert."""
    cap = max(1, min(cap, n))
    for d in range(cap, 0, -1):
        if n % d == 0:
            return d
    return 1


def fir_halo(n_taps: int) -> int:
    """Input rows a sample block reads before its first sample: K-1,
    rounded up to the 8-row sublane tile."""
    return -(-(n_taps - 1) // 8) * 8


def fir_bank_block(n_banks: int) -> int:
    """Banks per grid step: one 128-lane tile, or every bank when the
    count is not a multiple of 128 (a block equal to the array is legal)."""
    return 128 if n_banks % 128 == 0 else n_banks


def fir_vmem_bytes(n_banks: int, n_taps: int, block_n: int) -> float:
    """VMEM one grid step holds: the input window, the taps and the output
    tile, re and im planes each, float32, one lane-padded bank block wide."""
    lanes = -(-fir_bank_block(n_banks) // 128) * 128
    rows = 2 * (block_n + fir_halo(n_taps)) + 2 * n_taps + 2 * block_n
    return 4.0 * rows * lanes


def _fir_kernel(xr_ref, xi_ref, hr_ref, hi_ref, yr_ref, yi_ref, *,
                n_taps: int, halo: int, block_n: int, tap_unroll: int):
    # x window: [block_n + halo, banks]; window row halo + n is sample n of
    # this block, so tap k reads rows [halo - k, halo - k + block_n)
    banks = yr_ref.shape[-1]
    acc_r = jnp.zeros((block_n, banks), jnp.float32)
    acc_i = jnp.zeros((block_n, banks), jnp.float32)

    def tap_body(t, carry):
        ar, ai = carry
        for u in range(tap_unroll):                       # paper's unroll `b`
            k = t * tap_unroll + u
            hr = hr_ref[pl.ds(k, 1), :]                   # [1, banks]
            hi = hi_ref[pl.ds(k, 1), :]
            xr = xr_ref[pl.ds(halo - k, block_n), :]      # [block_n, banks]
            xi = xi_ref[pl.ds(halo - k, block_n), :]
            ar = ar + hr * xr - hi * xi
            ai = ai + hr * xi + hi * xr
        return ar, ai

    acc_r, acc_i = jax.lax.fori_loop(0, n_taps // tap_unroll, tap_body,
                                     (acc_r, acc_i))
    yr_ref[...] = acc_r.astype(yr_ref.dtype)
    yi_ref[...] = acc_i.astype(yi_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "tap_unroll", "interpret"))
def fir_filter_bank(x: jax.Array, h: jax.Array, *, block_n: int = 512,
                    tap_unroll: int = 1,
                    interpret: bool | None = None) -> jax.Array:
    """x: complex64 [M, N]; h: complex64 [M, K].  Returns y [M, N].

    VMEM per grid step at HPEC set 1 (M=64 banks padded to 128 lanes,
    K=128, block_n=512): (2 * (512 + 128) + 2 * 128 + 2 * 512) rows *
    128 lanes * 4 B ~= 1.3 MB, double-buffered ~= 2.6 MB << 16 MiB."""
    m, n = x.shape
    _, k = h.shape
    # proposed tile knobs are clamped, not asserted: the tuner owns
    # legality (TuningSpace predicate) and an illegal point must still
    # produce a correct, measurable kernel.  Both knobs are static under
    # jit, so the clamp (and its warning) happens once per trace.
    if n % block_n != 0 or block_n > n:
        eff = largest_divisor(n, block_n)
        warnings.warn(
            f"fir_filter_bank: block_n={block_n} invalid for n={n}; "
            f"clamped to {eff}", stacklevel=2)
        block_n = eff
    if k % tap_unroll != 0 or tap_unroll > k:
        eff = largest_divisor(k, tap_unroll)
        warnings.warn(
            f"fir_filter_bank: tap_unroll={tap_unroll} invalid for k={k}; "
            f"clamped to {eff}", stacklevel=2)
        tap_unroll = eff
    halo = fir_halo(k)
    bm = fir_bank_block(m)
    # [N + halo, M]: `halo` leading zeros make the causal edge a plain read
    xr = jnp.pad(jnp.real(x).astype(jnp.float32).T, ((halo, 0), (0, 0)))
    xi = jnp.pad(jnp.imag(x).astype(jnp.float32).T, ((halo, 0), (0, 0)))
    hr = jnp.real(h).astype(jnp.float32).T                   # [K, M]
    hi = jnp.imag(h).astype(jnp.float32).T

    # overlapping windows: element-offset indexing on both dims.  With one
    # bank block the lane offset is the literal 0, which Mosaic can prove
    # tile-aligned whatever the bank count.
    x_spec = pl.BlockSpec(
        (pl.Element(block_n + halo), pl.Element(bm)),
        lambda i, j: (j * block_n, 0 if bm == m else i * bm))
    h_spec = pl.BlockSpec((k, bm), lambda i, j: (0, i))
    y_spec = pl.BlockSpec((block_n, bm), lambda i, j: (j, i))
    yr, yi = pl.pallas_call(
        functools.partial(_fir_kernel, n_taps=k, halo=halo, block_n=block_n,
                          tap_unroll=tap_unroll),
        grid=(m // bm, n // block_n),
        in_specs=[x_spec, x_spec, h_spec, h_spec],
        out_specs=[y_spec, y_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), jnp.float32),
            jax.ShapeDtypeStruct((n, m), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xr, xi, hr, hi)
    return (yr.T + 1j * yi.T).astype(jnp.complex64)
