"""Arithmetic-intensity analysis — the paper's Step 2 (PGI-tool analogue).

The paper runs an arithmetic-intensity tool over each loop statement and
keeps the top ``a``.  Here the "tool" is a jaxpr walker: for a region
function we count flops (dot_general exact; elementwise 1/elem;
transcendentals weighted), count the bytes the region moves at its boundary
(inputs + outputs — the loop's "data size and access count"), and define

    AI = flops / boundary_bytes.

``alignment_penalty`` models the paper's FPGA-clock caveat on TPU: regions
whose innermost dims don't tile to the 128-lane / (8,128)-sublane layout get
their effective AI discounted, because an offload kernel cannot feed the MXU
efficiently.  Loops (scan/while) are multiplied by trip count, mirroring how
trip counts raise the paper's AI metric.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

# flop weight for transcendental ops (hardware transcendental units retire
# these slower than FMAs; the exact number only needs to rank loops)
TRANSCENDENTAL_WEIGHT = 8.0

_ELEMENTWISE_1 = {
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "floor", "ceil",
    "round", "sign", "rem", "and", "or", "xor", "not", "select_n", "clamp",
    "add_any", "pow",
    # comparisons and shifts retire one ALU op per element (integer
    # arithmetic used to silently fall through and count zero)
    "eq", "ne", "lt", "le", "ge", "gt",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
}
_TRANSCENDENTAL = {
    "exp", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan", "rsqrt",
    "sqrt", "logistic", "erf", "erf_inv", "cbrt", "atan2", "exp2",
}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "argmax", "argmin",
           "cumsum", "cumprod", "cummax", "cummin"}

# explicitly zero-flop: data movement / layout / type bookkeeping.  These
# retire no arithmetic, but classifying them (instead of silently falling
# through) keeps `unclassified` an honest to-do list for ops the extractor
# feeds through here.
_ZERO_FLOP = {
    "convert_element_type", "bitcast_convert_type", "reduce_precision",
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "rev", "gather", "scatter", "iota", "copy", "stop_gradient",
    "real", "imag", "conj", "is_finite", "device_put", "split",
    "optimization_barrier", "sharding_constraint",
}


def _aval_elems(aval) -> int:
    return int(np.prod(aval.shape)) if aval.shape else 1


def _aval_bytes(aval) -> int:
    return _aval_elems(aval) * jnp.dtype(aval.dtype).itemsize


@dataclass
class RegionAnalysis:
    name: str = ""
    flops: float = 0.0              # raw counts — never penalty-discounted,
    transcendentals: float = 0.0    # so roofline projections stay honest
    boundary_bytes: float = 0.0
    loop_count: int = 0             # jaxpr loop statements (scan/while/fori)
    max_trip: float = 1.0
    alignment: float = 1.0          # layout penalty, applied at ranking time
    # primitives the walker could not classify (name -> occurrences): any
    # entry here means the flop count may be low for this region
    unclassified: dict = field(default_factory=dict)

    @property
    def weighted_flops(self) -> float:
        # the penalty discounts the WHOLE weighted total: discounting only
        # `flops` would under-penalize transcendental-heavy misaligned
        # regions in the Step-2 AI ranking
        return self.alignment * (
            self.flops + TRANSCENDENTAL_WEIGHT * self.transcendentals)

    @property
    def arithmetic_intensity(self) -> float:
        return self.weighted_flops / max(self.boundary_bytes, 1.0)


def _count_jaxpr(jaxpr, mult: float, acc: RegionAnalysis) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        out_elems = sum(_aval_elems(v.aval) for v in eqn.outvars)
        if prim == "dot_general":
            dims = eqn.params["dimension_numbers"]
            (lc, _), _ = dims
            lhs = eqn.invars[0].aval
            contract = 1
            for d in lc:
                contract *= lhs.shape[d]
            acc.flops += mult * 2.0 * out_elems * contract
        elif prim == "conv_general_dilated":
            lhs = eqn.invars[0].aval
            rhs = eqn.invars[1].aval
            # flops = 2 * out_elems * (reduction size per output element)
            red = int(np.prod(rhs.shape[2:])) * rhs.shape[1] if len(rhs.shape) > 2 else _aval_elems(rhs)
            acc.flops += mult * 2.0 * out_elems * red
        elif prim in _TRANSCENDENTAL:
            acc.transcendentals += mult * out_elems
        elif prim in _ELEMENTWISE_1:
            acc.flops += mult * out_elems
        elif prim in _REDUCE:
            in_elems = sum(_aval_elems(v.aval) for v in eqn.invars)
            acc.flops += mult * in_elems
        elif prim == "integer_pow":
            acc.flops += mult * out_elems * 2
        elif prim == "top_k":
            # selection network: ~1 comparison per input element
            acc.flops += mult * _aval_elems(eqn.invars[0].aval)
        elif prim == "sort":
            n = max(_aval_elems(eqn.invars[0].aval), 2)
            acc.flops += mult * n * float(np.log2(n))
        elif prim == "scatter-add":
            # one add per routed update element (MoE slot dispatch)
            acc.flops += mult * _aval_elems(eqn.invars[2].aval)
        elif prim == "scan":
            length = float(eqn.params.get("length", 1))
            acc.loop_count += 1
            acc.max_trip = max(acc.max_trip, mult * length)
            _count_jaxpr(eqn.params["jaxpr"].jaxpr, mult * length, acc)
            continue
        elif prim == "while":
            acc.loop_count += 1
            # unknown dynamic trip count: assume 1 (conservative), still walk
            _count_jaxpr(eqn.params["body_jaxpr"].jaxpr, mult, acc)
            continue
        elif prim == "cond":
            for branch in eqn.params["branches"]:
                _count_jaxpr(branch.jaxpr, mult, acc)
            continue
        elif prim in ("jit", "custom_jvp_call", "custom_vjp_call",
                      "custom_vjp_call_jaxpr", "closed_call", "remat", "checkpoint"):
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr") or eqn.params.get("fun_jaxpr")
            if inner is not None:
                _count_jaxpr(getattr(inner, "jaxpr", inner), mult, acc)
            continue
        elif prim in _ZERO_FLOP:
            continue                # data movement: explicitly zero flops
        else:
            acc.unclassified[prim] = acc.unclassified.get(prim, 0) + 1
    return


def alignment_penalty(avals) -> float:
    """1.0 if the innermost dims are MXU/VPU friendly (multiples of 128, or
    >= 512); down to 0.25 for scalar-ish shapes (paper's FPGA-clock caveat:
    the offload only wins when the loop suits the accelerator)."""
    score = 1.0
    for aval in avals:
        if not aval.shape:
            continue
        last = aval.shape[-1]
        if last % 128 == 0:
            continue
        if last >= 512:
            score = min(score, 0.9)
        elif last >= 128:
            score = min(score, 0.75)
        else:
            score = min(score, 0.25)
    return score


def analyze_region(fn, *args, name: str = "") -> RegionAnalysis:
    """AI analysis of ``fn(*args)``.  Args may be arrays or
    ShapeDtypeStructs (no execution happens — pure tracing)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    acc = RegionAnalysis(name=name)
    _count_jaxpr(jaxpr.jaxpr, 1.0, acc)
    in_avals = [v.aval for v in jaxpr.jaxpr.invars]
    out_avals = [v.aval for v in jaxpr.jaxpr.outvars]
    acc.boundary_bytes = float(sum(_aval_bytes(a) for a in in_avals)
                               + sum(_aval_bytes(a) for a in out_avals))
    acc.alignment = alignment_penalty(in_avals)
    return acc


def count_loops(fn, *args) -> int:
    """Total loop statements (scan/while) in the traced program — the
    Step-1 'code analysis' loop census (Clang-parse analogue)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    acc = RegionAnalysis()
    _count_jaxpr(jaxpr.jaxpr, 1.0, acc)
    return acc.loop_count
