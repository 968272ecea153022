"""Static jaxpr loop/block extraction — Step 1 for *unannotated* programs.

The paper's Step 1 is a Clang-based static pass that enumerates an
application's loop statements before any measurement happens.  The
annotated path (``make_lm_program``, ``apps/``) plays that role by hand:
someone decides which blocks are regions.  This module is the automatic
version — trace a jitted function, walk its jaxpr, and statically
recognize the computational blocks the kernel registry already knows how
to offload (``attn_core``, ``mlp_core``, ``ssm_scan``, ``rglru_scan``,
``fir_bank``, ``rmsnorm``, ``mlp_gelu``, ``conv_stem``,
``moe_dispatch``), the function-block extension of the loop-statement
pipeline (arXiv 2004.09883).  Adjacent legal matches are additionally
*stitched* into fused regions (``left+right``) the planner prices against
their split forms, and every near-miss is recorded as a structured
:class:`Rejection` for diagnostics.  The result is an
:class:`~repro.core.program.OffloadableProgram` that flows into the
planner, strategies, surrogate, executor, and plan cache unchanged.

Layers
------
enumerator
    :func:`enumerate_sites` / ``_Ctx``: trace the function, walk the jaxpr
    descending ``scan``/``while``/``cond``/``jit`` sub-jaxprs, and emit
    candidate sites — the TPU analogue of the paper's loop statements:
    scans (affine carries, softmax-normalized matmul chains, FIR shapes),
    ``rsqrt`` norm anchors, gated ``dot_general`` clusters.
recognizers
    ``_match_*``: structural matchers from a site to a
    :class:`RegionMatch` — the kernel family, the jaxpr vars that become
    the variant's arguments/results, and the covered equation set.
legality
    ``_legalize``: trip-count visibility (nothing inside ``while``/
    ``cond`` is offloadable), side-effect check, escape analysis (no
    covered intermediate may be consumed outside the region), dtype
    gates, and the arithmetic-intensity / alignment numbers Step 2 needs
    (via :func:`repro.core.intensity.analyze_region`).
binder
    ``_region_fn`` slices the matched sub-jaxpr into a standalone callable
    with ``ShapeDtypeStruct`` signatures recovered from the jaxpr (the
    region's ``analysis_fn``), and ``_make_build`` re-emits the whole
    program through a jaxpr interpreter that routes every matched region
    through :func:`repro.core.regions.dispatch` — so ``build(impl)``
    honors arbitrary offload patterns exactly like an annotated program.

Entry points: :func:`extract` (analysis only, returns an
:class:`ExtractionReport`) and :func:`discover` (returns the planner-ready
``OffloadableProgram``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

try:                                    # jax >= 0.4.33
    from jax.extend.core import Literal
except ImportError:                     # pragma: no cover - older jax
    from jax.core import Literal

from repro.core.intensity import RegionAnalysis, analyze_region
from repro.core.program import OffloadableProgram, Region
from repro.core.regions import REGISTRY, Impl, dispatch, register_variant

# families this pass can recognize, in recognizer precedence order
FAMILIES = ("attn_core", "ssm_scan", "rglru_scan", "fir_bank", "moe_dispatch",
            "conv_stem", "mlp_gelu", "mlp_core", "rmsnorm")

# dtypes the registered kernel variants accept (legality gate)
_FLOAT_OK = ("bfloat16", "float32")
_FIR_OK = ("complex64", "float32")

# higher-order primitives whose single sub-jaxpr is evaluated inline
_WRAPPERS = ("jit", "closed_call", "custom_jvp_call", "custom_vjp_call",
             "remat2", "checkpoint", "custom_vjp_call_jaxpr")

# pure data-layout primitives (peelable during operand recovery)
_LAYOUT = ("reshape", "transpose", "squeeze", "expand_dims", "slice")


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


def _shape(v):
    return tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())


def _dtype(v) -> str:
    return str(getattr(getattr(v, "aval", None), "dtype", ""))


def _sub_jaxprs(eqn):
    """(jaxpr, consts) pairs of an eqn's sub-jaxprs, in evaluation order."""
    name = eqn.primitive.name
    out = []
    if name == "scan":
        c = eqn.params["jaxpr"]
        out.append((c.jaxpr, list(c.consts)))
    elif name == "while":
        for key in ("cond_jaxpr", "body_jaxpr"):
            c = eqn.params[key]
            out.append((c.jaxpr, list(c.consts)))
    elif name == "cond":
        for c in eqn.params["branches"]:
            out.append((c.jaxpr, list(c.consts)))
    elif name in _WRAPPERS:
        c = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
             or eqn.params.get("fun_jaxpr"))
        if c is not None:
            out.append((getattr(c, "jaxpr", c), list(getattr(c, "consts", ()))))
    return out


# ---------------------------------------------------------------------------
# Enumerator: the jaxpr walk
# ---------------------------------------------------------------------------
@dataclass
class _Node:
    """Per-jaxpr metadata the recognizers and the binder share."""
    jaxpr: Any
    consts: list
    path: tuple                              # enclosing container kinds
    parent: Optional[int]
    producers: dict = field(default_factory=dict)   # var -> (idx, eqn)
    consumers: dict = field(default_factory=dict)   # var -> [(idx, eqn)]
    invar_pos: dict = field(default_factory=dict)   # var -> invar index
    constvals: dict = field(default_factory=dict)   # constvar -> value
    eqn_children: dict = field(default_factory=dict)  # idx -> [jaxpr ids]


class _Ctx:
    """The traced program: root jaxpr plus every reachable sub-jaxpr.

    Holds strong references to the closed jaxpr so ``id(jaxpr)`` keys stay
    valid for the lifetime of any program built from this context."""

    def __init__(self, closed):
        self.closed = closed
        self.nodes: dict[int, _Node] = {}
        self.order: list[int] = []           # DFS pre-order
        self._register(closed.jaxpr, list(closed.consts), (), None)

    def _register(self, jaxpr, consts, path, parent):
        jid = id(jaxpr)
        if jid in self.nodes:                # shared sub-jaxpr: keep first
            return
        node = _Node(jaxpr, consts, path, parent)
        for i, v in enumerate(jaxpr.invars):
            node.invar_pos[v] = i
        node.constvals = dict(zip(jaxpr.constvars, consts))
        for i, e in enumerate(jaxpr.eqns):
            for v in e.outvars:
                if not _is_drop(v):
                    node.producers[v] = (i, e)
            for v in e.invars:
                if not isinstance(v, Literal):
                    node.consumers.setdefault(v, []).append((i, e))
        self.nodes[jid] = node
        self.order.append(jid)
        for i, e in enumerate(jaxpr.eqns):
            kids = []
            for sub, sconsts in _sub_jaxprs(e):
                kids.append(id(sub))
                self._register(sub, sconsts, path + (e.primitive.name,), jid)
            if kids:
                node.eqn_children[i] = kids

    def subtree(self, jid: int) -> set:
        """jaxpr ids of ``jid`` and everything nested under it."""
        out, stack = set(), [jid]
        while stack:
            j = stack.pop()
            if j in out:
                continue
            out.add(j)
            for kids in self.nodes[j].eqn_children.values():
                stack.extend(kids)
        return out


@dataclass
class CandidateSite:
    """One enumerator hit — the analogue of a paper 'loop statement'."""
    kind: str           # "scan" | "while" | "norm" | "gate" | "act" | "conv" | "route"
    path: tuple         # enclosing container kinds from the root
    eqn_index: int
    primitive: str


def enumerate_sites(ctx: _Ctx) -> list[CandidateSite]:
    """All candidate anchors: loops plus softmax/norm/gate/activation/conv/
    routing eqns."""
    sites = []
    for jid in ctx.order:
        node = ctx.nodes[jid]
        for i, e in enumerate(node.jaxpr.eqns):
            name = e.primitive.name
            if name in ("scan", "while"):
                sites.append(CandidateSite(name, node.path, i, name))
            elif name == "rsqrt":
                sites.append(CandidateSite("norm", node.path, i, name))
            elif name == "logistic":
                sites.append(CandidateSite("gate", node.path, i, name))
            elif name == "tanh":
                sites.append(CandidateSite("act", node.path, i, name))
            elif name == "conv_general_dilated":
                sites.append(CandidateSite("conv", node.path, i, name))
            elif name == "top_k":
                sites.append(CandidateSite("route", node.path, i, name))
            elif name == "jit" and _silu_inner(e) is not None:
                sites.append(CandidateSite("gate", node.path, i, name))
    return sites


# ---------------------------------------------------------------------------
# Var-chasing utilities
# ---------------------------------------------------------------------------
def _peel(ctx: _Ctx, jaxpr, v, allowed):
    """Follow ``v`` back through producer eqns whose primitive is in
    ``allowed``, staying at (or returning to) the given jaxpr level.
    Wrapper eqns (jit around a pad, sharding constraints) are crossed only
    when the chain fully exits through one of their inputs.  ``mul``/
    ``div``/``add`` are followed through their non-scalar operand."""
    while True:
        if isinstance(v, Literal):
            return jaxpr, v
        node = ctx.nodes[id(jaxpr)]
        prod = node.producers.get(v)
        if prod is None:
            return jaxpr, v
        _, eqn = prod
        name = eqn.primitive.name
        if name in _WRAPPERS:
            subs = _sub_jaxprs(eqn)
            if len(subs) != 1:
                return jaxpr, v
            inner = subs[0][0]
            pos = [i for i, o in enumerate(eqn.outvars) if o is v]
            ij, ivv = _peel(ctx, inner, inner.outvars[pos[0]], allowed)
            if ij is inner and not isinstance(ivv, Literal):
                ipos = ctx.nodes[id(inner)].invar_pos.get(ivv)
                if ipos is not None:
                    v = eqn.invars[ipos]
                    continue
            return jaxpr, v
        if name not in allowed:
            return jaxpr, v
        if name in ("mul", "div", "add", "sub"):
            a, b = eqn.invars
            if isinstance(b, Literal) or _shape(b) == ():
                v = a
            elif name in ("mul", "add") and (isinstance(a, Literal)
                                             or _shape(a) == ()):
                v = b
            else:
                return jaxpr, v
            continue
        v = eqn.invars[0]


def _forward(ctx: _Ctx, jaxpr, v, allowed, want_shape, limit: int = 12):
    """Follow single-consumer layout chains forward until the var has
    ``want_shape``.  Returns the var or None."""
    node = ctx.nodes[id(jaxpr)]
    for _ in range(limit):
        if _shape(v) == tuple(want_shape):
            return v
        cons = node.consumers.get(v, [])
        if len(cons) != 1:
            return None
        _, eqn = cons[0]
        if eqn.primitive.name not in allowed or eqn.invars[0] is not v:
            return None
        v = eqn.outvars[0]
    return None


def _backward_sources(node: _Node, v, stop_at=()) -> set:
    """All jaxpr invars backward-reachable from ``v`` within one jaxpr."""
    out, seen, stack = set(), set(), [v]
    stops = set(map(id, stop_at))
    while stack:
        cur = stack.pop()
        if isinstance(cur, Literal) or id(cur) in seen or id(cur) in stops:
            continue
        seen.add(id(cur))
        prod = node.producers.get(cur)
        if prod is None:
            if cur in node.invar_pos:
                out.add(cur)
            continue
        stack.extend(prod[1].invars)
    return out


def _slice_from(node: _Node, outs, stops):
    """Backward slice: covered eqn indices reachable from ``outs`` stopping
    at ``stops``; also returns free leaves beyond stops/constvars."""
    covered, leaves, seen = set(), [], set()
    stop_ids = set(map(id, stops))
    stack = list(outs)
    while stack:
        v = stack.pop()
        if isinstance(v, Literal) or id(v) in seen or id(v) in stop_ids:
            continue
        seen.add(id(v))
        prod = node.producers.get(v)
        if prod is None:
            if v not in node.constvals:
                leaves.append(v)
            continue
        idx, eqn = prod
        if idx not in covered:
            covered.add(idx)
            stack.extend(eqn.invars)
    return covered, leaves


# ---------------------------------------------------------------------------
# Matches
# ---------------------------------------------------------------------------
@dataclass
class RegionMatch:
    """One recognized block: where it lives, what the variant call binds.

    ``invars``/``outvars`` are jaxpr vars at the level ``jaxpr_id`` points
    to; ``covered`` the eqn indices the region replaces; ``static_kwargs``
    the variant's compile-time knobs (e.g. ``causal``/``window``)."""
    family: str
    jaxpr_id: int
    path: tuple
    invars: tuple = ()
    outvars: tuple = ()
    covered: frozenset = frozenset()
    static_kwargs: dict = field(default_factory=dict)
    legal: bool = True
    reason: str = ""
    analysis: Optional[RegionAnalysis] = None

    def arg_shapes(self) -> list[str]:
        return [f"{_dtype(v)}{list(_shape(v))}" for v in self.invars]


@dataclass
class Rejection:
    """A structured near-miss: a candidate site that looked like ``family``
    but failed a recognizer precondition, a legality gate, or a stitching
    check.  ``stage`` says which layer said no; ``reason`` is the
    human-readable diagnostic ``--explain`` renders."""
    family: str
    path: tuple
    reason: str
    primitive: str = ""
    eqn_index: int = -1
    stage: str = "recognizer"        # recognizer | legality | stitch


@dataclass
class ExtractionReport:
    """What the static pass found (before and after legality)."""
    name: str
    sites: list = field(default_factory=list)
    matches: list = field(default_factory=list)     # every RegionMatch
    rejections: list = field(default_factory=list)  # every Rejection
    loop_count: int = 0

    @property
    def legal_matches(self) -> list:
        return [m for m in self.matches if m.legal]

    @property
    def families(self) -> list[str]:
        seen = []
        for m in self.legal_matches:
            if m.family not in seen:
                seen.append(m.family)
        return seen

    def summary(self) -> str:
        lines = [f"extract[{self.name}]: {len(self.sites)} candidate sites, "
                 f"{self.loop_count} loops, "
                 f"{len(self.legal_matches)}/{len(self.matches)} legal matches, "
                 f"{len(self.rejections)} rejections"]
        for m in self.matches:
            mark = "+" if m.legal else "-"
            why = "" if m.legal else f"  [{m.reason}]"
            lines.append(f"  {mark} {m.family} @depth{len(m.path)} "
                         f"args={m.arg_shapes()}{why}")
        for r in self.rejections:
            at = f" @{r.primitive}" if r.primitive else ""
            lines.append(f"  ! {r.family} @depth{len(r.path)}{at} "
                         f"[{r.stage}] {r.reason}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Recognizer: rmsnorm
# ---------------------------------------------------------------------------
def _match_rmsnorm(ctx: _Ctx, jid: int, idx: int) -> Optional[RegionMatch]:
    node = ctx.nodes[jid]
    jaxpr = node.jaxpr
    rsqrt = jaxpr.eqns[idx]
    if rsqrt.primitive.name != "rsqrt":
        return None

    def producer(v, name):
        prod = node.producers.get(v)
        if prod and prod[1].primitive.name == name:
            return prod[1]
        return None

    # backward: rsqrt <- add(var, eps) <- div(sum, n) <- [bcast] <- reduce_sum
    #           <- mul(xf, xf) <- [convert] <- x
    add = producer(rsqrt.invars[0], "add")
    if add is None:
        return None
    eps = None
    mean_v = None
    for a, b in (add.invars, add.invars[::-1]):
        if isinstance(b, Literal) and np.ndim(b.val) == 0:
            eps, mean_v = float(b.val), a
    if eps is None:
        return None
    div = producer(mean_v, "div")
    if div is None or not isinstance(div.invars[1], Literal):
        return None
    n = float(div.invars[1].val)
    red_v = div.invars[0]
    bcast = producer(red_v, "broadcast_in_dim")
    if bcast is not None:
        red_v = bcast.invars[0]
    red = producer(red_v, "reduce_sum")
    if red is None:
        return None
    sq = producer(red.invars[0], "mul")
    if sq is None or sq.invars[0] is not sq.invars[1]:
        return None
    xf = sq.invars[0]
    _, x = _peel(ctx, jaxpr, xf, ("convert_element_type",))
    if _shape(x) == () or int(n) != _shape(x)[-1]:
        return None

    # forward: rsqrt out * xf, then * (1 + w) broadcast, then cast back
    def sole_mul(v):
        hits = [e for _, e in node.consumers.get(v, [])
                if e.primitive.name == "mul"]
        return hits[0] if len(hits) == 1 else None

    m1 = sole_mul(rsqrt.outvars[0])
    if m1 is None:
        return None
    m2 = sole_mul(m1.outvars[0])
    if m2 is None:
        return None
    scale_v = m2.invars[1] if m2.invars[0] is m1.outvars[0] else m2.invars[0]
    _, w = _peel(ctx, jaxpr, scale_v,
                 ("broadcast_in_dim", "convert_element_type", "add"))
    if len(_shape(w)) != 1 or _shape(w)[0] != _shape(x)[-1]:
        return None
    out = m2.outvars[0]
    cons = node.consumers.get(out, [])
    if len(cons) == 1 and cons[0][1].primitive.name == "convert_element_type" \
            and _dtype(cons[0][1].outvars[0]) == _dtype(x):
        out = cons[0][1].outvars[0]
    covered, leaves = _slice_from(node, [out], [x, w])
    if leaves:
        return None
    return RegionMatch("rmsnorm", jid, node.path, (x, w), (out,),
                       frozenset(covered), {"eps": eps})


# ---------------------------------------------------------------------------
# Recognizer: chunked online-softmax attention
# ---------------------------------------------------------------------------
def _match_attention(ctx: _Ctx, jid: int, idx: int) -> Optional[RegionMatch]:
    node = ctx.nodes[jid]
    outer = node.jaxpr.eqns[idx]
    if outer.primitive.name != "scan":
        return None
    b_o = outer.params["jaxpr"].jaxpr
    o_node = ctx.nodes[id(b_o)]
    inner_hits = [e for e in b_o.eqns
                  if e.primitive.name == "scan"
                  and e.params["num_carry"] == 3]
    if len(inner_hits) != 1:
        return None
    inner = inner_hits[0]
    b_i = inner.params["jaxpr"].jaxpr
    i_node = ctx.nodes[id(b_i)]
    prims = [e.primitive.name for e in b_i.eqns]
    dots = [e for e in b_i.eqns if e.primitive.name == "dot_general"]
    if len(dots) != 2 or "exp" not in prims or "reduce_max" not in prims:
        return None

    nc_i = inner.params["num_consts"]
    consts_i = set(b_i.invars[:nc_i])
    carries_i = set(b_i.invars[nc_i:nc_i + 3])
    # consts pulled apart with dynamic_slice inside the k-loop are the
    # chunked K / V planes; the remaining big float const is the Q chunk
    sliced = set()
    for e in b_i.eqns:
        if e.primitive.name == "dynamic_slice" and e.invars[0] in consts_i:
            sliced.add(e.invars[0])

    def const_sources(v):
        srcs = _backward_sources(i_node, v, stop_at=carries_i)
        return {s for s in srcs if s in consts_i and len(_shape(s)) >= 4}

    s_dot, pv_dot = dots
    qk_srcs = const_sources(s_dot.invars[0]) | const_sources(s_dot.invars[1])
    k_in = qk_srcs & sliced
    q_in = qk_srcs - sliced
    v_in = ((const_sources(pv_dot.invars[0])
             | const_sources(pv_dot.invars[1])) & sliced) - k_in
    if len(k_in) != 1 or len(q_in) != 1 or len(v_in) != 1:
        return None

    def lift_to_outer(v):
        """inner-scan const var -> var in the outer scan's body."""
        return inner.invars[i_node.invar_pos[v]]

    kb, vb = lift_to_outer(k_in.pop()), lift_to_outer(v_in.pop())
    qb = lift_to_outer(q_in.pop())
    # q is computed per outer iteration (slice + scale): peel to a body invar
    _, qb = _peel(ctx, b_o, qb, ("mul", "dynamic_slice", "squeeze",
                                 "convert_element_type", "broadcast_in_dim"))
    lifted = []
    for v in (qb, kb, vb):
        pos = o_node.invar_pos.get(v)
        if pos is None:
            return None
        lifted.append(outer.invars[pos])
    # at the site level, strip the ref prologue (pad to chunk multiple,
    # reshape to chunk grid) to recover the canonical [B, H, S, D] operands
    q, k, v = (_peel(ctx, node.jaxpr, lv, ("reshape", "pad"))[1]
               for lv in lifted)
    qs, ks, vs = _shape(q), _shape(k), _shape(v)
    if len(qs) != 4 or len(ks) != 4 or vs != ks:
        return None
    if qs[0] != ks[0] or qs[3] != ks[3] or qs[1] % max(ks[1], 1):
        return None

    ys = [ov for ov in outer.outvars[outer.params["num_carry"]:]
          if not _is_drop(ov)]
    if len(ys) != 1:
        return None
    out = _forward(ctx, node.jaxpr, ys[0],
                   ("transpose", "reshape", "slice", "squeeze"), qs)
    if out is None:
        return None

    causal = "le" in prims
    window = 0
    if "gt" in prims:
        lits = sorted({int(e.invars[1].val) for e in b_i.eqns
                       if e.primitive.name == "sub"
                       and isinstance(e.invars[1], Literal)
                       and np.ndim(e.invars[1].val) == 0
                       and "int" in _dtype(e.invars[0])})
        if not lits:
            return None            # windowed mask we can't parameterize
        window = lits[-1]
    covered, leaves = _slice_from(node, [out], [q, k, v])
    if leaves:
        return None
    return RegionMatch("attn_core", jid, node.path, (q, k, v), (out,),
                       frozenset(covered),
                       {"causal": causal, "window": window})


# ---------------------------------------------------------------------------
# Recognizer: affine-carry scans (SSM / RG-LRU) and FIR tap loops
# ---------------------------------------------------------------------------
def _counter_carries(body, nc, ncar):
    """Indices of scalar-int carries updated as ``c + 1`` (fori counters)."""
    out = []
    for ci in range(ncar):
        v = body.invars[nc + ci]
        if _shape(v) == () and "int" in _dtype(v):
            out.append(ci)
    return out


def _match_affine_scan(ctx: _Ctx, jid: int, idx: int) -> Optional[RegionMatch]:
    node = ctx.nodes[jid]
    eqn = node.jaxpr.eqns[idx]
    if eqn.primitive.name != "scan":
        return None
    body = eqn.params["jaxpr"].jaxpr
    b_node = ctx.nodes[id(body)]
    nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
    counters = _counter_carries(body, nc, ncar)
    data = [ci for ci in range(ncar) if ci not in counters]
    if len(data) != 1:
        return None
    if any(e.primitive.name == "scan" for e in body.eqns):
        return None                      # nested chunk loops: not this shape
    ci = data[0]
    h = body.invars[nc + ci]

    # the carry must feed exactly one mul (affine) or one add (accumulator),
    # possibly through a broadcast/reshape
    hv, chain = h, []
    for _ in range(3):
        cons = [c for c in b_node.consumers.get(hv, [])]
        if len(cons) != 1:
            return None
        e = cons[0][1]
        if e.primitive.name in ("broadcast_in_dim", "reshape",
                                "convert_element_type"):
            chain.append(e)
            hv = e.outvars[0]
            continue
        break
    if len(cons) != 1:
        return None
    upd = cons[0][1]
    dots = [e for e in body.eqns if e.primitive.name == "dot_general"]
    xs = body.invars[nc + ncar:]

    if upd.primitive.name == "add" and counters and not dots:
        return _match_fir(ctx, jid, idx, node, eqn, body, b_node, nc, ci, upd)
    if upd.primitive.name != "mul" or counters:
        return None

    # h_t = cum_a * h + cum_b
    cum_a = upd.invars[1] if upd.invars[0] is hv else upd.invars[0]
    adds = [c[1] for c in b_node.consumers.get(upd.outvars[0], [])
            if c[1].primitive.name == "add"]
    if len(adds) != 1:
        return None
    add = adds[0]
    cum_b = add.invars[1] if add.invars[0] is upd.outvars[0] else add.invars[0]
    a_src = _backward_sources(b_node, cum_a) & set(xs)
    b_src = (_backward_sources(b_node, cum_b) & set(xs)) - a_src
    if len(a_src) != 1 or len(b_src) != 1:
        return None
    a_var, b_var = next(iter(a_src)), next(iter(b_src))

    def lift(v, peel=("transpose", "reshape", "pad")):
        pos = b_node.invar_pos[v]
        return _peel(ctx, node.jaxpr, eqn.invars[pos], peel)[1]

    a = lift(a_var)
    bx = lift(b_var)
    h0 = eqn.invars[nc + ci]
    carry_out = eqn.outvars[ci]
    ys_out = [ov for ov in eqn.outvars[ncar:] if not _is_drop(ov)]
    if len(ys_out) != 1:
        return None

    if dots:                              # SSM: y_t = h_t . c_t
        if len(dots) != 1 or len(_shape(a)) != 4:
            return None
        dot = dots[0]
        c_src = ((_backward_sources(b_node, dot.invars[0])
                  | _backward_sources(b_node, dot.invars[1]))
                 & set(xs)) - {a_var, b_var}
        c_xs = list(c_src)
        if len(c_xs) != 1:
            return None
        c = lift(c_xs[0])
        bsz, s, d, _n = _shape(a)
        y = _forward(ctx, node.jaxpr, ys_out[0],
                     ("transpose", "reshape", "slice"), (bsz, s, d))
        if y is None:
            return None
        invars, family = (a, bx, c, h0), "ssm_scan"
    else:                                 # RG-LRU: gated diagonal recurrence
        if len(_shape(a)) != 3:
            return None
        bsz, s, d = _shape(a)
        y = _forward(ctx, node.jaxpr, ys_out[0],
                     ("transpose", "reshape", "slice"), (bsz, s, d))
        if y is None:
            return None
        invars, family = (a, bx, h0), "rglru_scan"
    # the variant returns (y, final_state); a dropped final state simply
    # isn't bound (zip in the binder discards the tail)
    outs = tuple(v for v in (y, carry_out) if not _is_drop(v))
    covered, leaves = _slice_from(node, list(outs), list(invars))
    if leaves:
        return None
    return RegionMatch(family, jid, node.path, invars, outs,
                       frozenset(covered))


def _match_fir(ctx, jid, idx, node, eqn, body, b_node, nc, ci, upd):
    """FIR tap loop: counter + accumulator carry, acc += h[:, j] * slice(x)."""
    term = upd.invars[1] if upd.invars[0] is body.invars[nc + ci] \
        else upd.invars[0]
    prod = b_node.producers.get(term)
    if prod is None or prod[1].primitive.name != "mul":
        return None
    consts = set(body.invars[:nc])
    srcs = (_backward_sources(b_node, prod[1].invars[0])
            | _backward_sources(b_node, prod[1].invars[1])) & consts
    acc_shape = _shape(body.invars[nc + ci])
    # the signal plane is (padded) at least accumulator-width; the tap
    # vector is the narrow one
    x_in = [s for s in srcs if len(_shape(s)) == len(acc_shape)
            and _shape(s)[0] == acc_shape[0]
            and _shape(s)[-1] >= acc_shape[-1]]
    h_in = [s for s in srcs if s not in x_in]
    if len(x_in) != 1 or len(h_in) != 1:
        return None
    x = _peel(ctx, node.jaxpr, eqn.invars[b_node.invar_pos[x_in[0]]],
              ("pad",))[1]
    h = eqn.invars[b_node.invar_pos[h_in[0]]]
    if _shape(x) != acc_shape:
        return None
    out = eqn.outvars[ci]
    covered, leaves = _slice_from(node, [out], [x, h])
    if leaves:
        return None
    return RegionMatch("fir_bank", jid, node.path, (x, h), (out,),
                       frozenset(covered))


def _match_affine_while(ctx: _Ctx, jid: int, idx: int) -> Optional[RegionMatch]:
    """A recurrence written with ``while``: recognized, but never legal —
    the trip count is invisible to the planner (paper: loops whose
    iteration count can't be determined are excluded in Step 1)."""
    node = ctx.nodes[jid]
    eqn = node.jaxpr.eqns[idx]
    body = eqn.params["body_jaxpr"].jaxpr
    prims = {e.primitive.name for e in body.eqns}
    if not ({"mul", "add"} <= prims or "dynamic_slice" in prims):
        return None
    family = "ssm_scan" if "dot_general" in prims else "fir_bank" \
        if "dynamic_slice" in prims else "rglru_scan"
    return RegionMatch(family, jid, node.path, (), (), frozenset(),
                       legal=False,
                       reason="data-dependent trip count (while loop)")


# ---------------------------------------------------------------------------
# Recognizer: SwiGLU MLP (gated dot_general cluster)
# ---------------------------------------------------------------------------
def _silu_inner(eqn):
    """Is this jit a traced ``silu`` (logistic + self-mul)?  -> inner jaxpr"""
    if eqn.primitive.name != "jit":
        return None
    inner = eqn.params.get("jaxpr")
    if inner is None or len(eqn.invars) != 1 or len(eqn.outvars) != 1:
        return None
    names = sorted(e.primitive.name for e in inner.jaxpr.eqns)
    return inner.jaxpr if names == ["logistic", "mul"] else None


def _is_matmul(eqn) -> bool:
    if eqn.primitive.name != "dot_general":
        return False
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs_rank = len(_shape(eqn.invars[0]))
    return (tuple(lc), tuple(rc)) == ((lhs_rank - 1,), (0,)) and not lb and not rb


def _match_swiglu(ctx: _Ctx, jid: int, idx: int) -> Optional[RegionMatch]:
    node = ctx.nodes[jid]
    eqn = node.jaxpr.eqns[idx]
    if _silu_inner(eqn) is None:
        return None
    prod = node.producers.get(eqn.invars[0])
    if prod is None or not _is_matmul(prod[1]):
        return None
    d1 = prod[1]
    x, wg = d1.invars
    muls = [c[1] for c in node.consumers.get(eqn.outvars[0], [])
            if c[1].primitive.name == "mul"]
    if len(muls) != 1:
        return None
    m = muls[0]
    other = m.invars[1] if m.invars[0] is eqn.outvars[0] else m.invars[0]
    p2 = node.producers.get(other)
    if p2 is None or not _is_matmul(p2[1]) or p2[1].invars[0] is not x:
        return None
    wu = p2[1].invars[1]
    d3s = [c[1] for c in node.consumers.get(m.outvars[0], [])
           if _is_matmul(c[1])]
    if len(d3s) != 1 or d3s[0].invars[0] is not m.outvars[0]:
        return None
    d3 = d3s[0]
    wd = d3.invars[1]
    if any(len(_shape(w)) != 2 for w in (wg, wu, wd)):
        return None
    out = d3.outvars[0]
    covered, leaves = _slice_from(node, [out], [x, wg, wu, wd])
    if leaves:
        return None
    return RegionMatch("mlp_core", jid, node.path, (x, wg, wu, wd), (out,),
                       frozenset(covered))


# ---------------------------------------------------------------------------
# Recognizer: gelu-MLP (dot -> gelu tanh-approx -> dot), whisper encoder
# ---------------------------------------------------------------------------
def _scalar_lit(v) -> bool:
    return isinstance(v, Literal) and np.ndim(v.val) == 0


def _gelu_anchor(ctx: _Ctx, node: _Node, tanh_eqn):
    """Recognize ``jax.nn.gelu``'s tanh approximation around a ``tanh`` eqn:
    ``0.5 * h * (1 + tanh(c1 * (h + c2 * h**3)))``.  Returns ``(h, g)`` —
    the gelu input var and output var — or None."""
    jaxpr = node.jaxpr
    prod = node.producers.get(tanh_eqn.invars[0])
    if prod is None or prod[1].primitive.name != "mul":
        return None
    a, b = prod[1].invars
    inner = a if _scalar_lit(b) else b if _scalar_lit(a) else None
    if inner is None:
        return None
    prod = node.producers.get(inner)
    if prod is None or prod[1].primitive.name != "add":
        return None
    h = None
    for x1, x2 in (tuple(prod[1].invars), tuple(prod[1].invars)[::-1]):
        p2 = node.producers.get(x2)
        if p2 is None or p2[1].primitive.name != "mul":
            continue
        ma, mb = p2[1].invars
        cube = ma if _scalar_lit(mb) else mb if _scalar_lit(ma) else None
        if cube is None:
            continue
        p3 = node.producers.get(cube)
        if p3 and p3[1].primitive.name == "integer_pow" \
                and p3[1].params.get("y") == 3 and p3[1].invars[0] is x1:
            h = x1
            break
    if h is None:
        return None
    # forward: (1 + tanh), then the 0.5 and h factors in either mul order
    adds = [e for _, e in node.consumers.get(tanh_eqn.outvars[0], [])
            if e.primitive.name == "add"]
    if len(adds) != 1:
        return None
    v, used_h = adds[0].outvars[0], False
    for _ in range(3):
        muls = [e for _, e in node.consumers.get(v, [])
                if e.primitive.name == "mul"]
        if len(muls) != 1:
            break
        m = muls[0]
        other = m.invars[1] if m.invars[0] is v else m.invars[0]
        if not _scalar_lit(other):
            _, src = _peel(ctx, jaxpr, other,
                           ("convert_element_type", "broadcast_in_dim"))
            if src is not h:
                break
            used_h = True
        v = m.outvars[0]
    if not used_h:
        return None
    return h, v


def _peel_bias(ctx: _Ctx, jaxpr, v, width: int):
    """Peel a broadcast/convert/reshape chain down to a 1-D ``width`` bias."""
    _, b = _peel(ctx, jaxpr, v,
                 ("broadcast_in_dim", "convert_element_type", "reshape"))
    if len(_shape(b)) == 1 and _shape(b)[0] == width:
        return b
    return None


def _match_gelu_mlp(ctx: _Ctx, jid: int, idx: int):
    node = ctx.nodes[jid]
    jaxpr = node.jaxpr
    eqn = jaxpr.eqns[idx]
    if eqn.primitive.name != "tanh":
        return None
    hit = _gelu_anchor(ctx, node, eqn)
    if hit is None:
        return None
    h, g = hit
    # backward: h = dot(x, w_up) + b_up
    _, hsrc = _peel(ctx, jaxpr, h, ("convert_element_type",))
    prod = node.producers.get(hsrc)
    if prod is None or prod[1].primitive.name != "add":
        return None
    dot = bias_v = None
    for a, b in (tuple(prod[1].invars), tuple(prod[1].invars)[::-1]):
        pa = node.producers.get(a)
        if pa and _is_matmul(pa[1]):
            dot, bias_v = pa[1], b
            break
    if dot is None:
        return None
    x, w_up = dot.invars
    if len(_shape(w_up)) != 2:
        return None
    b_up = _peel_bias(ctx, jaxpr, bias_v, _shape(w_up)[-1])
    if b_up is None:
        return None
    # forward: g @ w_down + b_down
    d2s = [e for _, e in node.consumers.get(g, []) if _is_matmul(e)]
    if len(d2s) != 1 or d2s[0].invars[0] is not g:
        return None
    w_down = d2s[0].invars[1]
    if len(_shape(w_down)) != 2:
        return None
    adds = [e for _, e in node.consumers.get(d2s[0].outvars[0], [])
            if e.primitive.name == "add"]
    if len(adds) != 1:
        return None
    add2 = adds[0]
    bias2 = add2.invars[1] if add2.invars[0] is d2s[0].outvars[0] \
        else add2.invars[0]
    b_down = _peel_bias(ctx, jaxpr, bias2, _shape(w_down)[-1])
    if b_down is None:
        return None
    out = add2.outvars[0]
    cons = node.consumers.get(out, [])
    if len(cons) == 1 and cons[0][1].primitive.name == "convert_element_type" \
            and _dtype(cons[0][1].outvars[0]) == _dtype(x):
        out = cons[0][1].outvars[0]
    invars = (x, w_up, b_up, w_down, b_down)
    covered, leaves = _slice_from(node, [out], list(invars))
    if leaves:
        return None
    return RegionMatch("mlp_gelu", jid, node.path, invars, (out,),
                       frozenset(covered))


# ---------------------------------------------------------------------------
# Recognizer: conv stem (conv_general_dilated + bias + gelu)
# ---------------------------------------------------------------------------
def _match_conv_stem(ctx: _Ctx, jid: int, idx: int):
    node = ctx.nodes[jid]
    jaxpr = node.jaxpr
    conv = jaxpr.eqns[idx]
    if conv.primitive.name != "conv_general_dilated":
        return None
    x, w = conv.invars
    if len(_shape(x)) != 3 or len(_shape(w)) != 3:
        return None                       # only 1-D (audio) stems
    p = conv.params
    strides = tuple(p["window_strides"])
    lhs_dil = tuple(p.get("lhs_dilation") or ())
    rhs_dil = tuple(p.get("rhs_dilation") or ())

    def rej(reason):
        return Rejection("conv_stem", node.path, reason,
                         primitive="conv_general_dilated", eqn_index=idx)

    if any(d != 1 for d in lhs_dil) or any(d != 1 for d in rhs_dil):
        return rej(f"dilated convolution (lhs_dilation={list(lhs_dil)}, "
                   f"rhs_dilation={list(rhs_dil)}) — no registered kernel "
                   "serves dilation")
    if p.get("feature_group_count", 1) != 1 \
            or p.get("batch_group_count", 1) != 1:
        return rej("grouped convolution — no registered kernel serves "
                   "feature/batch groups")
    want_dn = jax.lax.conv_dimension_numbers(_shape(x), _shape(w),
                                             ("NHC", "HIO", "NHC"))
    if p["dimension_numbers"] != want_dn:
        return rej(f"conv layout {p['dimension_numbers']} is not the "
                   "stem's NHC/HIO/NHC")
    win, ks, stride = _shape(x)[1], _shape(w)[0], strides[0]
    out_w = -(-win // stride)
    tot = max((out_w - 1) * stride + ks - win, 0)
    same = ((tot // 2, tot - tot // 2),)
    if tuple(tuple(q) for q in p["padding"]) != same:
        return rej(f"conv padding {list(p['padding'])} is not SAME — the "
                   "registered stem kernel assumes SAME padding")
    # forward: conv -> +bias -> gelu
    adds = [e for _, e in node.consumers.get(conv.outvars[0], [])
            if e.primitive.name == "add"]
    if len(adds) != 1:
        return None
    add = adds[0]
    bias_v = add.invars[1] if add.invars[0] is conv.outvars[0] \
        else add.invars[0]
    b = _peel_bias(ctx, jaxpr, bias_v, _shape(w)[-1])
    if b is None:
        return None
    h = add.outvars[0]
    g = None
    for e in jaxpr.eqns[idx:]:
        if e.primitive.name == "tanh":
            hit = _gelu_anchor(ctx, node, e)
            if hit is not None and hit[0] is h:
                g = hit[1]
                break
    if g is None:
        return None
    covered, leaves = _slice_from(node, [g], [x, w, b])
    if leaves:
        return None
    return RegionMatch("conv_stem", jid, node.path, (x, w, b), (g,),
                       frozenset(covered), {"stride": int(stride)})


# ---------------------------------------------------------------------------
# Recognizer: MoE dispatch (top-k gate -> one-hot routing -> expert swiglu)
# ---------------------------------------------------------------------------
def _back_to_router_dot(node: _Node, v, limit: int = 16):
    """Walk backward from the routed probabilities through the softmax chain
    (wrappers crossed via their data operand) to the router matmul."""
    for _ in range(limit):
        if isinstance(v, Literal):
            return None
        prod = node.producers.get(v)
        if prod is None:
            return None
        e = prod[1]
        nm = e.primitive.name
        if nm == "dot_general":
            return e
        if nm in _WRAPPERS or nm in (
                "div", "sub", "exp", "convert_element_type", "reduce_max",
                "mul", "add", "max", "stop_gradient", "transpose"):
            v = e.invars[0]
            continue
        return None
    return None


def _match_moe_dispatch(ctx: _Ctx, jid: int, idx: int):
    node = ctx.nodes[jid]
    jaxpr = node.jaxpr
    topk = jaxpr.eqns[idx]
    if topk.primitive.name != "top_k":
        return None
    k = int(topk.params.get("k", 0))

    def rej(reason):
        return Rejection("moe_dispatch", node.path, reason,
                         primitive="top_k", eqn_index=idx)

    router_dot = _back_to_router_dot(node, topk.invars[0])
    if router_dot is None or len(_shape(router_dot.invars[1])) != 2:
        return None                       # top_k not fed by a router matmul
    w_router = router_dot.invars[1]
    _, x = _peel(ctx, jaxpr, router_dot.invars[0], ("convert_element_type",))
    num_experts = _shape(w_router)[-1]

    # everything downstream of the routing decision, at this jaxpr level
    reach: set = set()
    stack = [v for v in topk.outvars if not _is_drop(v)]
    while stack:
        v = stack.pop()
        if id(v) in reach:
            continue
        reach.add(id(v))
        for _, e in node.consumers.get(v, []):
            stack.extend(ov for ov in e.outvars if not _is_drop(ov))

    # per-expert FFN: dot_generals whose rank-3 rhs is routing-independent
    # (expert weight stacks [E, D, F]) but whose lhs is routed data
    expert_dots = [e for e in jaxpr.eqns
                   if e.primitive.name == "dot_general"
                   and len(_shape(e.invars[1])) == 3
                   and id(e.invars[0]) in reach
                   and id(e.invars[1]) not in reach]
    if len(expert_dots) != 3:
        return rej("routing found but no per-expert FFN "
                   f"({len(expert_dots)} expert matmuls, expected 3)")
    gate_dot = down_dot = None
    for e in expert_dots:
        for _, c in node.consumers.get(e.outvars[0], []):
            if _silu_inner(c) is not None:
                gate_dot = e
        pl = node.producers.get(e.invars[0])
        if pl is not None and pl[1].primitive.name == "mul":
            down_dot = e
    up_dots = [e for e in expert_dots if e is not gate_dot and e is not down_dot]
    if gate_dot is None or down_dot is None or len(up_dots) != 1:
        return rej("per-expert FFN is not the swiglu shape "
                   "(gate/up/down matmuls not identified)")
    w_gate, w_up, w_down = (gate_dot.invars[1], up_dots[0].invars[1],
                            down_dot.invars[1])

    # combine: expert outputs gathered back to tokens by one more einsum
    combines = [e for _, e in node.consumers.get(down_dot.outvars[0], [])
                if e.primitive.name == "dot_general"]
    if len(combines) != 1:
        return rej("data-dependent MoE routing (scatter/gather combine) — "
                   "no dense combine einsum to bound statically")
    out = combines[0].outvars[0]
    cons = node.consumers.get(out, [])
    if len(cons) == 1 and cons[0][1].primitive.name == "convert_element_type" \
            and _dtype(cons[0][1].outvars[0]) == _dtype(x):
        out = cons[0][1].outvars[0]
    invars = (x, w_router, w_gate, w_up, w_down)
    covered, leaves = _slice_from(node, [out], list(invars))
    if leaves:
        return None
    # capacity bound: the dense form compares each token's queue position
    # against a compile-time int (keep = pos_in_expert < c); without it the
    # routed block has no static shape and cannot be offloaded
    capacity = None
    for i in covered:
        e = jaxpr.eqns[i]
        if e.primitive.name == "lt" and _scalar_lit(e.invars[1]) \
                and "int" in _dtype(e.invars[0]):
            capacity = max(capacity or 0, int(e.invars[1].val))
    if not capacity:
        return rej("data-dependent MoE routing without a capacity bound — "
                   "token queues have no static size")
    return RegionMatch("moe_dispatch", jid, node.path, invars, (out,),
                       frozenset(covered),
                       {"num_experts": int(num_experts), "k": k,
                        "capacity": int(capacity)})


# ---------------------------------------------------------------------------
# Legality analyzer
# ---------------------------------------------------------------------------
def _legalize(ctx: _Ctx, m: RegionMatch) -> RegionMatch:
    if not m.legal:
        return m
    node = ctx.nodes[m.jaxpr_id]
    jaxpr = node.jaxpr

    def fail(reason):
        m.legal, m.reason = False, reason
        return m

    if "while" in m.path:
        return fail("data-dependent trip count (inside while loop)")
    if "cond" in m.path:
        return fail("conditionally executed (inside cond branch)")
    for i in sorted(m.covered):
        if jaxpr.eqns[i].effects:
            return fail(f"side effects in region ({jaxpr.eqns[i].primitive.name})")
    # escape analysis: covered intermediates must stay inside the region
    outs_ok = set(map(id, m.outvars))
    root_outs = set(id(v) for v in jaxpr.outvars if not isinstance(v, Literal))
    for i in m.covered:
        for v in jaxpr.eqns[i].outvars:
            if _is_drop(v) or id(v) in outs_ok:
                continue
            if id(v) in root_outs:
                return fail("intermediate value escapes to program outputs")
            for ci, ce in node.consumers.get(v, []):
                if ci not in m.covered:
                    return fail("intermediate value escapes region "
                                f"(consumed by {ce.primitive.name})")
    # dtype gates: the registered kernels' supported input types
    ok = _FIR_OK if m.family == "fir_bank" else _FLOAT_OK
    for v in m.invars:
        dt = _dtype(v)
        if dt not in ok and not ("int" in dt and m.family == "fir_bank"):
            return fail(f"unsupported dtype {dt} for {m.family}")
    fam = REGISTRY.get(m.family, {})
    if not [v for v in fam if v != "ref"]:
        return fail(f"no offload variants registered for {m.family}")
    # intensity / alignment numbers for the Step-2 ranking
    try:
        fn = _region_fn(ctx, m)
        args = [jax.ShapeDtypeStruct(_shape(v), _dtype(v)) for v in m.invars]
        m.analysis = analyze_region(fn, *args, name=m.family)
    except Exception as e:                       # pragma: no cover - safety
        return fail(f"region slice does not trace: {type(e).__name__}: {e}")
    return m


# ---------------------------------------------------------------------------
# Binder: sliced ref callable + whole-program interpreter
# ---------------------------------------------------------------------------
def _read(env, v):
    return v.val if isinstance(v, Literal) else env[id(v)]


def _write(env, eqn, ans):
    outs = ans if eqn.primitive.multiple_results else [ans]
    for var, val in zip(eqn.outvars, outs):
        if not _is_drop(var):
            env[id(var)] = val


def _region_fn(ctx: _Ctx, m: RegionMatch) -> Callable:
    """The match's covered eqns as a standalone callable — the region's
    ``ref`` implementation with the signature recovered from the jaxpr."""
    node = ctx.nodes[m.jaxpr_id]
    jaxpr = node.jaxpr
    covered = sorted(m.covered)

    def fn(*args, **_static):
        env = {id(v): val for v, val in node.constvals.items()}
        for v, val in zip(m.invars, args):
            env[id(v)] = val
        for i in covered:
            eqn = jaxpr.eqns[i]
            vals = [_read(env, v) for v in eqn.invars]
            _write(env, eqn, eqn.primitive.bind(*vals, **eqn.params))
        outs = [env[id(v)] for v in m.outvars]
        return outs[0] if len(outs) == 1 else tuple(outs)

    fn.__name__ = f"extracted_{m.family}"
    return fn


def _coerce(val, var):
    """Variant outputs may drift in dtype (e.g. an f32-accumulating
    offload variant); pin them back to the jaxpr's recorded aval."""
    want = getattr(var, "aval", None)
    if want is None:
        return val
    if _shape(var) != tuple(np.shape(val)):
        val = jnp.reshape(val, _shape(var))
    if str(val.dtype) != str(want.dtype):
        val = val.astype(want.dtype)
    return val


def _make_build(ctx: _Ctx, matches: list) -> Callable[[Impl], Callable]:
    """build(impl): re-emit the traced program, routing every matched
    region with a non-ref pick through ``regions.dispatch``."""
    by_jaxpr: dict[int, list] = {}
    for m in matches:
        by_jaxpr.setdefault(m.jaxpr_id, []).append(m)

    def build(impl: Impl):
        impl = Impl(dict(impl))
        active = {}
        for jid, ms in by_jaxpr.items():
            picked = [m for m in ms if impl.pick(m.family) != "ref"]
            # a stitched region overlaps its split halves; largest cover
            # wins so a fused pick supersedes the two individual picks
            picked.sort(key=lambda m: -len(m.covered))
            kept, used = [], set()
            for m in picked:
                if m.covered & used:
                    continue
                used |= m.covered
                kept.append(m)
            if kept:
                active[jid] = kept
        hot = set()                       # jaxpr ids whose subtree substitutes
        for jid in active:
            for nid in ctx.order:
                if jid in ctx.subtree(nid):
                    hot.add(nid)

        def ev(jaxpr, consts, args):
            node = ctx.nodes[id(jaxpr)]
            env = {}
            for v, val in zip(jaxpr.constvars, consts):
                env[id(v)] = val
            for v, val in zip(jaxpr.invars, args):
                env[id(v)] = val
            skip, anchor = set(), {}
            for m in active.get(id(jaxpr), []):
                skip |= m.covered
                anchor[max(m.covered)] = m
            for i, eqn in enumerate(jaxpr.eqns):
                if i in anchor:
                    m = anchor[i]
                    vals = [_read(env, v) for v in m.invars]
                    res = dispatch(m.family, impl, *vals, **m.static_kwargs)
                    res = res if isinstance(res, tuple) else (res,)
                    for var, val in zip(m.outvars, res):
                        if not _is_drop(var):
                            env[id(var)] = _coerce(val, var)
                    continue
                if i in skip:
                    continue
                kids = node.eqn_children.get(i, [])
                if any(k in hot for k in kids):
                    _write(env, eqn, _reemit(eqn, env))
                    continue
                vals = [_read(env, v) for v in eqn.invars]
                _write(env, eqn, eqn.primitive.bind(*vals, **eqn.params))
            return [_read(env, v) for v in jaxpr.outvars]

        def _reemit(eqn, env):
            """Rebuild a higher-order eqn whose sub-jaxpr substitutes."""
            name = eqn.primitive.name
            vals = [_read(env, v) for v in eqn.invars]
            if name == "scan":
                closed = eqn.params["jaxpr"]
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                consts, init = vals[:nc], vals[nc:nc + ncar]
                xs = vals[nc + ncar:]

                def body(carry, x):
                    outs = ev(closed.jaxpr, list(closed.consts),
                              list(consts) + list(carry) + list(x))
                    return tuple(outs[:ncar]), tuple(outs[ncar:])

                carry, ys = jax.lax.scan(
                    body, tuple(init), tuple(xs),
                    length=eqn.params["length"],
                    reverse=eqn.params["reverse"],
                    unroll=eqn.params["unroll"])
                return list(carry) + list(ys)
            if name == "cond":
                branches = eqn.params["branches"]
                fns = [(lambda *a, _c=c: tuple(
                    ev(_c.jaxpr, list(_c.consts), list(a))))
                    for c in branches]
                out = jax.lax.switch(vals[0], fns, *vals[1:])
                return list(out)
            if name in _WRAPPERS:
                closed = (eqn.params.get("jaxpr")
                          or eqn.params.get("call_jaxpr"))
                return ev(getattr(closed, "jaxpr", closed),
                          list(getattr(closed, "consts", ())), vals)
            # while with substitutions inside is rejected by legality;
            # anything else falls back to the primitive itself
            return eqn.primitive.bind(*vals, **eqn.params)

        def run(*args):
            out = ev(ctx.closed.jaxpr, list(ctx.closed.consts), list(args))
            return out[0] if len(out) == 1 else tuple(out)

        return run

    return build


# ---------------------------------------------------------------------------
# Driver: enumerate -> recognize -> legalize
# ---------------------------------------------------------------------------
def _ensure_registry() -> None:
    """Import the modules that register the recognizable kernel families
    (lazy: keeps core import-clean of models/apps)."""
    import importlib
    for mod in ("repro.models.blocks", "repro.models.ssm",
                "repro.models.rglru", "repro.kernels.ops",
                "repro.apps.tdfir"):
        try:
            importlib.import_module(mod)
        except ImportError:               # pragma: no cover - optional deps
            pass


# Family -> recognizer entry point.  ``tools/check_patterns.py`` walks this
# table to enforce that every extractable family has a recognizer and test
# coverage; keep it in sync with FAMILIES.
RECOGNIZERS = {
    "attn_core": _match_attention,
    "ssm_scan": _match_affine_scan,
    "rglru_scan": _match_affine_scan,
    "fir_bank": _match_fir,
    "mlp_core": _match_swiglu,
    "rmsnorm": _match_rmsnorm,
    "mlp_gelu": _match_gelu_mlp,
    "conv_stem": _match_conv_stem,
    "moe_dispatch": _match_moe_dispatch,
}


def _find_matches(ctx: _Ctx):
    """Run every recognizer pass; returns ``(matches, rejections)`` where
    matches have been legalized and rejections are structured near-misses
    surfaced by recognizers themselves."""
    matches: list[RegionMatch] = []
    rejections: list[Rejection] = []
    claimed: dict[int, set] = {}
    suppressed: set[int] = set()          # jaxpr ids interior to a match

    def admit(m):
        used = claimed.setdefault(m.jaxpr_id, set())
        if m.covered & used:
            return
        used.update(m.covered)
        node = ctx.nodes[m.jaxpr_id]
        for i in m.covered:
            for kid in node.eqn_children.get(i, []):
                suppressed.update(ctx.subtree(kid))
        matches.append(m)

    passes = (
        ("scan", _match_attention),
        ("scan", _match_affine_scan),
        ("while", _match_affine_while),
        ("top_k", _match_moe_dispatch),
        ("conv_general_dilated", _match_conv_stem),
        ("jit", _match_swiglu),
        ("tanh", _match_gelu_mlp),
        ("rsqrt", _match_rmsnorm),
    )
    for prim, matcher in passes:
        for jid in ctx.order:
            if jid in suppressed:
                continue
            node = ctx.nodes[jid]
            for i, e in enumerate(node.jaxpr.eqns):
                if e.primitive.name != prim:
                    continue
                if i in claimed.get(jid, set()):
                    continue
                hit = matcher(ctx, jid, i)
                if isinstance(hit, Rejection):
                    rejections.append(hit)
                elif hit is not None:
                    admit(hit)
    return [_legalize(ctx, m) for m in matches], rejections


# ---------------------------------------------------------------------------
# Stitching: fuse adjacent legal regions into a single offload unit
# ---------------------------------------------------------------------------
def _register_fused(family: str) -> None:
    """Generic offload variant for a stitched pair: run each half via its
    best registered non-ref implementation, routing the boundary values
    directly (this is what saves the host<->device boundary transfers)."""
    if "offload" in REGISTRY.get(family, {}):
        return

    def fused(*args, left, right, n_left, wiring, left_kwargs, right_kwargs):
        def best(fam):
            fam_variants = REGISTRY.get(fam, {})
            for v in ("pallas", "offload", "seq", "ref"):
                if v in fam_variants:
                    return fam_variants[v]
            raise KeyError(f"no variant registered for {fam}")
        lres = best(left)(*args[:n_left], **dict(left_kwargs))
        louts = lres if isinstance(lres, tuple) else (lres,)
        rest = args[n_left:]
        rargs = [louts[i] if kind == "out"
                 else args[i] if kind == "larg" else rest[i]
                 for kind, i in wiring]
        return best(right)(*rargs, **dict(right_kwargs))

    fused.__name__ = f"fused_{family.replace('+', '_')}"
    register_variant(family, "offload")(fused)


def _stitch(ctx: _Ctx, matches: list):
    """Producer/consumer-adjacent legal matches in the same jaxpr emit an
    additional *fused* RegionMatch spanning both eqn slices.  The fused
    region is a first-class variant: the planner measures it against the
    split form and the registry version bump re-keys the plan cache."""
    fused: list[RegionMatch] = []
    rejections: list[Rejection] = []
    base = [m for m in matches if m.legal and "+" not in m.family]
    for m1 in base:
        for m2 in base:
            if m1 is m2 or m1.jaxpr_id != m2.jaxpr_id:
                continue
            node = ctx.nodes[m1.jaxpr_id]
            out_ids = {id(v): i for i, v in enumerate(m1.outvars)}
            if not any(id(v) in out_ids for v in m2.invars):
                continue                  # not adjacent
            if m1.covered & m2.covered:
                continue
            # no m1 input may be produced inside m2 (would be a cycle)
            if any(node.producers.get(v, (None,))[0] in m2.covered
                   for v in m1.invars):
                continue
            family = f"{m1.family}+{m2.family}"
            # fusion legality: the boundary must be internal to the pair
            union = m1.covered | m2.covered
            root_outs = set(id(v) for v in node.jaxpr.outvars
                            if not isinstance(v, Literal))
            escaped = False
            for v in m1.outvars:
                if id(v) in root_outs or any(
                        ci not in union
                        for ci, _ in node.consumers.get(v, [])):
                    escaped = True
                    break
            if escaped:
                rejections.append(Rejection(
                    family, node.path,
                    "fusion illegal: boundary value escapes the fused "
                    "region", stage="stitch"))
                continue
            larg_ids = {id(v): i for i, v in enumerate(m1.invars)}
            wiring, extra = [], []
            for v in m2.invars:
                if id(v) in out_ids:
                    wiring.append(("out", out_ids[id(v)]))
                elif id(v) in larg_ids:
                    wiring.append(("larg", larg_ids[id(v)]))
                else:
                    wiring.append(("arg", len(extra)))
                    extra.append(v)
            fm = RegionMatch(
                family, m1.jaxpr_id, node.path,
                tuple(m1.invars) + tuple(extra), tuple(m2.outvars),
                frozenset(union),
                {"left": m1.family, "right": m2.family,
                 "n_left": len(m1.invars),
                 "wiring": tuple(wiring),
                 "left_kwargs": dict(m1.static_kwargs),
                 "right_kwargs": dict(m2.static_kwargs)})
            _register_fused(family)
            fused.append(_legalize(ctx, fm))
    return fused, rejections


def extract(fn: Callable, args: tuple, *, name: str = "program"
            ) -> ExtractionReport:
    """Run the static pass only: trace ``fn(*args)``, enumerate candidate
    sites, and return every recognizer match with its legality verdict.
    ``args`` may be concrete arrays or ``ShapeDtypeStruct``s."""
    _ensure_registry()
    closed = jax.make_jaxpr(fn)(*args)
    ctx = _Ctx(closed)
    report = ExtractionReport(name=name)
    report.sites = enumerate_sites(ctx)
    report.loop_count = sum(1 for s in report.sites
                            if s.kind in ("scan", "while"))
    matches, rejections = _find_matches(ctx)
    stitched, srejs = _stitch(ctx, matches)
    report.matches = matches + stitched
    report.rejections = rejections + srejs + [
        Rejection(m.family, m.path, m.reason, stage="legality")
        for m in matches if not m.legal]
    report._ctx = ctx                     # keeps jaxpr ids alive
    return report


def discover(fn: Callable, args: tuple, *, name: str = "discovered",
             sample_inputs: Optional[Callable] = None,
             families: Optional[tuple] = None) -> OffloadableProgram:
    """Turn an *unannotated* function into a planner-ready program.

    Traces ``fn(*args)``, recognizes offloadable blocks, and returns an
    ``OffloadableProgram`` whose regions are the legal matches (one region
    per kernel family — picking a variant re-routes **every** match of
    that family, exactly like the annotated dispatch path) and whose
    ``build(impl)`` re-emits the traced program with the chosen variants
    substituted.  No ``register_variant`` / ``Region`` annotations are
    needed in the program's own definition.

    ``sample_inputs`` defaults to replaying the (concrete) trace ``args``
    for every measurement; pass a callable ``key -> args`` to randomize.
    ``families`` optionally restricts which kernel families become
    regions."""
    report = extract(fn, args, name=name)
    ctx = report._ctx
    picked: dict[str, list] = {}
    for m in report.legal_matches:
        if families and m.family not in families:
            continue
        picked.setdefault(m.family, []).append(m)
    regions = []
    for family, ms in picked.items():
        rep = max(ms, key=lambda m: m.analysis.flops if m.analysis else 0.0)
        fam_variants = REGISTRY.get(family, {})
        deploy = "pallas" if "pallas" in fam_variants else "offload"
        # measurement-variant parity with the annotated path: a sequential
        # fallback (ssm) is the cheap-to-time proxy when one is registered
        measure = ("seq" if "seq" in fam_variants
                   else ("offload" if "offload" in fam_variants else deploy))
        regions.append(Region(
            name=family,
            analysis_fn=_region_fn(ctx, rep),
            analysis_args=tuple(jax.ShapeDtypeStruct(_shape(v), _dtype(v))
                                for v in rep.invars),
            measure_variant=measure,
            deploy_variant=deploy,
            static_kwargs=dict(rep.static_kwargs)))
    build = _make_build(ctx, [m for ms in picked.values() for m in ms])

    concrete = all(hasattr(a, "dtype") and not isinstance(
        a, jax.ShapeDtypeStruct) for a in args)
    if sample_inputs is None:
        if not concrete:
            raise ValueError("discover() needs concrete trace args or an "
                             "explicit sample_inputs callable")
        sample_inputs = lambda key, _args=tuple(args): _args   # noqa: E731

    prog = OffloadableProgram(
        name=f"extract:{name}",
        regions=regions,
        build=build,
        sample_inputs=sample_inputs,
        source_loop_count=report.loop_count,
        description="regions discovered by static jaxpr extraction",
        cache_extra={
            "extractor": 1,
            "inputs": [f"{_dtype_of(a)}{list(np.shape(a))}" for a in args],
        })
    prog.extraction = report              # diagnostics for benchmarks/tests
    return prog


def _dtype_of(a) -> str:
    return str(getattr(a, "dtype", type(a).__name__))
