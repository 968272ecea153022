"""Mixture-of-Experts FFN: two capacity-bounded dispatch strategies
(region ``moe_ffn``) and one dropless one (region ``moe_dropless``).

* ``token_onehot`` — GShard-style token-choice top-k with a one-hot dispatch
  tensor [T, E, C].  Exact token-choice semantics; memory O(T*E*C) so it is
  the default only for small/test configs.
* ``expert_choice`` — expert-choice top-C gather (each expert picks its C
  best tokens).  Memory O(E*C*D); the default for the assigned 128/384-expert
  configs and the dry-run.  This is the standard memory-lean JAX formulation;
  semantics differ slightly from token-choice (documented in DESIGN.md).

Experts are sharded over the ``model`` mesh axis (expert parallelism); GSPMD
inserts the token all-to-all when token activations are data-sharded.

``moe_dropless`` has one semantics, token-choice top-k with no capacity: a
token's experts are the k largest of a float32 softmax over all routed
experts' logits, chosen from that token alone, and no pick is ever dropped.
The layer holds the experts ``[first, first + held)`` of them and adds their
share of the output only (expert parallelism's share, without the exchange).
Variants: ``ref`` runs every held expert over every token, masked by the
gate; ``gmm`` sorts the routed pairs by expert and runs the grouped Pallas
kernels of ``kernels/moe_gmm.py`` over the experts they reach.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.regions import register_variant


def router_probs(x: jax.Array, w_router: jax.Array) -> jax.Array:
    """x: [T, D] -> probs [T, E] (fp32 softmax)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.bfloat16), w_router,
                        preferred_element_type=jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


def _expert_ffn(xe: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """xe: [E, C, D]; weights: [E, D, F] / [E, F, D] -> [E, C, D]."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", xe, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_capacity(n_tokens: int, num_experts: int, k: int, capacity_factor: float) -> int:
    c = int(np.ceil(n_tokens * k * capacity_factor / num_experts))
    return max(8, -(-c // 8) * 8)   # round up to 8 for TPU-lane friendliness


@register_variant("moe_dispatch", "ref")
def moe_dispatch_dense(x, w_router, w_gate, w_up, w_down, *, num_experts: int,
                       k: int, capacity: int):
    """Capacity-bounded token-choice top-k with one-hot dispatch.  x: [T, D].

    The flat-argument, static-capacity form of the GShard dense dispatch —
    data-dependent routing is bounded by the Python-int ``capacity``, which
    is what makes the block legal for static offload (the extractor's
    ``moe_dispatch`` recognizer keys on exactly this bound)."""
    t, d = x.shape
    probs = router_probs(x, w_router)                         # [T, E]
    gate_vals, gate_idx = jax.lax.top_k(probs, k)             # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    c = int(capacity)

    # position of each (token, choice) within its expert queue
    onehot = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.int32)   # [T, k, E]
    flat = onehot.reshape(t * k, num_experts)
    pos = jnp.cumsum(flat, axis=0) - flat                     # [T*k, E]
    pos_in_expert = (pos * flat).sum(-1).reshape(t, k)        # [T, k]
    keep = pos_in_expert < c

    # dispatch tensor [T, E, C]
    disp = (jax.nn.one_hot(gate_idx, num_experts, dtype=x.dtype)[..., None]
            * jax.nn.one_hot(pos_in_expert, c, dtype=x.dtype)[:, :, None, :]
            * keep[:, :, None, None].astype(x.dtype))          # [T, k, E, C]
    combine = disp * gate_vals[:, :, None, None].astype(x.dtype)
    disp = disp.sum(1)                                        # [T, E, C]
    combine = combine.sum(1)                                  # [T, E, C]

    xe = jnp.einsum("td,tec->ecd", x, disp)                   # [E, C, D]
    ye = _expert_ffn(xe, w_gate, w_up, w_down)
    return jnp.einsum("ecd,tec->td", ye, combine).astype(x.dtype)


@register_variant("moe_dispatch", "offload")
def moe_dispatch_slots(x, w_router, w_gate, w_up, w_down, *, num_experts: int,
                       k: int, capacity: int):
    """Scatter-slot dispatch: token t's choice j lands at flat slot
    ``gate_idx*capacity + pos_in_expert`` (overflow tokens at a dead row), so
    the O(T*E*C) one-hot tensor never materializes.  Each slot receives at
    most one token, so scatter-add is exact — same semantics as ``ref``."""
    t, d = x.shape
    probs = router_probs(x, w_router)                         # [T, E]
    gate_vals, gate_idx = jax.lax.top_k(probs, k)             # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    c = int(capacity)

    onehot = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.int32)   # [T, k, E]
    flat = onehot.reshape(t * k, num_experts)
    pos = jnp.cumsum(flat, axis=0) - flat                     # [T*k, E]
    pos_in_expert = (pos * flat).sum(-1).reshape(t, k)        # [T, k]
    keep = pos_in_expert < c
    slot = jnp.where(keep, gate_idx * c + pos_in_expert,
                     num_experts * c).reshape(t * k)          # dead row at E*c

    src = jnp.broadcast_to(x[:, None, :], (t, k, d)).reshape(t * k, d)
    buf = jnp.zeros((num_experts * c + 1, d), x.dtype).at[slot].add(src)
    xe = buf[:-1].reshape(num_experts, c, d)                  # [E, C, D]
    ye = _expert_ffn(xe, w_gate, w_up, w_down)
    ye_pad = jnp.concatenate([ye.reshape(num_experts * c, d),
                              jnp.zeros((1, d), ye.dtype)])
    y_tok = ye_pad[slot].reshape(t, k, d)                     # dropped -> 0
    gates = (gate_vals * keep.astype(gate_vals.dtype)).astype(y_tok.dtype)
    return (y_tok * gates[:, :, None]).sum(1).astype(x.dtype)


@register_variant("moe_ffn", "ref")
def moe_token_onehot(x, params, *, num_experts: int, k: int,
                     capacity_factor: float, inner_impl=None):
    """Token-choice top-k with one-hot dispatch.  x: [T, D].

    Routes the capacity-bounded dispatch through the ``moe_dispatch``
    family, so an offload pattern can re-route the routed block itself
    (dense one-hot vs scatter-slot) within the token-choice strategy."""
    from repro.core.regions import dispatch
    c = moe_capacity(x.shape[0], num_experts, k, capacity_factor)
    return dispatch("moe_dispatch", inner_impl, x, params["router"],
                    params["w_gate"], params["w_up"], params["w_down"],
                    num_experts=num_experts, k=k, capacity=c)


@register_variant("moe_ffn", "offload")
def moe_expert_choice(x, params, *, num_experts: int, k: int,
                      capacity_factor: float, group_size: int = 4096,
                      inner_impl=None):
    """Group-local expert-choice routing.  x: [T, D].

    Tokens are split into groups of <= group_size; each expert picks its
    top-C tokens *within each group* (group-limited routing).  The dispatch
    tensor [G, E, C, D] shards G over 'data' and E over 'model', so per-device
    memory is (T*k*cf/devices) token slots regardless of global batch — this
    is what makes kimi-k2 (384e, 1M tokens/step) feasible, where global
    expert-choice would materialize a ~150 GB dispatch per device."""
    t, d = x.shape
    g = max(1, t // group_size)
    while t % g:                      # t is a power-of-two in all our shapes;
        g -= 1                        # degrade gracefully if not
    tg = t // g
    from repro.parallel.ctx import constrain
    xg = constrain(x.reshape(g, tg, d), ("batch", None, None))
    probs = jax.nn.softmax(
        jnp.einsum("gtd,de->gte", xg.astype(jnp.bfloat16), params["router"],
                   preferred_element_type=jnp.float32), axis=-1)   # [G,Tg,E]
    probs = constrain(probs, ("batch", None, None))
    c = min(moe_capacity(tg, num_experts, k, capacity_factor), tg)
    gate, idx = jax.lax.top_k(jnp.swapaxes(probs, 1, 2), c)        # [G,E,C]
    flat_idx = idx.reshape(g, num_experts * c)
    xe = jax.vmap(lambda xb, ib: jnp.take(xb, ib, axis=0))(xg, flat_idx)
    xe = xe.reshape(g, num_experts, c, d)                          # [G,E,C,D]
    xe = constrain(xe, ("batch", "experts", None, None))  # G->data, E->model (EP)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, params["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", xe, params["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    ye = ye * gate[..., None].astype(ye.dtype)

    def scatter_group(yb, ib):
        return jnp.zeros((tg, d), x.dtype).at[ib].add(yb.astype(x.dtype))

    out = jax.vmap(scatter_group)(ye.reshape(g, num_experts * c, d), flat_idx)
    # keep the combine group-local: without this constraint GSPMD resolves
    # the scatter across the pod axis by replicate+all-reduce (measured 11x
    # all-reduce bytes on the 2-pod kimi prefill cell — §Perf iteration 5)
    out = constrain(out, ("batch", None, None))
    return out.reshape(t, d)


def aux_load_balance_loss(probs: jax.Array, gate_idx: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style auxiliary load-balancing loss (fraction * prob)."""
    me = jnp.mean(probs, axis=0)                               # [E]
    ce = jnp.mean(
        jax.nn.one_hot(gate_idx[:, 0], num_experts, dtype=jnp.float32), axis=0)
    return num_experts * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# Dropless token-choice top-k over held experts (region "moe_dropless")
# ---------------------------------------------------------------------------
def route_topk(x, w_router, *, k: int, renormalize: bool):
    """Gate weights and expert ids [T, k] of each token: the top ``k`` of a
    float32 softmax over all routed experts' logits, rescaled to sum 1 only
    under ``renormalize`` (Mixtral does, Jamba does not)."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if renormalize:
        gate = gate / gate.sum(-1, keepdims=True)
    return gate, idx


def _local_ids(idx, first: int, valid):
    """Picks as indices into the held experts; -1 for a token that is not
    ``valid`` (bucket padding: routed nowhere)."""
    local = idx - first
    return local if valid is None else jnp.where(valid[:, None], local, -1)


def _counters(sizes):
    """The layer's counters int32 [2] from its pairs per held expert:
    pairs routed to held experts, held experts reached."""
    return jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)


@register_variant("moe_dropless", "ref")
def moe_dropless_dense(x, params, *, k: int, first: int = 0,
                       renormalize: bool = True, valid=None):
    """Every held expert over every token, weighted by the token's gate for
    it (0 where it did not pick it).  x: [T, D]; valid: [T] bool or None.
    Returns ([T, D], counts)."""
    gate, idx = route_topk(x, params["router"], k=k, renormalize=renormalize)
    held = params["w_gate"].shape[0]
    # ids out of [0, held) (held elsewhere, or padding) give zero rows
    onehot = jax.nn.one_hot(_local_ids(idx, first, valid), held,
                            dtype=jnp.float32)                # [T, k, held]
    weight = (onehot * gate[..., None]).sum(1)                # [T, held]

    def expert(acc, inp):
        wg, wu, wd, w = inp
        g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * jnp.dot(x, wu, preferred_element_type=jnp.float32)
        y = jnp.dot(h.astype(x.dtype), wd, preferred_element_type=jnp.float32)
        return acc + w[:, None] * y, None

    acc, _ = jax.lax.scan(
        expert, jnp.zeros(x.shape, jnp.float32),
        (params["w_gate"], params["w_up"], params["w_down"], weight.T))
    return acc.astype(x.dtype), _counters(onehot.sum((0, 1)))


@register_variant("moe_dropless", "gmm")
def moe_dropless_grouped(x, params, *, k: int, first: int = 0,
                         renormalize: bool = True, valid=None):
    """The routed pairs sorted by held expert, each expert's rows padded to
    whole row tiles, through the grouped kernels; each token sums its held
    picks.  Rows are sized for the worst case, so no pick is dropped."""
    from repro.kernels import moe_gmm

    t, d = x.shape
    gate, idx = route_topk(x, params["router"], k=k, renormalize=renormalize)
    held = params["w_gate"].shape[0]
    pairs = t * k
    tm = moe_gmm.row_tile(pairs, params["router"].shape[-1])
    m = moe_gmm.padded_rows(pairs, held, tm)
    local = _local_ids(idx, first, valid).reshape(pairs)
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, 0)
    onehot = jax.nn.one_hot(local, held, dtype=jnp.int32)     # [pairs, held]
    sizes = onehot.sum(0)
    tiles = -(-sizes // tm)                                   # [held]
    starts = (jnp.cumsum(tiles) - tiles) * tm                 # first row
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0) - onehot,
                               group[:, None], axis=1)[:, 0]
    dest = jnp.where(mine, starts[group] + rank, m)           # m: dropped
    rows = jnp.zeros((m, d), x.dtype).at[dest].set(
        jnp.repeat(x, k, axis=0), mode="drop")
    tile_group = jnp.repeat(jnp.arange(held, dtype=jnp.int32), tiles,
                            total_repeat_length=m // tm)
    n_tiles = tiles.sum(keepdims=True).astype(jnp.int32)
    h = moe_gmm.gate_up(rows, params["w_gate"], params["w_up"], tile_group,
                        n_tiles, tm=tm)
    y = moe_gmm.down(h, params["w_down"], tile_group, n_tiles, tm=tm)
    # rows of pairs held elsewhere were never written: select, not scale
    y_pair = jnp.where(mine[:, None], y[jnp.minimum(dest, m - 1)], 0.0)
    out = (y_pair * gate.reshape(pairs, 1)).reshape(t, k, d).sum(1)
    return out.astype(x.dtype), _counters(sizes)
