"""jamba2-mini as served: sizes from ``jamba2-mini.json`` (one 8-layer
period, 8 of 16 experts held, a 1/8 vocabulary slice), the weights drawn
from the seed, and the operation and byte counts of its steps and of its
grouped expert kernel.  The counts use the file's sizes only.
"""
from __future__ import annotations

import json
from pathlib import Path

CONF = json.loads(Path(__file__).with_suffix(".json").read_text())

# float32 element bytes of the recurrent state and A_log, bfloat16 the rest
_BF16, _F32 = 2, 4


def _dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    return dict(d=d, di=conf["mamba_expand"] * d, n=conf["mamba_d_state"],
                r=conf["mamba_dt_rank"], k=conf["mamba_d_conv"],
                f=conf["intermediate_size"], hq=hq, hkv=hkv, hd=d // hq,
                v=conf["vocab_size"], held=conf["num_experts"],
                routed=conf["router_experts"],
                top=conf["num_experts_per_tok"])


def layer_kinds(conf: dict) -> list[tuple[str, str]]:
    """(mixer, feed-forward) of each layer: ``attn``/``ssm``, ``moe``/``mlp``."""
    out = []
    for i in range(conf["num_hidden_layers"]):
        mixer = ("attn" if i % conf["attn_layer_period"]
                 == conf["attn_layer_offset"] else "ssm")
        ffn = ("moe" if i % conf["expert_layer_period"]
               == conf["expert_layer_offset"] else "mlp")
        out.append((mixer, ffn))
    return out


def mamba_matmul_params(conf: dict) -> int:
    """in_proj (x and z), x_proj (dt, B, C), dt_proj, out_proj."""
    m = _dims(conf)
    return (m["d"] * 2 * m["di"] + m["di"] * (m["r"] + 2 * m["n"])
            + m["r"] * m["di"] + m["di"] * m["d"])


def mamba_params(conf: dict) -> int:
    """A Mamba mixer with its pre-norm: the matrices, the conv taps and
    bias, dt bias, A_log, D, and the dt/B/C norm weights."""
    m = _dims(conf)
    return (mamba_matmul_params(conf) + m["d"] + m["k"] * m["di"] + m["di"]
            + m["di"] + m["di"] * m["n"] + m["di"] + m["r"] + 2 * m["n"])


def attn_matmul_params(conf: dict) -> int:
    m = _dims(conf)
    return 2 * m["d"] * m["hq"] * m["hd"] + 2 * m["d"] * m["hkv"] * m["hd"]


def attn_params(conf: dict) -> int:
    """Wq, Wk, Wv, Wo and the pre-norm (no biases, no positions)."""
    return attn_matmul_params(conf) + _dims(conf)["d"]


def expert_params(conf: dict) -> int:
    """One SwiGLU expert (gate, up, down); a dense MLP has the same."""
    m = _dims(conf)
    return 3 * m["d"] * m["f"]


def mlp_params(conf: dict) -> int:
    return expert_params(conf) + _dims(conf)["d"]


def moe_params(conf: dict) -> int:
    """The held experts, the router over every routed expert, the norm."""
    m = _dims(conf)
    return m["held"] * expert_params(conf) + m["d"] * m["routed"] + m["d"]


def param_count(conf: dict) -> int:
    m = _dims(conf)
    total = 2 * m["v"] * m["d"] + m["d"]             # embed, head, final norm
    for mixer, ffn in layer_kinds(conf):
        total += mamba_params(conf) if mixer == "ssm" else attn_params(conf)
        total += moe_params(conf) if ffn == "moe" else mlp_params(conf)
    return total


def weight_bytes(conf: dict) -> int:
    """Bytes of all weights as served: bfloat16, A_log in float32."""
    m = _dims(conf)
    n_ssm = sum(mixer == "ssm" for mixer, _ in layer_kinds(conf))
    return (param_count(conf) * _BF16
            + n_ssm * m["di"] * m["n"] * (_F32 - _BF16))


def cache_bytes_per_slot(conf: dict, ctx: int) -> int:
    """One slot's cache: K and V [kv_heads, ctx, head_dim] bfloat16 and
    slot positions int32 per attention layer; h [d_inner, state] float32
    and the conv tail [conv-1, d_inner] bfloat16 per Mamba layer."""
    m = _dims(conf)
    kinds = [mixer for mixer, _ in layer_kinds(conf)]
    kv = 2 * m["hkv"] * ctx * m["hd"] * _BF16 + ctx * 4
    state = m["di"] * m["n"] * _F32 + (m["k"] - 1) * m["di"] * _BF16
    return kinds.count("attn") * kv + kinds.count("ssm") * state


def expected_experts_hit(conf: dict, tokens: int) -> float:
    """Held experts of one expert layer that ``tokens`` tokens reach, in
    expectation under uniform routing: each is missed by a token with
    probability 1 - top/routed."""
    m = _dims(conf)
    return m["held"] * (1.0 - (1.0 - m["top"] / m["routed"]) ** tokens)


def decode_step_bytes(conf: dict, slots: int, experts_hit: float) -> float:
    """Least HBM traffic of one decode step over ``slots`` rows: every
    weight outside the experts read once (the head, not the embedding
    table), ``experts_hit`` experts per expert layer, one embedding row per
    slot, the whole cache read and written, the float32 logits written."""
    m = _dims(conf)
    n_moe = sum(ffn == "moe" for _, ffn in layer_kinds(conf))
    experts = n_moe * m["held"] * expert_params(conf) * _BF16
    ctx = conf["serving"]["ctx"]
    return (weight_bytes(conf) - experts - m["v"] * m["d"] * _BF16
            + n_moe * experts_hit * expert_params(conf) * _BF16
            + slots * m["d"] * _BF16
            + 2 * slots * cache_bytes_per_slot(conf, ctx)
            + slots * m["v"] * _F32)


def moe_gmm_flops(conf: dict, pairs: int) -> int:
    """The grouped expert kernel's operations for ``pairs`` routed (token,
    held expert) pairs: gate, up and down products, 2 d f each."""
    m = _dims(conf)
    return 6 * m["d"] * m["f"] * pairs


def moe_gmm_bytes(conf: dict, pairs: int, experts_hit: int) -> int:
    """Least HBM bytes of the grouped expert kernel: the weights of every
    (layer, expert) hit read once, and per pair its row in (d) and h out
    (f) of the gate/up kernel and h in (f) and y out (d, float32) of the
    down kernel."""
    m = _dims(conf)
    per_pair = 2 * m["d"] * _BF16 + 2 * m["f"] * _BF16 + m["d"] * (_F32 - _BF16)
    return experts_hit * expert_params(conf) * _BF16 + pairs * per_pair


def _body_flops_per_token(conf: dict) -> float:
    """Model FLOPs of one token through the layers, without attention over
    its context and without the head: the matrices, the scan and conv, the
    router, and the routed experts at their expected share here (top x
    held / routed experts per expert layer: one)."""
    m = _dims(conf)
    scan = 6 * m["di"] * m["n"] + 2 * m["k"] * m["di"]
    share = m["top"] * m["held"] / m["routed"]
    total = 0.0
    for mixer, ffn in layer_kinds(conf):
        total += (2 * mamba_matmul_params(conf) + scan if mixer == "ssm"
                  else 2 * attn_matmul_params(conf))
        total += (2 * m["d"] * m["routed"] + share * 2 * expert_params(conf)
                  if ffn == "moe" else 2 * expert_params(conf))
    return total


def head_flops(conf: dict) -> int:
    m = _dims(conf)
    return 2 * m["d"] * m["v"]


def attention_flops(conf: dict, context: int) -> int:
    """Scores and weighted sum of one query over ``context`` positions, in
    every attention layer."""
    m = _dims(conf)
    n_attn = sum(mixer == "attn" for mixer, _ in layer_kinds(conf))
    return n_attn * 4 * m["hq"] * m["hd"] * context


def prefill_flops(conf: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` real tokens: every token through the
    layers and causal attention over its prefix, the head at the last
    position only."""
    causal = attention_flops(conf, 1) * prompt_len * (prompt_len + 1) // 2
    return prompt_len * _body_flops_per_token(conf) + causal + head_flops(conf)


def decode_flops_per_token(conf: dict) -> float:
    """One generated token through the layers and the head; its attention
    over the cache depends on its position and is left out
    (:func:`attention_flops` gives it)."""
    return _body_flops_per_token(conf) + head_flops(conf)


# ---------------------------------------------------------------------------
# The program's view of the configuration, and the weights
# ---------------------------------------------------------------------------
def model_config(conf: dict):
    """The program's ``ModelConfig`` for these sizes."""
    from repro.configs.base import ATTN, SSM, ModelConfig
    d = conf["hidden_size"]
    pattern = tuple(ATTN if mixer == "attn" else SSM
                    for mixer, _ in layer_kinds(conf)[:conf["attn_layer_period"]])
    return ModelConfig(
        name=conf["name"], family="hybrid",
        num_layers=conf["num_hidden_layers"], d_model=d,
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=d // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        num_experts=conf["router_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        moe_d_ff=conf["intermediate_size"],
        moe_layer_period=conf["expert_layer_period"],
        moe_layer_offset=conf["expert_layer_offset"],
        moe_dropless=True, moe_renormalize=False,
        experts_first=conf["experts_first"],
        experts_held=conf["num_experts"],
        ssm_state=conf["mamba_d_state"], ssm_conv=conf["mamba_d_conv"],
        ssm_expand=conf["mamba_expand"], dt_rank=conf["mamba_dt_rank"],
        ssm_conv_bias=conf["mamba_conv_bias"], ssm_dt_norm=True,
        layer_pattern=pattern, use_rope=False,
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], dtype=conf["dtype"],
        source=conf["source"])


# norms, biases and the dt bias are drawn N(0, SMALL^2), not left at 0, so
# that the comparison with the reference sees them
SMALL = 0.1


def init_weights(cfg, key):
    """Every weight in one jitted call on the device, in the type it is
    served in, from ``key``; the tree is the program's parameter layout."""
    import jax
    import jax.numpy as jnp
    from repro.models import factory
    from repro.models.params import ParamSpec

    leaves, treedef = jax.tree.flatten(
        factory.template(cfg), is_leaf=lambda x: isinstance(x, ParamSpec))
    specs = [(s.shape, s.dtype, s.init, s.scale) for s in leaves]
    depth = 2 * cfg.num_layers

    @jax.jit
    def draw(key):
        out = []
        for k, (shape, dtype, init, scale) in zip(
                jax.random.split(key, len(specs)), specs):
            dt = jnp.dtype(dtype)
            if init == "zeros":
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * SMALL).astype(dt))
            elif init == "ones":
                out.append(jnp.ones(shape, dt))
            elif init == "a_log":
                a = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
                out.append(jnp.broadcast_to(a, shape).astype(dt))
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                std = (scale if init == "normal" else 1.0 / depth ** 0.5)
                std = std / fan_in ** 0.5
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dt))
        return out

    return jax.tree.unflatten(treedef, draw(key))
