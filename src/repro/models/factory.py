"""Model factory: per-arch entry points used by tests, training, and dry-run.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every model
input of the step function that the (arch × shape) cell lowers:

* train_*   -> ``train_step``  inputs: params, opt_state, batch
* prefill_* -> ``prefill_step`` inputs: params, batch
* decode_*  -> ``serve_step``  inputs: params, cache, tokens, pos

Frontend stubs (assignment): paligemma gets precomputed patch embeddings,
whisper gets precomputed frame embeddings, both as plain inputs.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SSM, ModelConfig, ShapeConfig
from repro.core.regions import Impl
from repro.models import lm
from repro.models import params as P


# ---------------------------------------------------------------------------
# Default impl (offload pattern) per config
# ---------------------------------------------------------------------------
def default_impl(cfg: ModelConfig) -> Impl:
    """Architectural defaults (NOT planner decisions): big MoE configs must
    use the memory-lean expert-choice dispatch (a dropless config's region
    keeps its plain ``ref``: the served plan names its variant); any stack
    with Mamba layers uses the time-sequential scan (``seq``: one step after another — §Perf
    iteration A1 cut the falcon-mamba memory term 58x vs associative).
    The schedule is the same whichever form the scan's inputs take; given
    ``mamba_block``'s factored form it runs as one Pallas kernel that
    discretises each step in VMEM, given arrays as a chunked XLA scan."""
    imp = Impl()
    if cfg.is_moe and not cfg.moe_dropless:
        # group-local expert-choice is the production dispatch for ANY expert
        # count: the token-choice one-hot path materializes a [T, E, C]
        # tensor that scales with the global token count (measured: 22 TB
        # per chip on the mixtral train cell) and exists for small-scale
        # semantic tests only (select explicitly via Impl({'moe_ffn':'ref'})).
        imp["moe_ffn"] = "offload"
    if SSM in cfg.layer_kinds():
        imp["ssm_scan"] = "seq"
    return imp


# ---------------------------------------------------------------------------
# Templates / init
# ---------------------------------------------------------------------------
def template(cfg: ModelConfig) -> dict:
    return lm.model_template(cfg)


def init_params(cfg: ModelConfig, key: jax.Array):
    return P.init(template(cfg), key)


def abstract_params(cfg: ModelConfig):
    return P.abstract(template(cfg))


def param_logical_axes(cfg: ModelConfig):
    return P.logical_axes(template(cfg))


def abstract_cache(cfg: ModelConfig, batch: int, ctx: int):
    return P.abstract(lm.cache_template(cfg, batch, ctx))


def init_cache(cfg: ModelConfig, batch: int, ctx: int, key: Optional[jax.Array] = None):
    return P.init(lm.cache_template(cfg, batch, ctx), key if key is not None
                  else jax.random.PRNGKey(0))


def cache_logical_axes(cfg: ModelConfig, batch: int, ctx: int):
    return P.logical_axes(lm.cache_template(cfg, batch, ctx))


# ---------------------------------------------------------------------------
# Input specs (dry-run stand-ins; weak-type-correct, no allocation)
# ---------------------------------------------------------------------------
def batch_spec(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    spec = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.frontend == "siglip_stub":
        spec["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.frontend_seq, cfg.frontend_dim), jnp.bfloat16)
    elif cfg.frontend == "audio_stub":
        spec["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.frontend_seq, cfg.frontend_dim), jnp.bfloat16)
    return spec


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Inputs for the step function of this cell (excluding params/opt/cache,
    which have their own abstract builders)."""
    if shape.kind in ("train", "prefill"):
        return {"batch": batch_spec(cfg, shape)}
    # decode: single new token against a seq_len cache
    b = shape.global_batch
    spec = {
        "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((b,), jnp.int32),
    }
    return spec


# ---------------------------------------------------------------------------
# Synthetic batches (smoke tests / examples)
# ---------------------------------------------------------------------------
def synthetic_request(cfg: ModelConfig, seq: int, key: jax.Array):
    """Single-sequence synthetic serving request: (tokens [S] int32,
    frontend patch/frame embeddings [S_f, D_f] or None) — the shapes
    ``ServeEngine.submit`` takes.  Shared by the serving drivers so the
    frontend-key fallback lives in one place."""
    b = synthetic_batch(cfg, 1, seq, key)
    fe = b.get("patches", b.get("frames"))
    return (np.asarray(b["tokens"][0]),
            None if fe is None else np.asarray(fe[0]))


def synthetic_batch(cfg: ModelConfig, batch: int, seq: int, key: jax.Array) -> dict:
    kt, kf = jax.random.split(key)
    out = {"tokens": jax.random.randint(kt, (batch, seq), 0, cfg.vocab_size, jnp.int32)}
    if cfg.frontend == "siglip_stub":
        out["patches"] = jax.random.normal(
            kf, (batch, cfg.frontend_seq, cfg.frontend_dim), jnp.bfloat16)
    elif cfg.frontend == "audio_stub":
        out["frames"] = jax.random.normal(
            kf, (batch, cfg.frontend_seq, cfg.frontend_dim), jnp.bfloat16)
    return out


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------
def make_forward(cfg: ModelConfig, impl: Optional[Impl] = None, remat: str = "none"):
    impl = impl if impl is not None else default_impl(cfg)

    def fwd(params, batch):
        fe = batch.get("patches", batch.get("frames"))
        return lm.forward(params, batch["tokens"], cfg=cfg, impl=impl,
                          frontend_emb=fe, remat=remat)
    return fwd


def make_loss(cfg: ModelConfig, impl: Optional[Impl] = None, remat: str = "none"):
    impl = impl if impl is not None else default_impl(cfg)

    def loss(params, batch):
        return lm.loss_fn(params, batch, cfg=cfg, impl=impl, remat=remat)
    return loss


def make_prefill_step(cfg: ModelConfig, impl: Optional[Impl] = None,
                      ctx: Optional[int] = None):
    impl = impl if impl is not None else default_impl(cfg)

    def prefill_step(params, batch):
        fe = batch.get("patches", batch.get("frames"))
        return lm.prefill(params, batch["tokens"], cfg=cfg, impl=impl,
                          frontend_emb=fe, ctx=ctx)
    return prefill_step


# ---------------------------------------------------------------------------
# Bucketed prefill (serving engine)
# ---------------------------------------------------------------------------
PREFILL_BUCKET_MIN = 8      # smallest padded prompt length


def prefill_bucket(n: int, max_len: int, min_bucket: int = PREFILL_BUCKET_MIN) -> int:
    """Padded length for an ``n``-token prompt: the smallest power of two
    >= n (floored at ``min_bucket``), capped at ``max_len`` (cache capacity
    minus any frontend prefix).  Distinct prompt lengths that share a bucket
    share one compiled prefill — the per-shape retrace this replaces is the
    serving analogue of the per-pattern recompile arXiv 2004.08548 warns
    naive placement pays."""
    if n > max_len:
        raise ValueError(f"prompt length {n} exceeds bucket cap {max_len}")
    b = max(min_bucket, 1 << max(n - 1, 0).bit_length())
    return min(b, max_len)      # b >= n, and the guard keeps max_len >= n


def make_bucketed_prefill_step(cfg: ModelConfig, impl: Optional[Impl] = None,
                               ctx: Optional[int] = None, counts: bool = False):
    """Prefill step over right-padded prompts: ``(params, batch, length)``
    where batch['tokens'] is [B, bucket] and ``length`` is the traced scalar
    count of real tokens.  Position/length masking inside ``lm.prefill``
    makes logits and caches exact for the real tokens, so the engine
    compiles once per bucket instead of once per distinct prompt length.
    With ``counts`` the step also returns the routing counters
    (``lm.prefill``)."""
    impl = impl if impl is not None else default_impl(cfg)

    def prefill_step(params, batch, length):
        fe = batch.get("patches", batch.get("frames"))
        return lm.prefill(params, batch["tokens"], cfg=cfg, impl=impl,
                          frontend_emb=fe, ctx=ctx, length=length,
                          counts=counts)
    return prefill_step


def make_serve_step(cfg: ModelConfig, impl: Optional[Impl] = None,
                    counts: bool = False):
    impl = impl if impl is not None else default_impl(cfg)

    def serve_step(params, cache, tokens, pos):
        return lm.decode_step(params, cache, tokens, pos, cfg=cfg, impl=impl,
                              counts=counts)
    return serve_step


def make_quantized_serve_step(cfg: ModelConfig, impl: Optional[Impl] = None):
    """Decode step over int8-quantized weights (dequant fuses into the
    consuming matmuls; weight HBM streaming halves — §Perf iteration 6)."""
    from repro.optim.quantize import dequantize_params

    impl = impl if impl is not None else default_impl(cfg)
    dt = jnp.dtype(cfg.dtype)

    def serve_step(qparams, cache, tokens, pos):
        params = dequantize_params(qparams, default_dtype=dt)
        return lm.decode_step(params, cache, tokens, pos, cfg=cfg, impl=impl)
    return serve_step
