"""Config system: model architecture configs, input-shape configs, registries.

Every assigned architecture is a ``ModelConfig`` produced by a module in this
package (``repro/configs/<arch>.py``).  Shapes are global (the assignment pairs
every LM arch with the same four shapes).  ``reduced()`` derives the smoke-test
config used by CPU tests: same family/topology, tiny dims.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence


# ---------------------------------------------------------------------------
# Layer-pattern vocabulary for hybrid archs.
# ---------------------------------------------------------------------------
ATTN = "attn"            # global (full) attention block
LOCAL_ATTN = "local"     # sliding-window attention block
RGLRU = "rglru"          # RG-LRU recurrent block (recurrentgemma)
SSM = "ssm"              # Mamba-1 selective-state-space block

# Feed-forward kinds of a layer (``ModelConfig.ffn_kinds``).
MLP = "mlp"              # dense SwiGLU (GELU for audio) MLP
MOE = "moe"              # routed experts


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  All sizes are the FULL assigned config; use
    :meth:`reduced` for CPU smoke tests."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads

    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0                # expert FFN width (if != d_ff)
    dense_residual_d_ff: int = 0     # arctic: parallel dense FFN next to MoE
    capacity_factor: float = 1.25
    # layer i has experts iff i % moe_layer_period == moe_layer_offset (jamba);
    # 0: the experts sit on the attention layers and every other layer has a
    # dense MLP
    moe_layer_period: int = 0
    moe_layer_offset: int = 0
    moe_dropless: bool = False       # dropless token-choice top-k (region
                                     # moe_dropless) in place of moe_ffn
    moe_renormalize: bool = True     # top-k gate weights rescaled to sum 1
    experts_first: int = 0           # first of the experts held here
    experts_held: int = 0            # experts held here (0 -> num_experts);
                                     # the router still spans num_experts

    # --- SSM (mamba-1) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: int = 0                 # 0 -> ceil(d_model / 16)
    ssm_conv_bias: bool = False      # the depthwise conv adds a bias
    ssm_dt_norm: bool = False        # dt, B and C RMS-normalised (learned
                                     # weights, norm_eps) before dt_proj / scan

    # --- hybrid (recurrentgemma) ---
    layer_pattern: Sequence[str] = ()   # repeating block pattern, e.g. (RGLRU, RGLRU, LOCAL_ATTN)
    attn_window: int = 0             # sliding window for LOCAL_ATTN layers
    rglru_d_rnn: int = 0             # RG-LRU recurrent width (0 -> d_model)

    # --- enc-dec (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0             # fixed encoder positions (whisper: 1500)
    cross_attention: bool = False

    # --- frontends (stubs per assignment) ---
    frontend: str = "none"           # none | siglip_stub | audio_stub
    frontend_seq: int = 0            # number of patch/frame embeddings provided
    frontend_dim: int = 0            # embedding dim provided by the stub
    conv_stem: bool = False          # audio frontend is a real 2-conv stem
                                     # (k=3 stride 1 then stride 2), not a
                                     # single linear projection

    # --- misc knobs ---
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    use_rope: bool = True            # False: attention sees no positions
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    source: str = ""                 # provenance tag from the assignment

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    @property
    def resolved_dt_rank(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    @property
    def d_inner(self) -> int:
        """Mamba inner width."""
        return self.ssm_expand * self.d_model

    @property
    def n_front(self) -> int:
        """Frontend tokens prepended to the decoder sequence (siglip patch
        embeddings; audio frames feed the encoder instead, not the prefix)."""
        return self.frontend_seq if self.frontend == "siglip_stub" else 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def resolved_experts_held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch can decode at 500k context without a full-size
        dense KV cache (SSM state / bounded local window)."""
        if self.family == "ssm":
            return True
        if self.family == "hybrid" and self.attn_window > 0 and ATTN not in tuple(self.layer_pattern):
            return True
        return False

    @property
    def has_decoder(self) -> bool:
        """False only for encoder-only archs (none assigned)."""
        return True

    def layer_kinds(self) -> list[str]:
        """Expanded per-layer block kinds for the decoder stack."""
        if self.family == "ssm":
            return [SSM] * self.num_layers
        if self.layer_pattern:
            pat = list(self.layer_pattern)
            return [pat[i % len(pat)] for i in range(self.num_layers)]
        return [ATTN] * self.num_layers

    def ffn_kinds(self) -> list[str]:
        """Per-layer feed-forward kind: ``MOE``, ``MLP`` or ``""`` (none)."""
        out = []
        for i, kind in enumerate(self.layer_kinds()):
            if not self.d_ff:
                out.append("")
            elif self.moe_layer_period:
                out.append(MOE if i % self.moe_layer_period
                           == self.moe_layer_offset else MLP)
            else:
                out.append(MOE if self.is_moe and kind in (ATTN, LOCAL_ATTN)
                           else MLP)
        return out

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-FLOPs)."""
        hd = self.resolved_head_dim
        emb = self.vocab_size * self.d_model
        out = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        counts = {k: 0 for k in (ATTN, LOCAL_ATTN, RGLRU, SSM)}
        for k in self.layer_kinds():
            counts[k] += 1
        n_attn = counts[ATTN] + counts[LOCAL_ATTN]
        # attention projections
        attn_p = (self.d_model * self.num_heads * hd          # Wq
                  + 2 * self.d_model * self.num_kv_heads * hd  # Wk, Wv
                  + self.num_heads * hd * self.d_model)        # Wo
        if self.qkv_bias:
            attn_p += (self.num_heads + 2 * self.num_kv_heads) * hd
        # FFN (SwiGLU: 3 mats) by each layer's kind; experts as held here
        eff = self.moe_d_ff or self.d_ff
        moe_p = (self.resolved_experts_held * 3 * self.d_model * eff
                 + self.d_model * self.num_experts)            # router
        if self.dense_residual_d_ff:
            moe_p += 3 * self.d_model * self.dense_residual_d_ff
        mlp_p = 3 * self.d_model * self.d_ff
        ffn_p = sum(moe_p if f == MOE else mlp_p if f == MLP else 0
                    for f in self.ffn_kinds())
        norm_p = 2 * self.d_model
        total = emb + out + self.d_model  # final norm
        total += n_attn * attn_p + self.num_layers * norm_p + ffn_p
        # recurrent blocks
        if counts[RGLRU]:
            d_rnn = self.rglru_d_rnn or self.d_model
            # input/gate projections + recurrent gates + output
            rg_p = (2 * self.d_model * d_rnn + 2 * d_rnn * (d_rnn // 8 if d_rnn >= 8 else d_rnn)
                    + d_rnn * self.d_model + 2 * d_rnn)
            total += counts[RGLRU] * rg_p
        if counts[SSM]:
            di, st, dtr = self.d_inner, self.ssm_state, self.resolved_dt_rank
            ssm_p = (self.d_model * 2 * di           # in_proj (x and z)
                     + di * self.ssm_conv            # depthwise conv
                     + di * (dtr + 2 * st)           # x -> dt, B, C
                     + dtr * di                      # dt_proj
                     + di * st                       # A_log
                     + di                            # D
                     + di * self.d_model)            # out_proj
            if self.ssm_conv_bias:
                ssm_p += di
            if self.ssm_dt_norm:
                ssm_p += dtr + 2 * st                # dt, B, C norm weights
            total += counts[SSM] * ssm_p
        # enc-dec extras
        if self.encoder_layers:
            enc_p = self.encoder_layers * (attn_p + 3 * self.d_model * self.d_ff + 2 * self.d_model)
            total += enc_p
            if self.cross_attention:
                total += n_attn * attn_p  # cross-attn per decoder layer
        if self.conv_stem:
            # two k=3 conv1d layers: frontend_dim -> d_model -> d_model
            total += (3 * self.frontend_dim * self.d_model + self.d_model
                      + 3 * self.d_model * self.d_model + self.d_model)
        return int(total)

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: only routed experts count).
        Where only some experts are held here, a token's picks land on them
        at their share: ``experts_per_token * held / num_experts`` experts
        per expert layer, in expectation."""
        if not self.is_moe:
            return self.param_count()
        eff = self.moe_d_ff or self.d_ff
        held = self.resolved_experts_held
        n_moe = self.ffn_kinds().count(MOE)
        per_expert = 3 * self.d_model * eff
        active = (n_moe * per_expert * self.experts_per_token * held
                  // self.num_experts)
        return int(self.param_count() - n_moe * held * per_expert + active)

    # ------------------------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        pat = tuple(self.layer_pattern[:3]) if self.layer_pattern else ()
        if self.moe_layer_period:
            # experts by layer position: one whole period of the pattern
            pat = tuple(self.layer_pattern)
        experts = min(self.num_experts, 4)
        held = (self.experts_held * experts // self.num_experts
                if self.experts_held else 0)
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            num_layers=min(self.num_layers, len(pat) or 2) if pat else 2,
            d_model=64,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            head_dim=16,
            # an MLP-free arch (falcon-mamba: d_ff=0) must stay MLP-free
            # when reduced — the extractor benchmark scores the reduced
            # trace against the full config's annotation
            d_ff=min(self.d_ff, 128),
            vocab_size=256,
            num_experts=experts,
            experts_held=held,
            experts_first=self.experts_first * experts // max(self.num_experts, 1),
            experts_per_token=min(self.experts_per_token, 2),
            moe_d_ff=64 if self.moe_d_ff else 0,
            dense_residual_d_ff=64 if self.dense_residual_d_ff else 0,
            ssm_state=min(self.ssm_state, 8),
            dt_rank=4 if self.family == "ssm" else 0,
            layer_pattern=pat,
            attn_window=min(self.attn_window, 32) if self.attn_window else 0,
            rglru_d_rnn=64 if self.rglru_d_rnn else 0,
            encoder_layers=min(self.encoder_layers, 2),
            encoder_seq=min(self.encoder_seq, 16) if self.encoder_seq else 0,
            # a conv stem downsamples frames 2x into encoder positions, so
            # the reduced frame count must stay 2x the reduced encoder_seq
            frontend_seq=(2 * min(self.encoder_seq, 16) if self.conv_stem
                          else min(self.frontend_seq, 16)
                          if self.frontend_seq else 0),
            frontend_dim=64 if self.frontend_dim else 0,
        )


# ---------------------------------------------------------------------------
# Shapes (assignment: same four shapes for every LM arch)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Assignment rules: long_500k only for sub-quadratic archs; decode only
    for archs with a decoder."""
    if shape.kind == "decode" and not cfg.has_decoder:
        return False, "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "long_500k needs sub-quadratic attention; %s is full-attention" % cfg.name
    return True, ""


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
ARCH_IDS = (
    "recurrentgemma-2b",
    "mistral-nemo-12b",
    "phi3-medium-14b",
    "qwen2-72b",
    "deepseek-67b",
    "kimi-k2-1t-a32b",
    "arctic-480b",
    "paligemma-3b",
    "whisper-small",
    "falcon-mamba-7b",
)

# beyond-assignment extras (separate so the assigned 40-cell accounting in
# EXPERIMENTS.md stays exact); loaded into the registry all the same.
BONUS_ARCH_IDS = (
    "mixtral-8x7b",
    "jamba2-mini",
)

_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if not _REGISTRY:
        _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def all_configs() -> dict[str, ModelConfig]:
    if not _REGISTRY:
        _load_all()
    return dict(_REGISTRY)


def _load_all() -> None:
    import importlib

    for arch in ARCH_IDS + BONUS_ARCH_IDS:
        importlib.import_module("repro.configs." + arch.replace("-", "_"))
