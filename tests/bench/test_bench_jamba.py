"""The jamba2-mini configuration's counts against hand arithmetic from its
sizes and against the program's own templates; the two per-layer metrics
of its cell on a synthetic trace and span set; and the cell end to end
through the harness at a tiny size on the CPU."""
from __future__ import annotations

import json
import time
from types import SimpleNamespace

import pytest

from bench import harness
from bench.peaks import peaks
from bench.trace import TraceSummary
from repro.obs import Span

JB = harness.load_module(harness.ROOT / "bench/configs/jamba2-mini.py")
ROOF = harness.load_module(
    harness.ROOT / "bench/metrics/kernels.moe_gmm_roofline.docqa.py")
SHARE = harness.load_module(
    harness.ROOT / "bench/metrics/kernels.moe_gmm_share.docqa.py")
CELL = "jamba2-mini.docqa"


def test_jamba_layout_of_the_cut():
    kinds = JB.layer_kinds(JB.CONF)
    assert [m for m, _ in kinds] == ["ssm"] * 4 + ["attn"] + ["ssm"] * 3
    assert [f for _, f in kinds] == ["mlp", "moe"] * 4


def test_jamba_params_by_layer_kind_by_hand():
    # in_proj 4096x16384, x_proj 8192x(256+32), dt_proj 256x8192,
    # out_proj 8192x4096
    assert JB.mamba_matmul_params(JB.CONF) == 105_119_744
    # plus norm 4096, conv 4x8192 and its bias 8192, dt bias 8192,
    # A_log 8192x16, D 8192, and the dt/B/C norms 256 + 16 + 16
    assert JB.mamba_params(JB.CONF) == 105_312_544
    # Wq, Wo 4096x4096; Wk, Wv 4096x1024; the norm
    assert JB.attn_params(JB.CONF) == 41_943_040 + 4096
    assert JB.expert_params(JB.CONF) == 3 * 4096 * 14336 == 176_160_768
    assert JB.mlp_params(JB.CONF) == 176_160_768 + 4096
    # 8 held experts, the router over all 16, the norm
    assert JB.moe_params(JB.CONF) == 8 * 176_160_768 + 4096 * 16 + 4096
    assert JB.param_count(JB.CONF) == (
        2 * 8192 * 4096 + 4096 + 7 * 105_312_544 + 41_947_136
        + 4 * 176_164_864 + 4 * 1_409_355_776) == 7_188_330_464


def test_jamba_bytes_match_the_program_templates():
    from repro.models import factory, lm
    from repro.models.params import param_bytes
    cfg = JB.model_config(JB.CONF)
    assert JB.weight_bytes(JB.CONF) == (
        7_188_330_464 * 2 + 7 * 8192 * 16 * 2) == 14_378_495_936
    assert param_bytes(factory.template(cfg)) == JB.weight_bytes(JB.CONF)
    ctx = JB.CONF["serving"]["ctx"]
    # K, V 8 x 4352 x 128 bfloat16 and slot positions; h 8192x16 float32
    # and the conv tail 3x8192 bfloat16 in each of the 7 Mamba layers
    per_slot = 2 * 8 * 4352 * 128 * 2 + 4352 * 4 + 7 * (524_288 + 49_152)
    assert JB.cache_bytes_per_slot(JB.CONF, ctx) == per_slot == 21_857_280
    assert param_bytes(lm.cache_template(cfg, 1, ctx)) == per_slot


def test_jamba_decode_bytes_by_hand():
    experts = 4 * 8 * 176_160_768 * 2
    expect = (14_378_495_936 - experts - 8192 * 4096 * 2 + experts
              + 16 * 4096 * 2 + 2 * 16 * 21_857_280 + 16 * 8192 * 4)
    assert JB.decode_step_bytes(JB.CONF, 16, 8) == expect == 15_011_475_392
    hit = JB.expected_experts_hit(JB.CONF, 16)
    assert hit == pytest.approx(8 * (1 - (14 / 16) ** 16))
    assert hit == pytest.approx(7.0555, abs=1e-4)
    # the expert weights read are about three quarters of the step's bytes
    share = 4 * hit * 176_160_768 * 2 / JB.decode_step_bytes(JB.CONF, 16, hit)
    assert 0.70 < share < 0.76


def test_jamba_flops_by_hand():
    scan = 6 * 8192 * 16 + 2 * 4 * 8192
    body = (7 * (2 * 105_119_744 + scan) + 2 * 41_943_040
            + 4 * 2 * 176_160_768 + 4 * (2 * 4096 * 16 + 2 * 176_160_768))
    assert body == 4_380_622_848            # about 4.4 GFLOP a token
    head = 2 * 4096 * 8192
    assert JB.decode_flops_per_token(JB.CONF) == body + head
    causal = 4 * 32 * 128 * (100 * 101 // 2)
    assert JB.prefill_flops(JB.CONF, 100) == 100 * body + causal + head
    assert JB.moe_gmm_flops(JB.CONF, 10) == 10 * 6 * 4096 * 14336
    per_pair = 2 * 4096 * 2 + 2 * 14336 * 2 + 4096 * 2
    assert JB.moe_gmm_bytes(JB.CONF, 10, 3) == (3 * 176_160_768 * 2
                                                 + 10 * per_pair)


def test_jamba_model_config_keeps_published_widths():
    cfg = JB.model_config(JB.CONF)
    assert (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv,
            cfg.resolved_dt_rank, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.moe_d_ff, cfg.num_experts,
            cfg.experts_per_token) == (4096, 8192, 16, 4, 256, 32, 8, 128,
                                       14336, 14336, 16, 2)
    assert (cfg.num_layers, cfg.resolved_experts_held, cfg.vocab_size) == (
        8, 8, 8192)
    assert cfg.ssm_conv_bias and cfg.ssm_dt_norm and not cfg.use_rope
    assert cfg.moe_dropless and not cfg.moe_renormalize
    assert JB.CONF["reduced"] == ["num_hidden_layers", "num_experts",
                                  "vocab_size"]
    assert JB.CONF["published"] == {"num_hidden_layers": 32,
                                    "num_experts": 16, "vocab_size": 65536}


# ---------------------------------------------------------------------------
# The two per-layer metrics on a synthetic trace and span set
# ---------------------------------------------------------------------------
T_END = 100.0


def _span(i, name, start, end, parent=None, **attrs):
    return Span(i, parent, name, start, end, attrs)


def _run(summary, spans, monkeypatch):
    monkeypatch.setattr(ROOF, "window_spans", lambda run: (spans, T_END))
    cell = SimpleNamespace(conf=JB.CONF, counts=JB, mix={"trace_s": 5})
    return SimpleNamespace(summary=summary, cell=cell,
                           peaks=peaks("TPU v5 lite"))


def _summary(secs=0.5, busy=4.0):
    customs = {"moe_gmm": [secs, 24]} if secs else {}
    return TraceSummary(window_s=5.0, busy_s=busy, chips=1, offset_s=0.0,
                        custom_calls=customs)


def _spans(with_counts=True):
    c = (lambda p, h: {"moe_pairs": p, "moe_experts_hit": h}) if with_counts \
        else (lambda p, h: {})
    return [
        # a tick before the traced window: not counted
        _span(1, "engine.tick", 94.0, 94.1),
        _span(2, "engine.decode", 94.0, 94.1, 1, slots=16, **c(16, 28)),
        # a tick that admits one request and decodes
        _span(3, "engine.tick", 95.5, 95.8),
        _span(4, "engine.admit", 95.5, 95.7, 3),
        _span(5, "engine.prefill", 95.5, 95.7, 4, bucket=4096,
              prompt_len=4000, **c(16_000, 32)),
        _span(6, "engine.decode", 95.7, 95.8, 3, slots=16, **c(16, 28)),
        # the last tick, which the window's end falls in
        _span(7, "engine.tick", 99.95, 100.02),
        _span(8, "engine.decode", 99.95, 100.02, 7, slots=16, **c(20, 30)),
    ]


def test_moe_gmm_roofline_by_hand(monkeypatch):
    p = peaks("TPU v5 lite")

    def least(pairs, hit):
        return max(JB.moe_gmm_flops(JB.CONF, pairs) / p.bf16_flops,
                   JB.moe_gmm_bytes(JB.CONF, pairs, hit) / p.hbm_bytes_s)
    run = _run(_summary(0.5), _spans(), monkeypatch)
    want = 100.0 * (least(16_000, 32) + least(16, 28) + least(20, 30)) / 0.5
    assert ROOF.read(run) == pytest.approx(want)
    # prefill is bound by compute, decode by the expert weights' bytes
    assert (least(16_000, 32) == JB.moe_gmm_flops(JB.CONF, 16_000)
            / p.bf16_flops)
    assert least(16, 28) == JB.moe_gmm_bytes(JB.CONF, 16, 28) / p.hbm_bytes_s


def test_moe_gmm_readers_read_nothing_without_the_kernel_or_counters(
        monkeypatch):
    # a program without the counters (the parent): spans carry no moe_pairs
    assert ROOF.read(_run(_summary(), _spans(False), monkeypatch)) is None
    # a trace without the kernel, or no trace (a CPU run)
    assert ROOF.read(_run(_summary(0), _spans(), monkeypatch)) is None
    assert ROOF.read(_run(None, _spans(), monkeypatch)) is None
    assert SHARE.read(_run(_summary(0), [], monkeypatch)) is None
    assert SHARE.read(_run(None, [], monkeypatch)) is None


def test_moe_gmm_share_of_busy_time(monkeypatch):
    run = _run(_summary(secs=1.5, busy=4.0), [], monkeypatch)
    assert SHARE.read(run) == pytest.approx(37.5)


# ---------------------------------------------------------------------------
# The cell end to end at a tiny size on the CPU
# ---------------------------------------------------------------------------
TINY_JAMBA = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "mamba_dt_rank": 8, "vocab_size": 256,
              "serving": {"slots": 3, "ctx": 72,
                          "plan": {"ssm_scan": "seq", "moe_dropless": "gmm"}}}
TINY_DOCQA = {"outstanding": 6, "block": 6, "ramp_s": 0.5, "trace_s": 1,
              "prompt_tokens": {"dist": "uniform", "min": 12, "max": 40},
              "output_tokens": {"dist": "uniform", "min": 2, "max": 6},
              "check": {"requests": 2, "tokens": 10}}
# At this size the served tokens' mean gap read at most 1.8e-3 over seeds
# 0-5 (CPU, bfloat16 as served); the float8 control reads from 0 to 3.4e-2
# there, and seed 3, 3.4e-2, is the one the control's test runs.
TINY_LIMIT = 5e-3


@pytest.fixture
def jamba_root(tiny_root):
    conf = tiny_root / "bench/configs/jamba2-mini.json"
    c = json.loads(conf.read_text())
    c.update(TINY_JAMBA)
    conf.write_text(json.dumps(c))
    mix = tiny_root / "bench/traffic/docqa.json"
    m = json.loads(mix.read_text())
    m.update(TINY_DOCQA)
    mix.write_text(json.dumps(m))
    check = tiny_root / f"bench/checks/{CELL}.json"
    lim = json.loads(check.read_text())
    lim["mean_logit_gap"]["limit"] = TINY_LIMIT
    check.write_text(json.dumps(lim))
    return tiny_root


@pytest.mark.parametrize("seed", [3, 2**31 + 12345])
def test_jamba_cell_end_to_end_on_the_cpu(jamba_root, seed):
    line = harness.run_cell(CELL, seed, 1.5, False,
                            t_start=time.perf_counter(), root=jamba_root,
                            device_kind="TPU v5 lite")
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["value"] > 0
    assert line["checks"]["mean_logit_gap"]["value"] <= TINY_LIMIT


def test_jamba_cell_float8_control_is_not_correct(jamba_root):
    cell = harness.load_cell(CELL, jamba_root)
    run = harness.Run(cell=cell, seed=3, seconds=1.5, trace=False,
                      peaks=peaks("TPU v5 lite"),
                      t_start=time.perf_counter(), control=True)
    cell.driver.run(run, harness.CompileCounter())
    assert run.correct and not run.control_correct
