"""The chip smoke run off the chip: it refuses to run without a TPU, and its
phase functions run end to end on reduced sizes with the kernels in
interpret mode."""
import dataclasses
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.apps import mriq, tdfir
from repro.configs import get_config
from repro.configs.paper_apps import MriQConfig, TdFirConfig
from repro.core.regions import REGISTRY
from repro.launch import smoke

ROOT = Path(__file__).resolve().parents[1]
FIR_SMALL = TdFirConfig(n_banks=4, n_taps=16, n_samples=256)
MRIQ_SMALL = MriQConfig(num_x=1024, num_k=128)


def _run_smoke(cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    proc = _run_smoke(ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def _reduced_kernel_cases():
    return smoke.kernel_cases(
        jax.random.PRNGKey(0), fir=FIR_SMALL, mriq=MRIQ_SMALL,
        ssm=get_config(smoke.SERVE_ARCH).reduced(), ssm_seq=64,
        attn=get_config(smoke.ATTN_ARCH).reduced(), attn_seq=128)


def test_kernel_phase_on_reduced_sizes():
    cases = _reduced_kernel_cases()
    assert [c.region for c in cases] == ["fir_bank", "compute_q",
                                         "ssm_scan", "attn_core"]
    assert smoke.run_kernels(cases) == []


def _plant(monkeypatch, fault):
    """Serve ``fault(pallas)`` as the ssm_scan pallas variant."""
    table = REGISTRY["ssm_scan"]
    monkeypatch.setitem(table, "pallas", fault(table["pallas"]))


def _dropped_state_term(pallas):
    # the last of the N state entries never reaches the output
    return lambda a, bx, c, h0, **kw: pallas(a, bx, c.at[..., -1].set(0),
                                             h0, **kw)


def test_kernel_phase_catches_a_dropped_state_term(monkeypatch):
    _plant(monkeypatch, _dropped_state_term)
    cases = [c for c in _reduced_kernel_cases() if c.region == "ssm_scan"]
    [failure] = smoke.run_kernels(cases)
    assert failure.startswith("kernel ssm_scan: error")


def test_planner_phase_on_reduced_sizes(tmp_path):
    progs = [tdfir.make_program(FIR_SMALL, FIR_SMALL),
             mriq.make_program(MRIQ_SMALL, MRIQ_SMALL)]
    assert smoke.run_planner(progs, tmp_path / "plans.json", seed=0) == []


def _serve_reduced(tmp_path, plan_arch=None):
    return smoke.run_serving(
        get_config(smoke.SERVE_ARCH).reduced(), seed=0, slots=2,
        prompt_len=16, new_tokens=4, requests=2, hbm_bytes=None,
        plan_arch=plan_arch, plan_cache_path=tmp_path / "plans.json")


def test_serving_phase_on_reduced_config(tmp_path):
    assert _serve_reduced(tmp_path, plan_arch=smoke.SERVE_ARCH) == []


def _time_shift(pallas):
    # the scan's output lags its input by one step
    def fn(a, bx, c, h0, **kw):
        y, h = pallas(a, bx, c, h0, **kw)
        return jnp.concatenate([jnp.zeros_like(y[:, :1]), y[:, :-1]], 1), h
    return fn


def test_serving_phase_catches_a_time_shift_in_the_scan(monkeypatch,
                                                        tmp_path):
    _plant(monkeypatch, _time_shift)
    [failure] = _serve_reduced(tmp_path)
    assert failure.startswith("serve ssm_scan=pallas: first-token logits "
                              "differ from the float32 reference scan")


@pytest.mark.parametrize("hbm_bytes,layers", [(1e12, 4), (1.0, 2)])
def test_fit_layers_halves_the_stack_only_when_memory_is_short(hbm_bytes,
                                                               layers):
    cfg = dataclasses.replace(get_config(smoke.SERVE_ARCH).reduced(),
                              num_layers=4)
    assert smoke.fit_layers(cfg, hbm_bytes, ctx=24,
                            bucket=16).num_layers == layers
    assert smoke.fit_layers(cfg, None, ctx=24, bucket=16) is cfg


def test_plan_failures_names_what_the_fallbacks_would_hide():
    from repro.core.planner import CandidateInfo, PlanReport
    from repro.core.intensity import RegionAnalysis
    from repro.core.resources import ResourceEstimate
    from repro.core.search import Measurement

    cand = CandidateInfo("r", RegionAnalysis(name="r"))
    cand.variant_estimates["pallas"] = ResourceEstimate(
        "r", "pallas", float("inf"), 0, 0.0, False, "refused")
    report = PlanReport("p", 0, 0, candidates=[cand])
    report.baseline = Measurement("all-ref", 0.0, 1.0, [1.0])
    report.measurements = [Measurement("{r: pallas}", 0.0, 0.0, [],
                                       ok=False, error="boom")]
    assert smoke.plan_failures(report) == [
        "r/pallas did not lower: refused",
        "measurement {r: pallas} failed: boom"]
