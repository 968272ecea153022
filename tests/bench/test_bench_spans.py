"""The per-layer metrics that read the program's spans (``repro.obs``),
on the tiny cells with ``--trace 1`` on the CPU: each reads a number that
agrees with what the run did, and none reads anything once the ring has
dropped spans or where the program records none."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

from bench import harness
from bench.peaks import peaks
from repro import obs

CHAT, BATCH, APP = "falcon-mamba-7b.chat", "falcon-mamba-7b.batch", "mriq.large"
READERS = {CHAT: ["engine.tick_host_ms.chat", "engine.admit_share.chat"],
           BATCH: ["engine.tick_host_ms.batch", "engine.prefill_pad_share.batch"],
           APP: ["planner.compile_s"]}


def _traced_run(root: Path, name: str, seed: int = 11) -> tuple:
    """One traced run of a tiny cell from an empty ring: the run and its
    result line."""
    obs.reset()
    cell = harness.load_cell(name, root)
    run = harness.Run(cell=cell, seed=seed, seconds=1.5, trace=True,
                      peaks=peaks("TPU v5 lite"), t_start=time.perf_counter())
    cell.driver.run(run, harness.CompileCounter())
    return run, harness.result_line(run)


def _reader(root: Path, metric: str):
    return harness.load_module(root / "bench" / "metrics" / f"{metric}.py").read


def _dropping_ring(monkeypatch) -> None:
    """Put the run's spans into a ring one record too small."""
    kept = obs.spans()
    small = obs.Ring(capacity=len(kept) - 1)
    for s in kept:
        small.append(tuple(s))
    assert small.dropped() == 1
    monkeypatch.setattr(obs, "_RING", small)


def test_chat_readers_read_ticks_and_admissions(tiny_root, monkeypatch):
    run, line = _traced_run(tiny_root, CHAT)
    got = {m: line["metrics"][m]["value"] for m in READERS[CHAT]}
    t0 = run.t_start + run.setup_s
    ticks = [s for s in obs.spans(t0, t0 + run.seconds) if s.name == "engine.tick"]
    longest = max(s.end - s.start for s in ticks)
    assert 0 < got["engine.tick_host_ms.chat"] <= 1e3 * longest
    assert 0 < got["engine.admit_share.chat"] < 100
    _dropping_ring(monkeypatch)
    for m in READERS[CHAT]:
        assert _reader(tiny_root, m)(run) is None


def test_batch_pad_share_is_the_arithmetic_over_the_submitted_prompts(
        tiny_root, monkeypatch):
    from repro.models import factory as F
    from repro.serving.engine import ServeEngine

    prompt_len = {}
    submit = ServeEngine.submit

    def recording_submit(self, prompt, *a, **kw):
        rid = submit(self, prompt, *a, **kw)
        prompt_len[rid] = len(prompt)
        return rid
    monkeypatch.setattr(ServeEngine, "submit", recording_submit)
    run, line = _traced_run(tiny_root, BATCH)
    ctx = run.cell.conf["serving"]["ctx"]
    t0 = run.t_start + run.setup_s
    rids = [s.attrs["rid"] for s in obs.spans(t0, t0 + run.seconds)
            if s.name == "engine.prefill"]
    assert rids
    tokens = sum(prompt_len[r] for r in rids)
    padded = sum(F.prefill_bucket(prompt_len[r], ctx) for r in rids)
    assert line["metrics"]["engine.prefill_pad_share.batch"]["value"] == \
        pytest.approx(100.0 * (1.0 - tokens / padded), rel=1e-12)
    assert line["metrics"]["engine.tick_host_ms.batch"]["value"] > 0
    _dropping_ring(monkeypatch)
    for m in READERS[BATCH]:
        assert _reader(tiny_root, m)(run) is None


def test_planner_compile_s_lies_within_the_search(tiny_root, monkeypatch):
    run, line = _traced_run(tiny_root, APP)
    compile_s = line["metrics"]["planner.compile_s"]["value"]
    assert 0 < compile_s <= line["metrics"]["planner.search_s"]["value"]
    _dropping_ring(monkeypatch)
    assert _reader(tiny_root, "planner.compile_s")(run) is None


@pytest.mark.parametrize("metric", [m for ms in READERS.values() for m in ms])
def test_reader_reads_nothing_from_a_program_without_spans(metric, monkeypatch):
    """The parent of this change has no ``repro.obs``: its traced runs
    leave the new metrics out of the line instead of failing."""
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)    # import fails
    run = harness.Run(cell=None, seed=0, seconds=1.0, trace=True, peaks=None,
                      t_start=0.0, setup_s=1.0)
    read = _reader(harness.ROOT, metric)
    assert read(run) is None
