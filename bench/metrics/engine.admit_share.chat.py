"""Share of the window's engine ticks spent admitting requests: the time
of the ``engine.admit`` spans (batch-1 prefill, cache insert and first
sample of each admitted request) over the time of the ``engine.tick``
spans that start in the window."""
from pathlib import Path

from bench.harness import load_module

window_spans = load_module(
    Path(__file__).with_name("engine.tick_host_ms.chat.py")).window_spans


def read(run):
    got = window_spans(run)
    if got is None:
        return None
    spans, t_end = got
    ticks = {s.id: s.end - s.start for s in spans
             if s.name == "engine.tick" and s.start < t_end}
    if not ticks:
        return None
    admit = sum(s.end - s.start for s in spans
                if s.name == "engine.admit" and s.parent in ticks)
    return 100.0 * admit / sum(ticks.values())
