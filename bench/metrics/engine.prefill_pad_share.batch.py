"""Share of the prefill work spent on padding: 100 x (1 - the prompt
tokens over the bucket tokens) of the ``engine.prefill`` spans that start
in the window.  A prompt is padded to its power-of-two bucket and the
prefill runs over the whole bucket."""
from pathlib import Path

from bench.harness import load_module

window_spans = load_module(
    Path(__file__).with_name("engine.tick_host_ms.chat.py")).window_spans


def read(run):
    got = window_spans(run)
    if got is None:
        return None
    spans, t_end = got
    pre = [s.attrs for s in spans
           if s.name == "engine.prefill" and s.start < t_end]
    bucket = sum(a["bucket"] for a in pre)
    if not bucket:
        return None
    return 100.0 * (1.0 - sum(a["prompt_len"] for a in pre) / bucket)
