"""The grouped expert kernel's least time over its device time in the
traced window.

The work is counted from the program's spans: each ``engine.prefill`` and
``engine.decode`` span carries ``moe_pairs`` (the (token, held expert)
pairs its expert layers routed) and ``moe_experts_hit`` (the held experts
they reached, summed over the layers).  The least time of a step is the
larger of its kernel operations at the bfloat16 peak and its kernel bytes
at HBM bandwidth (``moe_gmm_flops`` and ``moe_gmm_bytes`` of
``bench/configs/<config>.py``).  Both sides cover the same steps: the
traced window opens just before the first engine tick that starts in the
last ``trace_s`` seconds of the measured window and closes just after the
tick that ends it, so the steps counted are those inside ``engine.tick``
spans that start in those seconds, and the device time is the kernel's
(custom calls named ``moe_gmm``) in the trace.  None where the program
records no such spans or the trace holds no such kernel."""
from pathlib import Path

from bench.harness import load_module

KERNEL = "moe_gmm"
STEPS = ("engine.prefill", "engine.decode")

window_spans = load_module(
    Path(__file__).with_name("engine.tick_host_ms.chat.py")).window_spans


def read(run):
    s = run.summary
    if s is None or KERNEL not in s.custom_calls:
        return None
    got = window_spans(run)
    if got is None:
        return None
    spans, t_end = got
    t0 = t_end - float(run.cell.mix["trace_s"])
    ticks = {sp.id for sp in spans
             if sp.name == "engine.tick" and t0 <= sp.start < t_end}
    parent = {sp.id: sp.parent for sp in spans}
    conf, counts, peaks = run.cell.conf, run.cell.counts, run.peaks
    least = 0.0
    for sp in spans:
        if sp.name not in STEPS or "moe_pairs" not in sp.attrs:
            continue
        up = sp.parent
        while up is not None and up not in ticks:
            up = parent.get(up)
        if up is None:
            continue
        a = sp.attrs
        least += max(
            counts.moe_gmm_flops(conf, a["moe_pairs"]) / peaks.bf16_flops,
            counts.moe_gmm_bytes(conf, a["moe_pairs"], a["moe_experts_hit"])
            / peaks.hbm_bytes_s)
    secs = s.custom_calls[KERNEL][0]
    if least <= 0 or secs <= 0:
        return None
    return 100.0 * least / secs
