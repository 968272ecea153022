"""Mean host time of an engine tick in the window: over the ``engine.tick``
spans that start in it, the tick's duration less the time its
``engine.wait`` descendants (the waits for the device's prefill and decode
steps) cover.  Read from the program's spans (``repro.obs``); none where
the program records none or its ring dropped any."""


def window_spans(run):
    """The program's spans that start at or after the window's start, and
    the window's end; None where there are none to trust."""
    try:
        from repro import obs
    except ImportError:                  # a program without spans
        return None
    if obs.dropped():
        return None
    t0 = run.t_start + run.setup_s
    return obs.spans(t0), t0 + run.seconds


def read(run):
    got = window_spans(run)
    if got is None:
        return None
    spans, t_end = got
    ticks = {s.id: s for s in spans
             if s.name == "engine.tick" and s.start < t_end}
    if not ticks:
        return None
    parent = {s.id: s.parent for s in spans}
    waited = dict.fromkeys(ticks, 0.0)
    for s in spans:
        if s.name != "engine.wait":
            continue
        up = s.parent
        while up is not None and up not in ticks:
            up = parent.get(up)
        if up is not None:
            waited[up] += s.end - s.start
    host = [t.end - t.start - waited[i] for i, t in ticks.items()]
    return 1e3 * sum(host) / len(host)
