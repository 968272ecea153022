"""Seconds of the planner's search in set-up spent lowering or compiling:
within each ``planner.plan`` span that starts in set-up, the time in which
at least one ``planner.lower`` or ``planner.compile`` span is open, on any
thread (the union of their intervals).  Read from the program's spans
(``repro.obs``); none where the program records none or its ring dropped
any."""


def read(run):
    try:
        from repro import obs
    except ImportError:                  # a program without spans
        return None
    if obs.dropped():
        return None
    spans = obs.spans(run.t_start, run.t_start + run.setup_s)
    plans = [s for s in spans if s.name == "planner.plan"]
    if not plans:
        return None
    work = sorted((s.start, s.end) for s in spans
                  if s.name in ("planner.lower", "planner.compile"))
    total = 0.0
    for p in plans:
        end = p.start
        for a, b in work:
            a, b = max(a, end), min(b, p.end)
            if b > a:
                total += b - a
                end = b
    return total
