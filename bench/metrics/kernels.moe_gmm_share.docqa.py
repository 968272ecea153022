"""Share of the device's busy time in the traced window spent in the
grouped expert kernel (the custom calls named ``moe_gmm``): how much of
the cell the expert layer's kernel holds.  None where the trace has no
such kernel (a program without it)."""

KERNEL = "moe_gmm"


def read(run):
    s = run.summary
    if s is None or KERNEL not in s.custom_calls or s.busy_s <= 0:
        return None
    return 100.0 * s.custom_calls[KERNEL][0] / s.busy_s
