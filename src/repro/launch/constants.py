"""Roofline projection onto the target chip (peaks in ``repro.hw``)."""
from __future__ import annotations

from repro.hw import TARGET, TRANSCENDENTAL_RATE


def projected_tpu_seconds(flops: float, hbm_bytes: float,
                          transcendentals: float = 0.0,
                          collective_bytes: float = 0.0,
                          chips: int = 1) -> dict:
    """Three-term roofline time for a per-chip workload on the target chip
    (seconds)."""
    compute = flops / (chips * TARGET.bf16_flops)
    memory = hbm_bytes / (chips * TARGET.hbm_bw)
    trans = transcendentals / (chips * TRANSCENDENTAL_RATE)
    coll = collective_bytes / (chips * TARGET.ici_bw)
    terms = {"compute": compute, "memory": memory, "transcendental": trans,
             "collective": coll}
    bottleneck = max(terms, key=terms.get)
    return {**terms, "bound": bottleneck, "seconds": max(terms.values())}
