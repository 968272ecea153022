"""Roofline analysis over the dry-run JSONL (one row per (arch, shape, mesh)).

Terms (per assignment):
    compute    = HLO_FLOPs / (chips * 197 TF/s)
    memory     = HLO_bytes / (chips * 819 GB/s)
    collective = collective_bytes / (chips * 200 GB/s)   (1,600 Gbit/s)

Peaks come from ``repro.hw.TARGET`` (TPU v5e).

HLO_FLOPs / bytes come from the trip-count-attributed HLO analyzer (per
device; equivalent to global/chips).  MODEL_FLOPS = 6*N_active*tokens
(train) or 2*N_active*tokens (serve).  ``useful`` = MODEL_FLOPS time at peak
/ dominant term = the roofline fraction this report scores.
"""
from __future__ import annotations

import argparse
import json

from repro.configs import SHAPES, get_config
from repro.hw import TARGET


def model_flops(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # decode: one token/seq


def roofline_row(rec: dict) -> dict | None:
    if rec.get("status") != "ok" or "hlo_cost" not in rec:
        return None
    chips = rec["devices"]
    hc = rec["hlo_cost"]
    compute = hc["flops"] / TARGET.bf16_flops            # per-device flops
    # memory term uses the fusion-optimistic byte model (see hlo_analysis);
    # hbm_bytes (zero-fusion upper bound) is reported alongside.
    memory = hc.get("hbm_fused", hc["hbm_bytes"]) / TARGET.hbm_bw
    collective = hc["total_collective_bytes"] / TARGET.ici_bw
    terms = {"compute": compute, "memory": memory, "collective": collective}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    useful_time = mf / (chips * TARGET.bf16_flops)
    step_time = max(terms.values())
    hbm_gb = (rec["memory"]["argument_bytes"] + rec["memory"]["temp_bytes"]) / 1e9
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "chips": chips,
        "compute_s": compute, "memory_s": memory, "collective_s": collective,
        "memory_raw_s": hc["hbm_bytes"] / TARGET.hbm_bw,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hc["flops"] * chips,
        "flops_ratio": mf / max(hc["flops"] * chips, 1.0),
        "roofline_fraction": useful_time / max(step_time, 1e-30),
        "hbm_gb_per_chip": hbm_gb,
        "step_time_s": step_time,
    }


def load_rows(path: str) -> list[dict]:
    rows = []
    seen = set()
    for line in open(path):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        key = (rec.get("arch"), rec.get("shape"), rec.get("mesh"))
        row = roofline_row(rec)
        if row is not None:
            if key in seen:           # keep the latest record per cell
                rows = [r for r in rows
                        if (r["arch"], r["shape"], r["mesh"]) != key]
            seen.add(key)
            rows.append(row)
    return rows


def format_table(rows: list[dict], mesh: str = "single") -> str:
    hdr = (f"{'arch':22s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'coll_s':>10s} {'bound':>10s} {'MF/HLO':>7s} {'roofline%':>9s} "
           f"{'HBM GB':>7s}")
    lines = [hdr, "-" * len(hdr)]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        if r["mesh"] != mesh:
            continue
        lines.append(
            f"{r['arch']:22s} {r['shape']:12s} {r['compute_s']:10.4f} "
            f"{r['memory_s']:10.4f} {r['collective_s']:10.4f} "
            f"{r['dominant']:>10s} {r['flops_ratio']:7.2f} "
            f"{100*r['roofline_fraction']:8.1f}% {r['hbm_gb_per_chip']:7.1f}")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="inp", default="results/dryrun.jsonl")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--csv", action="store_true")
    args = ap.parse_args()
    rows = load_rows(args.inp)
    if args.csv:
        print("arch,shape,mesh,chips,compute_s,memory_s,collective_s,dominant,"
              "flops_ratio,roofline_fraction,hbm_gb_per_chip")
        for r in rows:
            print(f"{r['arch']},{r['shape']},{r['mesh']},{r['chips']},"
                  f"{r['compute_s']:.6f},{r['memory_s']:.6f},"
                  f"{r['collective_s']:.6f},{r['dominant']},"
                  f"{r['flops_ratio']:.3f},{r['roofline_fraction']:.4f},"
                  f"{r['hbm_gb_per_chip']:.2f}")
    else:
        print(format_table(rows, args.mesh))


if __name__ == "__main__":
    main()
