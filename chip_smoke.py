#!/usr/bin/env python3
"""Chip smoke run: the offload planner and the serving engine on one TPU.

Runs the system's main path once through its normal entry points: the
Pallas kernels at real sizes, the planner on the paper's two apps at paper
sizes, and the serving engine on falcon-mamba-7b at published widths (see
``src/repro/launch/smoke.py``).  Its last line is a JSON object naming the
device; it exits non-zero when any phase fails or when JAX finds no TPU.

    python3 chip_smoke.py [--seed N] [--out DIR]
"""
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.smoke import main
    sys.exit(main())
