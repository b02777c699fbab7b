#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

For each seed, in one process: a whole run of the cell (set-up, a window
of ``--seconds`` at the cell's own rate, the comparison with the float32
reference), and on the same sample of requests the readings of the
controls that apply to the configuration, in the same units: the
reference computed in fp8 (``float8_e4m3fn`` matmul operands, one
precision below the bfloat16 the configuration computes in) and, where
the index stores int8 K/V, with those K/V quantized to int4.  One JSON
line per seed: the program's readings, each control's, and ``correct``;
``--dump`` keeps the score arrays (``got``, ``ref``, ``stated`` and one
per control).

The limit sits above the largest program reading over a dozen seeds or
more and below the smallest control reading (``PERF.md`` keeps both).
The benchmark's own runs never compute the controls.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dump", default=None,
                    help="directory for each seed's score arrays (.npz)")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import cells
    import harness
    import system

    system.import_program(ROOT)
    import jax

    cell = cells.load(ROOT, args.workload)
    devs = jax.devices()[:cell.chips]
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print("[control] needs the chip", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        keep: dict = {}
        out = harness.run(ROOT, args.workload, seed, args.seconds, False,
                          devs, time.time(),
                          controls=harness.controls_for(cell.config),
                          keep=keep)
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            np.savez(Path(args.dump) / f"{args.workload}.{seed}.npz", **keep)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"],
            "checks": out["checks"],
            "controls": out["controls"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
