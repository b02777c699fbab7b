#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout whose JAX sees the chips the cell asks for
(``BENCHMARK.json``'s ``workloads``).  Anywhere else it exits non-zero and
prints no result.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number the
correctness comparison took, with its limit (also the last lines of
standard error).

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``, so
only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def process_start() -> float:
    """Wall-clock time this process started (Linux ``/proc``; the import
    of this module elsewhere)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_START = process_start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # libtpu would log to the fixed /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import cells
    import harness
    import system

    try:
        cell = cells.load(ROOT, args.workload)
        system.import_program(ROOT)
    except (OSError, ImportError, cells.CellError, KeyError) as e:
        print(f"[bench] cannot set up {args.workload}: {e}", file=sys.stderr)
        return 3
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"[bench] {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), devs[:cell.chips], T_START)
    for name, c in out["checks"].items():
        print(f"[bench] check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
