#!/usr/bin/env python3
"""Find a cell's knee: the cell's set-up once, then one open-loop window per
offered rate, on the chip.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 4,6,8

Prints one JSON line per rate: requests due, p50 / p95 latency, the lag of
the last answer behind the window's close (a backlog that grows through
the window shows as a lag that grows with the rate), and docs per second.
The set-up is ``harness.set_up``, the one every run of the cell makes.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import cells
    import system

    system.import_program(ROOT)
    import jax

    devs = jax.devices()[:cells.load(ROOT, args.workload).chips]
    if devs[0].platform != "tpu":
        print("[sweep] needs the chip", file=sys.stderr)
        return 2
    work = ROOT / "bench" / ".work" / ("sweep-" + args.workload)
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    try:
        return _sweep(args, devs, work)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _sweep(args, devs, work) -> int:
    import harness
    import traffic as traffic_lib

    su = harness.set_up(ROOT, args.workload, args.seed, args.seconds, devs,
                        work)
    cfg, spec, svc = su.cell.config, su.cell.traffic, su.service
    low = harness._Lowerings()
    for rate in (float(r) for r in args.rates.split(",")):
        spec_r = dict(spec, arrivals=dict(spec["arrivals"], rate_per_s=rate))
        tr_r = traffic_lib.generate(spec_r, cfg["vocab_size"], args.seed,
                                    args.seconds)
        svc.reset_stats()
        low.n = 0
        recs, span = harness.open_loop(
            svc, dataclasses.replace(su.traffic, requests=tr_r.requests),
            cfg, low)
        e2e = harness.end_to_end(recs, span, args.seconds, None, 0.0)
        s = svc.stats
        print(json.dumps({"rate": rate, "requests": len(recs),
                          "p50_ms": e2e.get("p50_latency_ms"),
                          "p95_ms": e2e.get("p95_latency_ms"),
                          "lag_s": span - args.seconds,
                          "docs_per_s": e2e["docs_per_s"],
                          "batches": s.n_batches, "pack_fill": s.pack_fill,
                          "hit_rate": s.doc_cache_hit_rate,
                          "load_s": s.load_s, "combine_s": s.combine_s,
                          "lowerings": low.n}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
