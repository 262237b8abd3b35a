#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, then a window at each rate.

    python bench/sweep.py --workload vr2k-select --seed 11 --seconds 15 \
        --rates 2,4,6,8,12

For each rate it prints the answered rate, p50/p95 latency, p50 of the
first and last third of the window, and how many requests were still
unanswered when the window closed.  The knee is the highest rate whose
backlog does not grow: the last third's p50 stays within 1.5x the first
third's and the unanswered count stays small.  The cell's mix file then
takes 4/5 of it, as a number.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    run.prepare_env(args.rehearse)
    h = run.Harness(spec, args.seed, args.rehearse)
    try:
        h.start()
        h.ingest()
        h.warm_shapes(spec["mix"])
        if spec["mix"].get("warm_fill"):
            import traffic
            h.warm_fill(traffic.catalogue(spec["mix"]["queries"], h.cfg,
                                          args.seed)
                        [:spec["mix"]["warm_fill"]])
        for rate in (float(r) for r in args.rates.split(",")):
            mix = json.loads(json.dumps(spec["mix"]))
            mix["arrivals"]["rate_per_s"] = rate
            job, _ = run.job_for(dict(spec, mix=mix), h.cfg, args.seed,
                                 args.seconds)
            job["sample"] = []
            h.spawn_client(job)
            h.wait_ready()
            win = h.window(args.seconds, None)
            recs, _ = h.collect()
            t0, end = win["t0"], win["t0"] + args.seconds
            lat = np.array([r.get("done", np.inf) - (t0 + r["due"])
                            for r in recs])
            due = np.array([r["due"] for r in recs])
            third = args.seconds / 3
            first = lat[due < third]
            last = lat[due >= 2 * third]
            out = sum(1 for r in recs if r.get("done", np.inf) > end)
            print(json.dumps({
                "rate": rate, "offered": len(recs),
                "answered_per_s": sum(1 for r in recs
                                      if r.get("done", np.inf) <= end)
                / args.seconds,
                "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                "p95_ms": float(np.percentile(lat, 95)) * 1e3,
                "p50_first_third_ms": float(np.median(first)) * 1e3,
                "p50_last_third_ms": float(np.median(last)) * 1e3,
                "unanswered_at_close": out}), flush=True)
    finally:
        h.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
