"""Find the knee of an open-loop cell: serve the cell's traffic at each
of several offered rates, in one process after one set-up, and print
what each rate gives.

    python3 bench/sweep.py --workload fleet.mixed --seed 5 \
        --rates 20,40,60,80 --seconds 10

Per rate: the offered and completed rates, p50 and p95 of the latency
from due time to answer, and the mean latency of the first and last
tenth of the requests: a last tenth well above the first is a backlog
that grows through the window, so the rate is above what the system
sustains.  The knee is the highest rate without one.  Runs on the chip
only; the rate a cell is given is written into its traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, args.seed)
    run.require_chips(cell.cell["chips"])
    from repro.launch import serve
    serve.use_compile_cache()
    if cell.mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is a closed loop: no knee")
    cell.build()
    cell.warm()
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix["rate_per_s"] = rate
        out = cell.run_window(args.seconds)
        lat = out["latency_ms"]
        tenth = max(1, len(lat) // 10)
        print(json.dumps({
            "rate_per_s": rate, "completed_per_s": out["answered"]
            / out["wall_s"], "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_tenth_ms": float(lat[:tenth].mean()),
            "last_tenth_ms": float(lat[-tenth:].mean()),
            "cycles": len(out["cycles"]),
            "mean_batch": out["answered"] / len(out["cycles"])}),
            flush=True)


if __name__ == "__main__":
    main()
