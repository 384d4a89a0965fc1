"""Readings for the limits of ``correct``: the program's compared numbers
and the control's, over several seeds, in one process.

    python3 bench/control.py --workload fleet.mixed \
        --seeds 101,102,103 --seconds 4

For each seed the cell is built and warmed, one short window is served
at the cell's own load (long enough to answer as many requests as a run
compares), and the sampled answers are compared with the reference
twice: as the program gave them, and as the control gives them.  The
control is the reference put in the program's place one precision step
below what the configuration states (the configuration's ``control``:
int4 rows for int8 storage, fp8 rows for bf16, fp8 inputs to the
stages' convolutions and matmuls where the configuration states XLA's
default precision, one bf16 pass).  Each line printed is one seed's
worst reading of each number, for the program and for the control.  Runs on the chip; the benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def readings(cell: harness.Cell, seconds: float) -> dict:
    """Build, warm and serve one short window of ``cell``; the program's
    and the control's worst reading of each compared number."""
    cell.build()
    cell.warm()
    mix = cell.mix
    if mix["loop"] == "open":
        seconds = max(seconds, 1.2 * mix["check_sample"] / mix["rate_per_s"])
    out = cell.run_window(seconds)
    cell.release()
    prog = reference.worst(cell.numbers(out))
    ctl = reference.worst(cell.numbers(out, cell.cfg["control"]))
    return {"answered": out["answered"], "compared": len(cell.sample(out)),
            "program": prog, "control": ctl}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    run.require_chips(harness.find_cell(bench, args.workload)[0]["chips"])
    from repro.launch import serve
    serve.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(args.workload, seed, bench)
        res = readings(cell, args.seconds)
        print(json.dumps({"seed": seed, **res}), flush=True)
        del cell
        gc.collect()


if __name__ == "__main__":
    main()
