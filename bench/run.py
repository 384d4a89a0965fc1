"""Run one benchmark cell once, on the chip, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``.  The run refuses any backend
but a TPU, and a device count under the cell's ``chips``.  It turns on
the persistent compile cache, builds the cell's configuration, makes the
traffic from ``--seed``, warms every shape the window uses (all of it
counted as set-up), measures for ``--seconds``, and checks the sampled
answers of the window against the plain reference.  With ``--trace 0``
the result holds the cell's end-to-end metrics, taken with tracing off;
with ``--trace 1`` the window runs under the profiler and the result
holds the per-layer metrics read from the trace.

Standard error ends with each compared number beside its limit; the last
line of standard output is the result, one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402

import harness  # noqa: E402
import reference  # noqa: E402
import tracereduce  # noqa: E402
import work  # noqa: E402

TRACE_DIR = BENCH.parent / ".bench_trace"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def require_chips(n: int) -> dict:
    """The device description, or exit non-zero where JAX finds no TPU or
    fewer than ``n`` of them."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (backend {backend!r}); "
                         "refusing to run")
    if jax.device_count() < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX sees "
                         f"{jax.device_count()}")
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n}


def memory_stats(n: int) -> list:
    return [d.memory_stats() for d in jax.devices()[:n]]


def memory_peak(n: int) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:n])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell = harness.Cell(args.workload, args.seed, bench)
    chips = cell.cell["chips"]
    device = require_chips(chips)
    peak_table = work.peaks(device["kind"])
    from repro.launch import serve
    serve.use_compile_cache()
    log(f"device {device['platform']} {device['kind']} x{device['count']}")

    with reference.CompileCounter() as cc:
        cell.build()
        cell.warm()
        c_setup = cc.snapshot()
        setup_s = time.perf_counter() - T_START
        log(f"memory after set-up: {memory_stats(chips)}; host peak RSS "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
        log(f"set-up {setup_s:.3f} s, compiles {c_setup[0]} "
            f"({c_setup[1]:.2f} s), {c_setup[2]} from the persistent cache")
        if args.trace:
            shutil.rmtree(TRACE_DIR / args.workload, ignore_errors=True)
            cell.trace = True
            # host events at level 1 (the harness spans are level 1) and
            # no Python tracer, which records every call
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR / args.workload),
                                     profiler_options=opts)
        try:
            out = cell.run_window(args.seconds)
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        c_win = [b - a for a, b in zip(c_setup, cc.snapshot())]
    peak = memory_peak(chips)
    stage_names = [s.name for s in cell.entry_stages()]
    cell.release()

    late = out["late_ms"]
    log(f"window {out['wall_s']:.3f} s, {out['answered']}/{out['n']} "
        f"answered in {len(out['cycles'])} cycles; generator lateness "
        f"mean {late.mean() if len(late) else 0.0:.3f} ms, max "
        f"{late.max() if len(late) else 0.0:.3f} ms over {len(late)} "
        f"wake-ups; compiles in the window {c_win[0]} ({c_win[1]:.2f} s)")
    log("garbage collection in the window: " + "; ".join(
        f"generation {g}: {n} runs, {t * 1e3:.1f} ms, longest "
        f"{m * 1e3:.1f} ms" for g, (n, t, m) in out["gc"].items()))
    slow = sorted(out["cycles"], key=lambda c: -sum(c["stage_s"].values()))
    for c in slow[:3]:
        log(f"slow cycle: {c['frames']} requests, " + ", ".join(
            f"{k} {v * 1e3:.1f} ms" for k, v in c["stage_s"].items())
            + f"; host CPU {c['cpu_s'] * 1e3:.1f} ms")
    result = {"correct": None, "attempted": out["n"],
              "failed": out["n"] - out["answered"]}
    if args.trace:
        view = tracereduce.TraceView.from_xplane(
            tracereduce.find_xplane(TRACE_DIR / args.workload),
            cycles=out["cycles"], peak=peak_table,
            stage_names=stage_names[:-1], match_stage=stage_names[-1])
        least, bound = work.least_s(
            [w for c in out["cycles"] for w in c["work"]], peak_table)
        log(f"trace: window {view.window_s:.3f} s, device busy "
            f"{view.busy_s:.4f} s; the searches' least time {least:.4f} s,"
            f" {bound}-bound")
        result["metrics"] = cell.per_layer(view)
        device.update(busy_s=view.busy_s, window_s=view.window_s)
        result["breakdown"] = view.breakdown()
    else:
        result["metrics"] = cell.end_to_end(out, setup_s, peak)
    device["memory_peak_bytes"] = peak
    result["device"] = device
    checks, readings = cell.checks(out)
    log("readings: " + json.dumps(readings))
    result["correct"] = bool(result["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
