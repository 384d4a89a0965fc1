"""Shared helpers of the benchmark's CPU tests: the benchmark's own
cells with the watchlists cut to a few thousand rows, so that a whole
run fits a test on the CPU (Pallas in interpret mode)."""
from __future__ import annotations

import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

N_TEMPLATES = 3000
SEED = 2 ** 31 + 12345               # more than 32 signed bits hold
V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
       "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def bench(tmp_path: Path) -> dict:
    """``BENCHMARK.json`` with each configuration's watchlist cut to
    ``N_TEMPLATES`` rows (its file rewritten under ``tmp_path``)."""
    b = copy.deepcopy(harness.load_benchmark())
    for c in b["configs"]:
        cfg = json.loads((harness.CHECKOUT / c["file"]).read_text())
        cfg["n_templates"] = N_TEMPLATES
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    return b


def run_main(monkeypatch, tmp_path, capsys, argv) -> dict:
    """``run.main`` on the CPU at the tiny size: the look for a chip is
    skipped and the v5e peaks stand in; returns the result line."""
    import run
    from repro.launch import serve
    tiny = bench(tmp_path)
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "load_benchmark", lambda *a: tiny)
    monkeypatch.setattr(run, "require_chips", lambda n: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": n})
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    run.main(argv)
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])
