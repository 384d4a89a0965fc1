"""The traffic generator, the lane loop's end-to-end arithmetic, and
finding a new configuration, mix or metric by its name alone."""
from __future__ import annotations

import filecmp
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import tiny
import harness
import traffic


def test_same_seed_same_schedule_and_requests():
    mix = "mixed"
    m = traffic.load_mix(mix)
    a = traffic.open_schedule(m, 10.0, tiny.SEED)
    b = traffic.open_schedule(m, 10.0, tiny.SEED)
    c = traffic.open_schedule(m, 10.0, tiny.SEED + 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # every seed: the same number of arrivals over the same span
    assert len(a) == len(c) == round(m["rate_per_s"] * 10.0)
    assert np.all(np.diff(a) >= 0) and a[0] == 0.0
    # the gaps are one multiset in another order (the last one unused)
    shared = np.intersect1d(np.round(np.diff(a), 9), np.round(np.diff(c), 9))
    assert len(shared) >= len(a) - 2 and a[-1] < 10.0 and c[-1] < 10.0
    names = list((m.get("tenant_shares") or {None: 1}).keys())
    x = traffic.choose(m, 500, tiny.SEED, names, 0.75)
    y = traffic.choose(m, 500, tiny.SEED, names, 0.75)
    z = traffic.choose(m, 500, tiny.SEED + 1, names, 0.75)
    assert all(np.array_equal(p, q) for p, q in zip(x, y))
    assert not np.array_equal(x[2], z[2])
    for p, q in zip(x[:2], z[:2]):         # same counts, another order
        assert np.array_equal(np.bincount(p), np.bincount(q))


def test_tenant_counts_follow_shares():
    m = traffic.load_mix("mixed")
    tenant, mated, _ = traffic.choose(m, 1000, 5, list(m["tenant_shares"]),
                                      0.75)
    assert np.bincount(tenant).tolist() == [100, 300, 600]
    assert mated.sum() == 750


class _Stage:
    """A stand-in stage that answers at once, or after a stall."""
    name = "watchlist_db"

    def __init__(self, stall_at=None, stall_s=0.0):
        self.calls, self.stall_at, self.stall_s = 0, stall_at, stall_s

    def process_batch(self, ms):
        self.calls += 1
        if self.calls == self.stall_at:
            time.sleep(self.stall_s)
        return [m.with_payload({"label": "g/0", "score": 1.0}) for m in ms]


def _fake_cell(mix, stage):
    cell = harness.Cell.__new__(harness.Cell)
    cell.mix, cell.seed, cell.trace = mix, 4, False
    cell.tenants, cell.probe_cfg = [], {"mate_share": 0.5}
    cell.cfg = {"dim": 8, "match_dtype": "int8"}
    cell.scopes = [harness.Scope(None, np.zeros((10, 8), np.float32))]
    cell.bank = np.zeros((4, 8), np.float32)
    cell.mated, cell.strangers = [np.arange(2)], [np.arange(2, 4)]
    cell.chain = [stage]
    cell.bench = {"end_to_end": [
        {"name": "p50_ms", "unit": "ms"}, {"name": "p95_ms", "unit": "ms"},
        {"name": "ids_per_s", "unit": "ids/s"}]}
    cell.workload = "x"
    return cell


def test_tail_is_over_all_requests_and_a_stall_moves_it():
    mix = {"loop": "open", "rate_per_s": 200.0, "max_batch": 8,
           "payload": "templates", "check_sample": 4}
    calm = _fake_cell(mix, _Stage()).run_open(1.0)
    stalled = _fake_cell(mix, _Stage(stall_at=50, stall_s=0.2)).run_open(1.0)
    for out in (calm, stalled):
        assert out["answered"] == out["n"] == 200
        assert np.isfinite(out["latency_ms"]).all()
    # the stall delays every request due during it, so the 95th
    # percentile of all requests moves by most of the stall
    p95 = lambda o: np.percentile(o["latency_ms"], 95)
    assert p95(calm) < 20 and p95(stalled) > 100
    e2e = _fake_cell(mix, _Stage()).end_to_end(stalled, 1.0, 0)
    assert e2e["p95_ms"]["value"] == pytest.approx(p95(stalled))
    assert e2e["p50_ms"]["value"] == pytest.approx(
        np.percentile(stalled["latency_ms"], 50))


def test_rate_is_all_answers_over_all_the_window():
    mix = {"loop": "closed", "max_batch": 64, "payload": "templates",
           "check_sample": 4}
    cell = _fake_cell(mix, _Stage(stall_at=3, stall_s=0.3))
    cell.bench = {"end_to_end": [{"name": "ids_per_s", "unit": "ids/s"}]}
    out = cell.run_closed(0.5)
    assert out["wall_s"] >= 0.5
    rate = cell.end_to_end(out, 1.0, 0)["ids_per_s"]["value"]
    assert rate == pytest.approx(out["answered"] / out["wall_s"])
    assert out["answered"] == 64 * len(out["cycles"])
    assert rate < 64 * len(out["cycles"]) / (out["wall_s"] - 0.3)


def test_closed_loop_numbers_requests_through_the_window():
    """Each request of a closed-loop window has its own sequence number,
    its place among the window's answers; templates record no stage
    outputs."""
    seqs = []

    class Seen(_Stage):
        def process_batch(self, ms):
            seqs.extend(m.seq for m in ms)
            return super().process_batch(ms)
    mix = {"loop": "closed", "max_batch": 16, "payload": "templates",
           "check_sample": 4}
    out = _fake_cell(mix, Seen()).run_closed(0.2)
    assert len(out["cycles"]) > 1
    assert seqs == list(range(out["n"])) and out["rec"] == {}


NEW_FILES_PROBE = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import harness, tracereduce
b = harness.load_benchmark()
cell = harness.Cell("fleet.tiny", 1, b)
assert cell.cfg["n_templates"] == 500 and cell.mix["rate_per_s"] == 3.0
view = tracereduce.TraceView({"bench.window": [(0.0, 1.0)],
                              "stage.watchlist_db": [(0.2, 0.4)]}, [],
                             cycles=[{"frames": 2, "work": []}])
print(json.dumps(cell.per_layer(view)))
"""


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds a configuration, a mix and a per-layer metric
    as new files and new entries, and edits no file that is there."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = harness.load_benchmark()
    cfg = json.loads((harness.CHECKOUT / bench["configs"][0]["file"])
                     .read_text())
    cfg["n_templates"] = 500
    (root / "bench/configs/fleet-tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/trickle.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 3.0, "max_batch": 2,
         "payload": "frames", "check_sample": 2}))
    (root / "bench/metrics/match.calls.py").write_text(
        "def read(view):\n    return float(len(view.cycles))\n")
    bench["configs"].append({"name": "fleet-tiny", "source": "test",
                             "file": "bench/configs/fleet-tiny.json",
                             "reduced": ["n_templates"], "why": "test"})
    bench["workloads"].append({"name": "fleet.tiny", "config": "fleet-tiny",
                               "traffic": "trickle", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "match.calls", "unit": "calls",
                               "better": "lower", "source": "device_trace",
                               "layer": "watchlist match",
                               "moves": "p95_ms",
                               "workloads": ["fleet.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", NEW_FILES_PROBE, str(root / "bench"),
         str(harness.CHECKOUT / "src")], capture_output=True, text=True,
        env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "match.calls": {"value": 1.0, "unit": "calls"}}
    # nothing that was there changed
    cmp = filecmp.dircmp(harness.BENCH, root / "bench",
                         ignore=["__pycache__", "tests"])
    assert not cmp.diff_files and not cmp.left_only
    for sub in cmp.subdirs.values():
        assert not sub.diff_files and not sub.left_only
