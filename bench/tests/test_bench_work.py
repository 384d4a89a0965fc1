"""The yardstick's arithmetic: the work a search needs, the peaks table,
and the reduction of a profiler trace to the per-layer metrics."""
from __future__ import annotations

import json

import pytest

import tiny
import tracereduce
import work
from harness import load_metric


def test_flops_and_bytes_of_one_search():
    assert work.flops(8, 1_600_000, 512) == 2 * 8 * 1_600_000 * 512
    rows = 1_600_000 * (512 + 4)
    assert work.bytes_moved(8, 1_600_000, 512, "int8") == \
        rows + 8 * 512 * 4 + 8 * 8
    assert work.bytes_moved(1, 1000, 128, "bf16") == \
        1000 * 256 + 512 + 8
    assert work.bytes_moved(1, 1000, 128, "fp32", k=5) == \
        1000 * 512 + 512 + 40


def test_least_time_takes_the_bound_that_binds():
    peak = work.peaks("TPU v5 lite")
    # one probe over 1.6M int8 rows reads 0.83 GB: memory-bound, ~1 ms
    t, bound = work.least_s([(1, 1_600_000, 512, "int8", 1)], peak)
    assert bound == "memory" and t == pytest.approx(
        work.bytes_moved(1, 1_600_000, 512, "int8") / 819e9)
    # 1024 probes: 1.68 TFLOP, compute-bound at ~8.5 ms
    t, bound = work.least_s([(1024, 1_600_000, 512, "int8", 1)], peak)
    assert bound == "compute" and t == pytest.approx(
        2 * 1024 * 1_600_000 * 512 / 197e12)
    # a cycle's searches add up before the bound is taken
    two, _ = work.least_s([(4, 333_343, 128, "bf16", 1)] * 2, peak)
    one, _ = work.least_s([(4, 333_343, 128, "bf16", 1)], peak)
    assert two == pytest.approx(2 * one)


def test_peaks_table_names_its_source_and_refuses_unknown_kinds():
    table = json.loads(work.PEAKS_FILE.read_text())
    assert "cloud.google.com" in table["_source"]
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v4", "cpu", "_source"):
        with pytest.raises(KeyError, match="no peaks"):
            work.peaks(kind)


def _view(**kw):
    spans = {"bench.window": [(0.0, 1.0)],
             "stage.facenet": [(0.10, 0.15), (0.50, 0.55)],
             "stage.watchlist_db": [(0.20, 0.40), (0.60, 0.80)],
             "lane.wait": [(0.40, 0.50)]}
    ops = [[("fusion.1", 0.11, 0.13), ("gallery_match", 0.25, 0.35),
            ("copy", 0.30, 0.36), ("gallery_match", 0.65, 0.70),
            ("late", 0.95, 1.20)]]
    cycles = [{"frames": 3, "work": [(3, 1000, 128, "bf16", 1)]},
              {"frames": 1, "work": [(1, 1000, 128, "bf16", 1)]}]
    return tracereduce.TraceView(spans, ops, cycles=cycles,
                                 peak=tiny.V5E, stage_names=["facenet"],
                                 match_stage="watchlist_db", **kw)


def test_busy_is_the_union_of_device_intervals_inside_the_window():
    v = _view()
    # 0.02 + (0.25..0.36) 0.11 + 0.05 + (0.95..1.0) 0.05
    assert v.busy_s == pytest.approx(0.23)
    assert v.busy_in(0.2, 0.4) == pytest.approx(0.11)
    assert v.device_idle() == pytest.approx(77.0)


def test_host_time_is_span_less_device_busy():
    v = _view()
    # (0.2 - 0.11) and (0.2 - 0.05), mean 0.12 s
    assert v.match_host_ms() == pytest.approx(120.0)
    assert v.stage_ms_per_frame() == pytest.approx(100.0 / 4)


def test_roofline_is_least_time_over_busy_inside_the_match_spans():
    v = _view()
    least, _ = work.least_s([(3, 1000, 128, "bf16", 1),
                             (1, 1000, 128, "bf16", 1)], tiny.V5E)
    got = v.match_roofline()
    assert got == pytest.approx(100 * least / 0.16)
    assert 0 < got <= 100


def test_idle_gaps_are_named_by_the_span_they_fall_in():
    b = _view().breakdown()
    idle = dict(b["idle_gaps"])
    assert idle["lane.wait"] == pytest.approx(0.10)
    assert idle["stage.watchlist_db"] == pytest.approx(0.09 + 0.15)
    assert idle["stage.facenet"] == pytest.approx(0.08)
    assert dict(b["device_ops"])["gallery_match"] == pytest.approx(0.15)
    assert sum(idle.values()) == pytest.approx(1.0 - 0.23)


def test_metric_files_read_the_view_and_skip_what_is_absent():
    v = _view()
    for name, want in (("device.idle.latency", 77.0),
                       ("match.host_ms.backfill", 120.0),
                       ("stages.ms_per_frame", 25.0)):
        assert load_metric(name).read(v) == pytest.approx(want)
    bare = tracereduce.TraceView({"bench.window": [(0.0, 1.0)]}, [])
    for name in ("device.idle.latency", "match.roofline.latency",
                 "match.host_ms.latency", "stages.ms_per_frame"):
        assert load_metric(name).read(bare) is None


DATA = tiny.BENCH / "tests" / "data"


def test_reduction_of_a_trace_recorded_on_the_chip():
    """A one-second `fleet.mixed --trace 1` window recorded on a TPU v5
    lite: the harness spans and the device's ops share one clock, every
    watchlist span holds device work, and the reduction stays in range."""
    view = tracereduce.TraceView.from_xplane(
        next(DATA.glob("*.xplane.pb")), stage_names=[
            "retinaface", "crfiqa", "facenet"], match_stage="watchlist_db")
    assert 0.9 < view.window_s < 1.5
    n = len(view.spans["stage.watchlist_db"])
    for stage in ("retinaface", "crfiqa", "facenet"):
        assert len(view.spans[f"stage.{stage}"]) == n > 0
    assert 0 < view.busy_s < view.window_s
    for s, e in view.spans["stage.watchlist_db"]:
        assert 0 < view.busy_in(s, e) < e - s
    assert 50 < view.device_idle() < 100
    assert 0 < view.match_host_ms() < 1e3
    view.cycles = [{"frames": 1, "work": [(1, 333_333, 128, "bf16", 1)]}] * n
    view.peak = work.peaks("TPU v5 lite")
    assert 0 < view.match_roofline() <= 100
    b = view.breakdown()
    assert any("custom-call" in name for name, _ in b["device_ops"])
    idle = dict(b["idle_gaps"])
    assert set(idle) <= set(view.spans) and idle["stage.watchlist_db"] > 0
    assert sum(idle.values()) == pytest.approx(view.window_s - view.busy_s)
