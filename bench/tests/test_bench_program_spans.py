"""The per-layer arithmetic over the program's own spans
(``programspans.py``): each watchlist phase's host time per call, what the
phases leave uncovered, the span arguments per call, the stage calls'
dispatches and waits per frame, and the reading of a chip trace that
holds them beside one that does not."""
from __future__ import annotations

import math
import shutil

import pytest

import tiny  # first: puts bench/ on the path
import programspans
import tracereduce
from harness import load_benchmark, load_metric

DATA = tiny.BENCH / "tests" / "data"
OLD = next(DATA.glob("*.xplane.pb"))
NEW = next((DATA / "spans").glob("*.xplane.pb"))
STAGES = ["retinaface", "crfiqa", "facenet"]
CELLS = ("latency", "backfill")
PHASE_METRICS = {f"match.{ph}": [f"match.{ph}_ms.{c}" for c in CELLS]
                 for ph in ("scope", "protect", "scan", "results")}
NEW_METRICS = sorted(m for ms in PHASE_METRICS.values() for m in ms) + [
    "match.untiled_ms.latency", "match.untiled_ms.backfill",
    "match.index_mb.latency", "match.labels_per_call.latency",
    "match.labels_per_call.backfill", "stages.call_ms_per_frame",
    "stages.sync_ms_per_frame", "stages.syncs_per_frame"]


def _view(program=None, ops=True):
    """Two watchlist calls, (0.2, 0.4) and (0.6, 0.8), with device work
    (0.25..0.36) and (0.65..0.70) inside them; four frames served."""
    spans = {"bench.window": [(0.0, 1.0)],
             "stage.facenet": [(0.10, 0.15), (0.50, 0.55)],
             "stage.watchlist_db": [(0.20, 0.40), (0.60, 0.80)]}
    device = [[("fusion.1", 0.11, 0.13), ("gallery_match", 0.25, 0.35),
               ("copy", 0.30, 0.36), ("gallery_match", 0.65, 0.70)]]
    cycles = [{"frames": 3, "work": []}, {"frames": 1, "work": []}]
    v = tracereduce.TraceView(spans, device if ops else [], cycles=cycles,
                              peak=tiny.V5E, stage_names=["facenet"],
                              match_stage="watchlist_db")
    v.program = program
    return v


def _program():
    sp = lambda s, e, **st: (s, e, st)   # noqa: E731
    return {
        "match.batch": [sp(0.20, 0.40), sp(0.60, 0.80)],
        "match.scope": [sp(0.20, 0.22), sp(0.60, 0.61)],
        "match.protect": [sp(0.22, 0.24), sp(0.61, 0.63)],
        "match.scan": [sp(0.24, 0.37, index_bytes=4000), sp(0.63, 0.70),
                       sp(0.70, 0.75, index_bytes=2000)],
        "match.results": [sp(0.37, 0.39, labels=9), sp(0.39, 0.40),
                          sp(0.75, 0.80, labels=9),
                          sp(1.10, 1.20, labels=9)],     # after the window
        "cartridge.call": [sp(0.11 + 0.01 * i, 0.112 + 0.01 * i)
                           for i in range(4)],
        "cartridge.sync": [sp(0.12 + 0.01 * i, 0.125 + 0.01 * i)
                           for i in range(4)] + [sp(1.5, 1.6)],
    }


def test_phase_is_its_spans_less_device_busy_over_the_watchlist_calls():
    v = _view(_program())
    # scan: (0.13 - 0.11) + (0.12 - 0.05) over two calls
    assert programspans.phase_ms(v, "match.scan") == pytest.approx(45.0)
    assert programspans.phase_ms(v, "match.scope") == pytest.approx(15.0)
    assert programspans.phase_ms(v, "match.protect") == pytest.approx(20.0)
    # the span after the window does not count
    assert programspans.phase_ms(v, "match.results") == pytest.approx(40.0)


def test_phases_that_tile_the_calls_sum_to_the_host_time_per_call():
    v = _view(_program())
    total = sum(programspans.phase_ms(v, p) for p in programspans.PHASES)
    assert total == pytest.approx(v.match_host_ms())


def test_what_the_phases_leave_uncovered_in_the_batch_spans():
    v = _view(_program())
    assert programspans.untiled_ms(v) == pytest.approx(0.0)
    prog = _program()
    prog["match.scope"][1] = (0.601, 0.61, {})
    prog["match.results"][1] = (0.39, 0.397, {})
    # 1 ms and 3 ms of host time in no phase, over two calls
    assert programspans.untiled_ms(_view(prog)) == pytest.approx(2.0)


def test_span_arguments_are_summed_per_watchlist_call():
    v = _view(_program())
    # absent reads 0; the span after the window does not count
    assert programspans.arg_per_call(v, "match.scan", "index_bytes") == \
        pytest.approx(3000)
    assert programspans.arg_per_call(v, "match.results", "labels") == \
        pytest.approx(9)
    assert load_metric("match.index_mb.latency").read(v) == \
        pytest.approx(0.003)


def test_stage_calls_are_counted_and_timed_per_frame_served():
    v = _view(_program())
    assert programspans.syncs_per_frame(v) == pytest.approx(1.0)
    assert programspans.stage_ms_per_frame(v, programspans.SYNC) == \
        pytest.approx(4 * 5.0 / 4)
    assert programspans.stage_ms_per_frame(v, programspans.CALL) == \
        pytest.approx(4 * 2.0 / 4)


def test_nothing_is_read_without_a_device_plane_spans_or_calls():
    for ph in programspans.PHASES:
        assert programspans.phase_ms(_view(_program(), ops=False), ph) is None
        assert programspans.phase_ms(_view({}), ph) is None
    assert programspans.untiled_ms(_view(_program(), ops=False)) is None
    assert programspans.untiled_ms(_view({})) is None
    assert programspans.arg_per_call(_view({}), "match.scan", "x") is None
    assert programspans.syncs_per_frame(_view(_program(), ops=False)) is None
    assert programspans.stage_ms_per_frame(_view({}), "cartridge.sync") \
        is None
    no_calls = _view(_program())
    no_calls.spans.pop("stage.watchlist_db")
    assert programspans.phase_ms(no_calls, "match.scan") is None
    assert programspans.untiled_ms(no_calls) is None
    assert programspans.arg_per_call(no_calls, "match.scan", "x") is None
    no_frames = _view(_program())
    no_frames.cycles = []
    assert programspans.syncs_per_frame(no_frames) is None


def test_metric_files_are_declared_and_read_the_program_spans():
    declared = {m["name"]: m for m in load_benchmark()["per_layer"]}
    v = _view(_program())
    for phase, names in PHASE_METRICS.items():
        for name in names:
            assert declared[name]["layer"] == "watchlist match"
            assert load_metric(name).read(v) == pytest.approx(
                programspans.phase_ms(v, phase))
    assert load_metric("stages.syncs_per_frame").read(v) == 1.0
    assert load_metric("stages.call_ms_per_frame").read(v) == \
        pytest.approx(2.0)
    assert load_metric("match.labels_per_call.backfill").read(v) == \
        pytest.approx(9)
    for name in NEW_METRICS:
        assert name in declared
        assert load_metric(name).read(_view({})) is None


@pytest.mark.parametrize("path", [OLD, NEW], ids=["harness_only", "program"])
def test_program_spans_leave_the_harness_reduction_as_it_was(path):
    kw = dict(stage_names=STAGES, match_stage="watchlist_db")
    plain = tracereduce.TraceView.from_xplane(path, **kw)
    view = tracereduce.TraceView.from_xplane(path, **kw)
    windows, view.program = programspans.read_xplane(path)
    assert windows == [view.window[0]]
    assert view.spans == plain.spans
    assert not any(k.startswith(programspans.PREFIXES) for k in view.spans)
    assert view.breakdown() == plain.breakdown()
    for m in ("match_host_ms", "device_idle", "stage_ms_per_frame"):
        assert getattr(view, m)() == getattr(plain, m)()


def test_the_trace_is_found_by_its_window(tmp_path):
    """A run's view finds its own trace among others under the trace
    directory; a trace with no program spans (an older program) reads
    as none, and each metric as absent."""
    import run
    assert programspans.TRACE_DIR == run.TRACE_DIR
    for cell, src in (("a", OLD), ("b", NEW)):
        d = tmp_path / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        shutil.copy(src, d / src.name)
    kw = dict(stage_names=STAGES, match_stage="watchlist_db")
    new = tracereduce.TraceView.from_xplane(NEW, **kw)
    assert programspans.find_trace(new, tmp_path) == \
        programspans.read_xplane(NEW)[1]
    old = tracereduce.TraceView.from_xplane(OLD, **kw)
    assert programspans.find_trace(old, tmp_path) == {}
    old.program = {}
    for name in NEW_METRICS:
        assert load_metric(name).read(old) is None


def test_reduction_of_a_chip_trace_with_program_spans():
    """A one-second `fleet.mixed --trace 1` window recorded on a TPU v5
    lite with the program's spans (38 frames served in 13 cycles, as the
    run reported): every new metric reads a finite value, the phases fill
    the watchlist calls' host time, and each frame's three stages are
    each waited on once."""
    view = tracereduce.TraceView.from_xplane(
        NEW, stage_names=STAGES, match_stage="watchlist_db")
    view.program = programspans.read_xplane(NEW)[1]
    a, b = view.window
    batches = [sp for sp in view.program["match.batch"] if a <= sp[0] < b]
    assert len(batches) == len(view.spans["stage.watchlist_db"]) > 0
    view.cycles = [{"frames": 38, "work": []}]
    for name in NEW_METRICS:
        got = load_metric(name).read(view)
        assert got is not None and math.isfinite(got) and got >= 0, name
    phases = sum(programspans.phase_ms(view, p) for p in programspans.PHASES)
    host = view.match_host_ms()
    assert 0.9 * host <= phases <= host + 1.0
    assert programspans.untiled_ms(view) < 1.0
    assert load_metric("stages.syncs_per_frame").read(view) == 3.0
    for s, e, st in batches:
        assert st == {}
        inside = [sp for p in programspans.PHASES
                  for sp in view.program[p] if s <= sp[0] and sp[1] <= e]
        assert inside and sum(y - x for x, y, _ in inside) <= e - s
