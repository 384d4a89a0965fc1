"""Every cell's chain, driven end to end through ``run.main`` at a tiny
size on the CPU (Pallas in interpret mode), with only the look for a
chip skipped."""
from __future__ import annotations

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
import harness
import run

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def test_refuses_a_backend_that_is_not_a_tpu(capsys):
    with pytest.raises(SystemExit, match="no TPU"):
        run.require_chips(1)
    with pytest.raises(SystemExit):
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_cell_runs_correct(workload, monkeypatch, tmp_path, capsys):
    res = tiny.run_main(monkeypatch, tmp_path, capsys, [
        "--workload", workload, "--seed", str(tiny.SEED),
        "--seconds", "0.4", "--trace", "0"])
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    bench = harness.load_benchmark()
    want = {m["name"] for m in bench["end_to_end"]
            if harness.applies(m, workload)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for k, m in res["metrics"].items()
               if k != "peak_hbm_gb")          # the CPU reports no peak
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert 0 <= c["value"] <= c["limit"]


def test_traced_run_reports_only_what_it_reads(monkeypatch, tmp_path,
                                               capsys):
    """A CPU trace has no TPU plane: the device metrics are left out, the
    harness's stage spans are still read."""
    res = tiny.run_main(monkeypatch, tmp_path, capsys, [
        "--workload", "fleet.mixed", "--seed", "7", "--seconds", "0.4",
        "--trace", "1"])
    assert res["correct"] is True
    assert set(res["metrics"]) == {"stages.ms_per_frame"}
    assert res["metrics"]["stages.ms_per_frame"]["value"] > 0
    assert res["device"]["window_s"] > 0
