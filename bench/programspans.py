"""The program's own spans in a traced window, and the per-layer arithmetic
that reads them.

The serving program marks its phases with ``jax.profiler.TraceAnnotation``
(``repro.core.spans``): ``match.batch`` around a watchlist call, the four
phases ``match.scope``, ``match.protect``, ``match.scan`` and
``match.results`` that tile it, and ``cartridge.call`` / ``cartridge.sync``
around each stage call's dispatch and wait.  They sit on the host plane of
the same trace as the harness's spans, on the same clock, with their
arguments as event stats.  The names are spelled here, not imported: the
benchmark also runs commits whose program has no such spans.

``TraceView`` (``tracereduce.py``) does not collect them, so ``of(view)``
reads them once from the trace the view was made from: the newest
``.xplane.pb`` under the run's trace directory whose ``bench.window`` span
starts where the view's window does.  A program without these spans (an
older commit) gives an empty set, and every metric here then reads
``None``, as the device metrics do on a trace with no device plane.
"""
from __future__ import annotations

import glob
import os
from pathlib import Path

import tracereduce

PREFIXES = ("match.", "cartridge.")
BATCH = "match.batch"
PHASES = ("match.scope", "match.protect", "match.scan", "match.results")
CALL, SYNC = "cartridge.call", "cartridge.sync"
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"


def read_xplane(path) -> tuple:
    """``(window starts, spans)`` of one trace: the start of each
    ``bench.window`` span, and ``{name: [(start, end, stats)]}`` of every
    host event named ``match.*`` or ``cartridge.*`` (seconds on the
    profiler's clock; ``stats`` the span's arguments)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    windows, spans = [], {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == tracereduce.WINDOW_SPAN:
                    windows.append(ev.start_ns * 1e-9)
                elif name.startswith(PREFIXES):
                    spans.setdefault(name, []).append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         dict(ev.stats)))
    return windows, {k: sorted(v, key=lambda t: t[:2])
                     for k, v in spans.items()}


def find_trace(view, root: Path = TRACE_DIR):
    """The program's spans of the trace under ``root`` (``run.py``'s
    trace directory) that ``view`` was made from, or None."""
    found = glob.glob(os.path.join(str(root), "**", "plugins", "profile",
                                   "*", "*.xplane.pb"), recursive=True)
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        windows, spans = read_xplane(path)
        if view.window[0] in windows:
            return spans
    return None


def of(view) -> dict:
    """The program's spans of ``view``'s trace, read once and kept on the
    view as ``view.program``; empty where the trace holds none."""
    if getattr(view, "program", None) is None:
        view.program = (find_trace(view) or {}) if view.device_ops else {}
    return view.program


def _in_window(view, name: str) -> list:
    a, b = view.window
    return [sp for sp in of(view).get(name, []) if a <= sp[0] < b]


def _calls(view) -> int:
    """Watchlist calls in the window: the denominator of
    ``TraceView.match_host_ms``; 0 where nothing is read."""
    if not view.device_ops:
        return 0
    return len(view._spans(f"stage.{view.match_stage}"))


def _host_s(view, name: str) -> float:
    """Host seconds of a span name in the window: over its spans, the span
    less the device busy time inside it."""
    return sum(e - s - view.busy_in(s, e) for s, e, _ in
               _in_window(view, name))


def phase_ms(view, name: str):
    """Host time of one phase per watchlist call (ms)."""
    calls = _calls(view)
    if not calls or not _in_window(view, name):
        return None
    return _host_s(view, name) / calls * 1e3


def untiled_ms(view):
    """Host time per watchlist call (ms) inside ``match.batch`` and outside
    every phase: the grouping loop's own lines, and whatever a change puts
    outside the phases."""
    calls = _calls(view)
    if not calls or not _in_window(view, BATCH):
        return None
    inner = sum(_host_s(view, p) for p in PHASES)
    return (_host_s(view, BATCH) - inner) / calls * 1e3


def arg_per_call(view, name: str, key: str):
    """A span argument summed over the window's spans of ``name`` (absent
    reads 0), per watchlist call."""
    calls = _calls(view)
    spans = _in_window(view, name)
    if not calls or not spans:
        return None
    return sum(st.get(key, 0) for _, _, st in spans) / calls


def _per_frame(view, name: str):
    """The window's spans of ``name`` and the frames served, or
    ``(None, 0)``."""
    if not view.device_ops:
        return None, 0
    spans = _in_window(view, name)
    frames = sum(c["frames"] for c in view.cycles)
    return (spans, frames) if spans and frames else (None, 0)


def syncs_per_frame(view):
    """Stage calls' waits for their results per frame served."""
    spans, frames = _per_frame(view, SYNC)
    return None if spans is None else len(spans) / frames


def stage_ms_per_frame(view, name: str):
    """Span time of the stage calls' dispatches (``cartridge.call``) or
    waits (``cartridge.sync``) per frame served (ms)."""
    spans, frames = _per_frame(view, name)
    if spans is None:
        return None
    return sum(e - s for s, e, _ in spans) / frames * 1e3
