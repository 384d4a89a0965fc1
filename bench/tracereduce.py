"""Reduce a profiler trace to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
From it this module takes the device's operations (the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane) and the harness's own spans (the
``TraceAnnotation`` events named ``bench.*``, ``stage.*`` and ``lane.*``
on the host plane), both on the profiler's clock.  Device busy time is
the union of the operations' intervals; a span's host time is its length
less the device busy time inside it; idle time is named by the harness
span it falls in.
"""
from __future__ import annotations

import glob
import os
import re
from pathlib import Path

import numpy as np

import work

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench.", "stage.", "lane.")
WINDOW_SPAN = "bench.window"


def find_xplane(log_dir) -> Path:
    found = sorted(glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def op_name(hlo: str) -> str:
    """A device op's event name, which is its whole HLO instruction, cut
    to the instruction's name, opcode and first result shape
    (``%pad.0 pad s8[1601536,512]``)."""
    m = re.match(r"\s*(%?[\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\]).*?"
                 r"\b([a-z][\w\-]*)\(", hlo)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else hlo[:80]


def _merge(intervals) -> np.ndarray:
    """Union of ``(start, end)`` intervals, as sorted disjoint rows."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = np.asarray(sorted(intervals), np.float64)
    out = [iv[0].copy()]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.asarray(out)


def _covered(iv: np.ndarray, cum: np.ndarray, t: float) -> float:
    """Busy seconds of the disjoint sorted intervals ``iv`` before ``t``
    (``cum`` holds their running lengths)."""
    j = int(np.searchsorted(iv[:, 0], t, side="right")) if len(iv) else 0
    if j == 0:
        return 0.0
    return float(cum[j - 1] + min(t, iv[j - 1, 1]) - iv[j - 1, 0])


class TraceView:
    """Spans, device busy intervals and the harness's records of one
    traced window.  Times are seconds on the profiler's clock."""

    def __init__(self, spans: dict, device_ops: list, *, cycles=(),
                 peak=None, stage_names=(), match_stage=None):
        self.spans = {k: sorted(v) for k, v in spans.items()}
        windows = self.spans.get(WINDOW_SPAN)
        if not windows:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        self.window = windows[0]
        self.window_s = self.window[1] - self.window[0]
        self.device_ops = device_ops          # per device: [(name, s, e)]
        self.busy = [_merge([(s, e) for _, s, e in ops]) for ops in
                     device_ops]
        self._cum = [np.concatenate([[0.0], np.cumsum(iv[:, 1] - iv[:, 0])])
                     for iv in self.busy]
        self.cycles = list(cycles)
        self.peak = peak
        self.stage_names = list(stage_names)
        self.match_stage = match_stage
        self.busy_s = self.busy_in(*self.window)

    @classmethod
    def from_xplane(cls, path, **kw) -> "TraceView":
        import jax
        pd = jax.profiler.ProfileData.from_file(str(path))
        spans, device_ops = {}, []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PLANE):
                ops = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops += [(op_name(ev.name), ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                                for ev in line.events]
                device_ops.append(ops)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIXES):
                            spans.setdefault(ev.name, []).append(
                                (ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9))
        return cls(spans, device_ops, **kw)

    def busy_in(self, a: float, b: float) -> float:
        """Device busy seconds inside ``[a, b]``, averaged over devices."""
        if not self.busy:
            return 0.0
        return sum(_covered(iv, cum, b) - _covered(iv, cum, a)
                   for iv, cum in zip(self.busy, self._cum)) / len(self.busy)

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took the most time, and the idle
        time by what the host was doing (the harness span it fell in;
        ``bench.window`` for time between spans), each the ``top``
        largest."""
        a, b = self.window
        by_op = {}
        for name, s, e in (self.device_ops[0] if self.device_ops else []):
            d = min(e, b) - max(s, a)
            if d > 0:
                by_op[name] = by_op.get(name, 0.0) + d
        idle = {}
        for name in self.spans:
            if name != WINDOW_SPAN:
                idle[name] = sum(e - s - self.busy_in(s, e)
                                 for s, e in self._spans(name))
        idle[WINDOW_SPAN] = (self.window_s - self.busy_s) - sum(idle.values())
        order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in order(by_op)],
                "idle_gaps": [[k, v] for k, v in order(idle)]}

    def _spans(self, name: str) -> list:
        a, b = self.window
        return [(s, e) for s, e in self.spans.get(name, []) if a <= s < b]

    # -- the per-layer arithmetic, read by metrics/<name>.py ------------------
    def stage_ms_per_frame(self):
        """Harness span time of the stage payloads, per frame served
        (the frames' transfer is outside them, in ``lane.h2d``)."""
        total = sum(e - s for n in self.stage_names
                    for s, e in self._spans(f"stage.{n}"))
        frames = sum(c["frames"] for c in self.cycles)
        if not frames or not total:
            return None
        return total / frames * 1e3

    def match_host_ms(self):
        """Mean over watchlist-stage spans of their host time: the span
        less the device busy time inside it."""
        spans = self._spans(f"stage.{self.match_stage}")
        if not spans or not self.device_ops:
            return None
        return float(np.mean([e - s - self.busy_in(s, e)
                              for s, e in spans])) * 1e3

    def match_roofline(self):
        """The least time of the searches the window needed over the
        device busy time inside the watchlist-stage spans."""
        spans = self._spans(f"stage.{self.match_stage}")
        busy = sum(self.busy_in(s, e) for s, e in spans)
        if not spans or busy <= 0 or self.peak is None:
            return None
        if len(spans) != len(self.cycles):
            raise ValueError(f"{len(spans)} watchlist spans for "
                             f"{len(self.cycles)} cycles")
        least = sum(work.least_s(c["work"], self.peak)[0]
                    for c in self.cycles)
        return least / busy * 100.0

    def device_idle(self):
        if not self.device_ops or self.window_s <= 0:
            return None
        return (1.0 - self.busy_s / self.window_s) * 100.0
