"""The serving path's host spans (``repro.core.spans``):
``jax.profiler.TraceAnnotation``s inside the watchlist match and the stage
calls, recorded while a profiler session runs and changing no answer."""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import messages as msg
from repro.core import spans as sp
from repro.core.cartridge import FnCartridge
from repro.crypto import SecureGallery
from repro.launch.serve import WatchlistCartridge

DIM = 128
TENANTS = ("checkpoint", "recon")


def _watchlist(n_shards=1, tenant_scoped=True):
    rng = np.random.default_rng(5)
    g = SecureGallery(DIM, n_shards=n_shards, match_dtype="fp32")
    enrolled = {}
    for t in TENANTS:
        raw = rng.standard_normal((40, DIM)).astype(np.float32)
        g.enroll(raw, [f"{t}-{i}" for i in range(len(raw))], tenant=t)
        enrolled[t] = raw
    wl = WatchlistCartridge(g, tenant_scoped=tenant_scoped)
    wl.load()
    return wl, enrolled


def _messages(enrolled):
    """Five probes, each a noisy copy of an enrolled row, alternating
    tenants; seq numbers start at 100."""
    rng = np.random.default_rng(6)
    out = []
    for j in range(5):
        t = TENANTS[j % 2]
        x = enrolled[t][j] + 0.05 * rng.standard_normal(DIM)
        out.append(msg.Message(msg.EMBEDDING, 100 + j, x.astype(np.float32),
                               {"tenant": t}))
    return out


def _stage():
    c = FnCartridge("double", lambda p, x: x * 2.0,
                    msg.MessageSpec(msg.EMBEDDING),
                    msg.MessageSpec(msg.EMBEDDING))
    c.load()
    return c


def _traced(tmp_path, fn):
    """``fn()`` under a profiler session; its result and the program's
    host spans as ``{name: [(start_ns, end_ns, stats)]}``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("match.", "cartridge.")):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             dict(ev.stats)))
    return out, {k: sorted(v, key=lambda t: t[:2]) for k, v in spans.items()}


def _answers(ms):
    return [(m.payload["label"], m.payload["score"]) for m in ms]


def test_watchlist_batch_spans_tile_the_call_in_order(tmp_path):
    wl, enrolled = _watchlist()
    ms = _messages(enrolled)
    out, spans = _traced(tmp_path, lambda: wl.process_batch(ms))

    batch, = spans[sp.MATCH_BATCH]
    assert batch[2] == {}
    b0, b1 = batch[:2]
    phases = sorted((s, e, name, st) for name in (
        sp.MATCH_SCOPE, sp.MATCH_PROTECT, sp.MATCH_SCAN,
        sp.MATCH_RESULTS) for s, e, st in spans[name])
    # inside the batch span, one after another, none overlapping
    assert all(b0 <= s <= e <= b1 for s, e, _, _ in phases)
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
    # the grouping, then per tenant group: stack, the gallery's own four
    # phases and the group's results; then the result messages
    group = [sp.MATCH_SCOPE, sp.MATCH_SCOPE, sp.MATCH_PROTECT,
             sp.MATCH_SCAN, sp.MATCH_RESULTS, sp.MATCH_RESULTS]
    assert [p[2] for p in phases] == \
        [sp.MATCH_SCOPE] + group * 2 + [sp.MATCH_RESULTS]
    # the phases leave only the loop's own lines uncovered
    assert sum(e - s for s, e, _, _ in phases) > 0.9 * (b1 - b0)

    # arguments only where a metric reads them: the row index sent with a
    # tenant subset, and the labels the gallery looked up (probes x k=1)
    g = wl.gallery
    args = [{} for _ in phases]
    for scan, tenant, probes in ((4, "checkpoint", 3), (10, "recon", 2)):
        rows = g._tenant_shard_rows(0, g._tenant_code(tenant))
        args[scan] = {"index_bytes": rows.nbytes}
        args[scan + 1] = {"labels": probes}
    assert [p[3] for p in phases] == args
    assert [m.payload["label"] for m in out] == \
        ["checkpoint-0", "recon-1", "checkpoint-2", "recon-3", "checkpoint-4"]


def test_shared_pool_and_shards_add_a_merge_span(tmp_path):
    """Untagged, over two shards: one scan span per shard, then the
    merge's; no row index is sent with the whole pool."""
    wl, enrolled = _watchlist(n_shards=2, tenant_scoped=False)
    ms = _messages(enrolled)
    _, spans = _traced(tmp_path, lambda: wl.process_batch(ms))
    scans = spans[sp.MATCH_SCAN]
    assert [st for _, _, st in scans] == [{}, {}, {}]
    assert scans[0][1] <= scans[1][0] and scans[1][1] <= scans[2][0]
    assert len(spans[sp.MATCH_PROTECT]) == 1
    assert [st for _, _, st in spans[sp.MATCH_RESULTS]] == \
        [{"labels": 5}, {}, {}]
    # a direct call at k > 1 looks up k labels per query
    q = np.stack([m.payload for m in ms])
    (labels, _), spans = _traced(tmp_path / "k4",
                                 lambda: wl.gallery.match(q, k=4))
    assert labels.shape == (5, 4)
    assert [st for _, _, st in spans[sp.MATCH_RESULTS]] == [{"labels": 20}]


def test_stage_call_and_sync_spans_per_frame(tmp_path):
    c = _stage()
    ms = [msg.Message(msg.EMBEDDING, i, jnp.full((4,), float(i)))
          for i in range(3)]
    out, spans = _traced(tmp_path, lambda: c.process_batch(ms))
    calls, syncs = spans[sp.CARTRIDGE_CALL], spans[sp.CARTRIDGE_SYNC]
    assert len(calls) == len(syncs) == 3
    for (cs, ce, cst), (ss, se, sst) in zip(calls, syncs):
        assert cst == sst == {}
        assert ce <= ss                  # the wait follows its dispatch
    assert [float(m.payload[0]) for m in out] == [0.0, 2.0, 4.0]
    assert c.stats == {"processed": 3}


def test_answers_are_the_same_with_and_without_a_session(tmp_path):
    wl, enrolled = _watchlist()
    ms = _messages(enrolled)
    plain = _answers(wl.process_batch(ms))
    traced, _ = _traced(tmp_path, lambda: wl.process_batch(ms))
    assert _answers(traced) == plain
    c = _stage()
    x = msg.Message(msg.EMBEDDING, 0, jnp.arange(4.0))
    want = np.asarray(c.process(x).payload)
    got, _ = _traced(tmp_path / "stage", lambda: c.process(x))
    np.testing.assert_array_equal(np.asarray(got.payload), want)


def test_a_refused_match_raises_from_inside_its_scope_span(tmp_path):
    wl, _ = _watchlist()
    with pytest.raises(ValueError, match="dtype must be one of"):
        _traced(tmp_path, lambda: wl.gallery.match(
            np.zeros((1, DIM), np.float32), dtype="fp16"))
    assert not jax.profiler.TraceAnnotation.is_enabled()
