"""Identification fast path: kernel dtype family, sharded SecureGallery,
engine event core, and the batched match stage.

The hypothesis property pins the whole kernel family (fp32 / bf16 / int8,
interpret mode) to a ``jax.lax.top_k`` oracle on both scores and indices —
including exact score ties (integer-grid embeddings), tail-padding blocks
(N not a multiple of bn), sub-block query counts (Q < 8), and the k > N
sentinel contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                        # property tests need hypothesis; the rest don't
    from hypothesis import given, settings, strategies as stn
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    def given(**kw):        # leave decorated tests collectable (skipped)
        return lambda fn: pytest.mark.skip("hypothesis not installed")(fn)

    class _StnStub:         # strategy expressions evaluate at import time
        def __getattr__(self, name):
            return lambda *a, **k: None

    stn = _StnStub()

from repro.crypto import SecureGallery
from repro.kernels import ref as R
from repro.kernels.gallery_match import (NEG, dequantize_gallery,
                                         gallery_match_pallas,
                                         gallery_match_quant_pallas,
                                         quantize_gallery)

if HAVE_HYPOTHESIS:
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")

DTYPES = ("fp32", "bf16", "int8")


def _normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


# ---------------------------------------------------------------------------
# hypothesis property: every dtype path vs the jax.lax.top_k oracle
# ---------------------------------------------------------------------------
@given(seed=stn.integers(0, 2**31 - 1),
       Q=stn.integers(1, 12),
       N=stn.integers(1, 300),
       k=stn.integers(1, 8),
       path=stn.sampled_from(DTYPES),
       ties=stn.booleans())
def test_gallery_match_property(seed, Q, N, k, path, ties):
    rng = np.random.default_rng(seed)
    D = 16
    if ties:
        # integer-grid embeddings force exact duplicate scores, so the
        # tie-breaking discipline itself is under test
        q = rng.integers(-1, 2, (Q, D)).astype(np.float32)
        g = rng.integers(-1, 2, (N, D)).astype(np.float32)
        q[np.all(q == 0, axis=1)] = 1.0          # avoid zero rows
        g[np.all(g == 0, axis=1)] = 1.0
    else:
        q = rng.normal(size=(Q, D)).astype(np.float32)
        g = rng.normal(size=(N, D)).astype(np.float32)
    qn = np.asarray(_normalize(jnp.asarray(q)))
    gn = np.asarray(_normalize(jnp.asarray(g)))

    # bn=64 < 300 exercises multi-block merges and tail-padding blocks
    if path == "int8":
        g_q, g_s = quantize_gallery(jnp.asarray(gn))
        s, i = gallery_match_quant_pallas(jnp.asarray(qn), g_q, g_s, k=k,
                                          bq=8, bn=64, interpret=True)
        g_oracle = np.asarray(dequantize_gallery(g_q, g_s))
    elif path == "bf16":
        qb = jnp.asarray(qn).astype(jnp.bfloat16)
        gb = jnp.asarray(gn).astype(jnp.bfloat16)
        s, i = gallery_match_pallas(qb, gb, k=k, bq=8, bn=64, interpret=True)
        # oracle sees the same storage-rounded values (fp32 accumulation)
        qn = np.asarray(qb.astype(jnp.float32))
        g_oracle = np.asarray(gb.astype(jnp.float32))
    else:
        s, i = gallery_match_pallas(jnp.asarray(qn), jnp.asarray(gn), k=k,
                                    bq=8, bn=64, interpret=True)
        g_oracle = gn
    sr, ir = R.gallery_match_ref(jnp.asarray(qn), jnp.asarray(g_oracle), k=k)
    s, i, sr, ir = (np.asarray(x) for x in (s, i, sr, ir))

    assert s.shape == (Q, k) and i.shape == (Q, k)
    k_eff = min(k, N)
    # k > N sentinel contract
    assert np.all(i[:, k_eff:] == -1) and np.all(s[:, k_eff:] == NEG)
    valid_s, valid_i = s[:, :k_eff], i[:, :k_eff]
    # scores match the oracle exactly-ish (both paths accumulate in fp32)
    np.testing.assert_allclose(valid_s, sr[:, :k_eff], atol=2e-5, rtol=1e-5)
    assert np.all(np.diff(valid_s, axis=1) <= 1e-6)          # descending
    assert np.all((valid_i >= 0) & (valid_i < N))
    # indices agree with the oracle except across exact-tie permutations
    agree = valid_i == ir[:, :k_eff]
    tie = np.isclose(valid_s, sr[:, :k_eff], atol=2e-5)
    assert np.all(agree | tie)
    # every returned (score, index) pair is self-consistent: the score IS
    # the cosine of the row it claims (robust to any tie permutation)
    recomputed = np.take_along_axis(qn @ g_oracle.T, valid_i, axis=1)
    np.testing.assert_allclose(valid_s, recomputed, atol=2e-5, rtol=1e-5)


def test_k_exceeds_gallery_sentinels():
    q = jnp.asarray(np.eye(3, 8, dtype=np.float32))
    g = jnp.asarray(np.eye(2, 8, dtype=np.float32))
    s, i = gallery_match_pallas(q, g, k=5, interpret=True)
    assert s.shape == (3, 5) and i.shape == (3, 5)
    assert np.all(np.asarray(i)[:, 2:] == -1)
    assert np.all(np.asarray(s)[:, 2:] == NEG)
    sr, ir = R.gallery_match_ref(q, g, k=5)
    np.testing.assert_allclose(np.asarray(s)[:, :2], np.asarray(sr)[:, :2],
                               atol=1e-6)


def test_fused_normalize_matches_separate_normalize():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(7, 32)).astype(np.float32)) * 5.0
    g = _normalize(jnp.asarray(rng.normal(size=(90, 32)).astype(np.float32)))
    s_fused, i_fused = gallery_match_pallas(q, g, k=4, fuse_norm=True,
                                            bn=64, interpret=True)
    s_sep, i_sep = gallery_match_pallas(_normalize(q), g, k=4, bn=64,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(s_fused), np.asarray(s_sep),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_fused), np.asarray(i_sep))


def test_quantize_gallery_roundtrip_error_bounded():
    rng = np.random.default_rng(4)
    g = np.asarray(_normalize(jnp.asarray(
        rng.normal(size=(50, 64)).astype(np.float32))))
    g_q, g_s = quantize_gallery(jnp.asarray(g))
    back = np.asarray(dequantize_gallery(g_q, g_s))
    # symmetric per-row: error <= half a quantization step per element
    step = np.asarray(g_s)[:, None]
    assert np.all(np.abs(back - g) <= 0.5 * step + 1e-7)


# ---------------------------------------------------------------------------
# sharded SecureGallery
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", DTYPES)
def test_sharded_match_agrees_with_monolithic(dtype):
    rng = np.random.default_rng(11)
    dim, n = 48, 400
    g = rng.normal(size=(n, dim)).astype(np.float32)
    labels = [f"id{i}" for i in range(n)]
    q = g[[7, 200, 333]] + 0.05 * rng.normal(size=(3, dim)).astype(np.float32)

    mono = SecureGallery(dim, seed=5)
    mono.enroll(g, labels)
    lm, sm = mono.match(q, k=3)

    store = SecureGallery(dim, seed=5, n_shards=4, match_dtype=dtype)
    store.enroll(g, labels)
    assert store.shard_sizes() == [100, 100, 100, 100]
    ls, ss = store.match(q, k=3)
    assert list(ls[:, 0]) == list(lm[:, 0])          # top-1 identical
    assert np.all(np.diff(np.asarray(ss), axis=1) <= 1e-6)
    if dtype == "fp32":
        np.testing.assert_allclose(np.asarray(ss), np.asarray(sm), atol=1e-5)


def test_shard_lifecycle_enroll_reshard_rekey_seal():
    rng = np.random.default_rng(12)
    dim = 32
    g = rng.normal(size=(120, dim)).astype(np.float32)
    store = SecureGallery(dim, seed=9, n_shards=3, match_dtype="int8")
    for lo in range(0, 120, 40):                     # incremental enrollment
        store.enroll(g[lo:lo + 40], list(range(lo, lo + 40)))
    assert sum(store.shard_sizes()) == 120
    assert max(store.shard_sizes()) - min(store.shard_sizes()) <= 1
    q = g[[17]] + 0.02 * rng.normal(size=(1, dim)).astype(np.float32)
    assert store.match(q, k=1)[0][0, 0] == 17
    store.reshard(5)
    assert store.n_shards == 5 and sum(store.shard_sizes()) == 120
    assert store.match(q, k=1)[0][0, 0] == 17
    store.rekey(77)                                  # revocation
    assert store.match(q, k=1)[0][0, 0] == 17
    store.seal()                                     # drop plaintext views
    assert all(not p for p in store._prep)
    assert store.match(q, k=1)[0][0, 0] == 17
    assert store.protected_gallery().shape == (120, dim)


def test_sharded_merge_sorts_when_k_spans_whole_gallery():
    """Regression: with sum(per-shard k) == k the merge must still sort —
    the per-shard result columns are not globally ordered."""
    rng = np.random.default_rng(14)
    dim, n = 16, 5
    g = rng.normal(size=(n, dim)).astype(np.float32)
    store = SecureGallery(dim, seed=3, n_shards=2)
    store.enroll(g, list(range(n)))
    labels, scores = store.match(g[[4]], k=n)              # k == gallery size
    assert labels[0, 0] == 4                               # exact self-match
    s = np.asarray(scores)
    assert np.all(np.diff(s, axis=1) <= 1e-6)              # globally sorted
    assert s[0, 0] >= 1.0 - 1e-5


def test_int8_recall_at_1_on_noisy_queries():
    rng = np.random.default_rng(13)
    dim, n, nq = 64, 2000, 128
    g = rng.normal(size=(n, dim)).astype(np.float32)
    store = SecureGallery(dim, seed=2, n_shards=4)
    store.enroll(g, list(range(n)))
    qidx = rng.integers(0, n, nq)
    q = g[qidx] + 0.1 * rng.normal(size=(nq, dim)).astype(np.float32)
    truth = store.match(q, k=1, dtype="fp32")[0][:, 0].astype(np.int64)
    got = store.match(q, k=1, dtype="int8")[0][:, 0].astype(np.int64)
    assert np.mean(got == truth) >= 0.99


# (mode, dtype, shards, tenant-scoped, nprobe, k, tuple labels)
LABEL_CASES = [
    ("exact", "fp32", 1, False, 8, 3, False),
    ("exact", "bf16", 2, False, 8, 3, False),
    ("exact", "int8", 1, True, 8, 3, False),
    ("exact", "fp32", 2, True, 8, 3, False),
    ("ann", "fp32", 1, False, 4, 3, False),
    ("ann", "bf16", 2, True, 4, 3, False),
    ("ann", "int8", 2, False, 4, 3, False),
    ("ann", "int8", 1, True, 1, 40, False),      # probed cells run short
    ("ann", "fp32", 2, False, 1, 40, False),
    ("exact", "int8", 2, True, 8, 3, True),
    ("ann", "bf16", 2, False, 1, 40, True),
]


@pytest.mark.parametrize(
    "mode,dtype,shards,scoped,nprobe,k,tuples", LABEL_CASES,
    ids=["-".join(map(str, c)) for c in LABEL_CASES])
def test_match_labels_are_the_enrolled_labels_of_the_merged_ids(
        monkeypatch, mode, dtype, shards, scoped, nprobe, k, tuples):
    """The labels ``match`` returns are those of the global ids the merge
    kept, read from an object array of every label built here, with None
    in -1 slots; a tuple label comes back as the very object enrolled."""
    rng = np.random.default_rng(15)
    dim, per = 32, 120
    store = SecureGallery(dim, seed=4, n_shards=shards, match_dtype=dtype)
    tenants = ("a", "b") if scoped else (None,)
    labels, rows = [], []
    for t in tenants:
        g = rng.normal(size=(per, dim)).astype(np.float32)
        lab = [(str(t), i) if tuples else f"{t}-{i}" for i in range(per)]
        store.enroll(g, lab, tenant=t)
        labels += lab
        rows.append(g)
    if mode == "ann":
        store.build_ann_index(n_cells=16)
    rows = np.concatenate(rows)
    tenant = tenants[-1]              # the last tenant, or the shared pool
    want_gid = per * (len(tenants) - 1) + np.arange(0, per, 23)[:5]
    q = rows[want_gid] + 0.02 * rng.normal(size=(5, dim)).astype(np.float32)

    seen = []
    lookup = store._labels_of

    def spy(gids):
        seen.append(np.array(gids))
        return lookup(gids)

    monkeypatch.setattr(store, "_labels_of", spy)
    got, _ = store.match(q, k=k, mode=mode, nprobe=nprobe, tenant=tenant)

    gids, = seen
    every = np.fromiter(labels, object, count=len(labels))
    ref = np.where(gids >= 0, every[np.clip(gids, 0, None)], None)
    assert got.shape == ref.shape == (len(q), k)
    assert got.tolist() == ref.tolist()
    assert [got[i, 0] for i in range(len(q))] == list(every[want_gid])
    if nprobe == 1:
        assert (gids < 0).any() and all(
            got[i, j] is None for i, j in zip(*np.nonzero(gids < 0)))
    if tuples:
        assert all(got[i, j] is labels[gids[i, j]]
                   for i, j in zip(*np.nonzero(gids >= 0)))


# ---------------------------------------------------------------------------
# engine event core
# ---------------------------------------------------------------------------
@given(events=stn.lists(stn.tuples(stn.floats(0, 10, allow_nan=False),
                                   stn.integers(0, 99)),
                        min_size=1, max_size=200))
def test_event_queue_disciplines_pop_identically(events):
    from repro.runtime.events import HeapEventQueue, ListEventQueue
    heap, lst = HeapEventQueue(), ListEventQueue()
    for t, tag in events:
        heap.push(t, None, (tag,))
        lst.push(t, None, (tag,))
    order_h = [heap.pop()[:2] for _ in range(len(events))]
    order_l = [lst.pop()[:2] for _ in range(len(events))]
    assert order_h == order_l                        # min-time, FIFO on ties
    assert len(heap) == len(lst) == 0


def test_engine_reports_identical_under_both_queues():
    from repro.bus import BusParams, SharedBus
    from repro.core import messages as msg
    from repro.core.cartridge import DeviceModel, FnCartridge
    from repro.runtime import (CapabilityRegistry, HeapEventQueue,
                               ListEventQueue, StreamEngine)
    reports = []
    for qcls in (HeapEventQueue, ListEventQueue):
        reg = CapabilityRegistry()
        spec = msg.MessageSpec(msg.IMAGE_FRAME)
        for i in range(3):
            reg.insert(i, FnCartridge(f"s{i}", lambda p, x: x, spec, spec,
                                      device=DeviceModel(service_s=0.01)))
        eng = StreamEngine(reg, SharedBus(BusParams("t",
                                                    base_overhead_s=1e-4)),
                           event_queue=qcls())
        eng.feed(60, interval_s=0.005)
        eng.schedule_remove(0.1, slot=1)             # hot-swap mid-run
        reports.append(eng.run(until=30))
    a, b = reports
    assert a.frames_out == b.frames_out == 60
    assert a.sim_time == b.sim_time
    np.testing.assert_allclose(a.latencies, b.latencies)


# ---------------------------------------------------------------------------
# batched match stage
# ---------------------------------------------------------------------------
def test_watchlist_stage_coalesces_microbatch_into_one_kernel_call():
    from repro.bus import BusParams, SharedBus
    from repro.core import messages as msg
    from repro.launch.serve import EMB_DIM, WatchlistCartridge
    from repro.runtime import CapabilityRegistry, StreamEngine
    rng = np.random.default_rng(21)
    g = rng.normal(size=(40, EMB_DIM)).astype(np.float32)
    gallery = SecureGallery(EMB_DIM, seed=7, n_shards=2)
    gallery.enroll(g, [f"s{i}" for i in range(40)])
    cart = WatchlistCartridge(gallery)
    reg = CapabilityRegistry()
    reg.insert(0, cart)
    eng = StreamEngine(reg, SharedBus(BusParams("t", base_overhead_s=1e-4)),
                       execute_payloads=True, queue_cap=8)
    n = 24
    eng.feed(n, interval_s=0.0,                      # all queued: max batches
             payload_fn=lambda i: jnp.asarray(g[i % 40]),
             frame_bytes=EMB_DIM * 4)
    rep = eng.run(until=60)
    assert rep.frames_out == n
    assert cart.stats["processed"] == n
    # coalesced: far fewer kernel dispatches than frames
    assert cart.stats["match_calls"] <= -(-n // 2)
    assert rep.stage_stats["watchlist_db"].max_batch > 1