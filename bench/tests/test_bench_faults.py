"""The comparison that decides ``correct`` fails a broken timed path:
each fault that a cell can have (an answer altered where it is produced:
the watchlist's label, the embedder's embedding, the detector's crop;
half of a batch left out) is planted under a tiny run driven through
``run.main``, and ``correct`` must come out false.  One chip, so no
exchange between chips to leave out."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from repro.launch import serve


def _alter_answer(monkeypatch):
    """The watchlist's answer altered where it is produced: every label
    names the next row of its scope."""
    orig = serve.WatchlistCartridge.process_batch

    def wrong(self, ms):
        out = orig(self, ms)
        for m in out:
            head, _, i = m.payload["label"].rpartition("/")
            m.payload["label"] = f"{head}/{int(i) + 1}"
        return out
    monkeypatch.setattr(serve.WatchlistCartridge, "process_batch", wrong)


def _half_batch(monkeypatch):
    """Half of each batch left out of the search, its answers copied
    from the half that was searched."""
    orig = serve.WatchlistCartridge.process_batch

    def half(self, ms):
        keep = ms[: max(1, len(ms) // 2)]
        done = orig(self, keep)
        return [done[i % len(done)] if i >= len(keep) else done[i]
                for i in range(len(ms))]
    monkeypatch.setattr(serve.WatchlistCartridge, "process_batch", half)


def _alter_embedding(monkeypatch):
    """The embedder's output altered where it is produced: each
    embedding's elements shifted by one place."""
    orig = serve.make_embedder

    def make(key):
        cart = orig(key)
        inner = cart._user_fn
        cart._user_fn = lambda p, crop: jnp.roll(inner(p, crop), 1)
        return cart
    monkeypatch.setattr(serve, "make_embedder", make)


def _wrong_crop(monkeypatch):
    """The detector's crop taken at the frame's corner, wherever the
    face is."""
    orig = serve.make_detector

    def make(key):
        cart = orig(key)
        cart._user_fn = lambda p, img: jax.lax.dynamic_slice(
            img, (0, 0, 0), (64, 64, 3))
        return cart
    monkeypatch.setattr(serve, "make_detector", make)


# (cell, fault, a number it must fail, window seconds): the open-loop
# fleet window is long enough for batches of more than one frame to form
FAULTS = [
    ("frte.backfill", _alter_answer, "label_gap", 0.3),
    ("frte.backfill", _half_batch, "label_gap", 0.3),
    ("fleet.mixed", _alter_answer, "label_gap", 0.3),
    ("fleet.mixed", _half_batch, "label_gap", 1.5),
    ("fleet.mixed", _alter_embedding, "embed_cos", 0.3),
    ("fleet.mixed", _wrong_crop, "det_gap", 0.3),
]


@pytest.mark.parametrize("workload,fault,number,seconds", FAULTS,
                         ids=[f"{w}-{f.__name__[1:]}" for w, f, _, _ in
                              FAULTS])
def test_fault_makes_correct_false(workload, fault, number, seconds,
                                   monkeypatch, tmp_path, capsys):
    fault(monkeypatch)
    res = tiny.run_main(monkeypatch, tmp_path, capsys, [
        "--workload", workload, "--seed", "3", "--seconds", str(seconds),
        "--trace", "0"])
    assert res["correct"] is False
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]
