"""A configuration names the program's chain: its stages and embedding
width reach the program's builder, the closed loop carries camera frames
and records a seeded sample of their stage outputs, and a shared scope
takes planted subjects.  Driven through ``run.main`` at a tiny size on
the CPU, with only the look for a chip skipped."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

import tiny
import harness
import traffic
from repro.core import messages as msg
from repro.crypto import SecureGallery
from repro.launch import serve
from repro.runtime import CapabilityRegistry

DATA = tiny.BENCH / "tests" / "data"
SHARED = "shared-frames-d128"
MIX = "frames-closed"
WORKLOAD = "shared.frames"


def _named_builder(seed=0, n_shards=1, match_dtype="fp32",
                   match_mode="exact", tenant_scoped=False, stages=None,
                   dim=serve.EMB_DIM):
    """A builder that takes ``stages=`` and ``dim=``: a table of the
    program's stage makers by name, each stage's weights from its
    entry's key of ``split(PRNGKey(seed), 4)``."""
    makers = {"retinaface": serve.make_detector,
              "crfiqa": serve.make_quality,
              "facenet": serve.make_embedder}
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    reg = CapabilityRegistry()
    for slot, s in enumerate(stages):
        reg.insert(slot, makers[s["name"]](ks[s["key"]]))
    gallery = SecureGallery(dim, seed=7, n_shards=n_shards,
                            match_dtype=match_dtype)
    reg.insert(len(stages), serve.WatchlistCartridge(
        gallery, mode=match_mode, tenant_scoped=tenant_scoped))
    return reg, gallery


BUILDERS = {"builder_of_one_chain": None, "builder_of_named_stages":
            _named_builder}


def _use_builder(monkeypatch, which):
    if BUILDERS[which] is not None:
        monkeypatch.setattr(serve, "build_biometric_pipeline",
                            BUILDERS[which])


def _with_shared_cell(monkeypatch, tmp_path, stages=None) -> dict:
    """The tiny benchmark plus a cell of the test-only shared-scope
    configuration under a closed loop of frames."""
    b = tiny.bench(tmp_path)
    cfg = json.loads((DATA / "configs" / f"{SHARED}.json").read_text())
    if stages is not None:
        cfg["stages"] = stages
    path = tmp_path / f"{SHARED}.json"
    path.write_text(json.dumps(cfg))
    b["configs"].append({"name": SHARED, "source": "test", "file": str(path),
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": WORKLOAD, "config": SHARED,
                           "traffic": MIX, "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "ids_per_s":
            m["workloads"] = m["workloads"] + [WORKLOAD]
    monkeypatch.setattr(traffic, "MIX_DIR", DATA / "traffic")
    return b


def _stage_outputs(chain, frames) -> list:
    """Each stage's outputs for ``frames``, through its ``process_batch``."""
    for c in chain:
        c.load()
    ms = [msg.Message(msg.IMAGE_FRAME, i, jax.numpy.asarray(f))
          for i, f in enumerate(frames)]
    outs = []
    for stage in chain[:-1]:
        ms = stage.process_batch(ms)
        outs.append(np.stack([np.asarray(m.payload) for m in ms]))
    return outs


@pytest.mark.parametrize("builder", BUILDERS)
def test_fleet_config_builds_the_default_chain(builder, monkeypatch,
                                               tmp_path):
    """The chain built for ``fleet-1m-d128-bf16`` gives bit for bit the
    crops, quality-scaled crops and embeddings of the builder's
    defaults."""
    frames = [harness.frames.frame_at(11, i) for i in range(3)]
    default, _ = serve.build_biometric_pipeline(seed=5)
    want = _stage_outputs(default.chain(), frames)
    _use_builder(monkeypatch, builder)
    cell = harness.Cell("fleet.mixed", tiny.SEED, tiny.bench(tmp_path))
    chain, gallery = harness.build_biometric(serve, cell.cfg, 5, True)
    assert [c.name for c in chain] == [
        "retinaface", "crfiqa", "facenet", "watchlist_db"]
    assert gallery.dim == 128 and gallery.match_dtype == "bf16"
    got = _stage_outputs(chain, frames)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("builder", BUILDERS)
def test_shared_scope_of_frames_runs_correct(builder, monkeypatch,
                                             tmp_path, capsys):
    """A configuration with explicit stages, no tenants and planted
    subjects, under a closed loop of frames: the window's sample is the
    reservoir's for its seed and length, and its answers are correct."""
    _use_builder(monkeypatch, builder)
    b = _with_shared_cell(monkeypatch, tmp_path)
    seen = {}
    orig_sample = harness.Cell.sample

    def sample(self, out):
        idx = orig_sample(self, out)
        seen.update(n=out["n"], idx=idx.tolist(), rec=sorted(out["rec"]),
                    planted=len(self.mated[0]))
        return idx
    monkeypatch.setattr(harness.Cell, "sample", sample)
    res = tiny.run_main(monkeypatch, tmp_path, capsys, [
        "--workload", WORKLOAD, "--seed", str(tiny.SEED), "--seconds", "1.0",
        "--trace", "0"], benchmark=b)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"label_gap", "embed_cos", "det_gap"}
    assert res["attempted"] == seen["n"] and seen["n"] % 8 == 0
    # 10 planted subjects, 4 captures each, all in the one scope
    assert seen["planted"] == 40
    # the same seed and length pick the same sample: the reservoir's
    ref = harness.Reservoir(6, harness.derive(tiny.SEED, 4))
    for base in range(0, seen["n"], 8):
        ref.offer(base, 8)
    assert seen["idx"] == seen["rec"] == ref.sample()
    assert len(seen["idx"]) == 6


def test_wrong_embedder_key_makes_correct_false(monkeypatch, tmp_path,
                                                capsys):
    """The program's embedder drawn from another key than the
    configuration's: the embeddings leave the reference's."""
    orig = serve.make_embedder
    monkeypatch.setattr(serve, "make_embedder",
                        lambda key: orig(jax.random.fold_in(key, 1)))
    b = _with_shared_cell(monkeypatch, tmp_path)
    res = tiny.run_main(monkeypatch, tmp_path, capsys, [
        "--workload", WORKLOAD, "--seed", "3", "--seconds", "0.3",
        "--trace", "0"], benchmark=b)
    assert res["correct"] is False
    c = res["checks"]["embed_cos"]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("stages,named", [
    ([{"name": "retinaface", "key": 0}, {"name": "crfiqa", "key": 1},
      {"name": "arcface_r100", "key": 2}], "arcface_r100"),
    ([{"name": "retinaface", "key": 0}, {"name": "facenet", "key": 2}],
     "facenet"),
    ([{"name": "retinaface", "key": 0}, {"name": "crfiqa", "key": 1},
      {"name": "facenet", "key": 3}], "facenet"),
])
def test_stage_the_program_does_not_build_fails_naming_it(
        stages, named, monkeypatch, tmp_path):
    b = _with_shared_cell(monkeypatch, tmp_path, stages=stages)
    cell = harness.Cell(WORKLOAD, 1, b)
    with pytest.raises(ValueError, match=f"stage '{named}'"):
        cell.build()


# the templates sample of the closed loop, as drawn before the closed
# loop carried frames: (seed, answered) -> first six indices, their sum
BACKFILL_SAMPLES = [
    (2 ** 31 + 12345, 1024 * 37, [111, 214, 273, 310, 489, 583], 9850314),
    (3150000001, 1024 * 37, [67, 91, 132, 255, 268, 275], 9979411),
]


@pytest.mark.parametrize("seed,answered,head,total", BACKFILL_SAMPLES)
def test_backfill_sample_is_unchanged(seed, answered, head, total):
    cell = harness.Cell("frte.backfill", seed)
    idx = cell.sample({"answered": answered, "keep": []})
    assert len(idx) == 512 and np.all(np.diff(idx) > 0)
    assert idx[:6].tolist() == head and int(idx.sum()) == total


def test_reservoir_is_fixed_by_its_seed_and_length():
    def pick(seed, n, mb=8, k=20):
        r = harness.Reservoir(k, seed)
        kept = set()
        for base in range(0, n, mb):
            kept = (kept & set(r.seq.tolist())) | r.offer(base, mb)
        assert kept == set(r.sample())
        return r.sample()
    a, b = pick(9, 400), pick(9, 400)
    assert a == b and len(a) == 20 and len(set(a)) == 20
    assert pick(10, 400) != a
    # the k lowest priorities: a longer window keeps what it does not
    # displace, and displaces only for later requests
    longer = pick(9, 800)
    assert {s for s in longer if s < 400} <= set(a)
    assert pick(9, 8, k=20) == list(range(8))
