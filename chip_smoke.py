"""Chip smoke test: the fleet-serving path on a TPU at a 10^6-template
watchlist.

    python chip_smoke.py              # one chip: four serving phases
    python chip_smoke.py --chips 4    # four chips: the sharded watchlist

One chip.  Builds the ``serve.py --mode fleet`` pipeline (detector ->
quality -> embedder -> watchlist, random weights from ``--seed``), enrolls
each tenant's planted subjects the way ``build_fleet`` does, and fills the
watchlist to 10^6 templates with seeded distractors enrolled under the
three fleet tenants (a tenant-scoped match scans only its own rows).  It
then serves ``DURATION_S`` seconds of the fleet's traffic through
``serve_fleet`` four times: exact fp32, exact bf16, exact int8, and ANN
int8.  Every answered frame is checked against a host reference (numpy
cosine top-1 over the tenant's raw embeddings); every phase must lose no
frame; and the compiled text of one served match call must hold the Mosaic
kernel (``tpu_custom_call``), not an interpreted one.

Four chips (``--chips 4``).  The same watchlist split into four shards,
exact int8: served once with shard ``s`` on device ``s``, and once with
every shard on device 0.  Both must give the same labels, scores within
the int8 tolerance, and agree with the host reference.

Each phase prints its wall time (a smoke timing, not a benchmark metric),
the programs compiled during serving (and how many of them the persistent
compile cache supplied) and the device's peak memory.  The last
line of standard output is one JSON object naming the device.  Any failed
check raises, so the exit code is non-zero and no result line is printed.
The script refuses to run anywhere but on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.kernels import ops as K  # noqa: E402
from repro.launch import serve  # noqa: E402

N_TEMPLATES = 1_000_000          # NIST FRTE 1:N galleries: 640K-12M
DURATION_S = 3.0                 # serve_fleet's seconds of offered traffic
PHASES = (("fp32", "exact"), ("bf16", "exact"), ("int8", "exact"),
          ("int8", "ann"))
# Score tolerance per match dtype, against the fp32 host reference.
# fp32: full-precision MXU passes, so f32 rounding only.  bf16: query and
# gallery each rounded to 8 significant bits, ~1.2e-3 worst case on the
# planted subjects.  int8: per-row quantization step amax/127, ~1.2e-3
# worst case, plus the query's bf16 rounding in the MXU pass.
SCORE_TOL = {"fp32": 1e-4, "bf16": 4e-3, "int8": 5e-3}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def _require(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


class CompileCounter:
    """Programs JAX compiled while the block ran, and how many of them
    came from the persistent compile cache, from JAX's monitoring events.
    JAX reports a compile for every program it builds; a cache hit builds
    it from the cache instead of running XLA."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def _on_duration(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration_secs

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def build_watchlist(n_templates: int, seed: int, n_shards: int = 1):
    """The fleet pipeline with ``n_templates`` enrolled in all.

    Returns ``(fleet, ref)``: ``fleet`` is ``build_fleet``'s tuple, and
    ``ref[tenant]`` is ``(names, rows)``, the tenant's labels and its
    L2-normalized raw embeddings (planted subjects first), kept on the
    host for the reference."""
    fleet = serve.build_fleet(seed=seed, n_shards=n_shards)
    reg, gallery, src, tenant_base = fleet
    tenants = [t.name for t in serve.FLEET_TENANTS]
    n_planted = len(gallery)
    _require(n_templates > n_planted,
             f"{n_templates} templates cannot hold {n_planted} planted")
    rng = np.random.default_rng(seed)
    distractors = rng.standard_normal((n_templates - n_planted,
                                       serve.EMB_DIM), dtype=np.float32)
    ref = {}
    for name, rows in zip(tenants, np.array_split(distractors,
                                                  len(tenants))):
        base = tenant_base[name]
        planted = serve._pipeline_embed(reg, src, range(base, base + 10))
        labels = [f"{name}/distractor{j}" for j in range(len(rows))]
        gallery.enroll(rows, labels, tenant=name)
        names = np.asarray([f"{name}/subject{j}" for j in range(10)]
                           + labels, object)
        raw = np.concatenate([planted, rows]).astype(np.float32)
        ref[name] = (names, raw / np.linalg.norm(raw, axis=1,
                                                 keepdims=True))
    _require(len(gallery) == n_templates,
             f"watchlist holds {len(gallery)}, not {n_templates}")
    return fleet, ref


def record_matches(gallery) -> list:
    """Record every ``gallery.match`` call the served path makes:
    ``(tenant, raw queries, top-1 labels, top-1 scores)``."""
    calls = []
    match = gallery.match

    def recording(raw_queries, *args, **kwargs):
        labels, scores = match(raw_queries, *args, **kwargs)
        calls.append((kwargs.get("tenant"), np.asarray(raw_queries),
                      np.asarray(labels)[:, 0], np.asarray(scores)[:, 0]))
        return labels, scores

    gallery.match = recording
    return calls


def check_answers(calls: list, ref: dict, tol: float) -> dict:
    """Hold every answered frame to the host reference: numpy cosine
    top-1 over the tenant's raw embeddings.  The answer's label must be
    the reference's, or one whose reference score is within ``tol`` of
    the reference top-1 (the stand-in embedder puts distinct subjects at
    cosines up to ~0.9996, closer than bf16 or int8 can resolve); its
    score must be within ``tol`` of its reference score."""
    index = {t: {n: i for i, n in enumerate(names)}
             for t, (names, _) in ref.items()}
    n = same = 0
    worst = 0.0
    for tenant, q, labels, scores in calls:
        names, rows = ref[tenant]
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        sims = qn.astype(np.float32) @ rows.T
        for r, (label, score) in enumerate(zip(labels, scores)):
            best = int(np.argmax(sims[r]))
            got = index[tenant].get(label)
            _require(got is not None,
                     f"{tenant}: answer {label!r} is not in its watchlist")
            _require(sims[r, got] >= sims[r, best] - tol,
                     f"{tenant}: answered {label!r} ({sims[r, got]:.6f}), "
                     f"reference {names[best]!r} ({sims[r, best]:.6f})")
            err = abs(float(score) - float(sims[r, got]))
            _require(err <= tol, f"{tenant}: score {score:.6f} is {err:.2e}"
                     f" from the reference {sims[r, got]:.6f}")
            n += 1
            same += int(got == best)
            worst = max(worst, err)
    return {"answered": n, "same_label": same, "max_score_err": worst}


def serve_phase(fleet, ref, dtype: str, mode: str,
                duration_s: float = DURATION_S) -> dict:
    """Serve the fleet traffic once with the watchlist matching in
    ``dtype``/``mode`` and check every answer; returns the phase's
    counts and the frames' answers."""
    reg, gallery, src, tenant_base = fleet
    gallery.match_dtype = dtype
    reg.slots[3].cartridge.mode = mode
    calls = record_matches(gallery)
    t0 = time.perf_counter()
    try:
        rep = serve.serve_fleet(reg, src, tenant_base, duration_s)
    finally:
        del gallery.match                       # drop the recorder
    wall_s = time.perf_counter() - t0
    _require(rep.frames_in > 0, "no frame was admitted")
    _require(rep.frames_out == rep.frames_in and rep.lost == 0,
             f"{dtype}/{mode}: frames {rep.frames_out}/{rep.frames_in}, "
             f"lost {rep.lost}")
    out = check_answers(calls, ref, SCORE_TOL[dtype])
    _require(out["answered"] == rep.frames_out,
             f"{dtype}/{mode}: {out['answered']} answers for "
             f"{rep.frames_out} frames")
    out.update(frames_in=rep.frames_in, frames_out=rep.frames_out,
               lost=rep.lost, wall_s=wall_s, calls=calls)
    return out


def served_kernel_text(fleet, ref) -> str:
    """Compiled text of the match call that one served frame made (exact
    int8, the deployment dtype): the first call's arguments, lowered and
    compiled again."""
    seen = []
    jitted = K.gallery_match_quant

    def capture(*args, **kwargs):
        if not seen:
            seen.append((args, kwargs))
        return jitted(*args, **kwargs)

    K.gallery_match_quant = capture
    try:
        serve_phase(fleet, ref, "int8", "exact", duration_s=0.2)
    finally:
        K.gallery_match_quant = jitted
    _require(seen, "the served path made no int8 match call")
    args, kwargs = seen[0]
    return jitted.lower(*args, **kwargs).compile().as_text()


def _place_on_one_chip(gallery, device):
    """Move every shard's prepared view to ``device``: the same gallery,
    with all of its shards on one chip."""
    for prep in gallery._prep:
        for key, val in prep.items():
            if isinstance(val, jax.Array):
                prep[key] = jax.device_put(val, device)


def sharded_phase(n_templates: int, seed: int, n_shards: int = 4,
                  duration_s: float = DURATION_S) -> dict:
    """Exact int8 over ``n_shards`` shards, served with shard ``s`` on
    device ``s mod count`` and again with every shard on device 0; the
    answers must agree frame by frame."""
    fleet, ref = build_watchlist(n_templates, seed, n_shards)
    gallery = fleet[1]
    spread = serve_phase(fleet, ref, "int8", "exact", duration_s)
    placed = {str(next(iter(p["gn"].devices()))) for p in gallery._prep}
    _require(len(placed) == min(n_shards, jax.device_count()),
             f"{n_shards} shards sit on devices {sorted(placed)}")
    _place_on_one_chip(gallery, jax.devices()[0])
    one = serve_phase(fleet, ref, "int8", "exact", duration_s)
    _require(len(spread["calls"]) == len(one["calls"]),
             "the two placements served different traffic")
    worst = 0.0
    for a, b in zip(spread["calls"], one["calls"]):
        _require(a[0] == b[0] and np.array_equal(a[1], b[1]),
                 "the two placements matched different queries")
        _require(np.array_equal(a[2], b[2]),
                 f"labels differ across placements: {a[2]} vs {b[2]}")
        worst = max(worst, float(np.abs(a[3] - b[3]).max()))
    _require(worst <= SCORE_TOL["int8"],
             f"scores differ by {worst:.2e} across placements")
    return {"spread": spread, "one_chip": one, "devices": sorted(placed),
            "max_placement_score_diff": worst}


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def _compiles(n, secs, hits) -> str:
    return f"compiles {n} ({secs:.2f} s), {hits} from the persistent cache"


def _phase_line(tag, out, compiles=None) -> str:
    line = (f"[{tag}] frames {out['frames_out']}/{out['frames_in']} "
            f"lost {out['lost']} reference labels "
            f"{out['same_label']}/{out['answered']} max |score err| "
            f"{out['max_score_err']:.3e} | smoke wall {out['wall_s']:.2f} s"
            f" (not a metric)")
    if compiles is not None:
        line += f" | {_compiles(*compiles)}"
    return f"{line} | peak_bytes_in_use {_peak_bytes()}"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded watchlist across chips")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (backend "
                         f"{backend!r}); refusing to run")
    serve.use_compile_cache()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x{jax.device_count()}",
          flush=True)
    with CompileCounter() as counter:
        t0 = time.perf_counter()
        if args.chips == 4:
            _require(jax.device_count() == 4,
                     f"--chips 4 sees {jax.device_count()} devices")
            out = sharded_phase(N_TEMPLATES, args.seed)
            for tag, res in (("int8/exact 4 shards on 4 chips", "spread"),
                             ("int8/exact 4 shards on 1 chip", "one_chip")):
                print(_phase_line(tag, out[res]))
            print(f"[sharded] devices {out['devices']} labels identical, "
                  f"max score diff {out['max_placement_score_diff']:.3e}")
        else:
            fleet, ref = build_watchlist(N_TEMPLATES, args.seed)
            print(f"[setup] {N_TEMPLATES} templates enrolled, smoke wall "
                  f"{time.perf_counter() - t0:.2f} s (not a metric) | "
                  f"{_compiles(*counter.snapshot())} | peak_bytes_in_use "
                  f"{_peak_bytes()}", flush=True)
            for dtype, mode in PHASES:
                if mode == "ann":
                    t = time.perf_counter()
                    fleet[1].build_ann_index(seed=args.seed)
                    print(f"[setup] ANN index built, smoke wall "
                          f"{time.perf_counter() - t:.2f} s (not a metric)",
                          flush=True)
                c0 = counter.snapshot()
                out = serve_phase(fleet, ref, dtype, mode)
                c1 = counter.snapshot()
                print(_phase_line(f"{dtype}/{mode}", out,
                                  [b - a for a, b in zip(c0, c1)]),
                      flush=True)
            text = served_kernel_text(fleet, ref)
            _require("tpu_custom_call" in text,
                     "the served match call holds no Mosaic kernel")
            print("[kernel] served int8 match compiles to tpu_custom_call")
        print(f"[total] {_compiles(*counter.snapshot())}, smoke wall "
              f"{time.perf_counter() - t0:.2f} s (not a metric)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
