"""The plain reference and the comparison that decides ``correct``.

Nothing here imports the program or takes anything it made.  The
watchlist reference is exact cosine top-1 in numpy over the raw
templates the benchmark enrolled; the stage references live in
``stages/<name>.py``, draw their own weights from the seed, and run at
the precision the configuration states.

Every answer is judged by what it says, teacher-forced at each
discrete choice, as a served token is judged by its logit:

- ``det_gap``: how far below the reference's best crop the program's
  crop lies in the reference heat map (relative), the widest over the
  sample, or its mean (``det_gap_mean``);
- ``embed_err``, ``embed_cos``: the widest element gap, and the widest
  cosine distance, between the program's embedding and the reference
  stages run on the program's crop (``embed_cos_mean``: the mean
  cosine distance);
- ``label_gap``: how far below the reference's best cosine in the
  request's scope the reference cosine of the program's label lies;
- ``score_err``: how far the program's score lies from the reference
  cosine of its label.

``CompileCounter`` is copied from ``chip_smoke.py``.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

STAGE_DIR = Path(__file__).resolve().parent / "stages"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
ROW_BLOCK = 65536
QUANT_LEVELS = {"int8": 127, "int4": 7}
NO_ANSWER = 2.0
# the numbers a check can compare: (per-request reading, reduction over
# the sample); a configuration's ``limits`` says which it compares
READINGS = {
    "det_gap": ("det_gap", np.max),
    "det_gap_mean": ("det_gap", np.mean),
    "embed_err": ("embed_err", np.max),
    "embed_cos": ("embed_cos", np.max),
    "embed_cos_mean": ("embed_cos", np.mean),
    "label_gap": ("label_gap", np.max),
    "score_err": ("score_err", np.max),
}


def worst(per_request: dict) -> dict:
    """Every number of ``READINGS`` that ``per_request`` can give."""
    return {name: float(fn(per_request[key]))
            for name, (key, fn) in READINGS.items() if key in per_request}


class CompileCounter:
    """Programs JAX compiled while the block ran, and how many of them
    came from the persistent compile cache, from JAX's monitoring
    events."""

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


def load_stage(name: str):
    path = STAGE_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_stage_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _keep(x):
    return x


def _fp8(x):
    """``x`` rounded to fp8 e4m3 with one scale for the tensor (its
    largest magnitude at the format's largest value), back in f32."""
    x = jnp.asarray(x, jnp.float32)
    top = float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# the rounding of every conv and matmul input: none at the precision the
# configuration states, fp8 in the control one step below it
CASTS = {"stated": _keep, "fp8": _fp8}


class StageReference:
    """The reference of a configuration's stage chain, its weights drawn
    from ``seed`` with the same key split as the biometric pipeline, at
    the precision the configuration states (XLA's default)."""

    def __init__(self, stages: list, seed: int):
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        self.names = [s["name"] for s in stages]
        self.mods = [load_stage(n) for n in self.names]
        self.params = [m.params(keys[s["key"]])
                       for m, s in zip(self.mods, stages)]
        self._maps = {}

    def _map_chain(self, start: int, cast: str):
        """The map stages from ``start`` to the end, jitted, their
        inputs rounded by ``CASTS[cast]``."""
        if (start, cast) not in self._maps:
            mods, params = self.mods[start:], self.params[start:]

            # the weights are arguments, not constants, so that one
            # compiled program serves every seed
            @jax.jit
            def run(params, x):
                for m, p in zip(mods, params):
                    x = m.apply(p, x, CASTS[cast])
                return x.astype(jnp.float32)
            self._maps[(start, cast)] = functools.partial(run, params)
        return self._maps[(start, cast)]

    def embed(self, frame) -> np.ndarray:
        """The whole chain on one frame: the embedding it enrolls."""
        x = np.asarray(frame)
        for i, (m, p) in enumerate(zip(self.mods, self.params)):
            if m.KIND == "choice":
                x = m.apply(p, x, _keep)
            else:
                return np.asarray(self._map_chain(i, "stated")(
                    jnp.asarray(x)))
        return x

    def numbers(self, frames, choices, embeds, control: str = "") -> dict:
        """Per request: the choice stage's gap, and the embedding's widest
        element gap and cosine distance from the reference run on the
        same crop.  ``choices``/``embeds`` are the program's crops and
        embeddings; with ``control`` (a key of ``CASTS``) the reference
        with that rounding takes the program's place."""
        ci = next(i for i, m in enumerate(self.mods) if m.KIND == "choice")
        det = self.mods[ci]
        heat = jax.jit(lambda w, x: det.heat(w, x, _keep))
        ref_map = self._map_chain(ci + 1, "stated")
        gaps, errs, coss = [], [], []
        for frame, crop, emb in zip(frames, choices, embeds):
            h = np.asarray(heat(self.params[ci], jnp.asarray(frame)))
            if control:
                crop = det.apply(self.params[ci], frame, CASTS[control])
                emb = np.asarray(self._map_chain(ci + 1, control)(
                    jnp.asarray(crop)))
            gaps.append(det.gap(h, frame, crop))
            ref = np.asarray(ref_map(jnp.asarray(crop)), np.float64)
            emb = np.asarray(emb, np.float64)
            errs.append(float(np.abs(emb - ref).max()))
            coss.append(1.0 - float(emb @ ref) / max(
                float(np.linalg.norm(emb) * np.linalg.norm(ref)), 1e-30))
        return {"det_gap": np.asarray(gaps), "embed_err": np.asarray(errs),
                "embed_cos": np.asarray(coss)}


def _quantize(rows: np.ndarray, kind: str) -> np.ndarray:
    """``rows`` stored as ``kind`` with a scale per row (symmetric
    integer levels, or fp8 e4m3 with the row's largest magnitude at the
    format's largest value), returned dequantized."""
    amax = np.maximum(np.abs(rows).max(axis=1, keepdims=True), 1e-12)
    if kind == "fp8":
        scale = amax / float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)
        return (rows / scale).astype(ml_dtypes.float8_e4m3fn).astype(
            np.float32) * scale
    levels = QUANT_LEVELS[kind]
    scale = amax / levels
    return np.clip(np.round(rows / scale), -levels, levels) * scale


def watchlist_numbers(queries, labels, scores, rows, prefix: str,
                      control: str = "") -> dict:
    """``label_gap`` and ``score_err`` of each answer over one scope.

    ``rows`` are the scope's raw templates (L2-normalized, row ``i``
    enrolled as ``f"{prefix}/{i}"``).  With ``control`` ("int8", "int4",
    "fp8"), the answer is the top-1 of the rows stored per row in that
    format, taken in the program's place."""
    q = np.asarray(queries, np.float64)
    q /= np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    q32 = q.astype(np.float32)
    n = len(rows)
    best_s = np.full(len(q), -np.inf)
    best_i = np.zeros(len(q), np.int64)
    ctl_s = np.full(len(q), -np.inf)
    ctl_i = np.zeros(len(q), np.int64)
    for lo in range(0, n, ROW_BLOCK):
        blk = rows[lo:lo + ROW_BLOCK]
        s = q32 @ blk.T
        j = s.argmax(axis=1)
        v = s[np.arange(len(q)), j]
        up = v > best_s
        best_s[up], best_i[up] = v[up], lo + j[up]
        if control:
            sc = q32 @ _quantize(blk, control).T.astype(
                np.float32)
            j = sc.argmax(axis=1)
            v = sc[np.arange(len(q)), j]
            up = v > ctl_s
            ctl_s[up], ctl_i[up] = v[up], lo + j[up]
    if control:
        chosen, scores = ctl_i, ctl_s
    else:
        chosen = np.array([_row_of(lab, prefix, n) for lab in labels])
    exact_best = np.einsum("qd,qd->q", q, rows[best_i].astype(np.float64))
    ok = chosen >= 0
    exact = np.full(len(q), -np.inf)
    exact[ok] = np.einsum("qd,qd->q", q[ok],
                          rows[chosen[ok]].astype(np.float64))
    # a label outside the scope reads 2, the widest gap two cosines have
    gap = np.where(ok, np.maximum(exact_best - exact, 0.0), NO_ANSWER)
    err = np.where(ok, np.abs(np.asarray(scores, np.float64) - exact),
                   NO_ANSWER)
    return {"label_gap": gap, "score_err": err}


def _row_of(label, prefix: str, n: int) -> int:
    """The scope row an answer's label names; -1 for a label outside the
    scope (another tenant's row, or none)."""
    if not isinstance(label, str) or "/" not in label:
        return -1
    head, _, idx = label.rpartition("/")
    if head != prefix or not idx.isdigit() or int(idx) >= n:
        return -1
    return int(idx)
