"""One cell of the benchmark: build the deployment a configuration file
describes, make its traffic from the seed, warm every shape, drive the
timed window through the cartridges' own ``process_batch``, and check
what the window produced against the plain reference.

The window is the lane loop the engine runs on each service cycle when
it executes payloads (``StreamEngine._try_start_lane``): it drains
whatever has arrived, up to the mix's ``max_batch``, and runs each
stage's ``process_batch`` on that micro-batch in slot order, ending in
the watchlist stage.  A request is timed from when it was due to the
return of the watchlist stage.  The engine's own dispatch and the front
door run on a virtual clock, so they stay out of the window.

Everything that belongs to one configuration, one mix or one per-layer
metric is a file found by its name: ``configs/<config>.json``,
``traffic/<mix>.json``, ``metrics/<metric>.py`` and ``stages/<stage>.py``.
"""
from __future__ import annotations

import contextlib
import faulthandler
import functools
import gc
import importlib.util
import json
import re
import resource
import sys
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import frames
import reference
import traffic

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
METRIC_DIR = BENCH / "metrics"
ROW_BLOCK = 131072                 # gallery rows generated per device call
STALL_S = 0.8                      # a window cycle this long prints stacks


def derive(seed: int, *stream: int) -> int:
    """A 31-bit seed for one use of the run's seed (``--seed`` may exceed
    what a 32-bit key holds)."""
    ss = np.random.SeedSequence([seed % 2 ** 64, *stream])
    return int(ss.generate_state(1)[0] >> 1)


def load_benchmark(path: Path = CHECKOUT / "BENCHMARK.json") -> dict:
    return json.loads(path.read_text())


def find_cell(bench: dict, workload: str):
    """``(cell, config entry, config, mix)`` of the named workload."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((CHECKOUT / entry["file"]).read_text())
    return cell, entry, cfg, traffic.load_mix(cell["traffic"])


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_metric(name: str):
    path = METRIC_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gaussian_rows(seed: int, n: int, d: int) -> np.ndarray:
    """``n`` L2-normalized Gaussian rows of width ``d``: block ``b`` is
    drawn from ``fold_in(key(seed), b)``, so any block can be made
    again on its own."""
    key = jax.random.PRNGKey(seed)
    out = np.empty((n, d), np.float32)
    for b, lo in enumerate(range(0, n, ROW_BLOCK)):
        blk = np.asarray(_unit_rows(key, b, d))
        out[lo:lo + ROW_BLOCK] = blk[: n - lo]
    return out


@functools.partial(jax.jit, static_argnums=2)
def _unit_rows(key, b, d):
    g = jax.random.normal(jax.random.fold_in(key, b), (ROW_BLOCK, d))
    return g / jnp.linalg.norm(g, axis=1, keepdims=True)


def probes_near(rows: np.ndarray, idx: np.ndarray, cos: float,
                rng: np.random.Generator) -> np.ndarray:
    """Mated probes: row ``idx`` plus isotropic noise sized so that the
    expected cosine to the row is ``cos``."""
    d = rows.shape[1]
    noise = rng.standard_normal((len(idx), d)).astype(np.float32)
    noise /= np.sqrt(d)
    return rows[idx] + np.float32(np.sqrt(1.0 / cos ** 2 - 1.0)) * noise


@contextlib.contextmanager
def stall_watch(seconds: float = STALL_S):
    """Print every thread's Python stack to standard error if the block
    runs longer than ``seconds``: where the host was during a stall."""
    faulthandler.dump_traceback_later(seconds, file=sys.__stderr__)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def host_cpu_s() -> float:
    """This process's CPU seconds so far, all threads: over a slow cycle,
    near its wall time says the host computed, far below it says the
    host waited."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


class GcPauses:
    """A ``gc.callbacks`` entry that times each collection, by
    generation."""

    def __init__(self):
        self.t0, self.pauses = None, {0: [], 1: [], 2: []}

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            self.pauses[info["generation"]].append(
                time.perf_counter() - self.t0)

    def summary(self) -> dict:
        return {g: (len(p), sum(p), max(p, default=0.0))
                for g, p in self.pauses.items()}


class Scope:
    """The rows one match can see: a tenant's, or the whole watchlist."""

    def __init__(self, tenant, rows: np.ndarray):
        self.tenant = tenant
        self.prefix = tenant if tenant is not None else "g"
        self.rows = rows

    def labels(self) -> list:
        return [f"{self.prefix}/{i}" for i in range(len(self.rows))]


class Cell:
    """One workload of ``BENCHMARK.json``, built for one seed."""

    def __init__(self, workload: str, seed: int, bench: dict = None):
        bench = bench or load_benchmark()
        self.bench = bench
        self.workload = workload
        self.cell, self.entry, self.cfg, self.mix = find_cell(bench, workload)
        self.seed = seed
        self.trace = False
        self.probe_cfg = self.cfg.get("probes", {})

    # -- set-up ---------------------------------------------------------------
    def build(self):
        """The program's chain for this configuration, with the
        benchmark's own templates enrolled."""
        from repro.crypto import SecureGallery
        from repro.launch import serve
        cfg = self.cfg
        tenants = cfg.get("tenants") or []
        self.tenants = tenants
        prog_seed = derive(self.seed, 0)
        if cfg["pipeline"] == "biometric":
            reg, gallery = serve.build_biometric_pipeline(
                seed=prog_seed, n_shards=cfg.get("n_shards", 1),
                match_dtype=cfg["match_dtype"],
                match_mode=cfg.get("match_mode", "exact"),
                tenant_scoped=bool(tenants))
            chain = reg.chain()
        else:
            gallery = SecureGallery(cfg["dim"], n_shards=cfg.get("n_shards", 1),
                                    match_dtype=cfg["match_dtype"])
            chain = [serve.WatchlistCartridge(
                gallery, mode=cfg.get("match_mode", "exact"),
                tenant_scoped=bool(tenants))]
        for c in chain:
            c.load()
        self.chain, self.gallery = chain, gallery
        self.stage_ref = None
        if cfg.get("stages"):
            self.stage_ref = reference.StageReference(cfg["stages"],
                                                      prog_seed)
        self._enroll()
        self._make_bank()

    def _enroll(self):
        cfg = self.cfg
        n, d = cfg["n_templates"], cfg["dim"]
        frame_seed = derive(self.seed, 1)
        self.frame_seed = frame_seed
        if not self.tenants:
            self.scopes = [Scope(None, gaussian_rows(derive(self.seed, 2),
                                                     n, d))]
        else:
            planted = cfg["planted_per_tenant"]
            rest = gaussian_rows(derive(self.seed, 2),
                                 n - planted * len(self.tenants), d)
            self.scopes = []
            for t, (name, rows) in enumerate(zip(
                    self.tenants, np.array_split(rest, len(self.tenants)))):
                subj = np.stack([self.stage_ref.embed(frames.frame_at(
                    frame_seed, t * planted + j)) for j in range(planted)])
                self.scopes.append(Scope(name, np.concatenate(
                    [subj.astype(np.float32), rows])))
        for s in self.scopes:
            self.gallery.enroll(s.rows, s.labels(), tenant=s.tenant)

    def _make_bank(self):
        """The payloads requests draw from: camera frames of enrolled
        subjects (fresh captures) and of strangers, or probe templates
        near enrolled rows and away from them."""
        p = self.probe_cfg
        rng = np.random.default_rng(derive(self.seed, 3))
        self.mated, self.strangers = [], []
        if self.mix["payload"] == "frames":
            planted, shots = self.cfg["planted_per_tenant"], p["captures"]
            bank = []
            for t in range(len(self.scopes)):
                ids = []
                for j in range(planted):
                    for shot in range(1, shots + 1):
                        ids.append(len(bank))
                        bank.append(frames.capture(self.frame_seed,
                                                   t * planted + j, shot,
                                                   p["capture_noise"]))
                self.mated.append(np.asarray(ids))
            base = planted * len(self.scopes) + 10_000
            first = len(bank)
            bank += [frames.frame_at(self.frame_seed, base + s)
                     for s in range(p["strangers"])]
            self.strangers = [np.arange(first, len(bank))] * len(self.scopes)
            self.bank = np.stack(bank)
        else:
            half = p["bank"] // 2
            bank = []
            for s in self.scopes:
                idx = rng.integers(0, len(s.rows), half)
                self.mated.append(np.arange(len(bank) * half,
                                            (len(bank) + 1) * half))
                bank.append(probes_near(s.rows, idx, p["genuine_cos"], rng))
            for s in self.scopes:
                self.strangers.append(np.arange(len(bank) * half,
                                                (len(bank) + 1) * half))
                g = rng.standard_normal((half, s.rows.shape[1]))
                bank.append((g / np.linalg.norm(g, axis=1, keepdims=True))
                            .astype(np.float32))
            self.bank = np.concatenate(bank)

    # -- requests -------------------------------------------------------------
    def entry_stages(self):
        """The stages a request passes: all, or from the watchlist stage
        on for template payloads."""
        return self.chain if self.mix["payload"] == "frames" \
            else self.chain[-1:]

    def items(self, tenant, mated, pick) -> np.ndarray:
        out = np.empty(len(tenant), np.int64)
        for r, (t, m, p) in enumerate(zip(tenant, mated, pick)):
            pool = self.mated[t] if m else self.strangers[t]
            out[r] = pool[p % len(pool)]
        return out

    def _requests(self, n: int, stream: int):
        tenant, mated, pick = traffic.choose(
            self.mix, n, self.seed, self.tenants or [None],
            self.probe_cfg.get("mate_share", 0.5), stream)
        return tenant, self.items(tenant, mated, pick)

    def _messages(self, reqs, tenant, item):
        """The cycle's requests as messages.  Camera frames are put on
        the device here, in a span of their own, so that the stage spans
        hold the stages' work and not the frames' transfer."""
        from repro.core import messages as msg
        data = [self.bank[item[r]] for r in reqs]
        kind = msg.EMBEDDING
        if self.mix["payload"] == "frames":
            kind = msg.IMAGE_FRAME
            with self._span("lane.h2d"):
                data = jax.block_until_ready(jax.device_put(data))
        names = self.tenants or [None]
        return [msg.Message(kind, int(r), x, {"tenant": names[tenant[r]]})
                for r, x in zip(reqs, data)]

    def _span(self, name: str):
        if self.trace:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def _cycle(self, reqs, tenant, item, keep=None, rec=None, times=None):
        """One service cycle over requests ``reqs``: every stage's
        ``process_batch`` in slot order.  Returns the watchlist stage's
        messages; stage outputs of requests in ``keep`` go to ``rec``,
        and each stage's seconds to ``times``."""
        ms = self._messages(reqs, tenant, item)
        for stage in self.entry_stages():
            t = time.perf_counter()
            with self._span(f"stage.{stage.name}"):
                ms = stage.process_batch(ms)
            if times is not None:
                times[stage.name] = time.perf_counter() - t
            if keep:
                for m in ms:
                    if m.seq in keep:
                        rec.setdefault(m.seq, {})[stage.name] = m.payload
        return ms

    def _record(self, cycles, frames, times, reqs, tenant, used):
        cycles.append({"frames": frames, "stage_s": times,
                       "work": self._work(reqs, tenant),
                       "cpu_s": host_cpu_s() - used})

    def _work(self, reqs, tenant) -> list:
        """The searches one cycle needs: one per tenant scope present."""
        d, dtype = self.cfg["dim"], self.cfg["match_dtype"]
        counts = np.bincount(np.asarray(tenant)[np.asarray(reqs)],
                             minlength=len(self.scopes))
        return [(int(q), len(self.scopes[t].rows), d, dtype, 1)
                for t, q in enumerate(counts) if q]

    def warm(self):
        """Every shape the window uses: each scope at every batch size
        up to ``max_batch`` (open loop), or the closed loop's batch."""
        mb = self.mix["max_batch"]
        sizes = range(1, mb + 1) if self.mix["loop"] == "open" else [mb]
        for t in range(len(self.scopes)):
            for q in sizes:
                tenant = np.full(q, t)
                item = self.items(tenant, np.arange(q) % 2 == 0,
                                  np.arange(q))
                ms = self._cycle(range(q), tenant, item)
                jax.block_until_ready([m.payload["score"] for m in ms])

    # -- the window -----------------------------------------------------------
    def run_open(self, seconds: float) -> dict:
        due = traffic.open_schedule(self.mix, seconds, self.seed)
        n = len(due)
        tenant, item = self._requests(n, 0)
        rng = np.random.default_rng(derive(self.seed, 4))
        keep = set(rng.choice(n, min(n, self.mix["check_sample"]),
                              replace=False).tolist())
        done = np.full(n, np.nan)
        labels, scores = [None] * n, np.zeros(n)
        rec, cycles, late = {}, [], []
        mb = self.mix["max_batch"]
        queue, i = deque(), 0
        clock = time.perf_counter
        with self._span("bench.window"):
            t0 = clock()
            while i < n or queue:
                now = clock() - t0
                while i < n and due[i] <= now:
                    queue.append(i)
                    i += 1
                if not queue:
                    with self._span("lane.wait"):
                        time.sleep(max(due[i] - now, 0.0))
                    late.append(clock() - t0 - due[i])
                    continue
                reqs = [queue.popleft() for _ in range(min(len(queue), mb))]
                times, used = {}, host_cpu_s()
                with stall_watch():
                    ms = self._cycle(reqs, tenant, item, keep, rec, times)
                t = clock() - t0
                for m in ms:
                    done[m.seq] = t
                    labels[m.seq] = m.payload["label"]
                    scores[m.seq] = m.payload["score"]
                self._record(cycles, len(reqs), times, reqs, tenant, used)
            wall = clock() - t0
        lat = (done - due) * 1e3
        return {"latency_ms": lat, "wall_s": wall, "n": n,
                "answered": int(np.isfinite(done).sum()), "labels": labels,
                "scores": scores, "tenant": tenant, "item": item,
                "keep": sorted(keep), "rec": rec, "cycles": cycles,
                "late_ms": np.asarray(late) * 1e3}

    def run_closed(self, seconds: float) -> dict:
        mb = self.mix["max_batch"]
        labels, scores, tenants, items, cycles = [], [], [], [], []
        clock = time.perf_counter
        with self._span("bench.window"):
            t0 = clock()
            c = 0
            while clock() - t0 < seconds:
                tenant, item = self._requests(mb, c + 1)
                times, used = {}, host_cpu_s()
                with stall_watch():
                    ms = self._cycle(range(mb), tenant, item, times=times)
                labels += [m.payload["label"] for m in ms]
                scores += [m.payload["score"] for m in ms]
                tenants.append(tenant)
                items.append(item)
                self._record(cycles, mb, times, range(mb), tenant, used)
                c += 1
            wall = clock() - t0
        return {"wall_s": wall, "n": len(labels), "answered": len(labels),
                "labels": labels, "scores": np.asarray(scores),
                "tenant": np.concatenate(tenants),
                "item": np.concatenate(items), "cycles": cycles,
                "rec": {}, "late_ms": np.zeros(0)}

    def run_window(self, seconds: float) -> dict:
        """The window, with the garbage collector's pauses in it timed."""
        pauses = GcPauses()
        gc.callbacks.append(pauses)
        try:
            if self.mix["loop"] == "open":
                out = self.run_open(seconds)
            else:
                out = self.run_closed(seconds)
        finally:
            gc.callbacks.remove(pauses)
        out["gc"] = pauses.summary()
        return out

    def release(self):
        """Drop the program's state, so that the reference runs beside
        nothing the program holds."""
        self.chain = self.gallery = None
        gc.collect()

    # -- the comparison -------------------------------------------------------
    def sample(self, out: dict) -> np.ndarray:
        if out.get("keep"):
            return np.asarray(out["keep"])
        rng = np.random.default_rng(derive(self.seed, 4))
        k = min(out["answered"], self.mix["check_sample"])
        return np.sort(rng.choice(out["answered"], k, replace=False))

    def numbers(self, out: dict, control: dict = None) -> dict:
        """Every compared number of every sampled request (arrays), for
        the program's answers or, with ``control``, the control's."""
        idx = self.sample(out)
        res = {}
        queries = self.bank[out["item"][idx]]
        if self.stage_ref is not None and self.mix["payload"] == "frames":
            choice = next(s["name"] for s, m in zip(
                self.cfg["stages"], self.stage_ref.mods)
                if m.KIND == "choice")
            last = self.cfg["stages"][-1]["name"]
            crops = [np.asarray(out["rec"][r][choice]) for r in idx]
            embeds = [np.asarray(out["rec"][r][last]) for r in idx]
            res.update(self.stage_ref.numbers(
                queries, crops, embeds,
                control["stages"] if control else ""))
            queries = np.stack(embeds)
        gap = np.zeros(len(idx))
        err = np.zeros(len(idx))
        labels = [out["labels"][r] for r in idx]
        scores = out["scores"][idx]
        tenant = out["tenant"][idx]
        for t, s in enumerate(self.scopes):
            sel = np.nonzero(tenant == t)[0]
            if not len(sel):
                continue
            w = reference.watchlist_numbers(
                queries[sel], [labels[i] for i in sel], scores[sel], s.rows,
                s.prefix, control["rows"] if control else "")
            gap[sel], err[sel] = w["label_gap"], w["score_err"]
        res.update(label_gap=gap, score_err=err)
        return res

    def checks(self, out: dict) -> tuple:
        """Each compared number (the configuration's ``limits``) beside its
        limit, and every reading the reference gives."""
        got = reference.worst(self.numbers(out))
        return {k: {"value": got[k], "limit": v}
                for k, v in self.cfg["limits"].items()}, got

    # -- metrics --------------------------------------------------------------
    def end_to_end(self, out: dict, setup_s: float, peak: int) -> dict:
        vals = {"setup_s": (setup_s, "s"),
                "peak_hbm_gb": (peak / 1e9, "GB")}
        if "latency_ms" in out:
            lat = out["latency_ms"][np.isfinite(out["latency_ms"])]
        for m in self.bench["end_to_end"]:
            name = m["name"]
            if not applies(m, self.workload) or name in vals:
                continue
            pct = re.fullmatch(r"p(\d+)_ms", name)
            if name == "ids_per_s":
                vals[name] = (out["answered"] / out["wall_s"], "ids/s")
            elif pct:
                vals[name] = (float(np.percentile(lat, int(pct.group(1)))),
                              "ms")
            else:
                raise KeyError(f"the harness takes no end-to-end metric "
                               f"{name!r}")
        return {m["name"]: {"value": vals[m["name"]][0], "unit": m["unit"]}
                for m in self.bench["end_to_end"]
                if applies(m, self.workload)}

    def per_layer(self, view) -> dict:
        out = {}
        reported = {m["name"] for m in self.bench["end_to_end"]
                    if applies(m, self.workload)}
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if self.workload not in m["workloads"]:
                    continue
            elif m["moves"] not in reported:
                continue
            value = load_metric(m["name"]).read(view)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

