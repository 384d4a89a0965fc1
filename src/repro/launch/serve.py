"""Serving driver: CHAMP fleet serving behind the multi-tenant front door.

Builds the paper's flagship pipeline — face detection -> quality scoring ->
embedding extraction -> encrypted watchlist match — as VDiSK cartridges
whose payload compute is real (small CNN/MLP stand-ins for the RetinaFace/
CR-FIQA/FaceNet bitstreams), and serves it three ways:

* ``--mode fleet`` (the canonical entry point): several tenants — live
  checkpoint operators with a latency SLO, recon feeds, archive
  backfill — share the box through the ``FrontDoor`` admission
  controller.  Each tenant screens against its *own* watchlist
  (tenant-scoped gallery views), the door sheds bulk work first under
  overload, and the run prints a per-tenant admission/SLO table.
* ``--mode biometric``: the single-operator scenario with a live
  hot-swap (the pre-fleet behaviour, unchanged).
* ``--mode lm``: batch LM serving (prefill + decode) for the
  transformer archs.

JAX picks its default backend: the TPU where one is attached.  A CPU run
says so itself with ``JAX_PLATFORMS=cpu`` (Pallas kernels then run in
interpret mode).
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.bus import BusParams, SharedBus, calibrated
from repro.core import messages as msg
from repro.core import spans
from repro.core.cartridge import Cartridge, DeviceModel, FnCartridge
from repro.crypto import SecureGallery
from repro.data import FrameStream
from repro.runtime import (CapabilityRegistry, FrontDoor, StreamEngine,
                           Tenant)


# ---------------------------------------------------------------------------
# Biometric cartridges (real payload compute)
# ---------------------------------------------------------------------------
EMB_DIM = 128


def _conv_params(key, cin, cout):
    return jax.random.normal(key, (3, 3, cin, cout), jnp.float32) * 0.1


def make_detector(key):
    """'RetinaFace' stand-in: blob-center detector -> one crop per frame."""
    w = _conv_params(key, 3, 8)

    def fn(params, img):
        x = jax.lax.conv_general_dilated(
            img[None], params, (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        heat = jnp.mean(jax.nn.relu(x), axis=-1)[0]
        iy, ix = jnp.unravel_index(jnp.argmax(heat), heat.shape)
        cy, cx = iy * 2, ix * 2
        crop = jax.lax.dynamic_slice(
            img, (jnp.clip(cy - 32, 0, img.shape[0] - 64),
                  jnp.clip(cx - 32, 0, img.shape[1] - 64), 0), (64, 64, 3))
        return crop

    return FnCartridge("retinaface", fn, msg.MessageSpec(msg.IMAGE_FRAME),
                       msg.MessageSpec(msg.FACE_CROPS, (64, 64, 3)),
                       params=w, capability_id=2,
                       device=DeviceModel(service_s=0.030))


def make_quality(key):
    """'CR-FIQA' stand-in: sharpness-gated passthrough (score in meta)."""
    def fn(params, crop):
        g = jnp.mean(jnp.abs(jnp.diff(crop, axis=0))) + \
            jnp.mean(jnp.abs(jnp.diff(crop, axis=1)))
        return crop * jnp.clip(g * 10, 0.5, 1.5)

    return FnCartridge("crfiqa", fn, msg.MessageSpec(msg.FACE_CROPS),
                       msg.MessageSpec(msg.FACE_CROPS, (64, 64, 3)),
                       capability_id=3, device=DeviceModel(service_s=0.030))


def make_embedder(key):
    """'FaceNet' stand-in: conv + pool + linear -> L2-normalized embedding."""
    k1, k2 = jax.random.split(key)
    params = {"conv": _conv_params(k1, 3, 16),
              "lin": jax.random.normal(k2, (16 * 8 * 8, EMB_DIM),
                                       jnp.float32) * 0.05}

    def fn(p, crop):
        x = jax.lax.conv_general_dilated(
            crop[None], p["conv"], (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x)
        x = jax.image.resize(x, (1, 8, 8, 16), "linear").reshape(-1)
        e = x @ p["lin"]
        return e / jnp.maximum(jnp.linalg.norm(e), 1e-9)

    return FnCartridge("facenet", fn, msg.MessageSpec(msg.FACE_CROPS),
                       msg.MessageSpec(msg.EMBEDDING, (EMB_DIM,)),
                       params=params, capability_id=4,
                       device=DeviceModel(service_s=0.030))


class WatchlistCartridge(Cartridge):
    """Database cartridge: encrypted gallery + in-protected-space match.

    A *batched match stage*: when the engine drains a micro-batch of
    queued embedding frames, ``process_batch`` coalesces them into one
    ``SecureGallery.match`` call — a single gallery-match kernel dispatch
    per engine service cycle instead of one per frame.

    ``mode="ann"`` routes the coalesced batch through the two-level ANN
    tier (coarse centroid scan + probed-cell rescore, ``nprobe`` cells
    per query) — the planet-scale watchlist path; the gallery must have
    ``build_ann_index()`` called after enrollment.

    ``tenant_scoped=True`` (fleet serving): frames are grouped by the
    tenant id they carry and each group matches only against that
    tenant's gallery view — one tenant's watchlist never serves
    another's match.  Frames without a tenant tag (or whose tenant has
    no enrolled rows) fall back to the shared fleet pool.
    """

    capability_id = 9
    name = "watchlist_db"
    consumes = msg.MessageSpec(msg.EMBEDDING, (EMB_DIM,))
    produces = msg.MessageSpec(msg.MATCH_RESULT)

    def __init__(self, gallery: SecureGallery, *, mode: str = "exact",
                 nprobe: int = 8, tenant_scoped: bool = False,
                 hit_threshold: float = 0.5):
        super().__init__(device=DeviceModel(service_s=0.010, load_s=0.8))
        self.gallery = gallery
        self.mode = mode
        self.nprobe = nprobe
        self.tenant_scoped = tenant_scoped
        self.hit_threshold = hit_threshold
        self.stats["match_calls"] = 0
        self.stats["hits"] = 0           # matches at/above hit_threshold

    def fn(self, params, emb):
        return emb  # jit side is identity; match below (host-side store)

    def process(self, m):
        return self.process_batch([m])[0]

    def _scope_of(self, m) -> object:
        """Which gallery view this frame screens against: its tenant's,
        or None (the shared pool) when untagged / not enrolled."""
        if not self.tenant_scoped:
            return None
        tenant = m.meta.get("tenant")
        if tenant is None or not self.gallery.has_tenant(tenant):
            return None
        return tenant

    def process_batch(self, ms):
        with TraceAnnotation(spans.MATCH_BATCH):
            return self._match_batch(ms)

    def _match_batch(self, ms):
        # host spans: scope, then per group the gallery's own phases,
        # then results (repro.core.spans)
        with TraceAnnotation(spans.MATCH_SCOPE):
            live = [m for m in ms if m.payload is not None]
            if not live:
                return ms
            # one gallery.match kernel dispatch per tenant scope in the
            # micro-batch (a single call when not tenant-scoped)
            groups: dict = {}
            for i, m in enumerate(live):
                groups.setdefault(self._scope_of(m), []).append(i)
            labels = [None] * len(live)
            scores = [0.0] * len(live)
        for tenant, idxs in groups.items():
            with TraceAnnotation(spans.MATCH_SCOPE):
                q = np.stack([np.asarray(live[i].payload) for i in idxs])
            lab, sc = self.gallery.match(q, k=1, mode=self.mode,
                                         nprobe=self.nprobe, tenant=tenant)
            with TraceAnnotation(spans.MATCH_RESULTS):
                sc = np.asarray(sc)
                self.stats["match_calls"] += 1
                for j, i in enumerate(idxs):
                    labels[i] = lab[j, 0]
                    scores[i] = float(sc[j, 0])
        with TraceAnnotation(spans.MATCH_RESULTS):
            self.stats["hits"] += sum(1 for s in scores
                                      if s >= self.hit_threshold)
            self.stats["processed"] += len(live)
            results = iter(zip(labels, scores))
            out = []
            for m in ms:
                if m.payload is None:
                    out.append(m)
                else:
                    lab, sc = next(results)
                    out.append(m.with_payload({"label": lab, "score": sc},
                                              msg.MATCH_RESULT))
            return out

    def load(self):
        self._loaded = True
        self._fn = lambda p, x: x
        return 0.0


def build_biometric_pipeline(seed=0, with_quality=True, n_shards=1,
                             match_dtype="fp32", match_mode="exact",
                             nprobe=8, tenant_scoped=False):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    reg = CapabilityRegistry()
    reg.insert(0, make_detector(ks[0]))
    if with_quality:
        reg.insert(1, make_quality(ks[1]))
    reg.insert(2, make_embedder(ks[2]))
    # one gallery shard per watchlist replica lane (cartridge scaling)
    gallery = SecureGallery(EMB_DIM, seed=7, n_shards=n_shards,
                            match_dtype=match_dtype)
    reg.insert(3, WatchlistCartridge(gallery, mode=match_mode,
                                     nprobe=nprobe,
                                     tenant_scoped=tenant_scoped))
    return reg, gallery


def _pipeline_embed(reg, src, frame_ids):
    """Offline enrollment embeddings: the same det->quality->embed path
    the streamed frames take."""
    det, qual, emb = (reg.slots[0].cartridge, reg.slots[1].cartridge,
                      reg.slots[2].cartridge)
    for c in (det, qual, emb):
        c.load()
    out = []
    for i in frame_ids:
        crop = det._fn(det.params, jnp.asarray(src.frame_at(i)))
        crop = qual._fn(qual.params, crop)
        out.append(np.asarray(emb._fn(emb.params, crop)))
    return np.stack(out)


def run_biometric(n_frames=30, hotswap=True):
    reg, gallery = build_biometric_pipeline()
    # enroll: run a few frames through det->quality->embed offline
    src = FrameStream(seed=3)
    gallery.enroll(_pipeline_embed(reg, src, range(10)),
                   [f"subject{i}" for i in range(10)])

    eng = StreamEngine(reg, SharedBus(calibrated("ncs2")),
                       execute_payloads=True)
    eng.feed(n_frames, interval_s=0.12,
             payload_fn=lambda i: jnp.asarray(src.frame_at(i % 10)))
    if hotswap:
        eng.schedule_remove(1.0, slot=1)   # pull the quality cartridge live
    rep = eng.run(until=60)
    wl = reg.slots[3].cartridge.stats      # watchlist match-hit accounting
    print(f"[serve] frames={rep.frames_out}/{rep.frames_in} "
          f"lost={rep.lost} hits={wl['hits']} "
          f"mean_latency={rep.mean_latency()*1e3:.1f}ms "
          f"downtime={rep.total_downtime():.2f}s")
    return rep


# ---------------------------------------------------------------------------
# Fleet serving: multi-tenant admission through the front door
# ---------------------------------------------------------------------------
# the three conventional tiers: checkpoint operators screening live
# subjects (tight SLO, sheds last), recon feeds, archive backfill (bulk)
FLEET_TENANTS = (
    Tenant("field_ops", priority=0, weight=8.0, slo_s=0.5, queue_cap=64),
    Tenant("recon", priority=1, weight=3.0, queue_cap=128),
    Tenant("backfill", priority=2, weight=1.0, queue_cap=64),
)
# offered load per tenant, as a fraction of the pipeline's bottleneck
# rate; summing past 1.0 = deliberate overload (backfill sheds first)
FLEET_LOAD = {"field_ops": 0.2, "recon": 0.6, "backfill": 1.2}


def build_fleet(seed=0, n_shards=1):
    """The fleet's pipeline with every tenant's own watchlist enrolled.

    Tenant ``i``'s subjects are frames ``[10*i, 10*i+10)`` of the shared
    frame bank, embedded by the same det->quality->embed path the
    streamed frames take.  Returns ``(reg, gallery, src, tenant_base)``;
    ``tenant_base[name]`` is the tenant's first frame."""
    reg, gallery = build_biometric_pipeline(seed=seed, n_shards=n_shards,
                                            tenant_scoped=True)
    src = FrameStream(seed=3)
    tenant_base = {}
    for i, t in enumerate(FLEET_TENANTS):
        base = 10 * i
        tenant_base[t.name] = base
        gallery.enroll(_pipeline_embed(reg, src, range(base, base + 10)),
                       [f"{t.name}/subject{j}" for j in range(10)],
                       tenant=t.name)
    return reg, gallery, src, tenant_base


def serve_fleet(reg, src, tenant_base, duration_s=3.0, load=None,
                hotswap=False):
    """Serve ``duration_s`` seconds of tenant traffic through the front
    door and the engine; each tenant streams its own subjects' frames
    at its offered rate.  Returns the ``EngineReport``."""
    fd = FrontDoor()
    for t in FLEET_TENANTS:
        fd.add_tenant(t)
    eng = StreamEngine(reg, SharedBus(calibrated("ncs2")),
                       execute_payloads=True, frontdoor=fd)
    # bottleneck stage service time sets the capacity the load fractions
    # scale from
    bottleneck_s = max(r.cartridge.device.service_s for r in reg.records())
    cap_fps = 1.0 / bottleneck_s
    for t in FLEET_TENANTS:
        rate = (load or FLEET_LOAD)[t.name] * cap_fps
        n = int(rate * duration_s)
        base = tenant_base[t.name]
        eng.feed_tenant(
            t.name, n, interval_s=1.0 / rate,
            payload_fn=lambda i, b=base: jnp.asarray(
                src.frame_at(b + i % 10)))
    if hotswap:
        eng.schedule_remove(1.0, slot=1)
    return eng.run(until=float("inf"))


def run_fleet(duration_s=3.0, load=None, hotswap=False):
    """The canonical fleet-serving entry point: the biometric pipeline
    behind the multi-tenant front door.  Each tenant enrolls its own
    watchlist (tenant-scoped gallery views) and streams frames at its
    offered rate; the door does weighted-fair admission with
    lowest-class shed, and the run prints the per-tenant ledger."""
    reg, _, src, tenant_base = build_fleet()
    rep = serve_fleet(reg, src, tenant_base, duration_s, load, hotswap)
    wl = reg.slots[3].cartridge.stats
    fdd = rep.frontdoor
    print(f"[serve-fleet] frames={rep.frames_out}/{rep.frames_in} "
          f"lost={rep.lost} hits={wl['hits']} "
          f"shed={fdd['shed']} credit={fdd['credit']:.2f}")
    for name, t in fdd["tenants"].items():
        print(f"  {name:10s} [{t['class']:11s}] offered={t['offered']:4d} "
              f"admitted={t['admitted']:4d} shed={t['shed']:4d} "
              f"goodput={t['goodput']:.2f} p99={t['latency'].get('p99', 0.0) * 1e3:7.1f}ms "
              f"slo_miss={t['slo_miss']}")
    return rep


# ---------------------------------------------------------------------------
# LM serving (prefill + decode)
# ---------------------------------------------------------------------------
def run_lm(arch="tinyllama-1.1b", batch=2, prompt_len=32, gen=16):
    from repro.configs import base as cb
    from repro.launch import specs as sp
    from repro.models import model as mdl
    from repro.sharding import init_params

    cfg = cb.smoke(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(mdl.param_specs(cfg), key, jnp.bfloat16)
    batch_d = sp.make_batch(cfg, prompt_len, batch, key, with_labels=False)
    T = prompt_len + gen

    last, cache = jax.jit(lambda p, b: mdl.prefill(p, cfg, b))(params, batch_d)
    cache_t = sp.init_cache(cfg, batch, T)

    def put(dst, src):
        if src.ndim == 0 or dst.shape == src.shape:
            return src.astype(dst.dtype)
        ax = [i for i, (a, b) in enumerate(zip(dst.shape, src.shape))
              if a != b][0]
        sl = [slice(None)] * dst.ndim
        sl[ax] = slice(0, src.shape[ax])
        return dst.at[tuple(sl)].set(src.astype(dst.dtype))

    cache = jax.tree.map(put, cache_t, cache)
    step = jax.jit(lambda p, t, i, c: mdl.serve_step(p, cfg, t, i, c))
    tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
    outs = [tok]
    t0 = time.time()
    for i in range(gen - 1):
        tok, cache = step(params, tok, jnp.int32(prompt_len + i), cache)
        outs.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    toks = jnp.concatenate(outs, axis=1)
    print(f"[serve-lm] {arch}: generated {gen}x{batch} tokens "
          f"({batch * (gen - 1) / dt:.1f} tok/s on "
          f"{jax.default_backend()}); "
          f"sample: {np.asarray(toks[0])[:12]}")
    return toks


CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache():
    """Turn on JAX's persistent compile cache for this process.  Called at
    start-up by the entry points, never on import.  ``JAX_COMPILATION_
    CACHE_DIR``, when set, is read by JAX itself and wins; otherwise the
    cache lives at ``<checkout>/.jax_cache``, a fixed path, because the
    directory is part of what a later run must find again.  Every
    compile is kept (the kernels compile in well under JAX's default
    one-second floor)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["fleet", "biometric", "lm"],
                    default="fleet")
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--frames", type=int, default=30)
    ap.add_argument("--duration", type=float, default=3.0,
                    help="fleet mode: seconds of offered traffic")
    ap.add_argument("--no-hotswap", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.mode == "fleet":
        run_fleet(args.duration)
    elif args.mode == "biometric":
        run_biometric(args.frames, hotswap=not args.no_hotswap)
    else:
        run_lm(args.arch)


if __name__ == "__main__":
    main()
