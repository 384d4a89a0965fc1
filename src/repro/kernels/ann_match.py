"""Pallas TPU kernel family: two-level ANN gallery matching (IVF-style).

The planet-scale identification path: exact brute-force scan is linear in
N, so a 10^7-10^8 identity watchlist blows the latency budget no matter
how many replica cartridges shard it.  This module splits the match into
two levels so only a small, query-dependent fraction of the gallery is
ever scored:

  level 1 — **coarse centroid scan**: queries vs the K-row centroid
      codebook (trained by ``kmeans_lite``), keep the top-c cells per
      query.  This is a dense cosine top-k at codebook scale, so it
      reuses the blocked ``gallery_match`` launcher — same storage-dtype
      family (fp32 / bf16 / int8 per-row quantized, fp32 accumulation),
      same fused query normalization.

  level 2 — **exact rescore inside the probed cells**: the gallery is
      stored cell-major, each cell padded to a fixed ``L`` rows, as a
      (K*L, D) array in the storage dtype.  A scalar-prefetch kernel
      (``PrefetchScalarGridSpec``) walks grid (Q, c): the prefetched
      (Q, c) probe table drives the BlockSpec index map, so each grid
      step DMA's exactly one (L, D) cell tile — the cells a query did
      not probe never leave HBM.  Scores accumulate in fp32; pad rows
      (row >= cell_len) and invalid probes (cell id -1) are masked to
      the ``NEG`` sentinel; a running (1, k) top-k accumulator merges
      across the sequential probe dimension exactly like the dense
      kernel merges across gallery blocks.

The rescore kernel returns *padded positions* (cell * L + row) — the
caller owns the padded-position -> gallery-row mapping (``CellLayout``
keeps it), which is how the sharded ``SecureGallery`` translates to
global identity ids.

Exactness contract: within the probed cells the rescore is the same
fp32-accumulated cosine as the dense kernel, so recall loss comes only
from probe selection (tracked in ``BENCH_gallery.json``: recall@1 >=
0.98 vs the fp32 oracle at <= 1/10 of the gallery rows scored).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gallery_match import (NEG, dequantize_gallery,
                                         gallery_match_pallas,
                                         gallery_match_quant_pallas,
                                         mxu_precision, quantize_gallery)

__all__ = ["NEG", "CellLayout", "kmeans_lite", "assign_cells",
           "build_cell_layout", "centroid_topc_pallas",
           "cell_rescore_pallas"]


# ---------------------------------------------------------------------------
# level 1 — coarse centroid scan (dense top-c at codebook scale)
# ---------------------------------------------------------------------------
def centroid_topc_pallas(q: jax.Array, centroids: jax.Array,
                         c_scale: Optional[jax.Array] = None, *, c: int,
                         bq: int = 128, bn=None, fuse_norm: bool = True,
                         interpret: bool = False):
    """Top-``c`` probe selection: q (Q, D) vs centroids (K, D) in the
    centroid storage dtype (f32 / bf16, or int8 + per-row ``c_scale``).
    Returns (scores (Q, c) f32, cell ids (Q, c) i32); when ``c > K`` the
    trailing columns hold the (NEG, -1) sentinels — i.e. invalid probes,
    which the rescore kernel masks."""
    if c_scale is not None:
        return gallery_match_quant_pallas(q, centroids, c_scale, k=c, bq=bq,
                                          bn=bn, fuse_norm=fuse_norm,
                                          interpret=interpret)
    return gallery_match_pallas(q, centroids, k=c, bq=bq, bn=bn,
                                fuse_norm=fuse_norm, interpret=interpret)


# ---------------------------------------------------------------------------
# level 2 — exact rescore restricted to the probed cells
# ---------------------------------------------------------------------------
def _rescore_kernel(ids_ref, lens_ref, q_ref, cell_ref, *rest, k: int,
                    L: int, fuse_norm: bool, quantized: bool):
    if quantized:
        scale_ref, scores_ref, pos_ref, acc_s, acc_p = rest
    else:
        scores_ref, pos_ref, acc_s, acc_p = rest
    i = pl.program_id(0)                             # query
    j = pl.program_id(1)                             # probe slot
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.full(acc_s.shape, NEG, acc_s.dtype)
        acc_p[...] = jnp.full(acc_p.shape, -1, acc_p.dtype)

    cid = ids_ref[i, j]                              # probed cell (or -1)
    # clamp for the length lookup; validity is enforced via masking below
    n_valid = jnp.where(cid < 0, 0,
                        lens_ref[jnp.maximum(cid, 0)])

    q = q_ref[...].astype(jnp.float32)               # (1, D)
    if fuse_norm:
        q = q * jax.lax.rsqrt(
            jnp.maximum(jnp.sum(q * q, axis=-1, keepdims=True), 1e-18))
    g = cell_ref[...].astype(jnp.float32)            # (L, D) one cell tile
    s = jax.lax.dot_general(
        q, g, (((1,), (1,)), ((), ())),
        precision=mxu_precision(cell_ref.dtype),
        preferred_element_type=jnp.float32)          # (1, L)
    if quantized:
        s = s * scale_ref[...]                       # (1, L) per-row dequant
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(row < n_valid, s, NEG)             # pad rows + dead probes
    pos = jnp.where(row < n_valid,
                    jnp.maximum(cid, 0) * L + row, -1)

    # merge carry and cell block: k unrolled max/argmax passes
    cs = jnp.concatenate([acc_s[...], s], axis=1)    # (1, k+L)
    cp = jnp.concatenate([acc_p[...], pos], axis=1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, cs.shape, 1)
    for slot in range(k):
        a = jnp.argmax(cs, axis=1)
        m = jnp.max(cs, axis=1)
        acc_s[:, slot] = m
        # an unfilled slot (every candidate already consumed / masked)
        # carries the -1 sentinel, not a stale position
        hit = lanes == a[:, None]                    # one lane per row
        acc_p[:, slot] = jnp.where(
            m <= NEG / 2, -1, jnp.max(jnp.where(hit, cp, -1), axis=1))
        cs = jnp.where(hit, NEG, cs)

    @pl.when(j == nj - 1)
    def _flush():
        scores_ref[...] = acc_s[...]
        pos_ref[...] = acc_p[...]


def cell_rescore_pallas(q: jax.Array, cells: jax.Array,
                        cell_ids: jax.Array, cell_lens: jax.Array,
                        cell_scale: Optional[jax.Array] = None, *,
                        k: int = 5, L: int, fuse_norm: bool = True,
                        interpret: bool = False):
    """Exact rescore of q (Q, D) against its probed cells only.

    ``cells``: (K*L, D) padded cell-major gallery in the storage dtype
    (f32 / bf16, or int8 with f32 ``cell_scale`` (K*L,)); ``cell_ids``:
    (Q, c) i32 probe table from the coarse scan (-1 = no probe);
    ``cell_lens``: (K,) i32 valid rows per cell.  Returns (scores (Q, k)
    f32, padded positions (Q, k) i32) with (NEG, -1) sentinels for
    unfilled slots; positions are ``cell * L + row`` in the padded
    layout.  Grid (Q, c) with the probe dimension sequential: the
    scalar-prefetched probe table drives the cell-tile index map, so an
    unprobed cell is never fetched.
    """
    Q, D = q.shape
    _, c = cell_ids.shape
    K = cell_lens.shape[0]
    assert cells.shape[0] == K * L, (cells.shape, K, L)
    quantized = cell_scale is not None
    if quantized:
        assert cells.dtype == jnp.int8, cells.dtype
        qp = q.astype(jnp.float32)
    elif cells.dtype == jnp.bfloat16:
        qp = q.astype(jnp.bfloat16)
    else:
        qp = q.astype(jnp.float32)

    ids = cell_ids.astype(jnp.int32)
    lens = cell_lens.astype(jnp.int32)

    # index maps see the prefetched scalars after the grid indices; an
    # invalid probe (-1) clamps to tile 0 and is masked inside the kernel.
    # Every operand gets a squeezed leading axis (query or cell), so each
    # block's last two dims equal the array's, as Mosaic requires.
    def _cell_map(i, j, ids_ref, lens_ref):
        return (jnp.maximum(ids_ref[i, j], 0), 0, 0)

    def _query_map(i, j, ids_ref, lens_ref):
        return (i, 0, 0)

    in_specs = [
        pl.BlockSpec((None, 1, D), _query_map),
        pl.BlockSpec((None, L, D), _cell_map),
    ]
    inputs = [qp.reshape(Q, 1, D), cells.reshape(K, L, D)]
    if quantized:
        in_specs.append(pl.BlockSpec((None, 1, L), _cell_map))
        inputs.append(cell_scale.astype(jnp.float32).reshape(K, 1, L))
    kernel = functools.partial(_rescore_kernel, k=k, L=L,
                               fuse_norm=fuse_norm, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Q, c),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, 1, k), _query_map),
            pl.BlockSpec((None, 1, k), _query_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((1, k), jnp.int32),
        ],
    )
    scores, pos = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, lens, *inputs)
    return scores.reshape(Q, k), pos.reshape(Q, k)


# ---------------------------------------------------------------------------
# codebook training + cell layout (host side, enrollment time)
# ---------------------------------------------------------------------------
def kmeans_lite(x: np.ndarray, n_cells: int, *, iters: int = 6,
                seed: int = 0) -> np.ndarray:
    """Spherical k-means-lite: train an (n_cells, D) L2-normalized
    centroid codebook over L2-normalized rows ``x``.  Deterministic
    (seeded row-sample init); an emptied cell keeps its previous
    centroid so the codebook never collapses.  Host-side numpy — this
    runs once per codebook at enrollment time, not in the match path."""
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    n_cells = max(1, min(n_cells, n))
    rng = np.random.default_rng(seed)
    cent = x[rng.choice(n, n_cells, replace=False)].copy()
    for _ in range(iters):
        assign = np.argmax(x @ cent.T, axis=1)
        for cell in range(n_cells):
            rows = x[assign == cell]
            if len(rows):
                m = rows.sum(axis=0)
                norm = np.linalg.norm(m)
                if norm > 1e-9:
                    cent[cell] = m / norm
    return cent


def assign_cells(x: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """Nearest-centroid (cosine) cell id per row — the incremental-enroll
    path: new rows join existing cells, the codebook is never retrained."""
    xn = np.asarray(x, np.float32)
    xn = xn / np.maximum(np.linalg.norm(xn, axis=-1, keepdims=True), 1e-9)
    return np.argmax(xn @ np.asarray(centroids, np.float32).T,
                     axis=1).astype(np.int32)


@dataclass
class CellLayout:
    """Padded cell-major physical layout of one gallery shard.

    ``perm``: (N,) shard-row id at each occupied padded slot, cell-major;
    ``pos_to_row``: (K*L,) shard-row id per padded position (-1 = pad);
    ``cell_lens``: (K,) occupancy; ``L``: pad width (max cell size,
    rounded up to a multiple of 8 so cell tiles stay sublane-aligned).
    """
    perm: np.ndarray
    pos_to_row: np.ndarray
    cell_lens: np.ndarray
    L: int

    @property
    def n_cells(self) -> int:
        return len(self.cell_lens)


def build_cell_layout(assign: np.ndarray, n_cells: int) -> CellLayout:
    """Group shard rows by cell id into the padded cell-major layout the
    rescore kernel streams.  O(N log N) host-side repack; stable within a
    cell (rows keep enrollment order, so in-cell score ties break toward
    the earliest-enrolled row, same as the dense kernel)."""
    assign = np.asarray(assign, np.int64)
    cell_lens = np.bincount(assign, minlength=n_cells).astype(np.int32)
    L = max(8, int(-(-max(1, cell_lens.max(initial=1)) // 8) * 8))
    perm = np.argsort(assign, kind="stable").astype(np.int64)
    pos_to_row = np.full(n_cells * L, -1, np.int64)
    starts = np.concatenate([[0], np.cumsum(cell_lens)[:-1]])
    for cell in range(n_cells):
        rows = perm[starts[cell]:starts[cell] + cell_lens[cell]]
        pos_to_row[cell * L:cell * L + len(rows)] = rows
    return CellLayout(perm=perm, pos_to_row=pos_to_row,
                      cell_lens=cell_lens, L=L)


def pack_cells(gn: np.ndarray, layout: CellLayout) -> np.ndarray:
    """Scatter normalized shard rows (N, D) into the (K*L, D) padded
    cell-major array (pad rows zero — masked in-kernel via cell_lens)."""
    out = np.zeros((layout.n_cells * layout.L, gn.shape[1]), np.float32)
    occ = layout.pos_to_row >= 0
    out[occ] = np.asarray(gn, np.float32)[layout.pos_to_row[occ]]
    return out


def pack_cells_quant(gn: np.ndarray, layout: CellLayout):
    """int8 packed cells: symmetric per-row quantization of the packed
    array (pad rows quantize to zeros with the minimum scale, and are
    masked by the kernel anyway)."""
    packed = pack_cells(gn, layout)
    q8, scale = quantize_gallery(jnp.asarray(packed))
    return np.asarray(q8), np.asarray(scale)
