"""Pallas TPU kernel family: blocked cosine top-k gallery matching.

The Database cartridge's hot path: score Q protected query templates
against an N-row protected gallery and keep the top-k matches per query.

TPU adaptation (vs. the GPU "matmul then sort" idiom): the gallery streams
through VMEM in (BN, D) tiles feeding the MXU per (BQ, BN) score block; a
running (BQ, k) top-k accumulator lives in VMEM scratch across the
sequential gallery-block grid dimension, merged with each new score block
by k unrolled max/argmax passes (k is small and static — no sort, and the
(Q, N) score matrix never round-trips HBM).

Dtype family (identification fast path):

  * fp32  — the parity oracle path (``kernels/ref.py``).
  * bf16  — gallery tiles stored/streamed as bf16, cast to f32 at the MXU
            boundary (fp32 accumulation); halves VMEM + bus traffic.
  * int8  — symmetric per-row quantized gallery (``quantize_gallery``)
            plus an f32 scale column; tiles stream at 1/4 the f32 bytes
            and scores accumulate in fp32, dequantized per gallery row.

Block schedule: the gallery grid dimension is sequential ("arbitrary"
semantics) so Pallas double-buffers the (BN, D) tile fetch against the
MXU pass.  Default BN is storage-dtype-aware (``_DEF_BN``): one tile is
kept ~2-4 MiB at D=512 so two in-flight tiles plus the query tile fit
VMEM.  The int8 scale travels as a lane-dense (1, BN) row, not a
(BN, 1) column that would pad to 128 lanes.

``fuse_norm=True`` L2-normalizes the query tile in-kernel (queries never
round-trip through a separate normalization op); the gallery is expected
pre-normalized at enrollment time by the caller.

Edge cases: ``k > N`` is clamped to the gallery size — the trailing
``k - N`` output columns are sentinel-filled (score ``NEG``, index
``-1``); ``Q < 8`` and ``N`` not a multiple of ``BN`` are handled by
zero-padding with tail-column masking.

Grid: (Q/BQ, N/BN); the gallery dimension iterates fastest (sequential on
TPU), the accumulator resets at j == 0 and flushes at j == last.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -3.0e38

# Storage-dtype-aware default gallery tile height: sized so one (BN, 512)
# tile stays ~2-4 MiB and double-buffers within the 16 MiB scoped VMEM
# alongside the query tile, the in-kernel f32 cast of the tile and the
# (BQ, k+BN) merge tile.  int8 shares bf16's BN: at 8192 rows the f32
# cast plus the merge tile overflow VMEM at D=512, Q=1024 (v5e compile).
_DEF_BN = {"float32": 2048, "bfloat16": 4096, "int8": 4096}


def _default_bn(g_dtype) -> int:
    return _DEF_BN.get(jnp.dtype(g_dtype).name, 512)


def mxu_precision(storage_dtype):
    """MXU precision for a tile stored as ``storage_dtype``.  fp32 storage
    (the parity-oracle path) gets full-precision passes.  Otherwise the
    default pass: bf16 tiles meet bf16 queries exactly, and an int8 tile
    is exact in bf16 while the query's bf16 rounding stays below the
    int8 quantization error."""
    if jnp.dtype(storage_dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def _match_kernel(*refs, k: int, bn: int, n_gallery: int,
                  fuse_norm: bool, quantized: bool):
    if quantized:
        q_ref, g_ref, gs_ref, scores_ref, idx_ref, acc_s, acc_i = refs
    else:
        q_ref, g_ref, scores_ref, idx_ref, acc_s, acc_i = refs
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.full(acc_s.shape, NEG, acc_s.dtype)
        acc_i[...] = jnp.zeros(acc_i.shape, acc_i.dtype)

    # tiles stream in storage dtype; the MXU boundary casts to f32 so the
    # MAC (and the top-k carry) always accumulates in fp32
    q = q_ref[...].astype(jnp.float32)               # (BQ, D)
    if fuse_norm:
        q = q * jax.lax.rsqrt(
            jnp.maximum(jnp.sum(q * q, axis=-1, keepdims=True), 1e-18))
    g = g_ref[...].astype(jnp.float32)               # (BN, D)
    s = jax.lax.dot_general(
        q, g, (((1,), (1,)), ((), ())),
        precision=mxu_precision(g_ref.dtype),
        preferred_element_type=jnp.float32)          # (BQ, BN)
    if quantized:
        # symmetric per-row dequantization: the (1, BN) scale row is
        # lane-dense, so it broadcasts over the (BQ, BN) score block
        s = s * gs_ref[...]
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < n_gallery, s, NEG)           # mask tail padding

    # merge carry and block: k unrolled max/argmax passes
    cs = jnp.concatenate([acc_s[...], s], axis=1)    # (BQ, k+BN)
    ci = jnp.concatenate([acc_i[...], col], axis=1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, cs.shape, 1)
    for slot in range(k):
        a = jnp.argmax(cs, axis=1)                   # (BQ,)
        m = jnp.max(cs, axis=1)
        acc_s[:, slot] = m
        hit = lanes == a[:, None]                    # one lane per row
        acc_i[:, slot] = jnp.max(jnp.where(hit, ci, -1), axis=1)
        cs = jnp.where(hit, NEG, cs)

    @pl.when(j == nj - 1)
    def _flush():
        scores_ref[...] = acc_s[...]
        idx_ref[...] = acc_i[...]


def _launch(q, g, g_scale, *, k: int, bq: int, bn, fuse_norm: bool,
            interpret: bool):
    Q, D = q.shape
    N = g.shape[0]
    if N == 0:
        raise ValueError("gallery_match: empty gallery")
    k_eff = max(1, min(k, N))                        # clamp k > N
    bq = min(bq, max(Q, 8))
    bn = bn if bn is not None else _default_bn(g.dtype)
    bn = min(bn, max(N, 8))
    Qp = -(-Q // bq) * bq
    Np = -(-N // bn) * bn
    qp = jnp.pad(q, ((0, Qp - Q), (0, 0)))           # storage dtype kept
    gp = jnp.pad(g, ((0, Np - N), (0, 0)))
    quantized = g_scale is not None
    inputs = [qp, gp]
    in_specs = [
        pl.BlockSpec((bq, D), lambda i, j: (i, 0)),
        pl.BlockSpec((bn, D), lambda i, j: (j, 0)),
    ]
    if quantized:
        gsp = jnp.pad(g_scale.astype(jnp.float32).reshape(1, -1),
                      ((0, 0), (0, Np - N)))
        inputs.append(gsp)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
    kernel = functools.partial(_match_kernel, k=k_eff, bn=bn, n_gallery=N,
                               fuse_norm=fuse_norm, quantized=quantized)
    scores, idx = pl.pallas_call(
        kernel,
        grid=(Qp // bq, Np // bn),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, k_eff), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k_eff), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k_eff), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k_eff), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k_eff), jnp.float32),
            pltpu.VMEM((bq, k_eff), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*inputs)
    scores, idx = scores[:Q], idx[:Q]
    if k_eff < k:                                    # k > N sentinels
        scores = jnp.pad(scores, ((0, 0), (0, k - k_eff)),
                         constant_values=NEG)
        idx = jnp.pad(idx, ((0, 0), (0, k - k_eff)), constant_values=-1)
    return scores, idx


def gallery_match_pallas(q: jax.Array, g: jax.Array, *, k: int = 5,
                         bq: int = 128, bn=None, fuse_norm: bool = False,
                         interpret: bool = False):
    """q: (Q, D) queries; g: (N, D) gallery rows (both normalized unless
    ``fuse_norm`` handles the queries in-kernel).  Storage dtype of ``g``
    (f32 or bf16) picks the tile schedule; accumulation is always fp32.
    Returns (scores (Q, k) f32, idx (Q, k) i32), scores descending; when
    ``k > N`` the trailing columns hold sentinel score/index (NEG, -1)."""
    if g.dtype == jnp.bfloat16:
        q = q.astype(jnp.bfloat16)
    else:
        q = q.astype(jnp.float32)
        g = g.astype(jnp.float32)
    return _launch(q, g, None, k=k, bq=bq, bn=bn, fuse_norm=fuse_norm,
                   interpret=interpret)


def gallery_match_quant_pallas(q: jax.Array, g_q: jax.Array,
                               g_scale: jax.Array, *, k: int = 5,
                               bq: int = 128, bn=None,
                               fuse_norm: bool = False,
                               interpret: bool = False):
    """int8 fast path: ``g_q`` (N, D) int8 symmetric per-row quantized
    gallery with f32 ``g_scale`` (N,); queries stay f32 (only the large
    operand is quantized).  Scores are fp32-accumulated then dequantized
    per gallery row, so ordering matches the dequantized-f32 oracle."""
    assert g_q.dtype == jnp.int8, g_q.dtype
    return _launch(q.astype(jnp.float32), g_q, g_scale, k=k, bq=bq, bn=bn,
                   fuse_norm=fuse_norm, interpret=interpret)


def quantize_gallery(g: jax.Array):
    """Symmetric per-row int8 quantization: returns (values (N, D) int8,
    scale (N,) f32) with ``values * scale[:, None] ~= g``."""
    g = g.astype(jnp.float32)
    amax = jnp.max(jnp.abs(g), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(g / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_gallery(g_q: jax.Array, g_scale: jax.Array) -> jax.Array:
    """Inverse of ``quantize_gallery`` (the int8 parity oracle input)."""
    return g_q.astype(jnp.float32) * g_scale[:, None].astype(jnp.float32)
