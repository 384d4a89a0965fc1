"""Cryptographically protected biometric templates (paper §3.1/§3.2).

The paper's database cartridge stores galleries encrypted and matches
templates "under encryption" with VDiSK's template-privacy layer. Two
complementary mechanisms, both pure JAX:

1. ``KeyedRotation`` — a secret orthogonal transform Q (seeded QR of a
   Threefry-generated Gaussian). Protected templates t' = Q t preserve
   inner products and norms *exactly*, so cosine-similarity matching (the
   FaceNet cartridge contract) runs directly on protected templates
   without revealing the raw embedding basis. This is the standard
   random-orthogonal-projection template-protection scheme and is the
   "homomorphic for cosine matching" property the paper invokes.
   Revocability: re-key by drawing a new Q (cancellable biometrics).

2. ``stream_cipher`` — Threefry counter-mode XOR cipher for templates and
   metadata at rest on the storage cartridge (byte-exact decrypt).

Key hygiene: keys are jax PRNG keys derived from a device secret +
gallery id; rotating either revokes every stored template.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# 1. Cosine-preserving keyed rotation
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class KeyedRotation:
    dim: int
    seed: int

    def _q(self) -> jax.Array:
        g = jax.random.normal(jax.random.PRNGKey(self.seed),
                              (self.dim, self.dim), jnp.float32)
        q, r = jnp.linalg.qr(g)
        # fix signs so Q is unique given the seed (deterministic re-keying)
        return q * jnp.sign(jnp.diag(r))[None, :]

    # the inner-product preservation above holds only at full f32: the
    # default matmul precision on TPU is a single bf16 pass
    def protect(self, t: jax.Array) -> jax.Array:
        """t: (..., dim) raw templates -> protected templates."""
        return jnp.einsum("...d,de->...e", t.astype(jnp.float32), self._q(),
                          precision=jax.lax.Precision.HIGHEST)

    def unprotect(self, tp: jax.Array) -> jax.Array:
        return jnp.einsum("...e,de->...d", tp.astype(jnp.float32), self._q(),
                          precision=jax.lax.Precision.HIGHEST)


def cosine_scores(queries: jax.Array, gallery: jax.Array) -> jax.Array:
    """(Q,d) x (N,d) -> (Q,N) cosine similarity (works on protected or raw
    templates identically when both sides share the same KeyedRotation)."""
    qn = queries / jnp.maximum(
        jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-9)
    gn = gallery / jnp.maximum(
        jnp.linalg.norm(gallery, axis=-1, keepdims=True), 1e-9)
    return qn @ gn.T


# ---------------------------------------------------------------------------
# 2. Threefry counter-mode stream cipher (encryption at rest)
# ---------------------------------------------------------------------------
def _keystream(key: jax.Array, n_words: int) -> jax.Array:
    """n_words of uint32 keystream from the jax Threefry PRNG."""
    return jax.random.bits(key, (n_words,), jnp.uint32)


def encrypt_bytes(key: jax.Array, data: bytes) -> np.ndarray:
    buf = np.frombuffer(data, np.uint8)
    pad = (-len(buf)) % 4
    buf = np.pad(buf, (0, pad))
    words = buf.view(np.uint32)
    ks = np.asarray(_keystream(key, len(words)))
    enc = (words ^ ks).view(np.uint8)
    return np.concatenate([enc, np.array([pad], np.uint8)])


def decrypt_bytes(key: jax.Array, blob: np.ndarray) -> bytes:
    pad = int(blob[-1])
    words = blob[:-1].view(np.uint32)
    ks = np.asarray(_keystream(key, len(words)))
    dec = (words ^ ks).view(np.uint8)
    return dec[: len(dec) - pad].tobytes()


def encrypt_array(key: jax.Array, x: np.ndarray) -> dict:
    blob = encrypt_bytes(key, np.ascontiguousarray(x).tobytes())
    return {"blob": blob, "shape": x.shape, "dtype": str(x.dtype)}


def decrypt_array(key: jax.Array, enc: dict) -> np.ndarray:
    raw = decrypt_bytes(key, enc["blob"])
    return np.frombuffer(raw, enc["dtype"]).reshape(enc["shape"]).copy()
