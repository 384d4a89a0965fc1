"""Plain reference of the quality stand-in ("CR-FIQA", slot 1): the
crop's mean absolute vertical plus horizontal difference as a sharpness
score g, and the crop scaled by clip(10 g, 0.5, 1.5).  It has no matmul,
so ``cast`` does not apply; the embedder's L2 normalization undoes the
scale, so this stage cannot change an answer."""
from __future__ import annotations

import jax.numpy as jnp

KIND = "map"


def params(key):
    return None


def apply(_, crop, cast):
    c = jnp.asarray(crop, jnp.float32)
    g = jnp.mean(jnp.abs(jnp.diff(c, axis=0))) + \
        jnp.mean(jnp.abs(jnp.diff(c, axis=1)))
    return c * jnp.clip(g * 10, 0.5, 1.5)
