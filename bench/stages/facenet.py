"""Plain reference of the embedder stand-in ("FaceNet", slot 2): a 3x3
stride-2 convolution to 16 channels, ReLU, bilinear resize to 8x8, a
linear map of the 1024 features to the 128-d embedding, and L2
normalization.  Its weights are drawn from the stage's key exactly as
the published stand-in draws them.  The convolution and the linear map
run at XLA's default precision, as the configuration states (on the TPU
one bf16 pass with f32 accumulation), with ``cast`` applied to each of
their inputs; the resize keeps ``jax.image.resize``'s own precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp

KIND = "map"
EMB_DIM = 128


def params(key):
    k1, k2 = jax.random.split(key)
    return {"conv": jax.random.normal(k1, (3, 3, 3, 16), jnp.float32) * 0.1,
            "lin": jax.random.normal(k2, (16 * 8 * 8, EMB_DIM),
                                     jnp.float32) * 0.05}


def apply(p, crop, cast):
    x = jax.lax.conv_general_dilated(
        cast(jnp.asarray(crop, jnp.float32)[None]), cast(p["conv"]),
        (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x = jax.nn.relu(x)
    x = jax.image.resize(x, (1, 8, 8, 16), "linear").reshape(-1)
    e = jnp.dot(cast(x), cast(p["lin"]))
    return e / jnp.maximum(jnp.linalg.norm(e), 1e-9)
