"""Plain reference of the detector stand-in ("RetinaFace", slot 0 of the
biometric pipeline): a 3x3 stride-2 convolution to 8 channels, the mean
of its ReLU as a heat map, and the 64x64 crop centred on the heat map's
maximum, clipped to the frame.

The convolution runs at XLA's default precision, as the configuration
states (on the TPU one bf16 pass with f32 accumulation); ``cast`` is
applied to each of its inputs (the identity, or the control's rounding).

It is a choice stage: its output is one of a finite set of crops.  The
check reads the program's choice as a gap: how far below the reference
heat map's maximum the reference heat of the program's crop lies,
relative to that maximum (0 when both choose the same crop).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KIND = "choice"
CROP = 64


def params(key):
    return jax.random.normal(key, (3, 3, 3, 8), jnp.float32) * 0.1


def heat(w, img, cast):
    x = jax.lax.conv_general_dilated(
        cast(img[None]), cast(w), (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jnp.mean(jax.nn.relu(x), axis=-1)[0]


def _offsets(shape_heat, shape_img):
    """Top-left corner of the crop each heat-map position chooses."""
    hh, hw = shape_heat
    oy = np.clip(np.arange(hh) * 2 - CROP // 2, 0, shape_img[0] - CROP)
    ox = np.clip(np.arange(hw) * 2 - CROP // 2, 0, shape_img[1] - CROP)
    return oy, ox


def apply(w, img, cast):
    """The crop the reference chooses, its inputs rounded by ``cast``."""
    h = np.asarray(heat(w, jnp.asarray(img), cast), np.float64)
    iy, ix = np.unravel_index(int(np.argmax(h)), h.shape)
    oy, ox = _offsets(h.shape, img.shape)
    return np.asarray(img)[oy[iy]:oy[iy] + CROP, ox[ix]:ox[ix] + CROP]


def gap(h_ref: np.ndarray, img: np.ndarray, crop: np.ndarray) -> float:
    """Relative gap of ``crop`` in the reference heat map ``h_ref``; 1.0
    when ``crop`` is no crop the detector could have chosen."""
    h_ref = np.asarray(h_ref, np.float64)
    img, crop = np.asarray(img), np.asarray(crop)
    oy, ox = _offsets(h_ref.shape, img.shape)
    best = float(h_ref.max())
    uy, ux = np.unique(oy), np.unique(ox)
    hits = np.all(img[np.ix_(uy, ux)] == crop[0, 0], axis=-1)
    found = None
    for a, b in zip(*np.nonzero(hits)):
        y, x = uy[a], ux[b]
        if np.array_equal(img[y:y + CROP, x:x + CROP], crop):
            found = (y, x)
            break
    if found is None:
        return 1.0
    at = h_ref[np.ix_(oy == found[0], ox == found[1])]
    return float((best - at.max()) / abs(best))
