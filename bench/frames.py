"""Synthetic camera frames for the benchmark's traffic.

A copy of the frame generator the fleet streams (``FrameStream.frame_at``
in ``repro/data/pipeline.py``), kept here so that the yardstick's inputs
do not follow later changes to the program: one 224x224x3 float frame
with a bright blob (the face the detector stand-in finds) over noise,
a pure function of ``(seed, i)``.
"""
from __future__ import annotations

import numpy as np

HW = (224, 224)


def frame_at(seed: int, i: int, hw=HW) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, i]))
    h, w = hw
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    cx, cy = rng.uniform(0.2, 0.8, 2) * (w, h)
    r = rng.uniform(0.1, 0.3) * min(h, w)
    blob = np.exp(-(((xx - cx) ** 2 + (yy - cy) ** 2) / (2 * r * r)))
    img = rng.normal(0.5, 0.1, (h, w, 3)).astype(np.float32)
    img += blob[..., None] * rng.uniform(0.3, 0.8, 3).astype(np.float32)
    return np.clip(img, 0, 1)


def capture(seed: int, subject: int, shot: int, noise: float) -> np.ndarray:
    """Capture ``shot`` of ``subject``: the subject's frame with fresh
    sensor noise of standard deviation ``noise`` (shot 0 is the frame the
    subject was enrolled from, without noise)."""
    img = frame_at(seed, subject)
    if shot == 0 or noise == 0:
        return img
    rng = np.random.default_rng(np.random.SeedSequence([seed, subject, shot]))
    img = img + rng.normal(0.0, noise, img.shape).astype(np.float32)
    return np.clip(img, 0, 1)
