"""Video frames for the benchmark: road scenes whose objects drift.

A copy of the synthetic road-scene renderer of ``repro.data.
synthetic_detection`` (sky/road gradient, textured patches, 1–12 objects
with the IVS-3cls class mix and per-class sizes), kept here so that a change
to the program cannot change what is measured. What the copy adds: every
object moves a few pixels per frame, so consecutive frames of the pool form
a video, and each frame carries fresh sensor noise.

Frames are float32 on the uint8 grid (k/255), as the program's synthetic
streams are, so the 8-bit encode layer sees them exactly.
"""
from __future__ import annotations

import numpy as np

CLASS_P = np.array([0.55, 0.22, 0.23])
# per-class (mean area as a share of the frame, aspect w/h)
SIZE_STATS = {0: (0.015, 1.9), 1: (0.004, 0.7), 2: (0.003, 0.45)}
SHADE = {0: (0.15, 0.25, 0.55), 1: (0.55, 0.2, 0.2), 2: (0.2, 0.5, 0.25)}


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and optional salts."""
    return np.random.default_rng([seed % 2**64, *salt])


def _scene(rng, hw, max_drift_px: float):
    """Static background, objects, their shades and velocities (px/frame)."""
    h, w = hw
    sky = np.linspace(0.65, 0.25, h, dtype=np.float32)[:, None, None]
    bg = np.repeat(np.repeat(sky, w, axis=1), 3, axis=2).copy()
    for _ in range(6):  # low-frequency texture (buildings, road patches)
        x0, y0 = rng.integers(0, w - 8), rng.integers(0, h - 8)
        ww, hh = rng.integers(8, w // 2), rng.integers(8, h // 2)
        bg[y0:y0 + hh, x0:x0 + ww] += rng.uniform(-0.15, 0.15)
    n_obj = int(rng.integers(1, 13))
    objects = []
    for c in rng.choice(3, size=n_obj, p=CLASS_P):
        area, aspect = SIZE_STATS[int(c)]
        a = float(np.exp(rng.normal(np.log(area), 0.6)))
        bh = min(float(np.sqrt(a / aspect)), 0.6)
        bw = min(float(a / max(bh, 1e-6)), 0.6)
        cx = float(rng.uniform(bw / 2, 1 - bw / 2))
        cy = float(rng.uniform(max(bh / 2, 0.33), 1 - bh / 2))  # on the road
        shade = np.asarray(SHADE[int(c)], np.float32) + rng.normal(0, 0.03, 3)
        vel = rng.uniform(-max_drift_px, max_drift_px, 2)  # (dx, dy) px/frame
        objects.append(((cx, cy, bw, bh), shade.astype(np.float32), vel))
    return bg, objects


def render_pool(seed: int, n_frames: int, hw, *, max_drift_px: float = 4.0,
                noise: float = 0.05) -> np.ndarray:
    """``n_frames`` consecutive frames of one scene drawn from ``seed``:
    (n_frames, H, W, 3) float32 on the uint8 grid, one contiguous array."""
    h, w = hw
    rng = seed_rng(seed, 1)
    bg, objects = _scene(rng, hw, max_drift_px)
    pool = np.empty((n_frames, h, w, 3), np.float32)
    for f in range(n_frames):
        img = bg + rng.standard_normal((h, w, 3), dtype=np.float32) * noise
        for (cx, cy, bw, bh), shade, (dx, dy) in objects:
            x0 = int(max(0, (cx - bw / 2) * w + dx * f))
            x1 = int(min(w, (cx + bw / 2) * w + dx * f))
            y0 = int(max(0, (cy - bh / 2) * h + dy * f))
            y1 = int(min(h, (cy + bh / 2) * h + dy * f))
            if x1 > x0 and y1 > y0:
                img[y0:y1, x0:x1] = shade
        np.clip(img, 0.0, 1.0, out=img)
        pool[f] = np.round(img * 255.0) / 255.0
    return pool


class ClipSource:
    """Clips of one stream: contiguous views ``pool[o:o+F]`` of the frame
    pool, so building a clip copies nothing. The first clip of stream i has
    ``clip_frames - stagger * i`` frames, so the streams' clip ends fall on
    different ticks; every later clip has ``clip_frames``. Offsets are drawn
    from (seed, stream)."""

    def __init__(self, pool: np.ndarray, seed: int, stream: int, *,
                 clip_frames: int, stagger: int):
        self.pool = pool
        self.rng = seed_rng(seed, 2, stream)
        self.next_len = clip_frames - stagger * stream
        self.clip_frames = clip_frames
        if not 1 <= self.next_len <= clip_frames <= len(pool):
            raise ValueError(
                f"stream {stream}: first clip of {self.next_len} frames, "
                f"clips of {clip_frames}, pool of {len(pool)}")

    def next_clip(self) -> np.ndarray:
        n = self.next_len
        self.next_len = self.clip_frames
        o = int(self.rng.integers(0, len(self.pool) - n + 1))
        return self.pool[o:o + n]
