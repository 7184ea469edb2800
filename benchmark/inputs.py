"""Seeded frames: textured image pairs with a known rigid shift.

A canvas of smooth multi-octave noise (image-like statistics, not white
noise) is drawn once from the seed; each pair is two crops of it, the second
displaced by a few pixels and given a little sensor noise.  ``uint8``
``[H, W, 3]``, as a camera or a decoder would hand them to a client.  The
same seed gives the same frames; the program sees only the frames.
"""

from __future__ import annotations

import io

import numpy as np


def _upsample(grid: np.ndarray, h: int, w: int) -> np.ndarray:
    """Bilinear resize of [gh, gw, c] to [h, w, c]."""
    gh, gw = grid.shape[:2]
    ys = np.linspace(0, gh - 1, h, dtype=np.float32)
    xs = np.linspace(0, gw - 1, w, dtype=np.float32)
    y0 = np.minimum(ys.astype(np.int32), gh - 2)
    x0 = np.minimum(xs.astype(np.int32), gw - 2)
    ty = (ys - y0)[:, None, None]
    tx = (xs - x0)[None, :, None]
    rows = grid[y0] * (1 - ty) + grid[y0 + 1] * ty          # [h, gw, c]
    return rows[:, x0] * (1 - tx) + rows[:, x0 + 1] * tx


def make_canvas(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    out = np.zeros((h, w, 3), np.float32)
    amp = 1.0
    for cell in (96, 48, 24, 12, 6, 3):
        grid = rng.random((h // cell + 2, w // cell + 2, 3), dtype=np.float32)
        out += amp * _upsample(grid, h, w)
        amp *= 0.6
    out -= out.min()
    return out / out.max()


def make_pairs(seed: int, n: int, height: int, width: int,
               max_shift: int = 6) -> list:
    """``n`` distinct (frame1, frame2) ``uint8`` pairs from ``seed``."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    margin = max_shift + 1
    canvas = make_canvas(rng, height + 2 * margin + 64,
                         width + 2 * margin + 64)
    pairs = []
    for _ in range(n):
        oy = int(rng.integers(margin, margin + 64))
        ox = int(rng.integers(margin, margin + 64))
        dy = int(rng.integers(-max_shift // 2, max_shift // 2 + 1))
        dx = int(rng.integers(-max_shift, max_shift + 1))
        frames = []
        for y, x in ((oy, ox), (oy + dy, ox + dx)):
            f = canvas[y:y + height, x:x + width]
            f = f + rng.normal(0.0, 0.01, f.shape).astype(np.float32)
            frames.append(np.clip(f * 255.0 + 0.5, 0, 255).astype(np.uint8))
        pairs.append(tuple(frames))
    return pairs


def npz_body(**arrays) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def npz_load(payload: bytes) -> dict:
    with np.load(io.BytesIO(payload)) as z:
        return {k: np.asarray(z[k]) for k in z.files}
