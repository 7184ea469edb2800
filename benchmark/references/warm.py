"""The reference ``warm``: RAFT's video protocol, one frame pair at a time.

Teed & Deng (ECCV 2020, arXiv:2003.12039) evaluate Sintel's scenes with a
"warm start": frame t's 1/8-resolution flow, projected forward along itself,
is where frame t+1's recurrence begins (upstream ``evaluate.py::
create_sintel_submission(warm_start=True)`` and ``core/utils/utils.py::
forward_interpolate``).  This file is that protocol in plain ``jax.numpy``,
``numpy`` and (as upstream's projection) ``scipy``: ``reference.py``'s layers
(float32, products at ``highest``, the dense all-pairs volume, no kernels, no
cache), the recurrence started at ``coords0 + flow_init``, and the
projection.  It imports nothing of the program.

``flow(weights, image1, image2, cfg, iters, precision, flow_init=None)``
returns ``(flow [H, W, 2], flow_lr [h, w, 2])``: the answer and the 1/8 flow
that :func:`forward_interpolate` turns into the next call's ``flow_init``.
The traffic mix's driver walks a session's frames through both
(``drivers/sessions.py``); ``check.forward`` passes ``flow_init`` on.

Departures from upstream, each on purpose:

* upstream scatters the UNROUNDED targets and lets ``scipy.interpolate.
  griddata(..., method='nearest')`` pick, per grid point, the nearest of
  them; here, as in the program, every pixel carries its flow to its ROUNDED
  target and pixels that share a target are averaged (what a nearest pick
  among them would choose arbitrarily).  Targets outside the frame are
  discarded by upstream's strict test on the unrounded target;
* grid points that no pixel lands on take the flow of the EXACT nearest hit
  (Euclidean, a k-d tree's; ties to the first in row-major order), which is
  what ``griddata`` does for them.  The program fills them by OpenCV's 3x3-mask
  distance transform (``raft_tpu/utils/frame_utils.py``), which is
  approximate: a tier-1 test counts the pixels where the two fills differ.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from scipy.spatial import cKDTree

import reference as ref


def forward_interpolate(flow_lr) -> np.ndarray:
    """``[h, w, 2]`` (x, y) flow projected forward along itself, float32."""
    f = np.asarray(flow_lr, np.float64)
    h, w = f.shape[:2]
    ys, xs = np.mgrid[0:h, 0:w]
    tx, ty = xs + f[..., 0], ys + f[..., 1]
    keep = (tx > 0) & (tx < w) & (ty > 0) & (ty < h)
    if not keep.any():
        return np.zeros((h, w, 2), np.float32)
    col = np.clip(np.rint(tx[keep]), 0, w - 1).astype(np.int64)
    row = np.clip(np.rint(ty[keep]), 0, h - 1).astype(np.int64)
    target = row * w + col
    count = np.bincount(target, minlength=h * w)
    out = np.stack([np.bincount(target, weights=f[..., c][keep],
                                minlength=h * w) for c in (0, 1)], -1)
    hit = count > 0
    out[hit] /= count[hit, None]
    # the unhit grid points: the exact nearest hit (a k-d tree's; of hits at
    # one distance the first in row-major order, looked for among the 16
    # nearest: a circle through more lattice points is wider than any hole)
    miss = np.flatnonzero(~hit)
    if len(miss):
        hit_rc = np.argwhere(hit.reshape(h, w))
        dist, near = cKDTree(hit_rc).query(
            np.stack([miss // w, miss % w], -1), k=min(16, len(hit_rc)))
        dist, near = dist.reshape(len(miss), -1), near.reshape(len(miss), -1)
        tied = dist <= dist[:, :1] + 1e-9
        out[miss] = out[hit][np.where(tied, near, len(hit_rc)).min(axis=1)]
    return out.reshape(h, w, 2).astype(np.float32)


def _forward(weights, image1, image2, flow_init, *, small, hidden, levels,
             radius, iters, precision):
    """``reference._forward`` with the recurrence started at ``coords0 +
    flow_init`` ([h, w, 2], on the padded frame's 1/8 grid); returns the
    cropped full-resolution flow and the 1/8 flow."""
    q = ref._quantiser(precision)
    H, W, _ = image1.shape
    t, b, l, r = ref.pad_amounts(H, W)

    def prep(im):
        x = 2.0 * (im.astype(jnp.float32) / 255.0) - 1.0
        return jnp.pad(x, ((t, b), (l, r), (0, 0)), mode="edge")[None]

    x1, x2 = prep(image1), prep(image2)
    fmap1 = ref._encoder(q, weights["fnet"], x1, "instance", small)[0]
    fmap2 = ref._encoder(q, weights["fnet"], x2, "instance", small)[0]
    if precision != "float32":
        # the volume's operands are what the lower precision would store
        fmap1, fmap2 = q(fmap1), q(fmap2)
    pyramid = ref._corr_pyramid(fmap1, fmap2, levels)

    cnet = ref._encoder(q, weights["cnet"], x1, "none" if small else "batch",
                        small)
    net = jnp.tanh(cnet[..., :hidden])
    inp = jax.nn.relu(cnet[..., hidden:])

    h, w = fmap1.shape[:2]
    xs, ys = jnp.meshgrid(jnp.arange(w, dtype=jnp.float32),
                          jnp.arange(h, dtype=jnp.float32), indexing="xy")
    coords0 = jnp.stack([xs, ys], -1)                        # [h, w, 2] (x, y)

    def step(carry, _):
        net, coords1, _ = carry
        corr = ref._lookup(pyramid, coords1, radius)[None]
        flow = (coords1 - coords0)[None]
        net, mask, delta = ref._update(q, weights["update_block"], small, net,
                                       inp, corr, flow)
        coords1 = coords1 + delta[0]
        if mask is None:
            mask = jnp.zeros((1, h, w, 0), jnp.float32)
        return (net, coords1, mask), None

    mask0 = jnp.zeros((1, h, w, 0 if small else 576), jnp.float32)
    (net, coords1, mask), _ = lax.scan(
        step, (net, coords0 + flow_init, mask0), None, length=iters)
    flow_lr = (coords1 - coords0)[None]
    up = (ref._upflow8(flow_lr) if small
          else ref._convex_upsample(flow_lr, mask))
    Hp, Wp = up.shape[:2]
    return up[t:Hp - b, l:Wp - r], flow_lr[0]


@functools.lru_cache(maxsize=None)
def _compiled(small, hidden, levels, radius, iters, precision):
    fn = functools.partial(_forward, small=small, hidden=hidden, levels=levels,
                           radius=radius, iters=iters, precision=precision)
    return jax.jit(fn)


def flow(weights, image1, image2, cfg: dict, iters: int,
         precision: str = "float32", flow_init=None):
    """``(flow [H, W, 2], flow_lr [h, w, 2])``, float32, of one ``uint8``
    pair with the recurrence started at ``flow_init`` ([h, w, 2] on the 1/8
    grid of the frame padded to a multiple of 8; None: zeros, which is
    ``reference.flow``).  ``cfg`` as ``reference.flow`` takes it."""
    fn = _compiled(bool(cfg["small"]), int(cfg["hidden_dim"]),
                   int(cfg["corr_levels"]), int(cfg["corr_radius"]),
                   int(iters), precision)
    H, W = image1.shape[:2]
    if flow_init is None:
        flow_init = np.zeros(((H + 7) // 8, (W + 7) // 8, 2), np.float32)
    with jax.default_matmul_precision("highest"):
        return fn(weights, jnp.asarray(image1), jnp.asarray(image2),
                  jnp.asarray(flow_init, jnp.float32))
