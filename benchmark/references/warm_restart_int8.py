"""The reference ``warm_restart_int8``: RAFT's video protocol for a session
whose previous frame's maps are STORED in int8.

``references/warm_restart.py`` is the protocol of a service that holds more
live sessions than device slots (Teed & Deng, ECCV 2020, arXiv:2003.12039, the
warm-start rows of the Sintel evaluation; a restart is the zero-seeded pair).
A service that wants twice the sessions a chip stores the previous frame's
maps in a narrower format, and that is a different answer, not a tolerance:
what such a service owes its client is written down here and nowhere in the
program's words.

This file is ``references/warm.py``'s forward pass (float32, products at
``highest``, the dense all-pairs volume, no kernels, no cache) with **ONE
departure**: frame 1's fnet map and frame 1's raw context-encoder output
(before the ``tanh`` / ``relu`` split) pass through the store's quantiser
before anything reads them.  For a map ``x[h, w, c]`` (float32; at a lower
``precision`` the map as that precision would hold it, ``reference.
_quantiser``'s rounding, taken to float32):

    s_c = max(max_{h,w} |x[h, w, c]|, 1e-12) / 127
    q   = clip(round_half_even(x / s_c), -127, 127)         (an int8 code)
    what is read back = q * s_c                             (float32)

one scale a channel and frame, symmetric, the absmax mapped to 127
(:func:`quantise` / :func:`stored`, plain ``jax.numpy``).  Frame 2's maps are
fresh from the encoder and are not stored before they are used; the seed
(``flow_init``) stays float32.  EVERY call quantises: an open's frame 0 is
frame 1 of the first advance, an advance's frame is frame 1 of the next, and a
cold restart encodes the kept frame anew and STILL stores it before the step
reads it (``restart=True`` drops the seed and nothing else).  It takes every
``precision`` that ``warm.py`` takes ('float32', 'bfloat16', the control's
'float8'): the lower precision's rounding first, then the same int8 step,
and what is read back is held at that precision again (the program casts the
dequantised row to its compute dtype).

``flow`` has ``warm_restart.flow``'s signature and ``walk`` is that file's,
so the churn driver walks a session through this reference unedited.  It
imports ``reference.py``, ``references/warm.py`` and ``warm_restart.walk``,
and nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import reference as ref
from references.warm import forward_interpolate  # noqa: F401  (the driver's)
from references.warm_restart import walk  # noqa: F401  (a session's walk)

# the largest code: 127 is int8's.  (The check's 4-bit control, PERF.md,
# reads this file with 7 here.)
LEVELS = 127


def quantise(x, levels: int = LEVELS):
    """``[h, w, c]`` float32 -> (int8 codes ``[h, w, c]``, float32 scales
    ``[c]``): symmetric, one scale a channel, the absmax mapped to
    ``levels``; an all-zero channel keeps a scale of 1e-12 / levels and
    reads back exact zeros."""
    x = x.astype(jnp.float32)
    scales = jnp.maximum(jnp.max(jnp.abs(x), axis=(0, 1)), 1e-12) / levels
    codes = jnp.clip(jnp.round(x / scales), -levels, levels)
    return codes.astype(jnp.int8), scales


def stored(x, levels: int = LEVELS):
    """What a slot gives back of the map ``x`` it was handed: float32."""
    codes, scales = quantise(x, levels)
    return codes.astype(jnp.float32) * scales


def _forward(weights, image1, image2, flow_init, *, small, hidden, levels,
             radius, iters, precision, codes):
    """``warm._forward`` with frame 1's two maps read back from the store
    (the two lines marked DEPARTURE); returns the cropped full-resolution
    flow and the 1/8 flow."""
    q = ref._quantiser(precision)
    H, W, _ = image1.shape
    t, b, l, r = ref.pad_amounts(H, W)

    def prep(im):
        x = 2.0 * (im.astype(jnp.float32) / 255.0) - 1.0
        return jnp.pad(x, ((t, b), (l, r), (0, 0)), mode="edge")[None]

    x1, x2 = prep(image1), prep(image2)
    fmap1 = ref._encoder(q, weights["fnet"], x1, "instance", small)[0]
    fmap2 = ref._encoder(q, weights["fnet"], x2, "instance", small)[0]
    cnet = ref._encoder(q, weights["cnet"], x1, "none" if small else "batch",
                        small)
    if precision != "float32":
        # the maps are what the lower precision would store
        fmap1, fmap2, cnet = q(fmap1), q(fmap2), q(cnet)
    # DEPARTURE: frame 1's maps come from a slot (and are held, read back,
    # at the precision again: the identity in float32)
    fmap1 = q(stored(fmap1, codes))
    cnet = q(stored(cnet[0], codes))[None]
    pyramid = ref._corr_pyramid(fmap1, fmap2, levels)

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
def _compiled(small, hidden, levels, radius, iters, precision, codes):
    fn = functools.partial(_forward, small=small, hidden=hidden, levels=levels,
                           radius=radius, iters=iters, precision=precision,
                           codes=codes)
    return jax.jit(fn)


def flow(weights, image1, image2, cfg: dict, iters: int,
         precision: str = "float32", flow_init=None, restart: bool = False):
    """``(flow [H, W, 2], flow_lr [h, w, 2])``, float32, of one ``uint8``
    pair whose frame 1 is read from an int8 slot, the recurrence started at
    ``flow_init`` ([h, w, 2] on the 1/8 grid of the padded frame; None:
    zeros); with ``restart`` the seed is dropped, whatever it holds, and the
    maps are stored all the same.  ``cfg`` as ``reference.flow`` takes it."""
    fn = _compiled(bool(cfg["small"]), int(cfg["hidden_dim"]),
                   int(cfg["corr_levels"]), int(cfg["corr_radius"]),
                   int(iters), precision, int(LEVELS))
    H, W = image1.shape[:2]
    if flow_init is None or restart:
        flow_init = np.zeros(((H + 7) // 8, (W + 7) // 8, 2), np.float32)
    with jax.default_matmul_precision("highest"):
        return fn(weights, jnp.asarray(image1), jnp.asarray(image2),
                  jnp.asarray(flow_init, jnp.float32))
