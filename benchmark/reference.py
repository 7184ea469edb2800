"""The plain reference: RAFT's forward pass in straight ``jax.numpy``.

Transcribed from the paper (Teed & Deng, ECCV 2020) and the official
architecture as ``tests/torch_raft_golden.py`` restates it, NOT from
``raft_tpu/models``: float32, matrix products at ``highest`` precision, the
dense all-pairs volume, a gather for the bilinear window lookup, plain
convolutional GRUs, no kernels, no batching, no cache.  It imports nothing of
the program and takes only what the benchmark made: the seeded weights
(weights.py) and the seeded frames (inputs.py).

One pair at a time: ``flow(weights, image1, image2)`` with ``uint8``
``[H, W, 3]`` frames returns the ``[H, W, 2]`` float32 flow after ``iters``
updates.  Frames are replicate-padded to a multiple of 8, the padding split
between both sides as the official ``InputPadder`` does for Sintel, and the
flow is cropped back.

``precision`` is the knob of the CONTROL, not of the reference: 'float32' is
the reference; 'bfloat16' rounds every convolution's operands to bfloat16
(what the served configuration states); 'float8' rounds them to e4m3 with a
per-tensor scale (the step below bfloat16 that a later PR might be tempted
by) and is what the output check has to tell from the served answer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


# ------------------------------------------------------------ quantisation

def _quantiser(precision: str):
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        def q(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return q
    raise ValueError(f"precision {precision!r}")


# ------------------------------------------------------------------ layers

def _conv(q, p, x, stride=1):
    w = p["w"]
    kh, kw = w.shape[0], w.shape[1]
    y = lax.conv_general_dilated(
        q(x), q(w), (stride, stride),
        ((kh // 2, kh // 2), (kw // 2, kw // 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y + p["b"]


def _instance_norm(x, eps=1e-5):
    mean = x.mean(axis=(1, 2), keepdims=True)
    var = ((x - mean) ** 2).mean(axis=(1, 2), keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


def _batch_norm(p, x, eps=1e-5):          # eval mode: running statistics
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["gamma"] + p["beta"]


def _norm(kind, p, x):
    if kind == "instance":
        return _instance_norm(x)
    if kind == "batch":
        return _batch_norm(p, x)
    return x


def _block(q, p, x, kind, stride, bottleneck):
    relu = jax.nn.relu
    if bottleneck:
        y = relu(_norm(kind, p.get("norm1"), _conv(q, p["conv1"], x)))
        y = relu(_norm(kind, p.get("norm2"), _conv(q, p["conv2"], y, stride)))
        y = relu(_norm(kind, p.get("norm3"), _conv(q, p["conv3"], y)))
    else:
        y = relu(_norm(kind, p.get("norm1"), _conv(q, p["conv1"], x, stride)))
        y = relu(_norm(kind, p.get("norm2"), _conv(q, p["conv2"], y)))
    if stride != 1:
        ds = p["downsample"]
        x = _norm(kind, ds.get("1"), _conv(q, ds["0"], x, stride))
    return relu(x + y)


def _encoder(q, p, x, kind, small):
    x = jax.nn.relu(_norm(kind, p.get("norm1"), _conv(q, p["conv1"], x, 2)))
    for li, stride in ((1, 1), (2, 2), (3, 2)):
        layer = p[f"layer{li}"]
        x = _block(q, layer["0"], x, kind, stride, small)
        x = _block(q, layer["1"], x, kind, 1, small)
    return _conv(q, p["conv2"], x)


# ------------------------------------------------------------- correlation

def _corr_pyramid(fmap1, fmap2, levels):
    """fmap [h, w, C] -> list of [Q, h/2^i, w/2^i] all-pairs volumes."""
    h, w, c = fmap1.shape
    f1 = fmap1.reshape(h * w, c)
    f2 = fmap2.reshape(h * w, c)
    corr = jnp.matmul(f1, f2.T, precision=HIGHEST) / jnp.sqrt(float(c))
    corr = corr.reshape(h * w, h, w)
    pyramid = [corr]
    for _ in range(levels - 1):
        hh, ww = corr.shape[1] // 2, corr.shape[2] // 2
        corr = corr[:, :hh * 2, :ww * 2].reshape(-1, hh, 2, ww, 2).mean((2, 4))
        pyramid.append(corr)
    return pyramid


def _sample_zeros(vol, x, y):
    """Bilinear samples of vol [Q, H, W] at pixel coordinates x, y [Q, n, n]
    (align_corners, zeros outside)."""
    Q, H, W = vol.shape
    x0, y0 = jnp.floor(x), jnp.floor(y)
    fx, fy = x - x0, y - y0
    x0, y0 = x0.astype(jnp.int32), y0.astype(jnp.int32)
    qi = jnp.arange(Q)[:, None, None]

    def at(yi, xi):
        ok = (xi >= 0) & (xi < W) & (yi >= 0) & (yi < H)
        v = vol[qi, jnp.clip(yi, 0, H - 1), jnp.clip(xi, 0, W - 1)]
        return jnp.where(ok, v, 0.0)

    return ((1 - fy) * (1 - fx) * at(y0, x0) + (1 - fy) * fx * at(y0, x0 + 1)
            + fy * (1 - fx) * at(y0 + 1, x0) + fy * fx * at(y0 + 1, x0 + 1))


def _lookup(pyramid, coords, radius):
    """coords [h, w, 2] (x, y) -> [h, w, L*(2r+1)^2].  The window is
    enumerated x-offset-major, as the official code does (it adds the
    (dy, dx) meshgrid to (x, y) coordinates) and released weights assume."""
    h, w, _ = coords.shape
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    flat = coords.reshape(h * w, 2)
    out = []
    for i, vol in enumerate(pyramid):
        cx = flat[:, 0, None, None] / 2 ** i + d[None, :, None]   # first axis
        cy = flat[:, 1, None, None] / 2 ** i + d[None, None, :]   # second axis
        cx, cy = jnp.broadcast_arrays(cx, cy)
        out.append(_sample_zeros(vol, cx, cy).reshape(h * w, -1))
    return jnp.concatenate(out, axis=-1).reshape(h, w, -1)


# ------------------------------------------------------------ update block

def _gru_gate(q, p, names, h, x):
    cz, cr, cq = names
    hx = jnp.concatenate([h, x], -1)
    z = jax.nn.sigmoid(_conv(q, p[cz], hx))
    r = jax.nn.sigmoid(_conv(q, p[cr], hx))
    c = jnp.tanh(_conv(q, p[cq], jnp.concatenate([r * h, x], -1)))
    return (1 - z) * h + z * c


def _update(q, p, small, net, inp, corr, flow):
    relu = jax.nn.relu
    e = p["encoder"]
    cor = relu(_conv(q, e["convc1"], corr))
    if not small:
        cor = relu(_conv(q, e["convc2"], cor))
    flo = relu(_conv(q, e["convf1"], flow))
    flo = relu(_conv(q, e["convf2"], flo))
    out = relu(_conv(q, e["conv"], jnp.concatenate([cor, flo], -1)))
    motion = jnp.concatenate([out, flow], -1)
    x = jnp.concatenate([inp, motion], -1)
    if small:
        net = _gru_gate(q, p["gru"], ("convz", "convr", "convq"), net, x)
    else:
        net = _gru_gate(q, p["gru"], ("convz1", "convr1", "convq1"), net, x)
        net = _gru_gate(q, p["gru"], ("convz2", "convr2", "convq2"), net, x)
    fh = p["flow_head"]
    delta = _conv(q, fh["conv2"], relu(_conv(q, fh["conv1"], net)))
    mask = None
    if not small:
        m = p["mask"]
        mask = 0.25 * _conv(q, m["2"], relu(_conv(q, m["0"], net)))
    return net, mask, delta


# --------------------------------------------------------------- upsampling

def _convex_upsample(flow, mask):
    """flow [1, h, w, 2], mask [1, h, w, 576] -> [8h, 8w, 2]."""
    _, h, w, _ = flow.shape
    m = jax.nn.softmax(mask[0].reshape(h, w, 9, 8, 8), axis=2)
    fp = jnp.pad(8.0 * flow[0], ((1, 1), (1, 1), (0, 0)))
    taps = jnp.stack([fp[dy:dy + h, dx:dx + w] for dy in range(3)
                      for dx in range(3)], axis=2)           # [h, w, 9, 2]
    up = jnp.einsum("hwkrc,hwkd->hrwcd", m, taps, precision=HIGHEST)
    return up.reshape(8 * h, 8 * w, 2)


def _upflow8(flow):
    """8x bilinear (align_corners) upsampling, values times 8."""
    _, h, w, _ = flow.shape
    f = flow[0]

    def lerp_axis(a, n_in, axis):
        n_out = 8 * n_in
        pos = jnp.arange(n_out, dtype=jnp.float32) * ((n_in - 1) / (n_out - 1))
        i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, n_in - 2)
        t = pos - i0
        shape = [1] * a.ndim
        shape[axis] = n_out
        t = t.reshape(shape)
        return (jnp.take(a, i0, axis) * (1 - t)
                + jnp.take(a, i0 + 1, axis) * t)

    return 8.0 * lerp_axis(lerp_axis(f, h, 0), w, 1)


# -------------------------------------------------------------------- model

def pad_amounts(h: int, w: int):
    ph, pw = (-h) % 8, (-w) % 8
    return ph // 2, ph - ph // 2, pw // 2, pw - pw // 2


def _forward(weights, image1, image2, *, small, hidden, levels, radius,
             iters, precision):
    q = _quantiser(precision)
    H, W, _ = image1.shape
    t, b, l, r = pad_amounts(H, W)

    def prep(im):
        x = 2.0 * (im.astype(jnp.float32) / 255.0) - 1.0
        return jnp.pad(x, ((t, b), (l, r), (0, 0)), mode="edge")[None]

    x1, x2 = prep(image1), prep(image2)
    fmap1 = _encoder(q, weights["fnet"], x1, "instance", small)[0]
    fmap2 = _encoder(q, weights["fnet"], x2, "instance", small)[0]
    if precision != "float32":
        # the volume's operands are what the lower precision would store
        fmap1, fmap2 = q(fmap1), q(fmap2)
    pyramid = _corr_pyramid(fmap1, fmap2, levels)

    cnet = _encoder(q, weights["cnet"], x1, "none" if small else "batch", small)
    net = jnp.tanh(cnet[..., :hidden])
    inp = jax.nn.relu(cnet[..., hidden:])

    h, w = fmap1.shape[:2]
    xs, ys = jnp.meshgrid(jnp.arange(w, dtype=jnp.float32),
                          jnp.arange(h, dtype=jnp.float32), indexing="xy")
    coords0 = jnp.stack([xs, ys], -1)                        # [h, w, 2] (x, y)

    def step(carry, _):
        net, coords1, _ = carry
        corr = _lookup(pyramid, coords1, radius)[None]
        flow = (coords1 - coords0)[None]
        net, mask, delta = _update(q, weights["update_block"], small, net,
                                   inp, corr, flow)
        coords1 = coords1 + delta[0]
        if mask is None:
            mask = jnp.zeros((1, h, w, 0), jnp.float32)
        return (net, coords1, mask), None

    mask0 = jnp.zeros((1, h, w, 0 if small else 576), jnp.float32)
    (net, coords1, mask), _ = lax.scan(step, (net, coords0, mask0), None,
                                       length=iters)
    flow_lr = (coords1 - coords0)[None]
    up = _upflow8(flow_lr) if small else _convex_upsample(flow_lr, mask)
    Hp, Wp = up.shape[:2]
    return up[t:Hp - b, l:Wp - r]


@functools.lru_cache(maxsize=None)
def _compiled(small, hidden, levels, radius, iters, precision):
    fn = functools.partial(_forward, small=small, hidden=hidden, levels=levels,
                           radius=radius, iters=iters, precision=precision)
    return jax.jit(fn)


def flow(weights, image1, image2, cfg: dict, iters: int,
         precision: str = "float32"):
    """[H, W, 2] float32 flow of one ``uint8`` pair.  ``cfg`` holds ``small``,
    ``hidden_dim``, ``corr_levels`` and ``corr_radius``."""
    fn = _compiled(bool(cfg["small"]), int(cfg["hidden_dim"]),
                   int(cfg["corr_levels"]), int(cfg["corr_radius"]),
                   int(iters), precision)
    with jax.default_matmul_precision("highest"):
        return fn(weights, jnp.asarray(image1), jnp.asarray(image2))
