"""Iterative update blocks: motion encoders, ConvGRU / SepConvGRU, flow head,
and the convex-upsampling mask head.

Functional re-design of reference networks/model_utils.py:110-194 with the
official RAFT channel plan; parameter dict keys mirror the official
state_dict segments (``update_block.encoder.convc1`` etc.).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.conv import apply_conv, apply_conv_fused, conv2d, init_conv
from ..telemetry.trace import stage


# ---------------------------------------------------------- motion encoders

def init_basic_motion_encoder(key, corr_dim: int) -> dict:
    k = jax.random.split(key, 5)
    return {
        "convc1": init_conv(k[0], 1, corr_dim, 256),
        "convc2": init_conv(k[1], 3, 256, 192),
        "convf1": init_conv(k[2], 7, 2, 128),
        "convf2": init_conv(k[3], 3, 128, 64),
        "conv": init_conv(k[4], 3, 192 + 64, 128 - 2),
    }


def apply_basic_motion_encoder(p: dict, flow: jax.Array, corr: jax.Array) -> jax.Array:
    cor = jax.nn.relu(apply_conv(p["convc1"], corr))
    cor = jax.nn.relu(apply_conv(p["convc2"], cor))
    flo = jax.nn.relu(apply_conv(p["convf1"], flow))
    flo = jax.nn.relu(apply_conv(p["convf2"], flo))
    out = jax.nn.relu(apply_conv(p["conv"], jnp.concatenate([cor, flo], -1)))
    return jnp.concatenate([out, flow], -1)          # 128 channels


def init_small_motion_encoder(key, corr_dim: int) -> dict:
    k = jax.random.split(key, 4)
    return {
        "convc1": init_conv(k[0], 1, corr_dim, 96),
        "convf1": init_conv(k[1], 7, 2, 64),
        "convf2": init_conv(k[2], 3, 64, 32),
        "conv": init_conv(k[3], 3, 96 + 32, 80),
    }


def apply_small_motion_encoder(p: dict, flow: jax.Array, corr: jax.Array) -> jax.Array:
    cor = jax.nn.relu(apply_conv(p["convc1"], corr))
    flo = jax.nn.relu(apply_conv(p["convf1"], flow))
    flo = jax.nn.relu(apply_conv(p["convf2"], flo))
    out = jax.nn.relu(apply_conv(p["conv"], jnp.concatenate([cor, flo], -1)))
    return jnp.concatenate([out, flow], -1)          # 82 channels


# ------------------------------------------------------------------- GRUs

def init_sep_conv_gru(key, hidden: int, input_dim: int) -> dict:
    k = jax.random.split(key, 6)
    hx = hidden + input_dim
    return {
        "convz1": init_conv(k[0], (1, 5), hx, hidden),
        "convr1": init_conv(k[1], (1, 5), hx, hidden),
        "convq1": init_conv(k[2], (1, 5), hx, hidden),
        "convz2": init_conv(k[3], (5, 1), hx, hidden),
        "convr2": init_conv(k[4], (5, 1), hx, hidden),
        "convq2": init_conv(k[5], (5, 1), hx, hidden),
    }


def apply_sep_conv_gru(p: dict, h: jax.Array, x: jax.Array) -> jax.Array:
    for suffix in ("1", "2"):        # horizontal (1x5) then vertical (5x1)
        hx = jnp.concatenate([h, x], -1)
        # z and r read the same input -> one fused conv (exact; see
        # apply_conv_fused)
        zc, rc = apply_conv_fused((p["convz" + suffix], p["convr" + suffix]), hx)
        z = jax.nn.sigmoid(zc)
        r = jax.nn.sigmoid(rc)
        q = jnp.tanh(apply_conv(p["convq" + suffix], jnp.concatenate([r * h, x], -1)))
        h = (1.0 - z) * h + z * q
    return h


# --------------------------- context hoisting (config.gru_ctx_hoist)
#
# Every gate conv reads hx = [h, inp, motion] (or [r*h, inp, motion] for q),
# and `inp` — the context-encoder features — never changes across GRU
# iterations.  Convolution is linear over input-channel blocks, so
#   conv(hx, W) = conv([h, motion], W_without_inp_cols) + conv(inp, W_inp) + b
# and the second term (plus the bias) can be computed ONCE before the
# lax.scan.  This removes the inp third of every gate conv's contraction
# from the loop body — exact, parameter-layout-untouched (kernels are
# sliced at apply time, like apply_conv_fused's concatenation).

_SEP_GATES = ("convz1", "convr1", "convq1", "convz2", "convr2", "convq2")
_GATES = ("convz", "convr", "convq")


def precompute_gru_ctx(p: dict, inp: jax.Array, hidden: int,
                       small: bool = False) -> dict:
    """The gate convs' terms over the loop-invariant context features.

    The returned terms carry the gate biases, so the in-loop convs run
    bias-free.  hx channel layout is [h (hidden), inp (ctx), motion]; the
    inp block is kernel columns [hidden : hidden + ctx).  Gates sharing a
    kernel shape read the same input, so each shape group runs as ONE
    fused conv (apply_conv_fused): z1/r1/q1 (1x5), z2/r2/q2 (5x1), or all
    three 3x3 gates of the small variant.
    """
    lo, hi = hidden, hidden + inp.shape[-1]

    def sliced(name: str) -> dict:
        q = {"w": p[name]["w"][:, :, lo:hi, :]}
        if "b" in p[name]:
            q["b"] = p[name]["b"]
        return q

    groups = ((_GATES,) if small
              else (_SEP_GATES[:3], _SEP_GATES[3:]))
    out = {}
    for names in groups:
        terms = apply_conv_fused([sliced(n) for n in names], inp)
        out.update(dict(zip(names, terms)))
    return out


def _gate_loop_w(w: jax.Array, hidden: int, ctx_dim: int) -> jax.Array:
    """Gate kernel with the context input-channel block removed (the in-loop
    input is [h, motion]).  Loop-invariant; XLA hoists the concatenation."""
    return jnp.concatenate([w[:, :, :hidden, :], w[:, :, hidden + ctx_dim:, :]],
                           axis=2)


def _hoisted_gate_step(p: dict, names: Tuple[str, str, str], h: jax.Array,
                       motion: jax.Array, ctx: dict, hidden: int,
                       ctx_dim: int) -> jax.Array:
    """One GRU gate pass with the context terms precomputed: fused z/r conv
    over [h, motion] (inp columns sliced out), ctx terms added back."""
    z_name, r_name, q_name = names
    hm = jnp.concatenate([h, motion], -1)
    wz = _gate_loop_w(p[z_name]["w"], hidden, ctx_dim)
    wr = _gate_loop_w(p[r_name]["w"], hidden, ctx_dim)
    zr = conv2d(hm, jnp.concatenate([wz, wr], axis=3))     # fused z/r
    z = jax.nn.sigmoid(zr[..., :hidden] + ctx[z_name])
    r = jax.nn.sigmoid(zr[..., hidden:] + ctx[r_name])
    wq = _gate_loop_w(p[q_name]["w"], hidden, ctx_dim)
    q = jnp.tanh(conv2d(jnp.concatenate([r * h, motion], -1), wq)
                 + ctx[q_name])
    return (1.0 - z) * h + z * q


def apply_sep_conv_gru_hoisted(p: dict, h: jax.Array, motion: jax.Array,
                               ctx: dict) -> jax.Array:
    """apply_sep_conv_gru with the context terms precomputed (exact)."""
    hidden = h.shape[-1]
    ctx_dim = p["convz1"]["w"].shape[2] - hidden - motion.shape[-1]
    for suffix in ("1", "2"):        # horizontal (1x5) then vertical (5x1)
        h = _hoisted_gate_step(
            p, ("convz" + suffix, "convr" + suffix, "convq" + suffix),
            h, motion, ctx, hidden, ctx_dim)
    return h


def apply_conv_gru_hoisted(p: dict, h: jax.Array, motion: jax.Array,
                           ctx: dict) -> jax.Array:
    """apply_conv_gru with the context terms precomputed (exact)."""
    hidden = h.shape[-1]
    ctx_dim = p["convz"]["w"].shape[2] - hidden - motion.shape[-1]
    return _hoisted_gate_step(p, _GATES, h, motion, ctx, hidden, ctx_dim)


def init_conv_gru(key, hidden: int, input_dim: int) -> dict:
    k = jax.random.split(key, 3)
    hx = hidden + input_dim
    return {
        "convz": init_conv(k[0], 3, hx, hidden),
        "convr": init_conv(k[1], 3, hx, hidden),
        "convq": init_conv(k[2], 3, hx, hidden),
    }


def apply_conv_gru(p: dict, h: jax.Array, x: jax.Array) -> jax.Array:
    hx = jnp.concatenate([h, x], -1)
    zc, rc = apply_conv_fused((p["convz"], p["convr"]), hx)
    z = jax.nn.sigmoid(zc)
    r = jax.nn.sigmoid(rc)
    q = jnp.tanh(apply_conv(p["convq"], jnp.concatenate([r * h, x], -1)))
    return (1.0 - z) * h + z * q


# ------------------------------------------------------------- flow / mask

def init_flow_head(key, in_dim: int, hidden: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {"conv1": init_conv(k1, 3, in_dim, hidden),
            "conv2": init_conv(k2, 3, hidden, 2)}


def apply_flow_head(p: dict, x: jax.Array) -> jax.Array:
    return apply_conv(p["conv2"], jax.nn.relu(apply_conv(p["conv1"], x)))


def init_mask_head(key, in_dim: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {"0": init_conv(k1, 3, in_dim, 256), "2": init_conv(k2, 1, 256, 64 * 9)}


# .25 mask scale as in official RAFT / reference; applied in
# apply_basic_update_block (the mask head's first conv is fused with the
# flow head's there).
MASK_SCALE = 0.25


# ------------------------------------------------------------ update blocks

def init_basic_update_block(key, corr_dim: int, hidden_dim: int = 128,
                            context_dim: int = 128) -> dict:
    k = jax.random.split(key, 4)
    return {
        "encoder": init_basic_motion_encoder(k[0], corr_dim),
        "gru": init_sep_conv_gru(k[1], hidden_dim, context_dim + 128),
        "flow_head": init_flow_head(k[2], hidden_dim, 256),
        "mask": init_mask_head(k[3], hidden_dim),
    }


def apply_basic_update_block(p: dict, net: jax.Array, inp: jax.Array,
                             corr: jax.Array, flow: jax.Array,
                             gru_ctx: Optional[dict] = None,
                             gru_impl: str = "xla",
                             gru_block_rows: int = 8
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    if gru_impl not in ("xla", "pallas"):
        # public entry point (models/__init__):
        # a typo must not quietly run the other GRU implementation
        raise ValueError(f"gru_impl must be 'xla' or 'pallas', "
                         f"got {gru_impl!r}")
    with stage("update/motion_encoder"):
        motion = apply_basic_motion_encoder(p["encoder"], flow, corr)
    if gru_impl == "pallas":
        # fused update-block kernel (ops/gru_pallas.py): one VMEM-resident
        # grid pass per iteration; requires the hoisted context terms
        # (raft_forward precomputes them whenever gru_impl='pallas', even
        # with gru_ctx_hoist off).  Lazy import: the XLA path must not pay
        # a Pallas import.
        if gru_ctx is None:
            raise ValueError("gru_impl='pallas' needs the hoisted context "
                             "terms: pass gru_ctx=precompute_gru_ctx(...)")
        from ..ops.gru_pallas import sep_conv_gru_pallas
        with stage("update/gru"):
            net = sep_conv_gru_pallas(p["gru"], net, motion, gru_ctx,
                                      block_rows=gru_block_rows)
    elif gru_ctx is not None:    # inp's gate-conv terms precomputed outside
        with stage("update/gru"):
            net = apply_sep_conv_gru_hoisted(p["gru"], net, motion, gru_ctx)
    else:
        x = jnp.concatenate([inp, motion], -1)
        with stage("update/gru"):
            net = apply_sep_conv_gru(p["gru"], net, x)
    # flow head conv1 and mask head [0] both read `net` with 3x3 kernels ->
    # one fused conv (exact), then each branch's own tail
    with stage("update/heads"):
        fh, mh = apply_conv_fused((p["flow_head"]["conv1"], p["mask"]["0"]),
                                  net)
        delta_flow = apply_conv(p["flow_head"]["conv2"], jax.nn.relu(fh))
        mask = MASK_SCALE * apply_conv(p["mask"]["2"], jax.nn.relu(mh))
    return net, mask, delta_flow


def init_small_update_block(key, corr_dim: int, hidden_dim: int = 96,
                            context_dim: int = 64) -> dict:
    k = jax.random.split(key, 3)
    return {
        "encoder": init_small_motion_encoder(k[0], corr_dim),
        "gru": init_conv_gru(k[1], hidden_dim, context_dim + 82),
        "flow_head": init_flow_head(k[2], hidden_dim, 128),
    }


def apply_small_update_block(p: dict, net: jax.Array, inp: jax.Array,
                             corr: jax.Array, flow: jax.Array,
                             gru_ctx: Optional[dict] = None
                             ) -> Tuple[jax.Array, Optional[jax.Array], jax.Array]:
    with stage("update/motion_encoder"):
        motion = apply_small_motion_encoder(p["encoder"], flow, corr)
    if gru_ctx is not None:      # inp's gate-conv terms precomputed outside
        with stage("update/gru"):
            net = apply_conv_gru_hoisted(p["gru"], net, motion, gru_ctx)
    else:
        x = jnp.concatenate([inp, motion], -1)
        with stage("update/gru"):
            net = apply_conv_gru(p["gru"], net, x)
    with stage("update/heads"):
        delta_flow = apply_flow_head(p["flow_head"], net)
    return net, None, delta_flow
