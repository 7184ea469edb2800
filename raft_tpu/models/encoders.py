"""Feature / context encoders (BasicEncoder, SmallEncoder).

Functional re-design of reference networks/model_utils.py:6-105: parameters
are nested dicts whose keys mirror the official PyTorch state_dict path
segments (``fnet.layer1.0.conv1.weight`` -> params['layer1']['0']['conv1']['w']),
which makes the checkpoint converter a pure name/layout map (SURVEY.md §3.4).

Norm modes per variant (reference RAFT.py:62-76):
  fnet: instance (affine-free)      cnet full: batch      cnet small: none
GroupNorm is also supported as a first-class NHWC op — in the reference it
was dead code with an NCHW bug (reference common/groupnorm.py, SURVEY.md §2).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.conv import apply_conv, init_conv
from ..ops.norm import batch_norm, group_norm, init_batch_norm, instance_norm
from ..telemetry.trace import stage


def _init_norm(norm_fn: str, c: int) -> Optional[dict]:
    if norm_fn == "batch":
        return init_batch_norm(c)
    if norm_fn == "group":
        p = init_batch_norm(c)
        return {"gamma": p["gamma"], "beta": p["beta"]}
    return None  # instance (affine-free) / none


def _apply_norm(norm_fn: str, params: Optional[dict], x: jax.Array,
                train: bool, axis_name: Optional[str]) -> Tuple[jax.Array, Optional[dict]]:
    if norm_fn == "instance":
        return instance_norm(x), params
    if norm_fn == "batch":
        return batch_norm(params, x, train=train, axis_name=axis_name)
    if norm_fn == "group":
        c = x.shape[-1]
        return group_norm(x, params["gamma"], params["beta"], num_groups=c // 8), params
    if norm_fn == "none":
        return x, params
    raise ValueError(norm_fn)


def _maybe(d: dict, key: str, val) -> None:
    if val is not None:
        d[key] = val


# ---------------------------------------------------------------- residual

def init_residual_block(key, c_in: int, c_out: int, norm_fn: str, stride: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "conv1": init_conv(k1, 3, c_in, c_out),
        "conv2": init_conv(k2, 3, c_out, c_out),
    }
    _maybe(p, "norm1", _init_norm(norm_fn, c_out))
    _maybe(p, "norm2", _init_norm(norm_fn, c_out))
    if stride != 1:
        p["downsample"] = {"0": init_conv(k3, 1, c_in, c_out)}
        _maybe(p["downsample"], "1", _init_norm(norm_fn, c_out))
    return p


def apply_residual_block(p: dict, x: jax.Array, norm_fn: str, stride: int,
                         train: bool, axis_name: Optional[str]) -> Tuple[jax.Array, dict]:
    p = dict(p)
    y = apply_conv(p["conv1"], x, stride=stride)
    y, n1 = _apply_norm(norm_fn, p.get("norm1"), y, train, axis_name)
    _maybe(p, "norm1", n1)
    y = jax.nn.relu(y)
    y = apply_conv(p["conv2"], y)
    y, n2 = _apply_norm(norm_fn, p.get("norm2"), y, train, axis_name)
    _maybe(p, "norm2", n2)
    y = jax.nn.relu(y)
    if stride == 1:
        res = x
    else:
        ds = dict(p["downsample"])
        res = apply_conv(ds["0"], x, stride=stride)
        res, nd = _apply_norm(norm_fn, ds.get("1"), res, train, axis_name)
        _maybe(ds, "1", nd)
        p["downsample"] = ds
    return jax.nn.relu(res + y), p


# -------------------------------------------------------------- bottleneck

def init_bottleneck_block(key, c_in: int, c_out: int, norm_fn: str, stride: int) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "conv1": init_conv(k1, 1, c_in, c_out // 4),
        "conv2": init_conv(k2, 3, c_out // 4, c_out // 4),
        "conv3": init_conv(k3, 1, c_out // 4, c_out),
    }
    _maybe(p, "norm1", _init_norm(norm_fn, c_out // 4))
    _maybe(p, "norm2", _init_norm(norm_fn, c_out // 4))
    _maybe(p, "norm3", _init_norm(norm_fn, c_out))
    if stride != 1:
        p["downsample"] = {"0": init_conv(k4, 1, c_in, c_out)}
        _maybe(p["downsample"], "1", _init_norm(norm_fn, c_out))
    return p


def apply_bottleneck_block(p: dict, x: jax.Array, norm_fn: str, stride: int,
                           train: bool, axis_name: Optional[str]) -> Tuple[jax.Array, dict]:
    p = dict(p)
    y = apply_conv(p["conv1"], x)
    y, n1 = _apply_norm(norm_fn, p.get("norm1"), y, train, axis_name)
    _maybe(p, "norm1", n1)
    y = jax.nn.relu(y)
    y = apply_conv(p["conv2"], y, stride=stride)
    y, n2 = _apply_norm(norm_fn, p.get("norm2"), y, train, axis_name)
    _maybe(p, "norm2", n2)
    y = jax.nn.relu(y)
    y = apply_conv(p["conv3"], y)
    y, n3 = _apply_norm(norm_fn, p.get("norm3"), y, train, axis_name)
    _maybe(p, "norm3", n3)
    y = jax.nn.relu(y)
    if stride == 1:
        res = x
    else:
        ds = dict(p["downsample"])
        res = apply_conv(ds["0"], x, stride=stride)
        res, nd = _apply_norm(norm_fn, ds.get("1"), res, train, axis_name)
        _maybe(ds, "1", nd)
        p["downsample"] = ds
    return jax.nn.relu(res + y), p


# ---------------------------------------------------------------- encoders

_BASIC_DIMS = (64, 64, 96, 128)     # stem, layer1..3 (reference model_utils.py:70-76)
_SMALL_DIMS = (32, 32, 64, 96)      # reference model_utils.py:93-99


def init_encoder(key, output_dim: int, norm_fn: str, small: bool = False) -> dict:
    dims = _SMALL_DIMS if small else _BASIC_DIMS
    block_init = init_bottleneck_block if small else init_residual_block
    keys = jax.random.split(key, 8)
    p: Dict[str, dict] = {"conv1": init_conv(keys[0], 7, 3, dims[0])}
    _maybe(p, "norm1", _init_norm(norm_fn, dims[0]))
    c_in = dims[0]
    for li, (dim, stride) in enumerate(zip(dims[1:], (1, 2, 2)), start=1):
        p[f"layer{li}"] = {
            "0": block_init(keys[2 * li - 1], c_in, dim, norm_fn, stride),
            "1": block_init(keys[2 * li], dim, dim, norm_fn, 1),
        }
        c_in = dim
    p["conv2"] = init_conv(keys[7], 1, c_in, output_dim)
    return p


def apply_encoder(p: dict, x: jax.Array, norm_fn: str, small: bool = False,
                  train: bool = False, axis_name: Optional[str] = None,
                  dropout: float = 0.0, rng: Optional[jax.Array] = None,
                  bn_train: Optional[bool] = None) -> Tuple[jax.Array, dict]:
    """Returns (features at 1/8 resolution, params-with-updated-BN-stats).

    ``bn_train`` overrides ``train`` for the normalization layers only
    (None = follow ``train``): the official finetune recipe freezes BN —
    running statistics used and left untouched — while the rest of the
    network (dropout included) stays in training mode.
    """
    bn_train = train if bn_train is None else bn_train
    block_apply = apply_bottleneck_block if small else apply_residual_block
    p = dict(p)
    with stage("encoder/stem"):
        y = apply_conv(p["conv1"], x, stride=2)
        y, n1 = _apply_norm(norm_fn, p.get("norm1"), y, bn_train, axis_name)
        _maybe(p, "norm1", n1)
        y = jax.nn.relu(y)
    for li, stride in zip((1, 2, 3), (1, 2, 2)):
        layer = dict(p[f"layer{li}"])
        with stage(f"encoder/layer{li}"):
            y, layer["0"] = block_apply(layer["0"], y, norm_fn, stride,
                                        bn_train, axis_name)
            y, layer["1"] = block_apply(layer["1"], y, norm_fn, 1,
                                        bn_train, axis_name)
        p[f"layer{li}"] = layer
    y = apply_conv(p["conv2"], y)
    if train and dropout > 0.0 and rng is not None:
        # channel dropout (torch nn.Dropout2d): zero whole channels per sample
        keep = 1.0 - dropout
        mask = jax.random.bernoulli(rng, keep, (y.shape[0], 1, 1, y.shape[-1]))
        # divide AFTER the select: identical values, and no division inside
        # a jnp.where branch (raftlint R5 — both branches are differentiated)
        y = jnp.where(mask, y, 0.0) / keep
    return y, p
