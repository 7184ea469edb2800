"""Seeded weights for a RAFT configuration, made on the device in one jitted
call.

The tree is keyed like the official PyTorch ``state_dict`` (``fnet.layer1.0.
conv1`` -> ``tree["fnet"]["layer1"]["0"]["conv1"]``) with convolution kernels
in HWIO and batch-norm leaves ``gamma/beta/mean/var``.  Both sides of the
output check consume this one tree: the plain reference (reference.py) reads
it as it is, and the system under test is handed it in place of a checkpoint.
Nothing here imports the program.

Kernels are Kaiming-normal over fan-out, as the official initialiser; biases
and the batch-norm statistics are small but not zero, so that every term of
the arithmetic is exercised.  An untrained RAFT is not contractive (PERF.md,
PR 21 finding 6: 1,587 px of flow after 12 iterations), which would make any
comparison at full depth a comparison of two chaotic orbits.  A trained one
is: its per-iteration update shrinks.  ``flow_head_scale`` in the
configuration's ``weights`` group stands in for that: it scales the flow
head's last convolution so that each iteration moves the flow by a fraction
of a pixel and twelve of them stay inside the image.
"""

from __future__ import annotations

import functools


def conv_plan(cfg: dict) -> tuple:
    """([(path, (kh, kw, cin, cout)), ...] for every convolution,
    [(path, channels), ...] for every batch norm) of the configuration."""
    small = bool(cfg["small"])
    hidden, ctx = int(cfg["hidden_dim"]), int(cfg["context_dim"])
    corr_dim = int(cfg["corr_levels"]) * (2 * int(cfg["corr_radius"]) + 1) ** 2
    convs, norms = [], []

    def encoder(root, out_dim, batch_norm):
        dims = (32, 32, 64, 96) if small else (64, 64, 96, 128)
        convs.append((root + ("conv1",), (7, 7, 3, dims[0])))
        if batch_norm:
            norms.append((root + ("norm1",), dims[0]))
        c_in = dims[0]
        for li, (dim, stride) in enumerate(zip(dims[1:], (1, 2, 2)), start=1):
            for bi, (ci, st) in enumerate(((c_in, stride), (dim, 1))):
                blk = root + (f"layer{li}", str(bi))
                if small:            # bottleneck: 1x1, 3x3, 1x1
                    convs.append((blk + ("conv1",), (1, 1, ci, dim // 4)))
                    convs.append((blk + ("conv2",), (3, 3, dim // 4, dim // 4)))
                    convs.append((blk + ("conv3",), (1, 1, dim // 4, dim)))
                    widths = (dim // 4, dim // 4, dim)
                else:                # residual: 3x3, 3x3
                    convs.append((blk + ("conv1",), (3, 3, ci, dim)))
                    convs.append((blk + ("conv2",), (3, 3, dim, dim)))
                    widths = (dim, dim)
                if batch_norm:
                    for ni, wd in enumerate(widths, start=1):
                        norms.append((blk + (f"norm{ni}",), wd))
                if st != 1:
                    convs.append((blk + ("downsample", "0"), (1, 1, ci, dim)))
                    if batch_norm:
                        norms.append((blk + ("downsample", "1"), dim))
            c_in = dim
        convs.append((root + ("conv2",), (1, 1, c_in, out_dim)))

    encoder(("fnet",), 128 if small else 256, batch_norm=False)
    encoder(("cnet",), hidden + ctx, batch_norm=not small)

    ub = ("update_block",)
    if small:
        convs += [
            (ub + ("encoder", "convc1"), (1, 1, corr_dim, 96)),
            (ub + ("encoder", "convf1"), (7, 7, 2, 64)),
            (ub + ("encoder", "convf2"), (3, 3, 64, 32)),
            (ub + ("encoder", "conv"), (3, 3, 128, 80)),
        ]
        hx = hidden + ctx + 82
        for g in ("convz", "convr", "convq"):
            convs.append((ub + ("gru", g), (3, 3, hx, hidden)))
        convs += [(ub + ("flow_head", "conv1"), (3, 3, hidden, 128)),
                  (ub + ("flow_head", "conv2"), (3, 3, 128, 2))]
    else:
        convs += [
            (ub + ("encoder", "convc1"), (1, 1, corr_dim, 256)),
            (ub + ("encoder", "convc2"), (3, 3, 256, 192)),
            (ub + ("encoder", "convf1"), (7, 7, 2, 128)),
            (ub + ("encoder", "convf2"), (3, 3, 128, 64)),
            (ub + ("encoder", "conv"), (3, 3, 256, 126)),
        ]
        hx = hidden + ctx + 128
        for g in ("convz", "convr", "convq"):
            convs.append((ub + ("gru", g + "1"), (1, 5, hx, hidden)))
            convs.append((ub + ("gru", g + "2"), (5, 1, hx, hidden)))
        convs += [(ub + ("flow_head", "conv1"), (3, 3, hidden, 256)),
                  (ub + ("flow_head", "conv2"), (3, 3, 256, 2)),
                  (ub + ("mask", "0"), (3, 3, hidden, 256)),
                  (ub + ("mask", "2"), (1, 1, 256, 64 * 9))]
    return convs, norms


def _put(tree: dict, path: tuple, leaf) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = leaf


def n_parameters(cfg: dict) -> int:
    convs, norms = conv_plan(cfg)
    n = sum(kh * kw * ci * co + co for _, (kh, kw, ci, co) in convs)
    return n + sum(2 * c for _, c in norms)   # running stats are not weights


@functools.lru_cache(maxsize=None)
def _maker(cfg_items: tuple):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    convs, norms = conv_plan(cfg)
    fh_scale = float(cfg.get("flow_head_scale", 1.0))
    total = (sum(kh * kw * ci * co + co for _, (kh, kw, ci, co) in convs)
             + sum(4 * c for _, c in norms))

    def make(key):
        # ONE draw for the whole tree, cut into leaves: a draw per leaf made
        # a program that took the chip's compiler 70 s (PERF.md, PR 23)
        flat = jax.random.normal(key, (total,), jnp.float32)
        pos = 0

        def take(shape):
            nonlocal pos
            n = 1
            for d in shape:
                n *= d
            out = flat[pos:pos + n].reshape(shape)
            pos += n
            return out

        tree: dict = {}
        for path, (kh, kw, ci, co) in convs:
            std = (2.0 / (kh * kw * co)) ** 0.5
            w = std * take((kh, kw, ci, co))
            b = 0.02 * take((co,))
            if path[-2:] == ("flow_head", "conv2"):
                w, b = fh_scale * w, fh_scale * b
            _put(tree, path, {"w": w, "b": b})
        for path, c in norms:
            _put(tree, path, {
                "gamma": 1.0 + 0.1 * take((c,)),
                "beta": 0.1 * take((c,)),
                "mean": 0.1 * take((c,)),
                "var": 1.0 + 0.2 * jnp.abs(take((c,))),
            })
        return tree

    return jax.jit(make)


def model_cfg(config: dict) -> dict:
    """The sizes weights and reference need, from a configuration file."""
    out = {k: config[k] for k in ("small", "hidden_dim", "context_dim",
                                  "corr_levels", "corr_radius")}
    out.update(config.get("weights", {}))
    return out


def make_weights(seed: int, cfg: dict) -> dict:
    """The whole tree, float32, on the default device, from ``seed``."""
    import jax
    # seeds go a little past 2**31: fold both halves in, keep every bit
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return _maker(tuple(sorted(cfg.items())))(key)
