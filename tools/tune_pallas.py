"""Block-size sweep for the fused Pallas kernels on real TPU.

VERDICT round 1 #9: pick block-size defaults from measured data, not
guesses.  Two sweeps, selected by ``--kernel``:

* ``corr`` (default) — the fused correlation lookup (ops/corr_pallas.py)
  across (q_blk, p_blk_target) combinations;
* ``gru`` — the fused SepConvGRU update kernel (ops/gru_pallas.py) across
  ``block_rows`` (output rows per grid program; larger blocks amortize the
  4-row pass-1 recompute halo at more VMEM), with the XLA GRU formulation
  timed alongside as the before/after reference.

Both run at the two shapes that matter: the 432x1024 eval/demo resolution
and the (368,496)-crop batch-6 training shape.  Prints a markdown table +
JSON; the winners are recorded in TUNING.md and wired into RAFTConfig
defaults.

* ``corr-levels`` — TUNING.md's per-tile table: one lookup launch a pyramid
  level, standalone, as the served programs run it (bfloat16 maps at
  ``highest``: one plane at level 0, three at the pooled levels; planes
  padded and the schedule made outside the timed launch; ``bf16`` out) at
  the served shapes of both models, under a smooth and a rough flow: ms a
  launch, us a query tile, the steps a tile and the sha256 of the output,
  so that a parent and a change run in one call can be held bit for bit.

Usage (needs the TPU; refuses to 'tune' on CPU interpret mode):
    python tools/tune_pallas.py [--quick] [--kernel corr|gru|corr-levels]
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _measure(fn, args, warmup=2, reps=20):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    out = None
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    float(np.asarray(jax.tree.leaves(out)[0].ravel()[0]))   # true sync
    return (time.perf_counter() - t0) / reps


def _sweep_gru(args) -> int:
    """block_rows sweep of the fused GRU kernel vs the XLA formulation."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models.update import (apply_sep_conv_gru_hoisted,
                                        init_sep_conv_gru, precompute_gru_ctx)
    from raft_tpu.ops.gru_pallas import sep_conv_gru_pallas

    dev = jax.devices()[0]
    print(f"# device: {dev.device_kind}  kernel: gru  dtype: {args.dtype}")
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    hid, mdim, ctxd = 128, 128, 128            # full-model channel plan
    shapes = [("eval 1x432x1024", 1, 54, 128),
              ("train 6x368x496", 6, 46, 62)]
    block_rows = (8, 16) if args.quick else (4, 8, 16, 32)

    results = []
    for label, B, h, w in shapes:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        p_gru = jax.tree.map(
            lambda a: a.astype(dt), init_sep_conv_gru(ks[0], hid, ctxd + mdim))
        hst = jax.random.normal(ks[1], (B, h, w, hid), dt)
        mot = jax.random.normal(ks[2], (B, h, w, mdim), dt)
        inp = jax.random.normal(ks[3], (B, h, w, ctxd), dt)
        ctx = precompute_gru_ctx(p_gru, inp, hid)
        print(f"\n## {label}  (latent {B}x{h}x{w}, hidden {hid})")
        print("| impl | block_rows | ms/iteration |")
        print("|---|---|---|")
        fn = jax.jit(apply_sep_conv_gru_hoisted)
        dt_x = _measure(fn, (p_gru, hst, mot, ctx),
                        reps=8 if args.quick else 20)
        print(f"| xla (hoisted) | — | {dt_x * 1e3:.3f} |", flush=True)
        results.append({"shape": label, "impl": "xla",
                        "ms": round(dt_x * 1e3, 4)})
        for T in block_rows:
            fn = jax.jit(functools.partial(
                sep_conv_gru_pallas, block_rows=T, interpret=False,
                impl="kernel"))
            try:
                dt_k = _measure(fn, (p_gru, hst, mot, ctx),
                                reps=8 if args.quick else 20)
                results.append({"shape": label, "impl": "pallas",
                                "block_rows": T, "ms": round(dt_k * 1e3, 4)})
                print(f"| pallas | {T} | {dt_k * 1e3:.3f} |", flush=True)
            except Exception as e:  # noqa: BLE001 — e.g. VMEM overflow combos
                print(f"| pallas | {T} | FAILED {type(e).__name__} |",
                      flush=True)
        best = min((r for r in results
                    if r["shape"] == label and r["impl"] == "pallas"),
                   key=lambda r: r["ms"], default=None)
        if best:
            print(f"best for {label}: block_rows={best['block_rows']} "
                  f"({best['ms']:.3f} ms vs xla {dt_x * 1e3:.3f} ms)")
    print(json.dumps(results))
    return 0


#: label -> (grid h, w, batch, channels, radius): the served programs, and
#: the training forward at the chairs recipe's crop and batch
LEVEL_CASES = {"things-135x240": (135, 240, 8, 256, 4),
               "small-135x240": (135, 240, 8, 128, 3),
               "things-55x128": (55, 128, 32, 256, 4),
               "train-46x62": (46, 62, 10, 256, 4)}


def _sweep_levels(args) -> int:
    """One launch a level at every case of ``LEVEL_CASES``, smooth and
    rough flow; a JSON list last on stdout."""
    import dataclasses
    import hashlib

    import jax
    import jax.numpy as jnp

    from raft_tpu.kernel_plans import corr_level_plan
    from raft_tpu.ops import corr_pallas
    from raft_tpu.ops.coords import coords_grid
    from raft_tpu.ops.corr import fmap2_pyramid
    from raft_tpu.ops.corr_pallas import (_lookup_level, f2_terms,
                                          level_schedule, pad_planes,
                                          schedule_steps)

    print(f"# device: {jax.devices()[0].device_kind}  kernel: corr-levels")
    prec, bf = jax.lax.Precision.HIGHEST, jnp.bfloat16
    reps = 6 if args.quick else 12
    results = []
    for label, (h, w, B, C, radius) in LEVEL_CASES.items():
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(43), 3)
        f1 = jax.random.normal(k1, (B, h * w, C), jnp.float32).astype(bf)
        fmap2 = jax.random.normal(k2, (B, h, w, C), jnp.float32).astype(bf)
        levels = [fmap2] + fmap2_pyramid(fmap2.astype(jnp.float32),
                                         args.levels)[1:]
        base = coords_grid(B, h, w)
        x, y = base[..., 0], base[..., 1]
        flows = {
            # a cell of amplitude at wavelengths of 50-70 cells: what served
            # traffic looks like to a tile
            "smooth": base + jnp.stack([jnp.sin(x / 9.0 + y / 11.0),
                                        jnp.cos(x / 10.0 - y / 8.0)], -1),
            # +-2.5 cells, every query its own: a tile's windows spread 5
            # rows more
            "rough": base + jax.random.uniform(k3, (B, h, w, 2),
                                               minval=-2.5, maxval=2.5)}
        print(f"\n## {label}  (batch {B}, C {C}, radius {radius})")
        print("| level | map, stored lanes | plan | flow | ms | us a tile | "
              "steps a tile | sha256 |")
        print("|---|---|---|---|---|---|---|---|")
        for level, f2 in enumerate(levels):
            h2, w2 = f2.shape[1:3]
            kw = dict(q_blk=128, p_blk_target=4096, grid_w=w)
            plan = corr_level_plan(h * w, h2, w2, radius=radius, **kw)
            planes = f2_terms(bf, f2, prec)
            tiles = B * plan.qp // plan.t
            forms = [("band" if plan.banded else "block", plan)]
            if plan.banded and plan.n_pblocks == 1:
                # the same map as ONE block, had the plan not banded it
                forms.append(("whole", dataclasses.replace(
                    plan, band_granule=0, band_rows=0, n_bands=0,
                    band_rows_padded=0)))
            for form, plan in forms:
                banded = plan.banded
                rows = plan.band_rows if banded else plan.h2_blk
                # the launch plans itself: hand it the form's plan
                corr_pallas.corr_level_plan = (
                    lambda *a, _plan=plan, **k: _plan)
                padded = jax.block_until_ready(pad_planes(planes, plan))
                fn = jax.jit(functools.partial(
                    _lookup_level, radius=radius, level=level,
                    interpret=False, shape=(h2, w2), corr_precision=prec,
                    out_dtype=bf, **kw))
                for name, coords in flows.items():
                    cf = coords.reshape(B, h * w, 2)
                    sched = (jax.block_until_ready(
                        level_schedule(cf, plan, level, radius))
                        if banded else None)
                    steps = (int(schedule_steps(sched, plan)) if banded
                             else plan.n_pblocks)
                    run = lambda: fn(f1, padded, cf,        # noqa: E731
                                     schedule=sched)
                    out = jax.block_until_ready(run())
                    jax.block_until_ready(run())
                    best = float("inf")
                    for _ in range(reps):
                        t0 = time.perf_counter()
                        jax.block_until_ready(run())
                        best = min(best, time.perf_counter() - t0)
                    sha = hashlib.sha256(
                        np.asarray(out).view(np.uint16).tobytes()).hexdigest()
                    rec = {"case": label, "level": level, "map": [h2, w2],
                           "w2p": plan.w2p, "form": form, "flow": name,
                           "ms": round(best * 1e3, 4),
                           "us_per_tile": round(best * 1e6 / tiles, 3),
                           "steps_per_tile": steps, "sha256": sha}
                    results.append(rec)
                    print(f"| {level} | {h2}x{w2}, {plan.w2p} | {form} of "
                          f"{rows} rows | {name} | "
                          f"{rec['ms']:.3f} | {rec['us_per_tile']:.2f} | "
                          f"{steps} | {sha[:12]} |", flush=True)
            corr_pallas.corr_level_plan = corr_level_plan
    print(json.dumps(results))
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true", help="fewer combos/reps")
    p.add_argument("--kernel", default="corr",
                   choices=["corr", "gru", "corr-levels"],
                   help="which fused kernel to sweep (gru = the update-block "
                        "kernel's block_rows; corr-levels = one launch a "
                        "pyramid level at the served shapes)")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"],
                   help="--kernel gru: I/O dtype of the swept iteration "
                        "(the kernel computes f32 internally either way)")
    p.add_argument("--radius", type=int, default=4)
    p.add_argument("--levels", type=int, default=4)
    p.add_argument("--precision", default="highest",
                   choices=["highest", "default"],
                   help="corr-matmul precision to tune for ('default' = bf16 "
                        "MXU inputs)")
    args = p.parse_args()

    from raft_tpu.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("ERROR: tuning requires the TPU backend (interpret-mode timings "
              "are meaningless)", file=sys.stderr)
        return 2
    if args.kernel == "gru":
        return _sweep_gru(args)
    if args.kernel == "corr-levels":
        return _sweep_levels(args)

    from raft_tpu.ops.coords import coords_grid
    from raft_tpu.ops.corr import fmap2_pyramid
    from raft_tpu.ops.corr_pallas import _fused_lookup_impl

    dev = jax.devices()[0]
    prec = (jax.lax.Precision.HIGHEST if args.precision == "highest"
            else jax.lax.Precision.DEFAULT)
    print(f"# device: {dev.device_kind}  corr precision: {args.precision}  "
          f"band schedule: by the "
          f"kernel's plan (fine p_blk targets get it)")

    # (label, B, full-res H, W); fmaps are at os=8, C=256 (full model)
    shapes = [("eval 1x432x1024", 1, 432, 1024),
              ("train 6x368x496", 6, 368, 496)]
    q_blks = (64, 128, 256) if not args.quick else (128, 256)
    p_blks = (1024, 2048, 4096, 8192) if not args.quick else (2048, 4096)

    C = 256
    results = []
    for label, B, H, W in shapes:
        h, w = H // 8, W // 8
        key = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(key, 3)
        fmap1 = jax.random.normal(k1, (B, h, w, C), jnp.float32)
        fmap2 = jax.random.normal(k2, (B, h, w, C), jnp.float32)
        f2_levels = tuple(fmap2_pyramid(fmap2, args.levels))
        coords = (coords_grid(B, h, w)
                  + jax.random.uniform(k3, (B, h, w, 2), minval=-6, maxval=6))
        print(f"\n## {label}  (fmap {B}x{h}x{w}x{C})")
        print("| q_blk | p_blk_target | ms/lookup |")
        print("|---|---|---|")
        for q_blk, p_blk in itertools.product(q_blks, p_blks):
            fn = jax.jit(functools.partial(
                _fused_lookup_impl, radius=args.radius, q_blk=q_blk,
                p_blk_target=p_blk, interpret=False, corr_precision=prec))
            try:
                dt = _measure(fn, (fmap1, f2_levels, coords),
                              reps=8 if args.quick else 20)
                results.append({"shape": label, "q_blk": q_blk,
                                "p_blk_target": p_blk, "ms": round(dt * 1e3, 4)})
                print(f"| {q_blk} | {p_blk} | {dt * 1e3:.3f} |", flush=True)
            except Exception as e:  # noqa: BLE001 — e.g. VMEM overflow combos
                print(f"| {q_blk} | {p_blk} | FAILED {type(e).__name__} |",
                      flush=True)
        best = min((r for r in results if r["shape"] == label),
                   key=lambda r: r["ms"], default=None)
        if best:
            print(f"best for {label}: q_blk={best['q_blk']} "
                  f"p_blk_target={best['p_blk_target']} ({best['ms']:.3f} ms)")
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
