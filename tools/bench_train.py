"""Training-step throughput: ``python tools/bench_train.py``.

Complements bench.py (inference pairs/sec/chip, the driver headline) with
the training-side number BASELINE.md's north star implies (v4-32 training):
pairs/sec/chip of the full jitted train step — forward, sequence loss over
all iteration outputs, backward with per-iteration remat, AdamW update —
at the official training shape (368x496 crop, batch 6, 12 GRU iterations).

Prints one JSON line; use --quick for a CPU-sized smoke.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, nargs=2, default=(368, 496))
    p.add_argument("--batch", type=int, default=6)
    p.add_argument("--iters", type=int, default=12)
    p.add_argument("--impl", default="pallas")
    p.add_argument("--precision", default=None,
                   choices=["default", "highest"],
                   help="override the candidate's corr precision (default: "
                        "whatever the candidate name means in bench.py)")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient-accumulation micro-steps (measures the "
                        "memory-for-time trade of TrainConfig.accum_steps)")
    p.add_argument("--unroll", type=int, default=None,
                   help="override RAFTConfig.scan_unroll for the GRU "
                        "iteration loop (A/B the unroll default)")
    p.add_argument("--quick", action="store_true",
                   help="tiny shapes for CI smoke (64x96, batch 2, 3 iters)")
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()

    if args.cpu:
        from _cpu_backend import force_cpu_backend
        force_cpu_backend()
    if args.quick:
        args.size, args.batch, args.iters = (64, 96), 2, 3

    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models import init_raft
    from raft_tpu.training import Batch, TrainState, make_optimizer, make_train_step

    from raft_tpu.compile_cache import configure_compile_cache
    configure_compile_cache()

    dev = jax.devices()[0]
    impl = args.impl
    if dev.platform != "tpu" and impl.startswith("pallas"):
        # the Pallas kernels run in interpret mode off the chip: a timing
        # of that is no measurement, and another impl is another result
        print(f"ERROR: --impl {impl!r} needs the TPU (found {dev.platform}:"
              f"{dev.device_kind}); name a non-Pallas impl for a CPU run, "
              f"e.g. --impl blockwise", file=sys.stderr)
        return 2
    H, W = args.size
    # candidate names share bench.py's mapping (-win/-pack/-winpack etc.);
    # explicit --precision and the training iteration count then override
    import dataclasses

    from bench import _cfg_for
    config = dataclasses.replace(_cfg_for(impl), iters=args.iters,
                                 compute_dtype="bfloat16")
    if args.precision is not None:
        config = dataclasses.replace(config, corr_precision=args.precision)
    if args.unroll is not None:
        config = dataclasses.replace(config, scan_unroll=args.unroll)
    tconfig = TrainConfig(num_steps=1000, batch_size=args.batch,
                          image_size=(H, W), accum_steps=args.accum)
    tx = make_optimizer(tconfig)
    state = TrainState.create(init_raft(jax.random.PRNGKey(0), config), tx)
    step = jax.jit(make_train_step(config, tconfig, tx), donate_argnums=0)

    rng = np.random.RandomState(0)
    batch = Batch(
        image1=jnp.asarray(rng.rand(args.batch, H, W, 3), jnp.float32),
        image2=jnp.asarray(rng.rand(args.batch, H, W, 3), jnp.float32),
        flow=jnp.asarray(rng.randn(args.batch, H, W, 2) * 4, jnp.float32),
        valid=jnp.ones((args.batch, H, W), jnp.float32))
    key = jax.random.PRNGKey(1)

    for _ in range(2):                       # compile + warm
        state, metrics = step(state, batch, key)
    jax.block_until_ready(state)
    reps = 10
    t0 = time.perf_counter()
    for _ in range(reps):
        state, metrics = step(state, batch, key)
    float(np.asarray(metrics["loss"]))       # true sync via readback
    dt = (time.perf_counter() - t0) / reps

    print(json.dumps({
        "metric": f"raft-things train-step throughput @ {args.iters} iters, "
                  f"{args.batch}x{H}x{W} ({impl}, {config.corr_precision}"
                  + (f", accum {args.accum}" if args.accum > 1 else "")
                  + (f", unroll {config.scan_unroll}"
                     if config.scan_unroll != 1 else "") + ")",
        "device": dev.device_kind,
        "value": round(args.batch / dt, 4),
        "unit": "pairs/sec/chip",
        "ms_per_step": round(dt * 1e3, 3),
        "accum_steps": args.accum,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
