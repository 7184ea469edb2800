"""Component-level timing breakdown of raft-things inference on the chip.

Times the jitted model at several GRU-iteration counts (the slope is the
per-iteration cost; the intercept is encoders + corr setup + upsample), and
the fused corr lookup in isolation, so optimization effort goes where the
time actually is.  The reference has no profiling beyond a crashing FLOPs
mode (reference infer_raft.py:80-95, SURVEY.md §3.3); this is the measured
counterpart on TPU.

Usage:  python tools/profile_breakdown.py [--size 432 1024] [--batch 1]
        [--impl pallas-bf16corr] [--unroll 1]
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _measure as measure  # shared timing/readback recipe




def main():
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, nargs=2, default=(432, 1024))
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--impl", default="pallas-bf16corr")
    p.add_argument("--unroll", type=int, default=1)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="also capture a jax.profiler trace of the iters=12 "
                        "steady-state reps — ops carry the raft/*, update/*, "
                        "corr/* named-scope prefixes (telemetry.trace), so "
                        "xprof attributes time per stage")
    p.add_argument("--trace-steps", type=int, default=4)
    args = p.parse_args()

    if args.cpu:
        from _cpu_backend import force_cpu_backend
        force_cpu_backend()
    from raft_tpu.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    import jax.numpy as jnp

    from bench import _cfg_for
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_inference_fn

    dev = jax.devices()[0]
    H, W = args.size
    B = args.batch
    cfg = dataclasses.replace(_cfg_for(args.impl), scan_unroll=args.unroll)
    print(f"device {dev.device_kind}  {B}x{H}x{W}  impl={args.impl} "
          f"unroll={args.unroll}", flush=True)

    params = init_raft(jax.random.PRNGKey(0), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (B, H, W, 3), jnp.float32)
    im2 = jax.random.uniform(k2, (B, H, W, 3), jnp.float32)

    # Null-call floor: a trivial jitted fn through the same timing loop —
    # the host's dispatch + readback cost per call.  It is NOT device time
    # and rides on every absolute number below (the per-iteration slope is
    # immune).
    tiny = jnp.ones((8, 128), jnp.float32)
    comp0 = jax.jit(lambda x: x + 1.0).lower(tiny).compile()
    print(f"null-call overhead     : {measure(comp0, (tiny,)) * 1e3:8.3f} ms",
          flush=True)

    times = {}
    for iters in (1, 2, 8, 12):
        fn = jax.jit(make_inference_fn(cfg, iters=iters))
        compiled = fn.lower(params, im1, im2).compile()
        trace = None
        if args.trace_dir and iters == 12:
            from raft_tpu.telemetry.trace import TraceWindow
            trace = TraceWindow(args.trace_dir, first=0,
                                steps=args.trace_steps,
                                log_fn=lambda m: print(f"# {m}", flush=True))
        dt = measure(compiled, (params, im1, im2), trace=trace)
        times[iters] = dt
        print(f"  iters={iters:2d}: {dt * 1e3:8.3f} ms", flush=True)

    per_iter = (times[12] - times[2]) / 10
    fixed = times[2] - 2 * per_iter
    print(f"per-GRU-iteration cost : {per_iter * 1e3:8.3f} ms")
    print(f"fixed cost (encoders + corr setup + upsample): "
          f"{fixed * 1e3:8.3f} ms")

    # pieces of the fixed cost, AOT-compiled in isolation
    from raft_tpu.models.encoders import apply_encoder
    from raft_tpu.ops.corr import fmap2_pyramid
    from raft_tpu.ops.upsample import convex_upsample_flow

    cdt = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    x1 = (2.0 * im1 - 1.0).astype(cdt)
    x2 = (2.0 * im2 - 1.0).astype(cdt)
    if cfg.compute_dtype == "bfloat16":   # params cast once, as in the model
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                              if a.dtype == jnp.float32 else a, params)

    def fnet_both(p, a, b):
        # both frames in one 2B-batched call, exactly as the model does
        f, _ = apply_encoder(p["fnet"], jnp.concatenate([a, b], 0), "instance",
                             small=cfg.small, train=False)
        return f[:a.shape[0]], f[a.shape[0]:]

    def cnet_fn(p, a):
        c, _ = apply_encoder(p["cnet"], a, "none" if cfg.small else "batch",
                             small=cfg.small, train=False)
        return c

    comp = jax.jit(fnet_both).lower(params, x1, x2).compile()
    dt_f = measure(comp, (params, x1, x2))
    print(f"fnet x2 frames         : {dt_f * 1e3:8.3f} ms")
    f1v, f2v = comp(params, x1, x2)

    # per-stage split of one fnet pass (2B-batched, as in the model); the
    # truncation lives in apply_encoder itself so this measures exactly the
    # structure the model runs
    def through(depth):
        def fn(p, a, b):
            y, _ = apply_encoder(p["fnet"], jnp.concatenate([a, b], 0),
                                 "instance", small=cfg.small, train=False,
                                 stages=depth)
            return y
        return fn

    prev = 0.0
    for depth, label in ((0, "conv1+norm"), (1, "+layer1"), (2, "+layer2"),
                         (3, "+layer3")):
        comp = jax.jit(through(depth)).lower(params, x1, x2).compile()
        dt = measure(comp, (params, x1, x2))
        print(f"  fnet {label:<10}       : {dt * 1e3:8.3f} ms "
              f"(stage {max(dt - prev, 0.0) * 1e3:+.3f} ms)")
        prev = dt

    comp = jax.jit(cnet_fn).lower(params, x1).compile()
    print(f"cnet                   : {measure(comp, (params, x1)) * 1e3:8.3f} ms")

    pyr = jax.jit(lambda f: tuple(fmap2_pyramid(f.astype(jnp.float32),
                                                cfg.corr_levels)))
    comp = pyr.lower(f2v).compile()
    print(f"fmap2 pyramid          : {measure(comp, (f2v,)) * 1e3:8.3f} ms")

    h, w = H // 8, W // 8
    flow_lr = jax.random.normal(jax.random.PRNGKey(5), (B, h, w, 2),
                                jnp.float32)
    mask = jax.random.normal(jax.random.PRNGKey(6), (B, h, w, 64 * 9),
                             jnp.float32)
    comp = jax.jit(convex_upsample_flow).lower(flow_lr, mask).compile()
    print(f"convex upsample        : "
          f"{measure(comp, (flow_lr, mask)) * 1e3:8.3f} ms")

    # the fused lookup in isolation, same fmap shapes the model produces
    h, w = H // 8, W // 8
    C = cfg.fnet_dim
    f1 = jax.random.normal(jax.random.PRNGKey(2), (B, h, w, C), jnp.float32)
    f2 = jax.random.normal(jax.random.PRNGKey(3), (B, h, w, C), jnp.float32)
    coords = jax.random.uniform(jax.random.PRNGKey(4), (B, h, w, 2),
                                jnp.float32, 0, min(h, w))
    if cfg.corr_impl == "pallas":
        from raft_tpu.ops.corr_pallas import make_fused_lookup
        prec = (jax.lax.Precision.HIGHEST if cfg.corr_precision == "highest"
                else jax.lax.Precision.DEFAULT)

        @jax.jit
        def lookup(f1, f2, coords):
            fn = make_fused_lookup(f1, f2, cfg.corr_levels, cfg.corr_radius,
                                   corr_precision=prec, q_blk=cfg.pallas_q_blk,
                                   p_blk_target=cfg.pallas_p_blk,
                                   lookup_style=cfg.pallas_lookup_style,
                                   pack_rows=cfg.pallas_pack)
            return fn(coords=coords)

        compiled = lookup.lower(f1, f2, coords).compile()
        dt = measure(compiled, (f1, f2, coords))
        print(f"fused lookup alone     : {dt * 1e3:8.3f} ms "
              f"(GRU-side remainder {(per_iter - dt) * 1e3:.3f} ms)")

    # --- gru stage: the update operator in isolation, XLA vs the fused
    # kernel (the GRU-bound regime's hot stage — round-2 attribution put
    # most of the per-iteration cost here, not in the corr lookup)
    if not cfg.small:
        import functools

        from raft_tpu.models.update import (apply_basic_update_block,
                                            init_basic_update_block,
                                            precompute_gru_ctx)

        up = init_basic_update_block(jax.random.PRNGKey(7),
                                     cfg.corr_feature_dim, cfg.hidden_dim,
                                     cfg.context_dim)
        if cfg.compute_dtype == "bfloat16":
            up = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                              if a.dtype == jnp.float32 else a, up)
        net = jnp.tanh(jax.random.normal(jax.random.PRNGKey(8),
                                         (B, h, w, cfg.hidden_dim), cdt))
        inp = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(9),
                                            (B, h, w, cfg.context_dim), cdt))
        corr_in = jax.random.normal(jax.random.PRNGKey(10),
                                    (B, h, w, cfg.corr_feature_dim), cdt)
        flow_in = jax.random.normal(jax.random.PRNGKey(11), (B, h, w, 2), cdt)
        ctx = jax.jit(functools.partial(precompute_gru_ctx,
                                        hidden=cfg.hidden_dim))(up["gru"], inp)
        impls = ["xla", "pallas"]
        for impl in impls:
            fn = jax.jit(functools.partial(
                apply_basic_update_block, gru_impl=impl,
                gru_block_rows=cfg.gru_block_rows))
            try:
                comp = fn.lower(up, net, inp, corr_in, flow_in, ctx).compile()
                dt = measure(comp, (up, net, inp, corr_in, flow_in, ctx))
                print(f"update block ({impl:>6}) : {dt * 1e3:8.3f} ms "
                      f"(motion enc + GRU + heads, 1 iteration)", flush=True)
            except Exception as e:  # noqa: BLE001 — report, keep profiling
                print(f"update block ({impl:>6}) : FAILED "
                      f"{type(e).__name__}: {str(e)[:120]}", flush=True)


if __name__ == "__main__":
    main()
