"""Official-recipe envelope check: ``python tools/envelope_check.py``.

Runs the chairs-stage recipe shape ONCE, end to end — the envelope no
prior round had executed (VERDICT r4 item 4): (368, 496) crop, global
batch 10 fitted through gradient accumulation, 12 GRU iterations,
freeze_bn off, per-iteration remat — and records the three numbers that
prove the design point:

1. XLA's own peak/temp memory for the compiled train step at accum 1 vs
   accum 5 (AOT ``compile().memory_analysis()`` — the accumulation knob's
   activation-memory reduction, measured from the compiler, not estimated);
2. one EXECUTED optimizer step at the recipe shape (accum path exercised
   for real) with wall time and peak host RSS;
3. the host input-pipeline rate at the same crop (data.loader_bench),
   sequential vs multi-process — the feed-vs-step crossover at the real
   shape.

On CPU the step time is not a TPU forecast; the memory analysis and the
accum/loader structure transfer.  Writes one JSON line per stage; run with
--out to also append to a log file.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(rec, out):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, nargs=2, default=(368, 496))
    p.add_argument("--batch", type=int, default=10)      # chairs preset
    p.add_argument("--iters", type=int, default=12)
    p.add_argument("--accum", type=int, default=5)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--skip-memory", action="store_true",
                   help="skip the AOT memory-analysis stage (implies "
                        "--skip-exec: the executed step reuses its "
                        "compiled executable) — what CI uses to run the "
                        "cheap envelope stages alone")
    p.add_argument("--skip-exec", action="store_true",
                   help="memory analysis + loader only (no executed step)")
    p.add_argument("--skip-loader", action="store_true")
    # iters-policy envelope (round 8): EPE under converge:* vs fixed-32
    p.add_argument("--skip-policy", action="store_true",
                   help="skip the converge-policy EPE envelope stage")
    p.add_argument("--policy-steps", type=int, default=300, metavar="N",
                   help="training steps for the small synthetic model the "
                        "policy stage evaluates (0 = random weights: "
                        "early exit never triggers, stage is vacuous)")
    p.add_argument("--policy-ckpt", default=None, metavar="NPZ",
                   help="reuse a trained raft-small checkpoint instead of "
                        "training in-process")
    p.add_argument("--policy-size", type=int, nargs=2, default=None,
                   metavar=("H", "W"),
                   help="training crop for the shared briefly-trained "
                        "small model (default: the synthetic stage "
                        "preset; CI passes 48 64 so the steps fit its "
                        "time budget — evaluation stays at 96x128)")
    p.add_argument("--policy-batch", type=int, default=None, metavar="N",
                   help="training batch size for the shared small model "
                        "(default: the synthetic stage preset)")
    p.add_argument("--policy-eps", default="1e-2,1e-3,0.8",
                   help="comma list of converge eps values to check")
    p.add_argument("--epe-envelope", type=float, default=0.25,
                   help="max allowed EPE regression of a TRIGGERED "
                        "converge arm vs fixed-32 (signed: improvements "
                        "always pass)")
    # post-training quantization envelope (--quant knobs, serving)
    p.add_argument("--skip-quant", action="store_true",
                   help="skip the post-training quantization EPE stage")
    p.add_argument("--quant-envelope", type=float, default=0.25,
                   help="max allowed EPE-vs-ground-truth regression of a "
                        "--quant storage arm (int8 slot rows, bf16w "
                        "encoder weights) against the same-weights f32 "
                        "arm; improvements always pass")
    p.add_argument("--out", default=None, metavar="FILE")
    args = p.parse_args()

    if args.cpu:
        from _cpu_backend import force_cpu_backend
        force_cpu_backend()

    import dataclasses

    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models import init_raft
    from raft_tpu.training import (Batch, TrainState, make_optimizer,
                                   make_train_step)

    H, W = args.size
    B = args.batch
    config = RAFTConfig.full(iters=args.iters)        # remat_iters defaults ON
    base = TrainConfig.for_stage("chairs", batch_size=B,
                                 image_size=(H, W), num_steps=1000)
    assert not base.freeze_bn                          # chairs recipe
    dev = jax.devices()[0]

    def build(accum):
        t = dataclasses.replace(base, accum_steps=accum)
        tx = make_optimizer(t)
        state = TrainState.create(init_raft(jax.random.PRNGKey(0), config), tx)
        step = jax.jit(make_train_step(config, t, tx), donate_argnums=0)
        return t, tx, state, step

    shapes = Batch(
        image1=jax.ShapeDtypeStruct((B, H, W, 3), jnp.float32),
        image2=jax.ShapeDtypeStruct((B, H, W, 3), jnp.float32),
        flow=jax.ShapeDtypeStruct((B, H, W, 2), jnp.float32),
        valid=jax.ShapeDtypeStruct((B, H, W), jnp.float32))

    # -- 1. compiler-reported memory, accum 1 vs accum N ------------------
    mem = {}
    keep = {}                     # reuse the accum-N executable in stage 2
    if args.skip_memory:          # stage 2 reuses stage 1's executable
        args.skip_exec = True
    # dedupe: --accum 1 would otherwise compile and emit the identical
    # configuration twice (ADVICE r5)
    for accum in () if args.skip_memory else dict.fromkeys((1, args.accum)):
        _, _, state, step = build(accum)
        t0 = time.perf_counter()
        compiled = step.lower(
            state, shapes, jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
        ma = compiled.memory_analysis()
        rec = {
            "stage": "memory_analysis", "accum_steps": accum,
            "backend": jax.default_backend(), "device": dev.device_kind,
            "shape": [B, H, W], "iters": args.iters,
            "compile_s": round(time.perf_counter() - t0, 1),
        }
        if ma is not None:
            rec.update(
                temp_mb=round(ma.temp_size_in_bytes / 2**20, 1),
                argument_mb=round(ma.argument_size_in_bytes / 2**20, 1),
                output_mb=round(ma.output_size_in_bytes / 2**20, 1),
                peak_estimate_mb=round(
                    (ma.temp_size_in_bytes + ma.argument_size_in_bytes)
                    / 2**20, 1))
            mem[accum] = ma.temp_size_in_bytes
        _emit(rec, args.out)
        if accum == args.accum:
            keep["compiled"], keep["state"] = compiled, state
        else:
            del compiled, state
        del step
    if args.skip_memory:
        pass
    elif len(mem) == 2 and mem[args.accum] > 0:
        _emit({"stage": "memory_ratio",
               "temp_reduction_accum": round(mem[1] / mem[args.accum], 2),
               "note": f"XLA temp memory, accum 1 vs {args.accum}"},
              args.out)
    else:
        _emit({"stage": "memory_ratio", "skipped": True,
               "note": ("only one accum configuration ran (--accum 1)"
                        if args.accum == 1 else
                        "memory analysis unavailable on this backend")},
              args.out)

    # -- 2. one executed step at the recipe shape -------------------------
    if not args.skip_exec:
        state = keep["state"]
        rng = np.random.RandomState(0)
        batch = Batch(
            image1=jnp.asarray(rng.rand(B, H, W, 3), jnp.float32),
            image2=jnp.asarray(rng.rand(B, H, W, 3), jnp.float32),
            flow=jnp.asarray(rng.randn(B, H, W, 2) * 4, jnp.float32),
            valid=jnp.ones((B, H, W), jnp.float32))
        key = jax.random.PRNGKey(1)
        t0 = time.perf_counter()
        state, metrics = keep["compiled"](state, batch, key)
        loss = float(np.asarray(metrics["loss"]))
        dt = time.perf_counter() - t0
        _emit({"stage": "executed_step", "accum_steps": args.accum,
               "backend": jax.default_backend(),
               "shape": [B, H, W], "iters": args.iters,
               "first_step_s": round(dt, 1), "loss": round(loss, 4),
               "finite": bool(np.isfinite(loss)),
               "peak_rss_mb": round(
                   resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                   / 1024, 1)}, args.out)

    # -- 3. host pipeline at the recipe crop ------------------------------
    if not args.skip_loader:
        from raft_tpu.data.loader_bench import run as loader_run
        res = loader_run(samples=24, workers=(2, 4), crop=(H, W))
        res["stage"] = "loader"
        _emit(res, args.out)

    # -- 4. converge-policy EPE envelope (round 8) ------------------------
    rc = 0
    if not args.skip_policy:
        rc = _policy_envelope(args)

    # -- 5. post-training quantization envelope ---------------------------
    if not args.skip_quant:
        rc = max(rc, _quant_envelope(args))
    return rc


def _trained_small_params(args, config):
    """Briefly trained raft-small weights, shared by the policy and quant
    envelope stages (trained ONCE per run: random weights behave
    chaotically through the recurrent refinement — the update norm has
    to have LEARNED to shrink — so neither stage is meaningful without
    some training).  Returns ``(params, provenance_label)``."""
    import time

    import jax
    import jax.numpy as jnp

    from raft_tpu.config import TrainConfig
    from raft_tpu.data.synthetic import SyntheticFlowDataset
    from raft_tpu.models import init_raft
    from raft_tpu.training import Batch, TrainState, make_optimizer, \
        make_train_step

    cached = getattr(args, "_trained_small", None)
    if cached is not None:
        return cached
    if args.policy_ckpt:
        from raft_tpu.convert import load_checkpoint_auto
        params = jax.tree.map(jnp.asarray,
                              load_checkpoint_auto(args.policy_ckpt))
        trained = f"ckpt:{args.policy_ckpt}"
    else:
        params = init_raft(jax.random.PRNGKey(0), config)
        trained = f"steps:{args.policy_steps}"
        if args.policy_steps:
            preset = {}
            if args.policy_size:
                preset["image_size"] = tuple(args.policy_size)
            if args.policy_batch:
                preset["batch_size"] = args.policy_batch
            t = TrainConfig.for_stage("synthetic", lr=2e-4,
                                      num_steps=args.policy_steps,
                                      **preset)
            tx = make_optimizer(t)
            state = TrainState.create(params, tx)
            step = jax.jit(make_train_step(config, t, tx), donate_argnums=0)
            ds = SyntheticFlowDataset(size=t.image_size, length=512, seed=0)
            t0 = time.perf_counter()
            rng = np.random.RandomState(0)
            for i in range(args.policy_steps):
                idx = rng.randint(0, len(ds), t.batch_size)
                s = [ds[j] for j in idx]
                batch = Batch(
                    image1=jnp.asarray(np.stack([x[0] for x in s])),
                    image2=jnp.asarray(np.stack([x[1] for x in s])),
                    flow=jnp.asarray(np.stack([x[2] for x in s])),
                    valid=jnp.asarray(np.stack([x[3] for x in s])))
                state, metrics = step(state, batch,
                                      jax.random.fold_in(
                                          jax.random.PRNGKey(1), i))
            loss = float(np.asarray(metrics["loss"]))
            from raft_tpu.training.state import merge_bn_state
            params = merge_bn_state(state.params, state.bn_state)
            _emit({"stage": "policy_train", "steps": args.policy_steps,
                   "image_size": list(t.image_size),
                   "batch_size": t.batch_size,
                   "final_loss": round(loss, 3),
                   "seconds": round(time.perf_counter() - t0, 1)}, args.out)
    args._trained_small = (params, trained)
    return args._trained_small


def _policy_envelope(args) -> int:
    """EPE under --iters-policy converge:* vs fixed-32, on a briefly
    trained raft-small synthetic model (random weights never reach any
    useful eps — the update norm has to have LEARNED to shrink).  A
    triggered arm (mean_iters < 32) must hold EPE within --epe-envelope of
    the fixed-32 baseline; improvements always pass (the toy model over-
    iterates past its training horizon, so early exit can help EPE)."""
    import dataclasses

    from raft_tpu.config import RAFTConfig
    from raft_tpu.data.synthetic import SyntheticFlowDataset
    from raft_tpu.training.evaluate import evaluate_dataset

    config = RAFTConfig.small_model(iters=8)       # demo-train recipe
    params, trained = _trained_small_params(args, config)
    held_out = SyntheticFlowDataset(size=(96, 128), length=16, seed=9001)
    eval_cfg = dataclasses.replace(config, iters=32)
    fixed = evaluate_dataset(params, eval_cfg, held_out, batch_size=4,
                             verbose=False)
    rows, violations, triggered = [], [], 0
    for eps in [e.strip() for e in args.policy_eps.split(",") if e.strip()]:
        ccfg = dataclasses.replace(eval_cfg, iters_policy=f"converge:{eps}")
        m = evaluate_dataset(params, ccfg, held_out, batch_size=4,
                             verbose=False)
        mean_iters = m.get("mean_iters", 32.0)
        delta = m["epe"] - fixed["epe"]
        fired = mean_iters < 31.999
        ok = (not fired) or delta <= args.epe_envelope
        if fired:
            triggered += 1
        if not ok:
            violations.append(f"converge:{eps}: epe +{delta:.4f} "
                              f"> envelope {args.epe_envelope}")
        rows.append({"policy": f"converge:{eps}",
                     "epe": round(m["epe"], 4),
                     "epe_delta_vs_fixed32": round(delta, 4),
                     "mean_iters": round(mean_iters, 3),
                     "triggered": fired, "within_envelope": ok})
    _emit({"stage": "iters_policy_envelope", "model": trained,
           "epe_envelope": args.epe_envelope,
           "fixed32_epe": round(fixed["epe"], 4), "rows": rows,
           "arms_triggered": triggered,
           "ok": not violations,
           "violations": violations or None}, args.out)
    return 1 if violations else 0


def _quant_envelope(args) -> int:
    """Quality guard for the post-training quantization knobs
    (``RAFTConfig.quant`` / serve ``--quant``).

    Each arm runs the SAME inference twice — quantized storage vs f32 —
    and the gate is the **EPE-vs-ground-truth regression** of the
    quantized arm, not the raw deviation between the two flow fields.
    The distinction matters on this stage's briefly trained raft-small
    (shared via ``_trained_small_params``): a partially trained
    refinement loop amplifies sub-1% feature-storage error into a
    multi-pixel flow deviation that keeps shrinking with training
    (measured: int8 deviation 47.9 px at 0 steps, 12.2 at 150, 7.5 at
    250), while the QUALITY delta is already stable and tiny (int8 EPE
    9.30 -> 9.26 at 250 steps).  Quantized serving is acceptable iff it
    doesn't make the answers worse, so that is what gates; the flow
    deviation is recorded as provenance.  Random weights are useless
    either way (``--policy-steps 0`` makes both stages vacuous).

    * ``int8`` — a warm stream advance whose previous-frame fmap/cnet
      rows round-tripped through int8 slot storage (``quantize_rows ->
      dequantize_rows``: the exact dequant-on-gather math the sbatch
      executable runs) vs the same advance from f32 rows;
    * ``bf16w`` — a pairwise forward with bf16-stored encoder weights
      (``cast_encoder_weights``; compute stays f32) vs f32 weights.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.data.synthetic import SyntheticFlowDataset
    from raft_tpu.models.raft import (cast_encoder_weights, dequantize_rows,
                                      encode_frame, make_stream_step_fn,
                                      quantize_rows, raft_forward)

    config = RAFTConfig.small_model(iters=8)
    params, trained = _trained_small_params(args, config)
    ds = SyntheticFlowDataset(size=(96, 128), length=4, seed=77)
    B = len(ds)
    im1 = jnp.asarray(np.stack([ds[i][0] for i in range(B)]))
    im2 = jnp.asarray(np.stack([ds[i][1] for i in range(B)]))
    gt = jnp.asarray(np.stack([ds[i][2] for i in range(B)]))

    def epe(a, b):
        return float(jnp.mean(jnp.linalg.norm(a - b, axis=-1)))

    # int8 arm: same stream advance, previous-frame rows stored int8
    step = jax.jit(make_stream_step_fn(config))
    fmap, cnet = jax.jit(
        lambda p, im: encode_frame(p, im, config))(params, im1)
    flow0 = jnp.zeros((B, im1.shape[1] // 8, im1.shape[2] // 8, 2),
                      jnp.float32)
    ref_stream = step(params, im2, fmap, cnet, flow0)[0]
    fq = dequantize_rows(*quantize_rows(fmap)).astype(fmap.dtype)
    cq = dequantize_rows(*quantize_rows(cnet)).astype(cnet.dtype)
    int8_flow = step(params, im2, fq, cq, flow0)[0]
    int8 = {"quant": "int8", "surface": "slot rows (stream advance)",
            "f32_epe": epe(ref_stream, gt), "quant_epe": epe(int8_flow, gt),
            "flow_dev_epe": epe(int8_flow, ref_stream)}

    # bf16w arm: same pairwise forward, encoder weights stored bf16
    qcfg = dataclasses.replace(config, quant="bf16w")
    fwd = jax.jit(lambda p, a, b: raft_forward(p, a, b, config)[0].flow)
    qfwd = jax.jit(lambda p, a, b: raft_forward(p, a, b, qcfg)[0].flow)
    pair_flow = fwd(params, im1, im2)
    bf16_flow = qfwd(cast_encoder_weights(params, qcfg), im1, im2)
    bf16 = {"quant": "bf16w", "surface": "encoder weights (pairwise)",
            "f32_epe": epe(pair_flow, gt), "quant_epe": epe(bf16_flow, gt),
            "flow_dev_epe": epe(bf16_flow, pair_flow)}

    violations = []
    for row in (int8, bf16):
        delta = row["quant_epe"] - row["f32_epe"]
        row["epe_delta"] = delta
        ok = delta <= args.quant_envelope          # NaN fails too
        row["within_envelope"] = bool(ok)
        if not ok:
            violations.append(f"{row['quant']}: epe {row['f32_epe']:.4f} "
                              f"-> {row['quant_epe']:.4f} (+{delta:.4f}) "
                              f"> envelope {args.quant_envelope}")
        for k in ("f32_epe", "quant_epe", "flow_dev_epe", "epe_delta"):
            row[k] = round(row[k], 4)
    _emit({"stage": "quant_envelope", "model": trained,
           "quant_envelope": args.quant_envelope,
           "rows": [int8, bf16],
           "ok": not violations,
           "violations": violations or None}, args.out)
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
