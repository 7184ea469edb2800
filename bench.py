"""Benchmark: image-pairs/sec/chip, raft-things (full model), 12 GRU
iterations — the BASELINE.json target metric.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "pairs/sec/chip", "vs_baseline": R,
   "mfu": M, "error": null | "..."}

vs_baseline: the reference publishes no numbers (BASELINE.md — no EPE code,
no benchmarks, flops mode crashed), so the baseline here is the *reference's
configuration* run on the same hardware by this framework: dense correlation
exactly as reference model_utils.py:199-221 materializes it, at the
reference's hardcoded 20 iterations (reference RAFT.py:33).  value/vs stays
honest: same hardware, reference algorithm vs our tuned path.

mfu: XLA cost_analysis flops of the winning compiled fn / measured step time
/ chip peak FLOP/s (dense bf16, MAC counted as 2 flops on both sides).

Device contract: a measurement needs the chip.  Without ``--cpu`` the first
device must be a TPU or the run exits non-zero; there is no probe, no retry
and no fallback.  ``--cpu`` is a functional check of one named candidate
(``--impl``) and says so in its unit.  The JSON line goes out on every exit
path; the exit code is non-zero whenever ``error`` is set — the reference
configuration or any listed candidate raising is a failure, never a skip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

# Dense bf16 peak FLOP/s per chip (MAC = 2 flops), by device_kind substring.
# Public spec-sheet numbers; used only as the MFU denominator.
_PEAK_FLOPS = [
    ("v6", 918e12),       # Trillium ("TPU v6 lite" / "TPU v6e")
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e reports as "TPU v5 lite"
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def _peak_flops(device_kind: str) -> float:
    """Peak FLOP/s of a TPU ``device_kind``.  A device that is not in the
    table is an error, not a default: an MFU over a guessed peak is a
    wrong number that looks like a measurement."""
    kind = device_kind.lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in kind:
            return peak
    raise ValueError(f"no peak FLOP/s known for device_kind {device_kind!r}; "
                     f"add it to bench._PEAK_FLOPS with its source")


def _init_device(force_cpu: bool):
    """The device this run measures: the first TPU, or — only under
    ``--cpu`` — the CPU.  Anything else raises."""
    if force_cpu:
        from _cpu_backend import force_cpu_backend
        return force_cpu_backend().devices()[0]
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the chip and found {dev.platform}:"
            f"{dev.device_kind}; pass --cpu --impl NAME for a functional "
            f"check on the CPU")
    return dev


# The sweep, best-known-first.  'blockwise' (gather lookup) is gather-BOUND
# by construction — it exists as the reference SampleCorr semantics twin /
# backward oracle, gathering ~(2r+2)^2*C bytes per query where the one-hot
# twin runs matmuls — so it stays last: measured for the record, never
# expected to win.
# The -pack/-winpack names still map (``_cfg_for``) but are not listed: the
# chip's compiler refuses the row-packed kernel
# (ops/corr_pallas._PACK_REFUSAL; ROADMAP A6/C3).
CANDIDATES = ("pallas-bf16corr-ctx-gru", "pallas-bf16corr",
              "pallas-bf16corr-ctx", "pallas-gru",
              "pallas-bf16corr-win", "pallas-bf16corr-vpu",
              "pallas", "dense-onehot", "dense-onehot-ctx",
              "dense", "blockwise-onehot", "blockwise")


def _cfg_for(name: str):
    """Map a candidate name (bare, no '+bf16'/',bN' suffixes) to config."""
    from raft_tpu.config import RAFTConfig

    tokens = name.split("-")
    # 'pallas-gru' prefix = the fused UPDATE-BLOCK kernel riding the
    # dense-onehot-ctx correlation path (isolates the GRU kernel from the
    # corr kernel).  A bare '-gru' token on any other candidate just
    # flips gru_impl.
    gru = "gru" in tokens
    if name.startswith("pallas-gru"):
        impl = "dense"
    else:
        impl = ("pallas" if name.startswith("pallas")
                else "dense" if name.startswith("dense")
                else "blockwise" if name.startswith("blockwise") else name)
    # pallas suffixes compose: -win (fine key row-blocks of 1024, so that
    # the kernel's own rule schedules more levels and each tile leaves more
    # out: lint/budget.corr_level_scheduled), -pack (row
    # packing), -winpack (both); they apply to any pallas candidate name,
    # not just the bf16corr family
    window = any(t in ("win", "winpack") for t in tokens)
    pack = any(t in ("pack", "winpack") for t in tokens)
    # -ctx: hoisted GRU context terms (implied by the fused GRU kernel)
    ctx = "ctx" in tokens or name.startswith("pallas-gru")
    return RAFTConfig.full(
        corr_impl=impl,
        corr_precision=("default" if name.startswith("pallas-bf16corr")
                        else "highest"),
        corr_lookup=("onehot" if ("onehot" in tokens
                                  or name.startswith("pallas-gru"))
                     else "gather"),
        pallas_lookup_style="vpu" if "vpu" in tokens else "matmul",
        pallas_p_blk=1024 if window else RAFTConfig.full().pallas_p_blk,
        pallas_pack=pack,
        gru_ctx_hoist=ctx,
        gru_impl="pallas" if gru else "xla",
        compute_dtype="bfloat16")


def _readback(x) -> float:
    """True synchronization: pull one scalar of the output back to host."""
    import jax
    import numpy as np
    leaf = jax.tree.leaves(x)[0]
    return float(np.asarray(leaf.ravel()[0]))


def _measure(fn, args, warmup: int = 2, reps: int = 10, trace=None) -> float:
    """Wall time per call (seconds), amortized over ``reps`` back-to-back
    dispatches with a single final readback, so fixed per-call host
    overhead is divided by ``reps`` instead of polluting every sample.

    ``trace``: optional telemetry.trace.TraceWindow.  Dispatch here is
    ASYNC (the whole point of the loop), so the device may still be
    executing rep 0 when the host reaches rep N — the window therefore
    opens at rep ``trace.first`` but closes only after the final readback,
    the one true sync point; closing mid-loop would capture microseconds
    of dispatch and none of the execution."""
    for _ in range(warmup):
        _readback(fn(*args))
    t0 = time.perf_counter()
    out = None
    for i in range(reps):
        if trace is not None:
            # clamp below the window end so on_step never auto-closes the
            # trace between async dispatches
            trace.on_step(min(i, trace.last - 1))
        out = fn(*args)     # async dispatch; device executes serially
    _readback(out)
    if trace is not None:
        trace.stop()
    return (time.perf_counter() - t0) / reps


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--size", type=int, nargs=2, default=(432, 1024),
                   metavar=("H", "W"))
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--iters", type=int, default=12)
    p.add_argument("--quick", action="store_true",
                   help="small size for CI smoke (128x256)")
    p.add_argument("--cpu", action="store_true",
                   help="functional check on the CPU backend (needs --impl; "
                        "never a measurement)")
    p.add_argument("--impl", default=None,
                   help="force a corr impl instead of auto-picking the best")
    p.add_argument("--budget", type=float, default=900.0,
                   help="wall-clock budget (s); later candidates are skipped "
                        "when exceeded (first compiles can be slow)")
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the winning "
                        "candidate's steady-state reps (telemetry.trace)")
    p.add_argument("--trace-steps", type=int, default=4,
                   help="reps captured by --trace-dir (default 4)")
    args = p.parse_args()
    if args.cpu and not args.impl:
        p.error("--cpu needs --impl NAME: the candidate sweep is a chip "
                "measurement, the CPU run checks one named configuration")
    t_start = time.perf_counter()

    from raft_tpu.compile_cache import configure_compile_cache
    configure_compile_cache()

    result = {
        "metric": f"raft-things inference throughput @ {args.iters} GRU iters",
        "value": None,
        "unit": ("pairs/sec (cpu functional check, not a device metric)"
                 if args.cpu else "pairs/sec/chip"),
        "vs_baseline": None,
        "mfu": None,
        "error": None,
    }
    try:
        _run(args, t_start, result)
    except Exception as e:  # noqa: BLE001 — the JSON line must still go out
        traceback.print_exc(file=sys.stderr)
        result["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(result), flush=True)
    return 1 if result["error"] else 0


def _run(args, t_start: float, result: dict) -> None:
    dev = _init_device(args.cpu)
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_inference_fn
    from raft_tpu.telemetry import Registry, config_hash, run_manifest
    from raft_tpu.telemetry.trace import TraceWindow

    # provenance: the config hash of the winning candidate is patched in
    # at the end
    result["manifest"] = run_manifest(mode="bench")
    registry = Registry()
    m_measured = registry.counter("raft_bench_candidates_measured_total",
                                  "Candidate configs that produced a number")
    m_tput = registry.gauge("raft_bench_pairs_per_sec",
                            "Measured throughput by candidate",
                            labelnames=("candidate",))

    if args.quick:
        args.size = (128, 256)

    H, W = args.size
    B = args.batch
    print(f"# device: {dev.platform}:{dev.device_kind}  input {B}x{H}x{W}  "
          f"iters {args.iters}", file=sys.stderr)
    # MFU is a device metric: a CPU functional check reports none
    peak = _peak_flops(dev.device_kind) if dev.platform == "tpu" else None

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))

    def throughput(config, iters, batch=None, trace=None):
        """AOT-compile so the same executable yields both the timing and the
        cost_analysis flops; returns (pairs/sec, mfu|None)."""
        batch = B if batch is None else batch
        im1 = jax.random.uniform(k1, (batch, H, W, 3), jnp.float32)
        im2 = jax.random.uniform(k2, (batch, H, W, 3), jnp.float32)
        params = init_raft(jax.random.PRNGKey(0), config)
        fn = jax.jit(make_inference_fn(config, iters=iters))
        compiled = fn.lower(params, im1, im2).compile()
        dt = _measure(compiled, (params, im1, im2), trace=trace)
        mfu = None
        if peak:
            costs = compiled.cost_analysis()
            if isinstance(costs, list):
                costs = costs[0]
            flops = float(costs.get("flops", 0.0))
            if flops > 0:
                mfu = flops / dt / peak
        return batch / dt, mfu

    # reference configuration FIRST (vs_baseline is the headline comparison):
    # dense fp32 corr volume + gather lookup, hardcoded 20 iters.  Explicit
    # literal formulation: the baseline must stay the REFERENCE's semantics
    # — dense fp32 volume, gather lookup, no hoist — or vs_baseline is
    # measured against an already-optimized 'reference'
    ref_cfg = RAFTConfig.full(corr_impl="dense", compute_dtype="float32",
                              corr_lookup="gather", gru_ctx_hoist=False)
    ref, ref_mfu = throughput(ref_cfg, 20)
    print(f"# reference-config (dense fp32, 20 iters): {ref:.3f} pairs/s"
          + (f"  mfu={ref_mfu:.3f}" if ref_mfu else ""), file=sys.stderr)

    # candidate tuned configurations, best-known-first so a tight budget
    # still measures the likely winner; best one is the headline number.
    # Every name listed here compiles for the chip (tests/test_tpu_compile
    # .py); a candidate that raises fails the run.
    candidates = ([args.impl] if args.impl else list(CANDIDATES))

    best_name, best, best_mfu = None, -1.0, None
    for name in candidates:
        if best_name is not None and time.perf_counter() - t_start > args.budget:
            print(f"# budget exceeded; skipping {name}", file=sys.stderr)
            continue
        tput, mfu = throughput(_cfg_for(name), args.iters)
        print(f"# {name}+bf16: {tput:.3f} pairs/s"
              + (f"  mfu={mfu:.3f}" if mfu else ""), file=sys.stderr)
        m_measured.inc()
        m_tput.labels(f"{name}+bf16").set(tput)
        if tput > best:
            best_name, best, best_mfu = f"{name}+bf16", tput, mfu

    # batching sweep on the winning config (free batch size is one of the
    # capabilities the reference lacked, reference readme.md:13; larger
    # batches raise MXU utilization and pairs/sec/chip)
    if B == 1:
        bare = best_name.split("+")[0]
        cfg = _cfg_for(bare)
        for nb in (4, 8, 16):
            if time.perf_counter() - t_start > args.budget:
                print(f"# budget exceeded; skipping batch {nb}", file=sys.stderr)
                break
            tput, mfu = throughput(cfg, args.iters, batch=nb)
            print(f"# {bare}+bf16 b{nb}: {tput:.3f} pairs/s"
                  + (f"  mfu={mfu:.3f}" if mfu else ""), file=sys.stderr)
            m_measured.inc()
            m_tput.labels(f"{bare}+bf16,b{nb}").set(tput)
            if tput > best:
                best, best_mfu = tput, mfu
                best_name = f"{bare}+bf16,b{nb}"

    bare = best_name.split("+")[0]
    bnum = int(best_name.split(",b")[1]) if ",b" in best_name else B

    # ---- adaptive-compute arm (round 8): per-sample early-exit rows -----
    # converge:* candidates ride the WINNING config: same executable shape,
    # the iteration count becomes data-dependent inside a compiled
    # while_loop.  The canonical eps rows (1e-2 / 1e-3 px at the 1/8 grid
    # — the trained-checkpoint operating points, TUNING.md) are measured
    # as-is; with random/untrained weights they honestly report
    # mean_iters = max, so an 'auto' row calibrates eps from THIS model's
    # own update-norm scale to demonstrate the early-exit mechanics and
    # the while-loop fast-path saving.  A mixed-difficulty sweep under
    # RecompileWatch then proves the static-shape claim: zero XLA
    # compiles across easy/hard batch compositions.
    if time.perf_counter() - t_start <= args.budget:
        result["converge"] = _converge_arm(
            args, registry, _cfg_for(bare), bnum, best, args.iters, (H, W))
    else:
        print("# budget exceeded; skipping converge arm", file=sys.stderr)

    # ---- quantization arm (ROADMAP item 3 remainder): post-training ----
    # quant rows ride the winning config: bf16w (encoder weights stored
    # bf16 — the serving engine's load-time cast) and the int8 SlotPool
    # row round-trip (quantize-on-scatter / dequantize-on-gather).
    if time.perf_counter() - t_start <= args.budget:
        result["quant"] = _quant_arm(
            args, registry, _cfg_for(bare), bnum, best, args.iters, (H, W))
    else:
        print("# budget exceeded; skipping quant arm", file=sys.stderr)

    if getattr(args, "trace_dir", None):
        # one extra steady-state measurement of the winner under the
        # profiler, so the trace shows exactly the headline configuration
        throughput(_cfg_for(bare), args.iters, batch=bnum,
                   trace=TraceWindow(args.trace_dir, first=0,
                                     steps=args.trace_steps,
                                     log_fn=lambda m: print(f"# {m}",
                                                            file=sys.stderr)))

    result["metric"] = (f"raft-things inference throughput @ {args.iters} "
                        f"GRU iters, {H}x{W} ({best_name})")
    result["value"] = round(best, 4)
    result["vs_baseline"] = round(best / ref, 4)
    result["mfu"] = round(best_mfu, 4) if best_mfu else None
    result["manifest"]["config_hash"] = config_hash(_cfg_for(bare))
    result["manifest"]["candidate"] = best_name
    result["metrics"] = registry.snapshot()


def _converge_arm(args, registry, base_cfg, bnum: int, fixed_tput: float,
                  iters: int, hw) -> dict:
    """Measure converge:* rows on the winning config + the mixed-difficulty
    zero-recompile proof.  Returns the JSON block for the result line."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_counted_inference_fn, raft_forward
    from raft_tpu.telemetry.watchdogs import RecompileWatch

    H, W = hw
    params = init_raft(jax.random.PRNGKey(0), base_cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    im1 = np.asarray(jax.random.uniform(k1, (bnum, H, W, 3), jnp.float32))
    im2 = np.asarray(jax.random.uniform(k2, (bnum, H, W, 3), jnp.float32))

    # eps calibration: the criterion's own quantity — mean ‖Δflow‖ at the
    # 1/8 grid — measured on THIS model with one iters=1 probe (the first
    # update's flow_lr IS its Δ; with untrained weights update norms only
    # grow from there, so the first is the floor).  eps_auto sits just
    # above every sample's first-update norm: the guaranteed-triggering
    # demonstration row for the early-exit mechanics.
    lr = np.asarray(jax.jit(
        lambda p, a, b: raft_forward(p, a, b, base_cfg, iters=1,
                                     train=False, all_flows=False)[0]
        .flow_lr)(params, im1, im2))
    dn1 = np.linalg.norm(lr, axis=-1).mean(axis=(1, 2))           # [B]
    eps_auto = float(dn1.max() * 1.05)

    m_iters = registry.gauge("raft_bench_mean_iters",
                             "Mean GRU iterations per pair by candidate",
                             labelnames=("candidate",))
    m_tput = registry.get("raft_bench_pairs_per_sec")
    out = {"baseline_pairs_per_sec": round(fixed_tput, 4),
           "baseline_mean_iters": float(iters),
           "eps_auto": round(eps_auto, 5), "rows": []}
    compiled_auto = None
    for spec in ("converge:1e-2", "converge:1e-3",
                 f"converge:{eps_auto:.5g}"):
        cfg = dataclasses.replace(base_cfg, iters_policy=spec)
        fn = jax.jit(make_counted_inference_fn(cfg, iters=iters))
        compiled = fn.lower(params, im1, im2).compile()
        dt = _measure(compiled, (params, im1, im2))
        _, iu = compiled(params, im1, im2)
        mean_iters = float(np.mean(np.asarray(iu)))
        tput = bnum / dt
        name = spec if spec.endswith(("1e-2", "1e-3")) else "converge:auto"
        m_tput.labels(f"{name}").set(tput)
        m_iters.labels(f"{name}").set(mean_iters)
        out["rows"].append({"policy": spec, "pairs_per_sec": round(tput, 4),
                            "mean_iters": round(mean_iters, 3),
                            "vs_fixed": round(tput / fixed_tput, 4)
                            if fixed_tput else None})
        print(f"# {spec}: {tput:.3f} pairs/s  mean_iters {mean_iters:.2f} "
              f"(fixed {iters})", file=sys.stderr)
        if name == "converge:auto":
            compiled_auto = compiled

    # mixed-difficulty sweep under the recompile watchdog: identical-frame
    # (easy) rows exit earliest, noise (hard) rows run longest — every
    # composition must reuse the ONE warm executable (static shapes)
    half = max(bnum // 2, 1)
    easy2 = im1.copy()
    mixed2 = im2.copy()
    mixed2[:half] = im1[:half]
    sweeps = {"easy": (im1, easy2), "mixed": (im1, mixed2),
              "hard": (im1, im2)}
    for a, b in sweeps.values():        # pre-arm pass caches the readback
        _readback(compiled_auto(params, a, b))
    watch = RecompileWatch().install()
    watch.arm()
    sweep_iters = {}
    try:
        for name, (a, b) in sweeps.items():
            _, iu = compiled_auto(params, a, b)
            sweep_iters[name] = float(np.mean(np.asarray(iu)))
    finally:
        watch.remove()
    out["mixed_sweep"] = {"mean_iters": {k: round(v, 3)
                                         for k, v in sweep_iters.items()},
                          "recompiles_after_warmup": watch.recompiles}
    print(f"# mixed-difficulty sweep: iters {sweep_iters}  "
          f"recompiles {watch.recompiles}", file=sys.stderr)
    if watch.recompiles:
        raise RuntimeError(
            f"{watch.recompiles} XLA compile(s) during the mixed-difficulty "
            f"sweep — the static-shape early-exit contract is broken")
    return out


def _quant_arm(args, registry, base_cfg, bnum: int, fixed_tput: float,
               iters: int, hw) -> dict:
    """Measure the post-training quantization rows on the winning config
    (ROADMAP item 3 remainder).  Two rows:

    bf16w — the serving engine's load-time encoder-weight cast
    (models.raft.cast_encoder_weights): full-pipeline throughput with the
    cast params + the encoder param-HBM halving it buys.

    int8 — the SlotPool row format (quantize-on-scatter /
    dequantize-on-gather): compression ratio of one encoded frame's
    (fmap, cnet) rows, the reconstruction error of the round-trip, and
    the round-trip rate (frames/s) — the per-step tax a streaming
    session pays to fit ~4x more sessions per chip."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import (cast_encoder_weights, dequantize_rows,
                                      encode_frame, make_inference_fn,
                                      quantize_rows)

    def _nbytes(tree) -> int:
        return int(sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(tree)))

    H, W = hw
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    im1 = jax.random.uniform(k1, (bnum, H, W, 3), jnp.float32)
    im2 = jax.random.uniform(k2, (bnum, H, W, 3), jnp.float32)
    m_tput = registry.get("raft_bench_pairs_per_sec")
    out = {"rows": []}

    # --- bf16w: encoder weights stored bf16 on device -------------------
    cfg = dataclasses.replace(base_cfg, quant="bf16w")
    params = init_raft(jax.random.PRNGKey(0), cfg)
    enc = {k: params[k] for k in ("fnet", "cnet") if k in params}
    enc_f32 = _nbytes(enc)
    qparams = cast_encoder_weights(params, cfg)
    enc_bf16 = _nbytes({k: qparams[k] for k in ("fnet", "cnet")
                        if k in qparams})
    fn = jax.jit(make_inference_fn(cfg, iters=iters))
    compiled = fn.lower(qparams, im1, im2).compile()
    dt = _measure(compiled, (qparams, im1, im2))
    tput = bnum / dt
    m_tput.labels("quant:bf16w").set(tput)
    out["rows"].append({
        "quant": "bf16w",
        "pairs_per_sec": round(tput, 4),
        "vs_fixed": round(tput / fixed_tput, 4) if fixed_tput else None,
        "encoder_bytes_f32": enc_f32,
        "encoder_bytes_bf16w": enc_bf16,
        "encoder_hbm_ratio": (round(enc_f32 / enc_bf16, 3)
                              if enc_bf16 else None),
    })
    print(f"# quant:bf16w: {tput:.3f} pairs/s  encoder HBM "
          f"{enc_f32 / 1e6:.2f} -> {enc_bf16 / 1e6:.2f} MB "
          f"(x{enc_f32 / max(enc_bf16, 1):.2f})", file=sys.stderr)

    # --- int8: SlotPool row round-trip ----------------------------------
    enc_fn = jax.jit(lambda p, a: encode_frame(p, a, base_cfg))
    fmap, cnet = enc_fn(params, im1)
    rt_fn = jax.jit(lambda r: dequantize_rows(*quantize_rows(r)))
    dt_rt = _measure(rt_fn, (fmap,))
    ref = np.asarray(fmap, np.float32)
    rec = np.asarray(rt_fn(fmap))
    max_err = float(np.max(np.abs(rec - ref)))
    # per-channel relative error: absmax maps to 127, so the bound is
    # half a quantization step ≈ absmax/254 per channel
    absmax = np.max(np.abs(ref), axis=(1, 2))          # [B, C]
    rel = float(np.max(np.max(np.abs(rec - ref), axis=(1, 2))
                       / np.maximum(absmax, 1e-12)))
    # baseline = what the SlotPool stores WITHOUT quant: the rows as the
    # encoder emits them (bf16 under bf16 compute, f32 under f32) — so the
    # ratio is the honest HBM saving for this config, ~2x from bf16 rows
    # and ~4x from f32 rows
    raw_bytes = _nbytes(fmap) + _nbytes(cnet)
    q_bytes = sum(_nbytes(t) for t in
                  (*quantize_rows(fmap), *quantize_rows(cnet)))
    out["rows"].append({
        "quant": "int8-rows",
        "row_dtype": str(fmap.dtype),
        "row_bytes_raw": raw_bytes,
        "row_bytes_int8": q_bytes,
        "compression": round(raw_bytes / q_bytes, 3) if q_bytes else None,
        "max_abs_err": round(max_err, 6),
        "max_rel_err": round(rel, 6),
        "roundtrip_frames_per_sec": round(bnum / dt_rt, 2),
    })
    print(f"# quant:int8-rows: x{raw_bytes / max(q_bytes, 1):.2f} "
          f"compression vs {fmap.dtype} rows  max_rel_err {rel:.2e}  "
          f"roundtrip {bnum / dt_rt:.1f} frames/s", file=sys.stderr)
    if rel > 1.0 / 127.0:
        raise RuntimeError(
            f"int8 row round-trip error {rel:.4g} exceeds the one-step "
            f"bound 1/127 — quantize_rows scale math is broken")
    return out


if __name__ == "__main__":
    sys.exit(main())
