"""Command-line driver: ``python -m raft_tpu.cli <mode> ...``

Covers the reference CLI surface (reference infer_raft.py:50-95) and makes
every mode real:

  test    single-pair inference -> colorized flow PNG (+ optional .flo)
  val     EPE evaluation over a dataset (the reference accepted 'val' with no
          handler at all, infer_raft.py:57-58)
  train   full training loop (absent from the reference, SURVEY.md §3.6)
  export  save params npz + StableHLO of the jitted forward (reference's
          export branch was ``pass``, infer_raft.py:71-72)
  flops   param table + XLA cost analysis (the reference's flops mode crashed
          on an arity bug before printing, SURVEY.md §3.3)

The reference hardcoded its output filename to raft_flow_raft-things.png even
for --small (infer_raft.py:44); here the name follows the variant.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np


def _iters_policy_spec(spec: str) -> str:
    """argparse type hook: validate --iters-policy at parse time (a typo'd
    policy must exit 2 with the parser's usage line, not traceback deep in
    the model)."""
    from .config import parse_iters_policy
    parse_iters_policy(spec)        # raises ValueError on malformed specs
    return spec


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="raft_tpu",
                                description="TPU-native RAFT optical flow")
    p.add_argument("-m", "--mode", default="test",
                   choices=["train", "val", "test", "export", "flops",
                            "serve", "serve_fleet"],
                   help="run mode (reference infer_raft.py:57-58 surface; "
                        "'serve' starts the long-lived micro-batching "
                        "inference server, 'serve_fleet' a replica fleet "
                        "behind one router — SERVING.md)")
    p.add_argument("--im1", default="assets/frame_0016.png", help="left image")
    p.add_argument("--im2", default="assets/frame_0017.png", help="right image")
    p.add_argument("--load", default=None,
                   help="checkpoint: torch .pth, reference .npz, or native .npz")
    p.add_argument("--out", default=".", help="output directory")
    p.add_argument("--small", action="store_true", help="raft-small variant")
    p.add_argument("--iters", type=int, default=None,
                   help="GRU iterations (default: 32 full / 12 small)")
    p.add_argument("--iters-policy", type=_iters_policy_spec, default=None,
                   metavar="POLICY",
                   help="iteration policy: 'fixed' (default) runs --iters "
                        "GRU iterations; 'converge:eps[:min_iters]' adds a "
                        "per-sample early exit — a sample whose mean 1/8-"
                        "grid flow update ‖Δflow‖ drops below eps (pixels) "
                        "freezes in place (static shapes, no recompiles), "
                        "and inference stops once the whole batch has "
                        "converged.  Iterations used are reported via the "
                        "raft_iters_used histogram (TUNING.md round 8)")
    p.add_argument("--size", type=int, nargs=2, default=(432, 1024),
                   metavar=("H", "W"), help="inference resolution")
    p.add_argument("--batch", type=int, default=None,
                   help="batch size (default: 1 for test/export, the stage "
                        "preset's batch for train, 4 under --demo-train)")
    p.add_argument("--corr-impl", default="dense",
                   choices=["dense", "blockwise", "pallas"])
    p.add_argument("--corr-lookup", default=None,
                   choices=["gather", "onehot"],
                   help="window-lookup formulation (default onehot — "
                        "measured winner on TPU and CPU; 'gather' is the "
                        "reference's SampleCorr semantics)")
    p.add_argument("--dtype", default=None, choices=["float32", "bfloat16"],
                   help="compute dtype (params stay float32).  Default: "
                        "bfloat16 on TPU for inference/eval modes (measured: "
                        "~1.5x throughput, held-out EPE delta +0.0009 on the "
                        "trained flagship — PERF.md round 5), float32 on "
                        "other backends and for train mode (bf16 training "
                        "convergence not yet validated end-to-end; opt in "
                        "explicitly)")
    p.add_argument("--ctx-hoist", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="precompute the GRU gate convs' context terms outside "
                        "the iteration loop (exact rewrite; default ON from "
                        "measured A/Bs — --no-ctx-hoist disables; TUNING.md)")
    p.add_argument("--gru-impl", default=None, choices=["xla", "pallas"],
                   help="SepConvGRU execution (full model): 'pallas' runs "
                        "each GRU iteration as ONE fused VMEM-resident "
                        "kernel (ops/gru_pallas.py; implies ctx hoisting; "
                        "off-TPU its XLA twin runs), 'xla' the conv "
                        "formulation (default)")
    p.add_argument("--gru-block-rows", type=int, default=None, metavar="T",
                   help="fused-GRU kernel: output rows per grid program "
                        "(default 8; tools/tune_pallas.py --kernel gru "
                        "sweeps it)")
    p.add_argument("--rgb", action="store_true",
                   help="input is RGB (default BGR, matching the reference)")
    p.add_argument("--save-flo", action="store_true", help="also write .flo")
    p.add_argument("--export-reference-npz", action="store_true",
                   help="export mode: additionally write the params in the "
                        "reference's tensorpack npz naming (W/gamma/mean-EMA "
                        "leaves, SURVEY.md §3.4) — loadable by the "
                        "reference's own weight-load path")
    p.add_argument("--show", action="store_true", help="cv2.imshow the result")
    p.add_argument("--cpu", action="store_true", help="force the CPU backend")
    p.add_argument("--spatial", type=int, default=None, metavar="N",
                   help="test mode: row-shard the whole model over N devices "
                        "(sequence-parallel inference: halo convs, psum "
                        "norms, ring-pass correlation — parallel/spatial."
                        "make_shard_inference_fn). H must be divisible by "
                        "8*N*2^(corr_levels-1)")
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="write a jax.profiler trace (XPlane, viewable in "
                        "TensorBoard/Perfetto) of a steady-state step "
                        "window — train: steps 5..5+N, val/serve: device "
                        "calls after the compile, test: the second run "
                        "(telemetry.trace.TraceWindow, OBSERVABILITY.md)")
    p.add_argument("--trace-steps", type=int, default=None, metavar="N",
                   help="steps/device calls captured by the --trace window "
                        "(default 4)")
    p.add_argument("--watchdogs", action="store_true",
                   help="enable the telemetry watchdogs: stack-wide "
                        "recompile counter, NaN/Inf sentinel with stage "
                        "provenance, HBM gauges (equivalent to "
                        "RAFT_TPU_WATCHDOGS=1 — OBSERVABILITY.md)")
    p.add_argument("--run-log", default=None, metavar="PATH",
                   help="run-event log: a directory (events.jsonl appended "
                        "inside) or a .jsonl path; every mode stamps its "
                        "manifest (git sha, jax versions, device, config "
                        "hash) as the first record.  Default: <--out>/"
                        "events.jsonl; 'none' disables")
    # dataset / training flags
    p.add_argument("--data", default=None, help="dataset root directory")
    p.add_argument("--dataset", default="sintel",
                   choices=["sintel", "chairs", "things", "kitti", "synthetic"])
    p.add_argument("--weighting", default=None,
                   choices=["sample", "pixel"],
                   help="val-mode metric aggregation: 'sample' averages "
                        "per-image means (Sintel protocol), 'pixel' pools "
                        "valid pixels across images (official KITTI "
                        "convention; default for --dataset kitti)")
    p.add_argument("--max-samples", type=int, default=None, metavar="N",
                   help="val mode: evaluate only the first N samples "
                        "(quick spot checks on big datasets)")
    p.add_argument("--dump-flow", default=None, metavar="DIR",
                   help="val mode: also write every prediction to DIR — "
                        "16-bit flow PNG encoding for --dataset kitti "
                        "(devkit <frame>_10.png naming, directly server-"
                        "submittable), .flo named frame_<idx:06d> otherwise")
    p.add_argument("--split", default=None,
                   choices=["training", "testing"],
                   help="val mode, --dataset kitti/sintel: which split to "
                        "run (default training; 'testing' has no ground "
                        "truth — metrics are skipped and --dump-flow is "
                        "required, producing a server-submission directory: "
                        "devkit <frame>_10.png PNGs for kitti, "
                        "<dstype>/<scene>/frame%%04d.flo for sintel — the "
                        "official create_sintel_submission naming)")
    p.add_argument("--dstype", default=None, choices=["clean", "final"],
                   help="val mode, --dataset sintel: which render pass "
                        "(default clean; submissions need both)")
    p.add_argument("--warm-start", action="store_true",
                   help="val mode, --dataset sintel: official video "
                        "protocol — each frame's low-res flow, forward-"
                        "projected, seeds the next frame of the same scene "
                        "(sequential; incompatible with --eval-batch)")
    p.add_argument("--eval-batch", type=int, default=None, metavar="N",
                   help="val mode: samples per device call, grouped by "
                        "padded shape (identical metrics; amortizes per-call "
                        "overhead — worth 8-16 on TPU for small shapes)")
    p.add_argument("--bucket", type=int, default=None,
                   help="val-mode resolution bucket (pad H,W to this "
                        "multiple; default: 8, the InputPadder protocol, or "
                        "64 for kitti's per-image sizes)")
    p.add_argument("--demo-train", action="store_true",
                   help="shortcut: train raft-small on the procedural "
                        "synthetic-flow dataset (no --data needed) for a few "
                        "hundred steps; EPE demonstrably drops from random "
                        "init, curve streamed to metrics.jsonl")
    p.add_argument("--num-steps", type=int, default=None)
    p.add_argument("--freeze-bn", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="train mode: freeze batch-norm running stats "
                        "(official recipe for every stage after chairs; "
                        "the stage presets set this — the flag overrides)")
    p.add_argument("--ckpt-every", type=int, default=None, metavar="N",
                   help="train mode: checkpoint period in steps (default: "
                        "the stage preset's; shorten for failure-recovery "
                        "drills — multi-host training resumes from the "
                        "latest checkpoint after a process failure)")
    p.add_argument("--keep-checkpoints", type=int, default=None,
                   metavar="N",
                   help="train mode: retain only the newest N step-"
                        "numbered checkpoints — the oldest are pruned "
                        "AFTER each successful atomic save (default: keep "
                        "everything); resume skips a corrupt/truncated "
                        "newest file with a warning instead of crashing")
    p.add_argument("--log-every", type=int, default=None, metavar="N",
                   help="train mode: metrics.jsonl/console logging period")
    p.add_argument("--async-ckpt", dest="async_ckpt", action="store_true",
                   default=None,
                   help="train mode: checkpoint through the background "
                        "writer thread — the step loop snapshots to host "
                        "and never blocks on serialization/fsync/verify "
                        "(the default; training/resilience.py)")
    p.add_argument("--sync-ckpt", dest="async_ckpt", action="store_false",
                   help="train mode: historical inline checkpointing — the "
                        "step loop blocks for the whole write (bit-for-bit "
                        "today's behavior; disables the async verify pass)")
    p.add_argument("--max-rollbacks", type=int, default=None, metavar="N",
                   help="train mode: divergence rollback budget — a "
                        "non-finite loss/grad-norm at any step restores the "
                        "last finite checkpoint snapshot and skips past the "
                        "offending data window, aborting after N "
                        "CONSECUTIVE rollbacks (default 3; 0 disables and "
                        "restores the halt-after-3-logged-steps behavior)")
    p.add_argument("--worker-respawns", type=int, default=None, metavar="N",
                   help="train mode, with --workers: respawn budget for "
                        "dead/stalled data workers — the pool is rebuilt "
                        "(shm slots reclaimed, queues replaced) up to N "
                        "times per 2-minute window before the loader "
                        "escalates to the historical error (default 3; "
                        "0 = fail fast)")
    p.add_argument("--chaos-train", default=None, metavar="SPEC",
                   help="train mode: arm the training-plane fault injector "
                        "(training/faults.py; env RAFT_TPU_CHAOS_TRAIN), "
                        "e.g. 'seed=5,worker_kill=0.02,worker_stall=0.01,"
                        "nan_loss=0.05,torn_ckpt=0.5,preempt=40' — rates "
                        "per arm, preempt takes the step at which SIGTERM "
                        "is self-delivered; tools/train_chaos.py is the "
                        "scripted drill")
    p.add_argument("--train-size", type=int, nargs=2, default=None,
                   metavar=("H", "W"),
                   help="training crop size (default: the stage preset's "
                        "crop, e.g. 368x496 chairs / 400x720 things; "
                        "96x128 for synthetic)")
    p.add_argument("--mp-start", default="forkserver",
                   choices=["fork", "forkserver", "spawn"],
                   help="worker start method (default forkserver: fork-safe "
                        "under JAX's threads); fork inherits the dataset "
                        "copy-on-write but can deadlock in a threaded parent")
    p.add_argument("--stall-timeout", type=float, default=300.0,
                   help="abort if live data workers deliver nothing for this "
                        "many seconds (deadlock/stalled-storage detection); "
                        "0 disables")
    p.add_argument("--workers", type=int, default=0,
                   help="decode/augment worker processes (0 = in-line in the "
                        "prefetch thread); the PrefetchDataZMQ analog")
    p.add_argument("--device-aug", action="store_true",
                   help="train mode: run the FlowAugmentor recipe ON DEVICE "
                        "(data/augment_device.py) — workers only decode "
                        "uint8 frames; photometric/scale/flip/crop/eraser "
                        "execute as one jitted batched program in the "
                        "prefetch stage (dense-gt stages only)")
    p.add_argument("--prefetch-depth", type=int, default=2, metavar="N",
                   help="train mode: staged device batches buffered ahead "
                        "of the consumer (PrefetchLoader depth; "
                        "raft_data_wait_seconds tells you if it is too low)")
    p.add_argument("--shm-slots", type=int, default=None, metavar="N",
                   help="train mode, with --workers: shared-memory sample "
                        "ring size for the zero-copy transport (default "
                        "2*workers+2; 0 falls back to pickling samples "
                        "through queues)")
    p.add_argument("--accum", type=int, default=None, metavar="K",
                   help="train mode: split each batch into K sequential "
                        "micro-batches inside the jitted step (gradient "
                        "accumulation; K must divide the batch) — fits the "
                        "official large-batch recipes in one chip's HBM")
    p.add_argument("-o", "--optimizer", default="adamw",
                   choices=["adam", "adamw", "sgd", "sgd_cyclic", "sgd_1cycle"])
    p.add_argument("--lr", type=float, default=None)
    # multi-host (multi-process) coordination over DCN: the same command line
    # runs unchanged on a v4-32 pod slice — one process per host, e.g.
    #   python -m raft_tpu.cli -m train --coordinator host0:1234 \
    #       --num-processes 4 --process-id $WORKER_ID ...
    # (env fallbacks RAFT_TPU_COORDINATOR / RAFT_TPU_NUM_PROCESSES /
    # RAFT_TPU_PROCESS_ID let launchers avoid per-host argv edits)
    p.add_argument("--shard-data", action="store_true",
                   help="multi-host train: each process loads only its own "
                        "1/N shard of the dataset (decode cost scales out; "
                        "streams decorrelate via per-host seeds; --workers "
                        "allowed). Default: every host builds the identical "
                        "global stream and keeps its slice (deterministic, "
                        "but decode cost replicates)")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multi-host train: coordinator address for "
                        "jax.distributed.initialize")
    p.add_argument("--num-processes", type=int, default=None,
                   help="multi-host train: total process count")
    p.add_argument("--process-id", type=int, default=None,
                   help="multi-host train: this process's rank")
    # serve mode (SERVING.md): every device shape is declared here, up
    # front — the engine AOT-compiles the (bucket x batch-step) grid before
    # accepting traffic, so steady-state serving never recompiles
    p.add_argument("--host", default="127.0.0.1",
                   help="serve mode: bind address")
    p.add_argument("--port", type=int, default=8000,
                   help="serve mode: bind port (0 = ephemeral, printed)")
    p.add_argument("--buckets", default="432x1024", metavar="HxW,HxW",
                   help="serve mode: pre-declared resolution buckets; each "
                        "request pads to the smallest fitting bucket "
                        "(sides must be multiples of 8)")
    p.add_argument("--max-batch", type=int, default=4,
                   help="serve mode: micro-batcher coalescing cap (batch 4 "
                        "measured 27.47 vs 21.12 pairs/s solo — PERF.md)")
    p.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="serve mode: max time the oldest queued request "
                        "waits for batch-mates before a partial flush")
    p.add_argument("--queue-depth", type=int, default=128,
                   help="serve mode: admission-queue bound; submissions "
                        "beyond it are shed with 429 (backpressure) "
                        "instead of queueing unboundedly")
    p.add_argument("--deadline-ms", type=float, default=2000.0,
                   help="serve mode: default per-request deadline; a "
                        "request still queued past it returns 504 "
                        "(clients can lower per call, never raise)")
    p.add_argument("--serve-dp", type=int, default=None, metavar="N",
                   help="serve mode: shard each device batch over N local "
                        "devices (parallel.make_dp_eval_fn); batch steps "
                        "are rounded up to multiples of N")
    p.add_argument("--no-warmup", action="store_true",
                   help="serve mode: skip the AOT warmup of the "
                        "(bucket x batch-step) compile grid (first "
                        "request per shape then pays its compile)")
    p.add_argument("--max-sessions", type=int, default=64, metavar="N",
                   help="serve mode: streaming (/v1/stream) session bound "
                        "— at most N sessions keep device-resident "
                        "feature maps; past it the LRU session's maps are "
                        "evicted and its next frame cold-restarts "
                        "(two encoder passes, correct flow).  0 disables "
                        "streaming entirely")
    p.add_argument("--session-ttl-s", type=float, default=300.0,
                   metavar="T",
                   help="serve mode: streaming sessions idle longer than "
                        "T seconds are reaped; advancing a reaped id is a "
                        "404 (the client reopens)")
    p.add_argument("--ragged", action="store_true",
                   help="serve mode: ragged mixed-resolution batching "
                        "(SERVING.md 'Ragged serving') — every request is "
                        "zero-embedded corner-anchored into the max "
                        "declared bucket and carries per-row live sizes, "
                        "so ONE executable per (kind, batch-step) serves "
                        "every bucket and requests of different "
                        "resolutions coalesce into one device batch "
                        "(requires corr_impl=pallas or the XLA ragged "
                        "reference; single-device only)")
    p.add_argument("--ragged-batch-pixels", type=int, default=0,
                   metavar="N",
                   help="serve mode (with --ragged): cap one device "
                        "batch's LIVE-pixel footprint — a popped run is "
                        "chunked so co-batched live pixels stay under N "
                        "(keeps one large frame from starving a group of "
                        "small ones).  0 = unbounded")
    # chaos + self-healing (SERVING.md "Failure modes & degradation
    # ladder"): fault injection is a first-class drill surface, and the
    # breaker/supervisor knobs gate what /healthz reports
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="serve mode: ARM FAULT INJECTION (drills only) — "
                        "a seeded spec like 'seed=11,engine_error=0.05,"
                        "latency=0.02,latency_ms=150,nan=0.03,session=0.05,"
                        "kill=0.01' (serving/faults.py; RAFT_TPU_CHAOS is "
                        "the env equivalent).  Injected faults are counted "
                        "in raft_fault_injected_total{arm=}")
    p.add_argument("--breaker-window", type=int, default=64, metavar="N",
                   help="serve mode: circuit-breaker sliding window (device "
                        "calls); error rate over it >= the threshold opens "
                        "the breaker (shed 503 + Retry-After).  0 disables")
    p.add_argument("--breaker-threshold", type=float, default=0.5,
                   metavar="R",
                   help="serve mode: error-rate fraction that opens the "
                        "breaker (in (0, 1])")
    p.add_argument("--breaker-cooldown-s", type=float, default=5.0,
                   metavar="T",
                   help="serve mode: seconds the breaker stays open before "
                        "half-open probes test recovery")
    # request-scoped tracing (telemetry/spans.py, OBSERVABILITY.md)
    p.add_argument("--trace-sample", type=float, default=1.0, metavar="P",
                   help="serve mode: fraction of completed request traces "
                        "retained (flight recorder + run-log 'trace' "
                        "events; error traces are always kept while > 0). "
                        "0 disables request tracing entirely — no spans, "
                        "no meta.timings, no SLO/flight-recorder families "
                        "on /metrics")
    p.add_argument("--slo-pair-ms", type=float, default=1000.0, metavar="T",
                   help="serve mode: /v1/flow latency objective; slower "
                        "(or failed) requests burn error budget — "
                        "raft_slo_burn_rate{class=pair} on /metrics")
    p.add_argument("--slo-stream-ms", type=float, default=500.0,
                   metavar="T",
                   help="serve mode: /v1/stream per-advance latency "
                        "objective (class=stream burn rate)")
    p.add_argument("--flightrec", default=None, metavar="PATH",
                   help="serve mode: flight-recorder dump path — written "
                        "on batcher crash, breaker open, post-warmup "
                        "recompile, and shutdown/SIGTERM (default "
                        "<--out>/flightrec.jsonl; '' disables the file, "
                        "GET /debug/traces still serves the ring)")
    p.add_argument("--engine-cache-dir", default=None, metavar="DIR",
                   help="serve mode: AOT executable cache — warmup load-or-"
                        "compiles serialized executables keyed by (config "
                        "hash, device kind, jax version); a warm DIR boots "
                        "the replica with ZERO XLA compiles (serve_fleet "
                        "shares one DIR across every replica).  Default "
                        "off: warmup always compiles")
    # metric time-series + anomaly sentinels (telemetry/timeseries.py,
    # telemetry/anomaly.py — OBSERVABILITY.md "Time-series & anomaly
    # detection"): the detection plane over the recovery plane
    p.add_argument("--history-interval-s", type=float, default=1.0,
                   metavar="T",
                   help="serve mode: metric-history sampling interval — a "
                        "background thread snapshots /metrics every T "
                        "seconds into a bounded ring (GET /debug/history, "
                        "anomaly sentinels, metrics_ts.jsonl).  0 disables "
                        "all three")
    p.add_argument("--history-window", type=int, default=600, metavar="N",
                   help="serve mode: metric-history ring depth (samples "
                        "retained; N x interval seconds of lookback)")
    p.add_argument("--history-path", default=None, metavar="PATH",
                   help="serve mode: metric time-series spill (one JSON "
                        "line per sample, manifest first; tlm top --replay "
                        "reads it).  Default <--out>/metrics_ts.jsonl; '' "
                        "keeps the ring + endpoint but skips the file")
    p.add_argument("--no-anomaly", action="store_true",
                   help="serve mode: disable the anomaly sentinels (the "
                        "p95-drift / burn / occupancy / queue / miss-"
                        "trickle / restart-rate rules armed after warmup; "
                        "raft_anomaly_active{rule=} + 'anomaly' run-log "
                        "events)")
    p.add_argument("--anomaly-window-s", type=float, default=15.0,
                   metavar="T",
                   help="serve mode: recent window every sentinel rule "
                        "evaluates over")
    p.add_argument("--anomaly-baseline-s", type=float, default=60.0,
                   metavar="T",
                   help="serve mode: trailing baseline window for the "
                        "p95-drift rule (must exceed the rule window)")
    p.add_argument("--quant", default=None,
                   choices=("none", "int8", "bf16w", "int8+bf16w"),
                   help="serve mode: post-training quantization — 'int8' "
                        "stores slot-pool fmap/cnet rows as int8 + per-"
                        "channel f32 scales (dequant on gather; 1.98x more "
                        "sessions per HBM byte under --dtype bfloat16: a "
                        "1080p slot 33.44 -> 16.85 MB, the float32 seed "
                        "staying; 3.4x against float32 rows), 'bf16w' casts the fnet/"
                        "cnet encoder weights to bf16 for device storage "
                        "(f32 math), 'int8+bf16w' both.  EPE delta is "
                        "gated by tools/envelope_check.py")
    # serve_fleet mode (SERVING.md "Fleet"): N serve subprocesses behind
    # one session-affinity router; every serve flag above is forwarded to
    # each replica verbatim
    p.add_argument("--replicas", type=int, default=2,
                   help="serve_fleet mode: initial replica count")
    p.add_argument("--min-replicas", type=int, default=None,
                   help="serve_fleet mode: autoscaler floor (default 1)")
    p.add_argument("--max-replicas", type=int, default=None,
                   help="serve_fleet mode: autoscaler/scale_to ceiling "
                        "(default max(--replicas, 2))")
    p.add_argument("--autoscale", action="store_true",
                   help="serve_fleet mode: enable the signal-driven "
                        "autoscaler (SLO burn rate, queue fill, shed rate, "
                        "breaker state; hysteretic, see SERVING.md Fleet)")
    p.add_argument("--fleet-port", type=int, default=None,
                   help="serve_fleet mode: router bind port (default "
                        "--port; replicas always bind ephemeral ports)")
    p.add_argument("--pin-cpus", action="store_true",
                   help="serve_fleet mode: pin each replica to a disjoint "
                        "round-robin CPU-core slice (sched_setaffinity) so "
                        "replicas scale cores instead of fighting for them")
    p.add_argument("--health-poll-s", type=float, default=None,
                   help="serve_fleet mode: replica /healthz + /metrics "
                        "poll cadence — also the failure-detection clock "
                        "(default 1.0)")
    p.add_argument("--scale-poll-s", type=float, default=None,
                   help="serve_fleet mode: autoscaler decision cadence "
                        "(default 5.0)")
    return p


def _start_run_log(args, config):
    """Open this run's event log (telemetry.events) with the manifest —
    git sha, jax/jaxlib versions, device kind + count, config hash, argv —
    as its first record, and make it the process-wide active log so the
    watchdogs and the training loop attach their events to it.  Every CLI
    mode calls this right after building its config (OBSERVABILITY.md)."""
    dest = getattr(args, "run_log", None)
    if dest == "none":
        return None
    if dest is None:
        # programmatic callers (tests, harnesses) build Namespaces by hand;
        # no --out and no --run-log means nowhere sensible to write
        dest = getattr(args, "out", None)
        if not dest:
            return None
    from .telemetry import events, watchdogs
    log = events.start_run(Path(dest), mode=args.mode, config=config)
    events.set_current(log)
    if watchdogs.watchdogs_enabled():
        # trace-time switch: models compiled from here on carry the NaN/Inf
        # sentinel callbacks (stage-provenanced; free when off)
        watchdogs.enable_nan_sentinel(True, run_log=log)
    return log


def _make_config(args):
    from .config import RAFTConfig
    dtype = args.dtype
    if dtype is None:
        # measured default (round 5): on TPU, bf16 compute wins ~1.5x with a
        # +0.0009 held-out-EPE cost on the trained flagship (negligible);
        # CPU emulates bf16 (slower), and bf16 TRAINING convergence has no
        # end-to-end validation run yet — so those keep float32 unless
        # explicitly requested.  (--cpu has already pinned the backend by
        # the time mode handlers call this.)
        # restricted to test/val: train convergence is unvalidated in bf16,
        # and export/flops artifacts must not change numerics with the host
        # they happened to run on
        import jax
        dtype = ("bfloat16" if jax.default_backend() == "tpu"
                 and args.mode in ("test", "val", "serve") else "float32")
        if (dtype == "bfloat16" and args.mode == "val"
                and getattr(args, "split", None) == "testing"
                and getattr(args, "dump_flow", None)):
            # ADVICE r5: submission artifacts (server-uploadable .flo/PNG)
            # must not silently vary with the host backend — same contract
            # as export/flops above.  Pin float32; --dtype bfloat16 still
            # opts in explicitly.
            dtype = "float32"
            print("[val] testing-split submission export: pinning float32 "
                  "(artifacts must not vary with the host backend; pass "
                  "--dtype bfloat16 to override)")
    overrides = dict(corr_impl=args.corr_impl, compute_dtype=dtype)
    if args.ctx_hoist is not None:       # tri-state: None = config default
        overrides["gru_ctx_hoist"] = args.ctx_hoist
    # getattr: programmatic callers (tests, serving harnesses) build
    # Namespaces by hand and may predate these flags
    if getattr(args, "gru_impl", None) is not None:
        overrides["gru_impl"] = args.gru_impl
    if getattr(args, "gru_block_rows", None) is not None:
        overrides["gru_block_rows"] = args.gru_block_rows
    if args.corr_lookup is not None:
        overrides["corr_lookup"] = args.corr_lookup
    if getattr(args, "iters_policy", None) is not None:
        overrides["iters_policy"] = args.iters_policy
    if getattr(args, "quant", None) is not None:
        overrides["quant"] = args.quant
    if args.iters is not None:
        overrides["iters"] = args.iters
    if args.small:
        return RAFTConfig.small_model(**overrides)
    return RAFTConfig.full(**overrides)


def _load_params(args, config):
    import jax
    from .models import init_raft
    if args.load:
        from .convert import load_checkpoint_auto
        from .convert.weights import detect_format
        import jax.numpy as jnp
        params = load_checkpoint_auto(args.load)
        if not args.rgb and detect_format(args.load) == "torch":
            # official torch checkpoints are RGB-trained; inputs arrive BGR
            from .convert import swap_rgb_bgr
            swap_rgb_bgr(params)
            print("swapped stem convs RGB->BGR for torch checkpoint")
        params = jax.tree.map(jnp.asarray, params)
        print(f"loaded checkpoint from {args.load}")
    else:
        from .config import init_rng
        params = init_raft(init_rng(), config)
        print("WARNING: no --load given; using RANDOM weights", file=sys.stderr)
    return params


def _read_pair(args):
    import cv2
    im1 = cv2.imread(args.im1)        # BGR uint8, like the reference pipeline
    im2 = cv2.imread(args.im2)
    if im1 is None or im2 is None:
        raise FileNotFoundError(f"could not read {args.im1} / {args.im2}")
    if args.rgb:
        im1, im2 = im1[:, :, ::-1], im2[:, :, ::-1]
    h, w = args.size
    im1 = cv2.resize(im1, (w, h)).astype(np.float32) / 255.0
    im2 = cv2.resize(im2, (w, h)).astype(np.float32) / 255.0
    return im1[None], im2[None]


def mode_test(args) -> int:
    import jax
    import jax.numpy as jnp
    from .models.raft import make_inference_fn
    from .utils import flow_to_color, write_flo

    config = _make_config(args)
    _start_run_log(args, config)
    params = _load_params(args, config)
    im1, im2 = _read_pair(args)
    if args.batch > 1:
        im1 = np.repeat(im1, args.batch, axis=0)
        im2 = np.repeat(im2, args.batch, axis=0)

    if args.spatial and args.spatial > 1:
        # sequence-parallel path: the whole model runs row-sharded over N
        # devices (explicit shard_map: halo-exchange convs, psum'd norms,
        # ring-pass correlation) — the runnable CLI surface of the
        # long-context story, complementing multi-host -m train
        from jax.sharding import Mesh
        from .parallel.spatial import (make_shard_inference_fn,
                                       required_h_multiple)

        n = args.spatial
        if len(jax.devices()) < n:
            print(f"ERROR: --spatial {n} needs {n} devices, have "
                  f"{len(jax.devices())}")
            return 2
        need = required_h_multiple(config, n)
        h = im1.shape[1]
        if h % need:
            print(f"ERROR: --spatial {n} requires H divisible by {need} "
                  f"(8 * N devices * 2^(corr_levels-1)); got H={h}. "
                  f"Pick --size accordingly, e.g. H={((h // need) + 1) * need}")
            return 2
        mesh = Mesh(np.array(jax.devices()[:n]), ("spatial",))
        fn = make_shard_inference_fn(config, mesh)
        print(f"[test] sequence-parallel: rows sharded over {n} devices")
    else:
        fn = jax.jit(make_inference_fn(config))
    t0 = time.time()
    flow = np.asarray(fn(params, jnp.asarray(im1), jnp.asarray(im2)))
    t1 = time.time()
    if args.trace:
        jax.profiler.start_trace(args.trace)
    flow2 = np.asarray(fn(params, jnp.asarray(im1), jnp.asarray(im2)))
    t2 = time.time()
    if args.trace:
        jax.profiler.stop_trace()
        print(f"wrote profiler trace to {args.trace}")
    del flow2
    print(f"flow {flow.shape}  compile+run {t1 - t0:.2f}s  steady {t2 - t1:.3f}s")

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    variant = "raft-small" if args.small else "raft-things"
    png = outdir / f"raft_flow_{variant}.png"
    color = flow_to_color(flow[0], convert_to_bgr=True)
    import cv2
    cv2.imwrite(str(png), color)
    print(f"wrote {png}")
    if args.save_flo:
        flo = outdir / f"raft_flow_{variant}.flo"
        write_flo(flow[0], flo)
        print(f"wrote {flo}")
    if args.show:
        cv2.imshow("raft_flow", color)
        cv2.waitKey(0)
    return 0


def mode_flops(args) -> int:
    import jax.numpy as jnp
    from .models import init_raft
    from .models.raft import make_inference_fn
    from .utils import count_params, flops_report, param_table

    config = _make_config(args)
    _start_run_log(args, config)
    from .config import init_rng
    params = init_raft(init_rng(), config)
    print(param_table(params))
    print(f"trainable parameters: {count_params(params):,}")
    # the reference profiled at 1x256x448x3 (infer_raft.py:83-84)
    im = jnp.zeros((1, 256, 448, 3), jnp.float32)
    fn = make_inference_fn(config)
    flops, msg = flops_report(fn, params, im, im)
    print(msg)
    return 0


def mode_export(args) -> int:
    import jax
    import jax.numpy as jnp
    from .convert import save_params_npz, to_reference_npz
    from .models.raft import make_inference_fn

    config = _make_config(args)
    _start_run_log(args, config)
    params = _load_params(args, config)
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    variant = "raft-small" if args.small else "raft-things"

    ckpt = outdir / f"{variant}.npz"
    save_params_npz(jax.tree.map(np.asarray, params), ckpt)
    print(f"wrote {ckpt}")

    if args.export_reference_npz:
        ref = outdir / f"{variant}.reference.npz"
        to_reference_npz(jax.tree.map(np.asarray, params), ref)
        print(f"wrote {ref} (reference/tensorpack naming, SURVEY.md §3.4)")

    h, w = args.size
    im = jnp.zeros((args.batch, h, w, 3), jnp.float32)
    lowered = jax.jit(make_inference_fn(config)).lower(params, im, im)
    hlo = outdir / f"{variant}.stablehlo.txt"
    hlo.write_text(lowered.as_text())
    print(f"wrote {hlo} (StableHLO, input {im.shape})")
    return 0


def mode_val(args) -> int:
    from .training.evaluate import evaluate_cli
    config = _make_config(args)
    _start_run_log(args, config)
    return evaluate_cli(args, config, _load_params)


def mode_train(args) -> int:
    from .training.loop import train_cli
    config = _make_config(args)
    _start_run_log(args, config)
    return train_cli(args, config)


def mode_serve(args) -> int:
    from .serving.server import serve_cli
    config = _make_config(args)
    _start_run_log(args, config)
    return serve_cli(args, config, _load_params)


def mode_serve_fleet(args) -> int:
    from .fleet import keep_launcher_off_chip, serve_fleet_cli
    # before anything below asks JAX for a backend (the dtype default, the
    # manifest's device query, the seeded weight init): the chips belong
    # to the replicas
    keep_launcher_off_chip()
    config = _make_config(args)
    _start_run_log(args, config)
    return serve_fleet_cli(args, config, _load_params)


def parse_args(argv=None):
    """argv -> the Namespace every mode handler expects: argparse plus the
    defaults that depend on other flags.  One function, so an in-process
    driver of the real entry points (chip_smoke.py) builds exactly what
    ``python -m raft_tpu.cli`` builds."""
    args = _build_parser().parse_args(argv)
    if args.demo_train:
        args.mode = "train"
        args.dataset = "synthetic"
        args.small = True
        if args.num_steps is None:
            args.num_steps = 300
        if args.lr is None:
            args.lr = 2e-4
        if args.iters is None:
            args.iters = 8
        if args.batch is None:
            args.batch = 4
    if args.batch is None and args.mode != "train":
        # train mode leaves None so the stage preset's batch size applies
        args.batch = 1
    if args.watchdogs:
        # one switch for every subsystem: the training loop, the serving
        # stack and the model's NaN sentinel all read this env var
        import os
        os.environ["RAFT_TPU_WATCHDOGS"] = "1"
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    return args


def main(argv=None) -> int:
    from .compile_cache import configure_compile_cache
    configure_compile_cache()
    args = parse_args(argv)
    if args.mode == "train":
        # must run before anything touches a device: jax.distributed connects
        # the processes and makes jax.devices() span every host (env
        # fallbacks for all three args live inside initialize)
        from .parallel.distributed import initialize
        initialize(coordinator_address=args.coordinator,
                   num_processes=args.num_processes,
                   process_id=args.process_id)
    return {"test": mode_test, "flops": mode_flops, "export": mode_export,
            "val": mode_val, "train": mode_train,
            "serve": mode_serve,
            "serve_fleet": mode_serve_fleet}[args.mode](args)


if __name__ == "__main__":
    sys.exit(main())
