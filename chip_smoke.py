#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that raft-tpu still starts on the chip.

    python3 chip_smoke.py             # one chip: device, kernels, serve,
                                      # small, int8, train
    python3 chip_smoke.py --int8      # one chip: the int8 phase alone
    python3 chip_smoke.py --chips 4   # four chips: fleet, dp=4 step, spatial-4
    python3 chip_smoke.py --flows     # one chip: the served flows' sha256

Drives the system's main path once through the entry points a user calls —
the real ``-m serve`` server and the real ``-m train`` trainer — at the
published widths of raft-things (``RAFTConfig.full``: fnet 256, hidden 128,
context 128, 4 levels, radius 4), with seeded random weights and the
committed Sintel pair ``assets/frame_0016.png`` / ``frame_0017.png``; the
``small`` phase runs RAFT-S's served program (hidden 96, radius 3) once at the
benchmark's 8 x 1080x1920 against ``benchmark/reference.py``; the ``int8``
phase opens, advances, demotes and restarts a session on the 256-slot 1080p
int8 pool of ``benchmark/configs/raft-things-1080p-stream-int8.json`` (PR 45:
flows finite, the pool's bytes printed).  ``--flows``
is a tool and no part of the smoke: it prints the sha256 of the five served
programs' flows on a fixed seed, for a change to the lookup that has to leave
them the parent's bit for bit (run it on both trees in one call).

Contract: one process holds the chip; the first device must be a TPU (no
probe child, no retry, no CPU); a phase that fails ends the run with a
non-zero exit code and no result line; on success the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and every earlier line worth reading is one JSON object per phase (seconds,
compile seconds, compile-cache hits, peak device bytes).  Work files go to
``<checkout>/.chip_smoke`` (git-ignored); nothing outside the checkout is
written except where ``JAX_COMPILATION_CACHE_DIR`` points.

Times printed here are set-up facts, not performance numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import io
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")
sys.path.insert(0, ROOT)


#: The five served programs that look correlation up (BENCHMARK.json's five
#: configurations: ``pair`` at each ``/v1/flow`` configuration's frame and
#: batch, the stream configurations' batched advance and solo step; their
#: ``encode`` has no lookup): (name, configuration file, kind, batch).
FLOW_PROGRAMS = (
    ("things-sintel/pair-32", "raft-things.json", "pair", 32),
    ("things-1080p/pair-8", "raft-things-1080p.json", "pair", 8),
    ("small-1080p/pair-8", "raft-small-1080p.json", "pair", 8),
    ("things-stream/sbatch-8", "raft-things-1080p-stream.json", "sbatch", 8),
    ("things-stream-churn/stream-1", "raft-things-1080p-stream-churn.json",
     "stream", 1))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every shape the smoke runs at.  ``main`` always uses ``Sizes()`` —
    the real ones; the CPU rehearsal (tests/test_chip_smoke.py) passes tiny
    ones with ``interpret=True`` through the same phase functions."""

    interpret: bool = False           # Pallas interpret mode (CPU rehearsal)
    iters: int = 12
    # serve: the Sintel pair as committed, padded by the server's own
    # padder (data.pipeline.pad_to_shape) into the one declared bucket
    bucket: tuple = (440, 1024)
    # the kernels' query grids, with the lookup's channels and radius: the
    # bucket / 8 (Sintel: levels 0 and 1 are banded, level 1's rows two to a
    # 128-lane row; levels 2 and 3 one block each, four and eight to a row),
    # and 1080x1920 / 8, whose levels 0 to 2 are banded (each query tile
    # fetches a band of 16 key rows; level 2's lie two to a 128-lane row,
    # level 3's, one block, four) and whose GRU rows are 244 stored columns
    # wide (a VMEM limit of its own), for raft-things and for RAFT-S's
    # lookup (the three configurations BENCHMARK.json serves)
    kernel_cases: tuple = ((55, 128, 256, 4), (135, 240, 256, 4),
                           (135, 240, 128, 3))
    # train: the chairs recipe's crop and global batch (config.py
    # TrainConfig.for_stage("chairs")).  One micro-batch of 10 does not fit
    # a 16 GB chip, so the fit knob is --accum, chosen from the chip
    # compiler's memory_analysis() of the whole train step (TRAIN_FIT).
    train_size: tuple = (368, 496)
    train_batch: int = 10
    train_accum: int = 2
    train_corr: str = "pallas"
    train_workers: int = 2
    # small: RAFT-S as benchmark/configs/raft-small-1080p.json serves it,
    # at the batch and frame size the cell small-1080p-b8-closed times
    small_batch: int = 8
    small_hw: tuple = (1080, 1920)
    # int8: the slot pool of benchmark/configs/raft-things-1080p-stream-
    # int8.json (its serve arguments: the bucket and --max-sessions are
    # exchanged for these in the CPU rehearsal alone)
    int8_hw: tuple = (1080, 1920)
    int8_slots: int = 256
    # flows: the five served programs (FLOW_PROGRAMS) at their own frames,
    # batches and iteration counts; the CPU rehearsal cuts all three
    flow_programs: tuple = FLOW_PROGRAMS
    flow_hw: tuple = ()
    flow_batch: int = 0
    flow_iters: int = 0
    # --chips 4
    dp_batch: int = 8                 # global batch of the dp=4 comparison
    # the pjit step cannot carry the Pallas kernel ("Mosaic kernels cannot
    # be automatically partitioned. Please wrap the call in a shard_map" —
    # sandbox compile for v5e:2x2, PR 21); the dense volume partitions
    dp_corr: str = "dense"
    spatial_hw: tuple = (1024, 1920)  # H divisible by 8 * 4 chips * 2^3


# memory_analysis() of jit(make_train_step(RAFTConfig.full(iters=12), chairs
# crop 368x496, global batch 10, f32), donate_argnums=0) compiled in the
# sandbox for a described v5e (no chip attached).  The device reports
# bytes_limit 15.75 GiB.  Smallest --accum whose program leaves half the chip
# free wins; 'pallas' over 'dense' because it needs less at every accum and
# is the path that never builds the (HW)^2 volume.
TRAIN_FIT = {
    "source": "sandbox compile for described v5e:2x2 device 0, PR 21",
    "dense,accum=1": "temp 17.6 GB > 16 GB: does not fit (ISSUE 21)",
    "dense,accum=2": "temp 9.89 GiB",
    "dense,accum=5": "temp 4.99 GiB",
    "pallas,accum=1": "temp 13.33 GiB: 85% of the chip before batches, "
                      "augmentation and checkpoint copies",
    "pallas,accum=2": "temp 7.88 GiB, 8 tpu_custom_call  <- chosen",
    "pallas,accum=5": "temp 4.21 GiB, 8 tpu_custom_call",
}


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def emit(**rec) -> None:
    print(json.dumps(rec, default=str), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------ bookkeeping

class CompileMeter:
    """Sums XLA compile seconds and counts persistent-cache hits/misses from
    JAX's own monitoring events (the backend-compile event also covers the
    retrieval time of a cache hit, so 'seconds' shrinks when the cache
    works)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.seconds, self.hits, self.misses)


class Phase:
    """``with Phase(meter, "serve") as ph: ... ph.note(k=v)`` prints one
    JSON line when the block ends; a raise prints the failure and
    propagates (the run ends non-zero)."""

    def __init__(self, meter: CompileMeter, name: str):
        self.meter, self.name, self.notes = meter, name, {}

    def note(self, **kv):
        self.notes.update(kv)

    def __enter__(self):
        self.t0 = time.monotonic()
        self.c0 = self.meter.snapshot()
        return self

    def __exit__(self, etype, e, tb):
        import jax
        c1 = self.meter.snapshot()
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        emit(phase=self.name, ok=etype is None,
             seconds=round(time.monotonic() - self.t0, 2),
             compile_seconds=round(c1[0] - self.c0[0], 2),
             cache_hits=c1[1] - self.c0[1], cache_misses=c1[2] - self.c0[2],
             peak_bytes=[s.get("peak_bytes_in_use") for s in stats],
             **self.notes,
             **({"error": f"{etype.__name__}: {e}"} if etype else {}))
        return False


def read_pair():
    """The committed Sintel pair, as ``cli._read_pair`` reads images: BGR
    uint8 -> float32 in [0, 1], [H, W, 3]."""
    import cv2
    import numpy as np
    ims = []
    for name in ("frame_0016.png", "frame_0017.png"):
        im = cv2.imread(os.path.join(ROOT, "assets", name))
        check(im is not None, f"assets/{name} is missing from the checkout")
        ims.append(im.astype(np.float32) / 255.0)
    return ims


def fit_to(im, hw):
    """The pair as sent: untouched at the real size (436x1024 fits the
    440x1024 bucket); the CPU rehearsal's tiny buckets get it resized to 4
    rows short of the bucket, so the server's padder still has work."""
    import cv2
    h, w = hw
    if im.shape[0] <= h and im.shape[1] <= w:
        return im
    return cv2.resize(im, (w, h - 4))


def npz_body(**arrays) -> bytes:
    import numpy as np
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def http_call(url: str, method: str, path: str, body: bytes = None):
    """-> (status, payload bytes, headers).  npz in, npz out."""
    host, port = url.split("//", 1)[1].split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=300)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/octet-stream",
                              "Accept": "application/octet-stream"}
                     if body is not None else {})
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.getheaders())
    finally:
        conn.close()


def npz_load(payload: bytes) -> dict:
    import numpy as np
    with np.load(io.BytesIO(payload)) as z:
        return {k: np.asarray(z[k]) for k in z.files}


def prom_value(text: str, name: str) -> float:
    """Sum of every sample of metric ``name`` in a Prometheus exposition."""
    from raft_tpu.fleet.manager import parse_prom_text
    vals = [v for k, v in parse_prom_text(text).items()
            if k.split("{", 1)[0] == name]
    check(vals, f"/metrics has no sample of {name}")
    return sum(vals)


# ------------------------------------------------------------------ device

def phase_device(meter, need: int) -> dict:
    """The first device must be a TPU, ``need`` of them visible, and the
    fleet's libtpu-free chip count (device files) must agree with JAX."""
    import jax
    with Phase(meter, "device") as ph:
        devs = jax.devices()
        d = devs[0]
        ph.note(platform=d.platform, kind=d.device_kind, count=len(devs),
                jax=jax.__version__,
                compile_cache_dir=jax.config.jax_compilation_cache_dir)
        check(d.platform == "tpu",
              f"jax.devices()[0].platform is {d.platform!r}, not 'tpu' "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): "
              f"chip_smoke.py runs on the chip or not at all")
        check(len(devs) == need,
              f"this run needs {need} chip(s) and JAX sees {len(devs)}")
        from raft_tpu.fleet.manager import local_chip_count
        files = local_chip_count()
        ph.note(device_files=files)
        check(files == len(devs),
              f"fleet.manager.local_chip_count() = {files} but JAX sees "
              f"{len(devs)} chip(s): the fleet would mis-place its replicas")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# ----------------------------------------------------------------- kernels

# GRU kernel vs its XLA twin at f32 I/O.  The retired tools/hw_smoke.py gated
# this at 1e-4 and never ran on a chip.  On the chip both sides feed the MXU
# at its DEFAULT precision — f32 operands rounded to bf16, f32 accumulation;
# that is XLA's default for the twin's convs and Mosaic's for the kernel's
# dots — so two values that differ in their last f32 bit can round to
# different bf16 neighbours: a 2^-8 relative flip in one of 640 products per
# output, through two chained passes.  Measured on the chip (PR 21): 1.0e-3
# between kernel and twin, 1.6e-2 between the kernel and a HIGHEST-precision
# oracle (the cost of the MXU default itself, shared by the XLA path the
# kernel replaces).  5e-3 passes the first and fails the second.
GRU_F32_TOL = 5e-3


def phase_kernels(meter, sz: Sizes) -> None:
    """Both Pallas kernels at every case of ``sz.kernel_cases`` (Sintel's
    grid and 1080p's, raft-things' lookup and RAFT-S's: a changed kernel
    meets every served shape), compiled by Mosaic
    (``interpret=False`` / ``impl='kernel'``), executed on the chip and
    compared with their XLA oracles: the corr kernel at HIGHEST precision
    against ``lookup_dense`` at HIGHEST, 1e-4 (both exact f32, only the
    summation order differs — the retired tools/hw_smoke.py's gate), and
    its bfloat16 output against its float32 output rounded, bit for bit (the
    kernel rounds the same float32 sums as it writes them); the GRU kernel
    against ``sep_conv_gru_xla`` at GRU_F32_TOL for f32 I/O and
    5e-2 for bf16 I/O (the kernel rounds to bf16 at its boundary)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.models.update import init_sep_conv_gru, precompute_gru_ctx
    from raft_tpu.ops.coords import coords_grid
    from raft_tpu.ops.corr import build_pyramid, fmap2_pyramid, lookup_dense
    from raft_tpu.ops.corr_pallas import (_fused_lookup_impl, level_plans,
                                          level_shapes, lookup_schedules,
                                          schedule_keyblocks)
    from raft_tpu.ops.gru_pallas import sep_conv_gru_pallas, sep_conv_gru_xla

    def run(fn, *args):
        """Lower, assert the kernel is in the lowered text of what runs,
        compile, execute."""
        lowered = jax.jit(fn).lower(*args)
        if not sz.interpret:
            check("tpu_custom_call" in lowered.as_text(),
                  "no tpu_custom_call in the lowered kernel program")
        return np.asarray(lowered.compile()(*args), np.float32)

    def one_grid(h: int, w: int, C: int, radius: int) -> dict:
        levels = 4
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        f1 = jax.random.normal(k1, (1, h, w, C), jnp.float32)
        f2 = jax.random.normal(k2, (1, h, w, C), jnp.float32)
        coords = coords_grid(1, h, w) + jax.random.uniform(
            k3, (1, h, w, 2), minval=-8, maxval=8)
        # oracle at HIGHEST precision: the default would round the f32
        # contraction's inputs to bf16 on the MXU and swamp the 1e-4 gate
        want = np.asarray(lookup_dense(
            build_pyramid(f1, f2, levels,
                          precision=jax.lax.Precision.HIGHEST),
            coords, radius))
        f2_levels = tuple(fmap2_pyramid(f2, levels))
        errs = {}
        # the launches as the kernel's own plan bands them at this grid and
        # at finer blocks (more levels banded, shorter bands), and every
        # row-block walked (the parent's values, bit for bit: PRs 26-36)
        got = {}
        for name, p_blk, sched in (("rule", 4096, None),
                                   ("rule-fine", 1024, None),
                                   ("all", 4096, (None,) * levels)):
            got[name] = run(functools.partial(
                _fused_lookup_impl, radius=radius, q_blk=128,
                p_blk_target=p_blk, interpret=sz.interpret,
                schedules=sched), f1, f2_levels, coords)
            errs[f"corr/{name}"] = float(np.abs(got[name] - want).max())
            check(errs[f"corr/{name}"] < 1e-4,
                  f"corr kernel ({name}) at {h}x{w} vs lookup_dense: "
                  f"max|err| {errs[f'corr/{name}']:.3e} >= 1e-4")
        errs["corr/rule_vs_all"] = float(np.abs(got["rule"]
                                                - got["all"]).max())
        check(errs["corr/rule_vs_all"] == 0.0,
              f"the banded launches at {h}x{w} differ from the all-rows "
              f"ones by {errs['corr/rule_vs_all']:.3e}: every tap lies in one "
              f"band, rows left out add exact zeros")
        shapes = level_shapes(f2_levels)
        plans = level_plans(h * w, w, shapes, radius)
        sched = lookup_schedules(coords, shapes, radius)
        errs["corr/scheduled_levels"] = [i for i, s in enumerate(sched)
                                         if s is not None]
        # rows that share their 128 lanes (PR 43): level -> [map rows to a
        # 128-lane row, banded]; every served grid has a packed level that
        # is banded and one that is one block, for either model
        packed = {i: [p.pack, p.banded] for i, p in enumerate(plans)
                  if p.pack > 1}
        errs["corr/packed_levels"] = packed
        check(sz.interpret or {True, False} <= {b for _, b in packed.values()},
              f"the levels of {h}x{w} that lay rows side by side, {packed}, "
              f"do not hold a banded one and a one-block one")
        visited, _, tiles, steps = (int(v) for v in schedule_keyblocks(
            sched, 1, plans)[:4])
        errs["corr/bands_per_tile"] = round(visited / tiles, 4)
        errs["corr/steps_per_tile"] = round(steps / tiles, 4)
        # the flow above pulls tiles over two and three bands, so its banded
        # launches take that many steps a tile; under a cell of smooth flow,
        # as served, every tile keeps to one band and a launch's third grid
        # dimension is 1 (PR 38): that launch too against the all-rows walk
        base = coords_grid(1, h, w)
        x, y = base[..., 0], base[..., 1]
        smooth = base + 0.4 * jnp.stack(
            [jnp.sin(x / 9.0 + y / 7.0), jnp.cos(x / 8.0 - y / 6.0)], -1)
        _, _, tiles, steps = (int(v) for v in schedule_keyblocks(
            lookup_schedules(smooth, shapes, radius), 1, plans)[:4])
        errs["corr/steps_per_tile_smooth"] = round(steps / tiles, 4)
        check(steps == tiles, f"a smooth flow at {h}x{w} takes {steps} grid "
              f"steps for {tiles} (tile, level) pairs: one band a tile is "
              f"one step a tile")
        one_step = [run(functools.partial(
            _fused_lookup_impl, radius=radius, q_blk=128, p_blk_target=4096,
            interpret=sz.interpret, schedules=s), f1, f2_levels, smooth)
            for s in (None, (None,) * levels)]
        errs["corr/rule_vs_all_smooth"] = float(
            np.abs(one_step[0] - one_step[1]).max())
        check(errs["corr/rule_vs_all_smooth"] == 0.0,
              f"the one-step launches at {h}x{w} differ from the all-rows "
              f"ones by {errs['corr/rule_vs_all_smooth']:.3e}")
        # the window as the update block consumes it: float32 maps as above,
        # and bfloat16 maps over a float32-pooled pyramid as the served
        # program hands them over (level 0 one plane, the others three)
        bf = jnp.bfloat16
        served = (f1.astype(bf), (f2.astype(bf),) + tuple(
            fmap2_pyramid(f2.astype(bf).astype(jnp.float32), levels)[1:]))
        for name, maps, f32_out in (("f32-maps", (f1, f2_levels), got["rule"]),
                                    ("bf16-maps", served, None)):
            written = lambda dt: run(functools.partial(   # noqa: E731
                _fused_lookup_impl, radius=radius, interpret=sz.interpret,
                out_dtype=dt), *maps, coords)
            if f32_out is None:
                f32_out = written(jnp.float32)
            bf16_out = written(bf)
            rounded = f32_out.astype(bf).astype(np.float32)
            key = f"corr/bf16_out_vs_f32_out_rounded/{name}"
            errs[key] = int((bf16_out.view(np.uint32)
                             != rounded.view(np.uint32)).sum())
            check(errs[key] == 0,
                  f"{errs[key]} values of the lookup written in bfloat16 at "
                  f"{h}x{w} ({name}) are not the float32 output rounded")

        if radius != 4:       # the GRU kernel is raft-things' alone
            return errs
        hid = mdim = ctxd = 128                    # full-model channel plan
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        p_gru = init_sep_conv_gru(ks[0], hid, ctxd + mdim)
        hst = jax.random.normal(ks[1], (1, h, w, hid), jnp.float32)
        mot = jax.random.normal(ks[2], (1, h, w, mdim), jnp.float32)
        inp = jax.random.normal(ks[3], (1, h, w, ctxd), jnp.float32)
        for dt, tol in ((jnp.float32, GRU_F32_TOL), (jnp.bfloat16, 5e-2)):
            pd = jax.tree.map(lambda a: a.astype(dt), p_gru)
            hd, md = hst.astype(dt), mot.astype(dt)
            ctx = precompute_gru_ctx(pd, inp.astype(dt), hid)
            want = np.asarray(sep_conv_gru_xla(pd, hd, md, ctx), np.float32)
            with jax.default_matmul_precision("highest"):
                exact = np.asarray(sep_conv_gru_xla(pd, hd, md, ctx),
                                   np.float32)
            got = run(functools.partial(
                sep_conv_gru_pallas, block_rows=8, interpret=sz.interpret,
                impl="kernel"), pd, hd, md, ctx)
            name = f"gru/{jnp.dtype(dt).name}"
            errs[name] = float(np.abs(got - want).max())
            errs[name + "_vs_highest_oracle"] = float(
                np.abs(got - exact).max())      # information, not a gate
            check(errs[name] < tol, f"GRU kernel ({name}) at {h}x{w} vs "
                  f"sep_conv_gru_xla: max|err| {errs[name]:.3e} >= {tol}")
        return errs

    with Phase(meter, "kernels") as ph:
        ph.note(max_abs_err={f"{h}x{w},C{c},r{r}": one_grid(h, w, c, r)
                             for h, w, c, r in sz.kernel_cases})


# ------------------------------------------------------------------- serve

# Serve-vs-reference.  The server computes in bf16 (the CLI's own TPU default)
# with both Pallas kernels; the reference is the dense fp32 volume with gather
# lookup.  An UNTRAINED raft-things is not contractive: on this pair its flow
# grows to a mean of 1587 px by iteration 12, and any rounding grows with it
# — first chip run, PR 21: bf16 and fp32 are 36% apart after 12 iterations,
# and the server's own stream and pair answers (same dtype, other fusions)
# 2188 px.  A 12-iteration gate would test the chaos, not the program.  So
# the served CONFIGURATION is compared with the reference at a depth cut to
# SERVE_REF_ITERS, where bf16 rounding (2^-8 per op, ~50 layers of encoder,
# correlation, GRU, flow head, convex upsampling) has not been amplified
# yet; the kernels phase pins the kernels themselves at 1e-6..1e-3.  The
# gate is the mean end-point difference over the reference's mean flow
# magnitude; the 12-iteration figure is printed, not gated.  Measured on the
# chip after 1 iteration: 1.02% (1.03 px of 100.6 px; my chip run, PR 21, the
# same in two runs), CPU emulation of bf16: 1.25%; the gate is 5%.  (Trained
# weights: +0.0009 px held-out EPE for bf16, PERF.md.)
SERVE_REF_ITERS = 1
SERVE_REL_TOL = 0.05


def serve_args(sz: Sizes, out_dir: str, cache_dir: str) -> list:
    bh, bw = sz.bucket
    argv = ["-m", "serve", "--buckets", f"{bh}x{bw}",
            "--corr-impl", "pallas", "--gru-impl", "pallas",
            "--max-batch", "2", "--max-sessions", "2",
            "--engine-cache-dir", cache_dir, "--port", "0",
            "--out", out_dir]
    if sz.iters != 12:
        argv += ["--iters", str(sz.iters)]
    return argv


def build_cli_server(argv: list):
    """What ``python -m raft_tpu.cli -m serve`` builds (cli.mode_serve +
    serving.server.serve_cli), stopping short of its signal loop."""
    from raft_tpu import cli
    from raft_tpu.serving.server import build_server
    args = cli.parse_args(argv)
    config = cli._make_config(args)
    cli._start_run_log(args, config)
    return build_server(args, config, cli._load_params), config


def phase_serve(meter, sz: Sizes) -> None:
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.data.pipeline import pad_to_shape, unpad
    from raft_tpu.models.raft import make_inference_fn

    out_dir = os.path.join(WORK, "serve")
    cache_dir = os.path.join(out_dir, "engine-cache")
    argv = serve_args(sz, out_dir, cache_dir)
    im1, im2 = (fit_to(im, sz.bucket) for im in read_pair())

    with Phase(meter, "serve") as ph:
        t0 = time.monotonic()
        server, config = build_cli_server(argv)
        server.start()                    # binds port 0, warms every key
        ph.note(argv=" ".join(argv), dtype=config.compute_dtype,
                executables=server.engine.executables,
                warmup_seconds=round(time.monotonic() - t0, 2))
        if not sz.interpret:
            check(config.compute_dtype == "bfloat16",
                  f"the CLI's TPU dtype default is bfloat16, got "
                  f"{config.compute_dtype}")
            for key, ex in sorted(server.engine._exec.items()):
                if key[0] in ("pair", "stream", "sbatch"):
                    check("tpu_custom_call" in ex.as_text(),
                          f"served executable {key} holds no "
                          f"tpu_custom_call: the kernels did not run")

        result = {}

        def client():
            """4 pairwise requests, one streaming session (demoted by two
            more and resumed), healthz, metrics — over HTTP, from a thread
            of this process."""
            try:
                url = server.url
                body = npz_body(image1=im1, image2=im2)
                flows = []
                for _ in range(4):
                    st, payload, _ = http_call(url, "POST", "/v1/flow", body)
                    check(st == 200, f"POST /v1/flow -> {st}: "
                          f"{payload[:200]!r}")
                    flows.append(npz_load(payload)["flow"])
                result["flows"] = flows
                st, payload, _ = http_call(url, "POST", "/v1/stream",
                                           npz_body(image=im1))
                check(st == 200, f"/v1/stream open -> {st}: "
                      f"{payload[:200]!r}")
                sid = str(npz_load(payload)["session"])
                stream = []
                for im in (im2, im1):
                    st, payload, _ = http_call(
                        url, "POST", "/v1/stream",
                        npz_body(op=np.asarray("advance"),
                                 session=np.asarray(sid), image=im))
                    check(st == 200, f"/v1/stream advance -> {st}: "
                          f"{payload[:200]!r}")
                    stream.append(npz_load(payload)["flow"])
                result["stream"] = stream
                # more sessions than the server's two slots: the second of
                # these opens takes the first session's slot (LRU), so its
                # next frame restarts cold from the frame the server kept
                # (im1): re-seated when its group is placed (the kept
                # frame's encode and a zero-seeded commit_row), the row
                # rides the batched step; the answer is the pair's, reported
                # warm: false, and the frame after it is warm again
                # (SERVING.md "Sizing the slot pool")
                others = []
                for _ in range(2):
                    st, payload, _ = http_call(url, "POST", "/v1/stream",
                                               npz_body(image=im1))
                    check(st == 200, f"/v1/stream open -> {st}")
                    others.append(str(npz_load(payload)["session"]))
                resumed = []
                for im in (im2, im1):
                    st, payload, _ = http_call(
                        url, "POST", "/v1/stream",
                        npz_body(session=np.asarray(sid), image=im))
                    check(st == 200, f"/v1/stream advance of a demoted "
                          f"session -> {st}: {payload[:200]!r}")
                    got = npz_load(payload)
                    resumed.append((got["flow"], bool(got["warm"])))
                result["resumed"] = resumed
                for s in others + [sid]:
                    st, payload, _ = http_call(
                        url, "POST", "/v1/stream",
                        npz_body(op=np.asarray("close"),
                                 session=np.asarray(s)))
                    check(st == 200, f"/v1/stream close -> {st}")
                st, payload, _ = http_call(url, "GET", "/healthz")
                check(st == 200, f"/healthz -> {st}")
                result["health"] = json.loads(payload)
                st, payload, _ = http_call(url, "GET", "/metrics")
                check(st == 200, f"/metrics -> {st}")
                result["metrics"] = payload.decode()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                result["error"] = e

        try:
            t = threading.Thread(target=client, name="smoke-client")
            t.start()
            t.join()
            if "error" in result:
                raise result["error"]

            h, w = im1.shape[:2]
            for name, fl in ([("pair", f) for f in result["flows"]]
                             + [("stream", f) for f in result["stream"]]
                             + [("resumed", f) for f, _ in result["resumed"]]):
                check(fl.shape[-3:] == (h, w, 2),
                      f"{name} flow has shape {fl.shape}, want (.., {h}, "
                      f"{w}, 2)")
                check(np.isfinite(fl).all(), f"{name} flow is not finite")
            misses = prom_value(result["metrics"],
                                "raft_serving_compile_cache_misses_total")
            check(misses == 0, f"raft_serving_compile_cache_misses_total = "
                  f"{misses} after warm-up: a request compiled")
            check(result["health"].get("status") == "ok",
                  f"/healthz status {result['health'].get('status')!r}")
            warm = [w for _, w in result["resumed"]]
            check(warm == [False, True],
                  f"a session demoted by two later opens answered warm="
                  f"{warm}: want a cold restart, then a warm advance")
            from raft_tpu.fleet.manager import parse_prom_text
            prom = parse_prom_text(result["metrics"])
            restarts = {c: prom.get('raft_stream_cold_restarts_total'
                                    f'{{cause="{c}"}}')
                        for c in ("demoted", "displaced", "degraded")}
            check(restarts == {"demoted": 1, "displaced": 0, "degraded": 0},
                  f"raft_stream_cold_restarts_total by cause: {restarts}")
            lru = prom.get('raft_stream_evictions_total{reason="lru"}')
            check(lru == 2, f"two slots, three sessions, one resume: want 2 "
                  f"LRU demotions, /metrics says {lru}")
            # the engine calls a restart makes: one encoder pass of the kept
            # frame beside the three opens', and its frame's as a row of a
            # batched step like the other three advances'; no solo step and
            # no wait for a batch staged behind
            made = {
                "restarts_batched": prom.get(
                    "raft_stream_restarts_batched_total"),
                **{f"{c}_calls": prom.get(
                    f'raft_stream_encoder_passes_total{{call="{c}"}}')
                   for c in ("encode", "stream")},
                **{stage: prom.get('raft_serving_stage_seconds_total'
                                   f'{{stage="stream.cold.{stage}"}}')
                   for stage in ("step", "wait")}}
            check(made == {"restarts_batched": 1, "encode_calls": 4,
                           "stream_calls": 4, "step": 0, "wait": 0},
                  f"a restart at its group's place makes one encode call "
                  f"and rides the batched step: /metrics says {made}")

            # reference on the same chip, same weights, same padded pair
            ref_cfg = dc.replace(config, corr_impl="dense",
                                 corr_lookup="gather", gru_impl="xla",
                                 compute_dtype="float32")
            params = server.engine.params
            p1, pads = pad_to_shape(im1[None], sz.bucket)
            p2, _ = pad_to_shape(im2[None], sz.bucket)
            p1, p2 = jnp.asarray(p1), jnp.asarray(p2)

            def flow_of(cfg, iters):
                out = jax.jit(make_inference_fn(cfg, iters=iters))(
                    params, p1, p2)
                return unpad(np.asarray(out, np.float32), pads)[0]

            def rel_epe(a, b):
                epe = float(np.linalg.norm(a - b, axis=-1).mean())
                mag = float(np.linalg.norm(b, axis=-1).mean())
                return epe, mag, epe / mag

            got = np.asarray(result["flows"][0], np.float32)
            got = got.reshape(got.shape[-3:])

            def apart(a, b):
                """Largest difference of two answers, px."""
                return round(float(np.abs(a.reshape(got.shape)
                                          - b.reshape(got.shape)).max()), 4)

            cut = min(SERVE_REF_ITERS, sz.iters)
            epe, mag, rel = rel_epe(flow_of(config, cut),
                                    flow_of(ref_cfg, cut))
            full = rel_epe(got, flow_of(ref_cfg, server.engine.iters))
            # the HTTP path (pad, queue, batch, execute, unpad) must return
            # exactly what the model function computes on the padded pair:
            # same program, same chip, so bit for bit
            direct = float(np.abs(
                got - flow_of(config, server.engine.iters)).max())
            ph.note(ref_iters=cut, ref_mean_flow_px=round(mag, 4),
                    mean_epe_vs_ref_px=round(epe, 4), rel=round(rel, 5),
                    rel_tol=SERVE_REL_TOL,
                    at_full_depth={"ref_mean_flow_px": round(full[1], 2),
                                   "rel": round(full[2], 4)},
                    http_vs_direct_call_px=direct,
                    stream_vs_pair_px=apart(result["stream"][0], got),
                    # the cold restart computes the same zero-seeded pair
                    # (im1, im2): against the pair program noted and not
                    # gated for the reason above (at full depth the weights
                    # amplify what another fusion rounds otherwise); against
                    # the session's first advance it is the same two
                    # programs on the same inputs (the encode of im1, the
                    # batched step of one row from a zero seed): gated
                    # below, bit for bit
                    cold_restart_vs_pair_px=apart(
                        result["resumed"][0][0], got),
                    cold_restart_vs_first_advance_px=apart(
                        result["resumed"][0][0], result["stream"][0]))
            check(direct == 0.0,
                  f"the flow served over HTTP is {direct:.3e} px from a "
                  f"direct call of the same model function")
            again = apart(result["resumed"][0][0], result["stream"][0])
            check(again == 0.0,
                  f"a cold restart of (im1, im2) is {again} px from the "
                  f"session's first advance over the same frames: it did "
                  f"not start from the kept frame and a zero seed")
            check(rel <= SERVE_REL_TOL,
                  f"the served configuration is {epe:.4f} px (mean EPE) "
                  f"from the dense fp32 reference after {cut} iteration(s), "
                  f"over {SERVE_REL_TOL} x its mean magnitude {mag:.4f} px")
        finally:
            server.stop()

        # a second server against the same --engine-cache-dir must LOAD
        # every executable (serving/aot_cache.py on real TPU executables)
        # and answer the same request bit-identically
        t1 = time.monotonic()
        c_before = meter.snapshot()
        server2, _ = build_cli_server(argv)
        server2.start()
        try:
            stats = server2.engine_cache.stats
            ph.note(second_server={"loaded": stats.hits,
                                   "compiled": stats.misses,
                                   "seconds": round(time.monotonic() - t1, 2),
                                   "compile_seconds": round(
                                       meter.snapshot()[0] - c_before[0], 2)})
            check(stats.misses == 0 and
                  stats.hits == server2.engine.executables,
                  f"second server compiled {stats.misses} and loaded "
                  f"{stats.hits} of {server2.engine.executables} "
                  f"executables: the engine cache did not serve it")
            st, payload, _ = http_call(server2.url, "POST", "/v1/flow",
                                       npz_body(image1=im1, image2=im2))
            check(st == 200, f"second server POST /v1/flow -> {st}")
            check(np.array_equal(npz_load(payload)["flow"],
                                 result["flows"][0]),
                  "a loaded executable answered differently from the "
                  "compiled one it was saved from")
        finally:
            server2.stop()


# ------------------------------------------------------------------- small

SMALL_CONFIG = os.path.join(ROOT, "benchmark", "configs",
                            "raft-small-1080p.json")
SMALL_SEED = 3_100_000_031


def phase_small(meter, sz: Sizes) -> None:
    """RAFT-S's served pair program (the benchmark configuration's own serve
    arguments through ``cli.parse_args`` / ``_make_config``; the engine's
    function, key-block counts beside the flow) ONCE at ``sz.small_batch`` x
    ``sz.small_hw``, on the benchmark's seeded weights and frames, held to
    ``benchmark/reference.py`` the way the cell's own check holds its
    answers: ``precision_ratio`` (check.py) of one row under the
    configuration's ``check.ratio_limit``.  The radius-3 lookup (a 7x7
    window, 49 lanes, 128-channel maps) runs through the one lookup body
    the full model's radius 4 uses: a fault there shows here before the
    benchmark meets it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import cli
    from raft_tpu.models.raft import make_inference_fn

    bench = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, bench)
    try:
        import check as bcheck
        import inputs as binputs
        import weights as bweights
    finally:
        sys.path.remove(bench)
    with open(SMALL_CONFIG) as f:
        conf = json.load(f)
    args = cli.parse_args(["-m", "serve"]
                          + [str(a) for a in conf["serve_args"]])
    config = cli._make_config(args)
    (h, w), b = sz.small_hw, sz.small_batch
    with Phase(meter, "small") as ph:
        for key, value in conf["program"].items():
            check(getattr(config, key) == value,
                  f"{SMALL_CONFIG} says {key}={value!r}, its serve "
                  f"arguments give {getattr(config, key)!r}")
        mcfg = bweights.model_cfg(conf)
        params = jax.block_until_ready(
            bweights.make_weights(SMALL_SEED, mcfg))
        pairs = binputs.make_pairs(SMALL_SEED, b, h, w, 12)
        im1, im2 = (jnp.asarray(np.stack([p[i] for p in pairs])
                                .astype(np.float32) / 255.0) for i in (0, 1))
        lowered = jax.jit(make_inference_fn(
            config, iters=args.iters, keyblocks=True)).lower(params, im1, im2)
        if not sz.interpret:
            check(lowered.as_text().count("tpu_custom_call") >= 4,
                  "fewer than four lookup launches in the small program")
        compiled = lowered.compile()
        t0 = time.monotonic()
        flow, keyblocks = jax.block_until_ready(compiled(params, im1, im2))
        ph.note(program=f"{b}x{h}x{w}", iters=args.iters,
                dtype=config.compute_dtype,
                run_seconds=round(time.monotonic() - t0, 3))
        flow = np.asarray(flow, np.float32)
        visited, possible, tiles, steps = (int(v)
                                           for v in np.asarray(keyblocks)[:4])
        finite = bool(np.isfinite(flow).all())
        check(flow.shape == (b, h, w, 2) and finite,
              f"small flow: shape {flow.shape}, finite {finite}")
        check(0 < tiles <= visited <= steps <= possible,
              f"band counts at radius 3: {visited} of {possible} bands in "
              f"{steps} grid steps, {tiles} (tile, level) pairs")
        row = b - 1                       # the batch's last row
        refs = bcheck.reference_flows(params, pairs, [row], mcfg, args.iters)
        own = bcheck.reference_flows(params, pairs, [row], mcfg, args.iters,
                                     conf["check"]["own_precision"])
        limit = float(conf["check"]["ratio_limit"])
        lines = []
        verdict = bcheck.compare([(row, row, flow[row])], refs, own, limit,
                                 lines.append)
        ph.note(precision_ratio=round(verdict["worst"] or 0.0, 4),
                limit=limit, keyblock_share=round(visited / possible, 4),
                bands_per_tile=round(visited / tiles, 4),
                steps_per_tile=round(steps / tiles, 4))
        check(verdict["correct"], "small program against "
              "benchmark/reference.py: " + "; ".join(lines))


# ------------------------------------------------------------------- flows

FLOW_SEED = 4_300_000_043


def phase_int8(meter, sz: Sizes) -> None:
    """The int8 slot pool at the size a deployment holds it, through the
    real server: ``benchmark/configs/raft-things-1080p-stream-int8.json``'s
    serve arguments (``--quant int8 --max-sessions 256`` at 1080x1920)
    without the warm-up, so that only what the walk needs is compiled
    (``encode``, ``szero``, the one-row ``sbatch`` and ``scommit``).  A
    session is opened and advanced; ``int8_slots`` more opens fill the pool
    and take its slot (LRU); its next frame restarts cold, re-seated where
    its group is placed, and the frame after that is warm.  Every flow is
    finite, the pool is full, its bytes are the gauges' and the leaves'
    dtypes are int8 and float32, and every committed row was quantised.  A
    changed pool format is seen here before a benchmark run meets it."""
    import numpy as np

    from raft_tpu.fleet.manager import parse_prom_text

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "raft-things-1080p-stream-int8.json")) as f:
        argv = [str(a) for a in json.load(f)["serve_args"]]
    h, w = sz.int8_hw
    argv[argv.index("--buckets") + 1] = f"{h}x{w}"
    argv[argv.index("--max-sessions") + 1] = str(sz.int8_slots)
    if sz.interpret:                  # the CPU rehearsal: float32, no kernels
        argv[argv.index("--dtype") + 1] = "float32"
        argv[argv.index("--gru-impl") + 1] = "xla"
        argv[argv.index("--iters") + 1] = str(min(sz.iters, 2))
    out_dir = os.path.join(WORK, "int8")
    argv = ["-m", "serve"] + argv + ["--no-warmup", "--port", "0",
                                     "--out", out_dir]
    rng = np.random.default_rng(45)
    base = rng.integers(0, 255, (h + 16, w + 16, 3), dtype=np.uint8)
    frames = [base[k:k + h, 2 * k:2 * k + w].astype(np.float32) / 255.0
              for k in range(4)]      # a scene that pans: a real flow field

    with Phase(meter, "int8") as ph:
        server, config = build_cli_server(argv)
        check(config.quant_slots, f"quant={config.quant!r}: not int8 slots")
        server.start()
        result = {}

        def post(**arrays):
            st, payload, _ = http_call(server.url, "POST", "/v1/stream",
                                       npz_body(**arrays))
            check(st == 200, f"/v1/stream {sorted(arrays)} -> {st}: "
                  f"{payload[:200]!r}")
            return npz_load(payload)

        def client():
            try:
                sid = str(post(image=frames[0])["session"])
                answers = [post(session=np.asarray(sid), image=frames[1])]
                t0 = time.monotonic()
                for _ in range(sz.int8_slots):
                    post(image=frames[0])
                result["fill_seconds"] = time.monotonic() - t0
                for im in frames[2:]:
                    answers.append(post(session=np.asarray(sid), image=im))
                result["answers"] = answers
                st, payload, _ = http_call(server.url, "GET", "/metrics")
                check(st == 200, f"/metrics -> {st}")
                result["prom"] = parse_prom_text(payload.decode())
            except BaseException as e:  # noqa: BLE001 — re-raised below
                result["error"] = e

        try:
            t = threading.Thread(target=client, name="smoke-int8")
            t.start()
            t.join()
            if "error" in result:
                raise result["error"]
            warm = [bool(a["warm"]) for a in result["answers"]]
            check(warm == [True, False, True],
                  f"open, advance, {sz.int8_slots} opens, two advances "
                  f"answered warm={warm}: want warm, a cold restart, warm")
            for a in result["answers"]:
                check(a["flow"].shape[-3:] == (h, w, 2)
                      and np.isfinite(a["flow"]).all(),
                      f"a flow of shape {a['flow'].shape} is not finite")
            prom = result["prom"]
            bufs = server.engine.pool.buffers((h, w))
            dtypes = [str(leaf.dtype) for leaf in (*bufs[0], *bufs[1],
                                                   bufs[2])]
            check(dtypes == ["int8", "float32", "int8", "float32",
                             "float32"], f"the pool's leaves are {dtypes}")
            pool = {leaf: int(prom[f'raft_stream_pool_bytes{{leaf="{leaf}"}}'])
                    for leaf in ("vals", "scales", "seed")}
            rows, q = sz.int8_slots + 1, (h // 8) * (w // 8)
            want = {"vals": rows * q * (bufs[0][0].shape[-1]
                                        + bufs[1][0].shape[-1]),
                    "scales": rows * 4 * (bufs[0][1].shape[-1]
                                          + bufs[1][1].shape[-1]),
                    "seed": rows * q * 2 * 4}
            check(pool == want, f"raft_stream_pool_bytes {pool}, the "
                  f"arrays' shapes give {want}")
            bucket = f'{{bucket="{h}x{w}"}}'
            fill = (prom["raft_stream_slots_in_use" + bucket],
                    prom["raft_stream_slot_capacity" + bucket])
            check(fill == (sz.int8_slots, sz.int8_slots),
                  f"slots in use / capacity = {fill}")
            counted = {k: int(prom[f"raft_stream_{k}_total"]) for k in
                       ("rows_quantized", "frames", "opens",
                        "restarts_batched")}
            check(counted["rows_quantized"] == counted["frames"]
                  + counted["opens"] + counted["restarts_batched"]
                  == 3 + (sz.int8_slots + 1) + 1,
                  f"rows quantised = frames + opens + restarts: {counted}")
            lru = prom['raft_stream_evictions_total{reason="lru"}']
            check(lru == 2, f"one open too many and one resume: want 2 LRU "
                  f"demotions, /metrics says {lru}")
            ph.note(argv=" ".join(argv), dtype=config.compute_dtype,
                    executables=server.engine.executables,
                    slots=sz.int8_slots, pool_bytes=pool,
                    pool_gb=round(sum(pool.values()) / 1e9, 4),
                    fill_seconds=round(result["fill_seconds"], 2),
                    mean_abs_flow=[round(float(np.abs(a["flow"]).mean()), 4)
                                   for a in result["answers"]], **counted)
        finally:
            server.stop()


def served_flow_hashes(sz: Sizes, note=lambda **kv: None) -> dict:
    """name -> sha256 of the flow each program of ``sz.flow_programs``
    answers on ``FLOW_SEED``: the configuration's own serve arguments
    through ``cli.parse_args`` / ``_make_config``, the engine's functions
    (key-block counts beside the outputs), the benchmark's seeded weights
    and frames.  The stream kinds advance frame 1's maps (one ``encode``
    pass, a zero seed) by frame 2; the batched advance gathers them from a
    pool of ``--max-sessions`` + 1 rows."""
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import cli
    from raft_tpu.models.raft import (make_encode_fn, make_inference_fn,
                                      make_stream_batch_step_fn,
                                      make_stream_step_fn)

    bench = os.path.join(ROOT, "benchmark")
    sys.path.insert(0, bench)
    try:
        import inputs as binputs
        import weights as bweights
    finally:
        sys.path.remove(bench)
    out = {}
    for name, file, kind, batch in sz.flow_programs:
        with open(os.path.join(bench, "configs", file)) as f:
            conf = json.load(f)
        args = cli.parse_args(["-m", "serve"]
                              + [str(a) for a in conf["serve_args"]])
        config = cli._make_config(args)
        h, w = sz.flow_hw or tuple(
            int(v) for v in args.buckets.split(",")[0].split("x"))
        b = min(batch, sz.flow_batch or batch)
        iters = sz.flow_iters or args.iters
        params = bweights.make_weights(FLOW_SEED, bweights.model_cfg(conf))
        pairs = binputs.make_pairs(FLOW_SEED, b, h, w, 12)
        im1, im2 = (jnp.asarray(np.stack([p[i] for p in pairs])
                                .astype(np.float32) / 255.0) for i in (0, 1))
        t0 = time.monotonic()
        if kind == "pair":
            flow = jax.jit(make_inference_fn(
                config, iters=iters, keyblocks=True))(params, im1, im2)[0]
        else:
            fmap, cnet = jax.jit(make_encode_fn(config))(params, im1)
            seed = jnp.zeros((b, h // 8, w // 8, 2), jnp.float32)
            if kind == "stream":
                flow = jax.jit(make_stream_step_fn(
                    config, iters=iters, keyblocks=True))(
                        params, im2, fmap, cnet, seed)[0]
            else:
                rows = args.max_sessions + 1

                def pool(x):
                    return jnp.zeros((rows,) + x.shape[1:],
                                     x.dtype).at[:b].set(x)

                flow = jax.jit(make_stream_batch_step_fn(
                    config, iters=iters, keyblocks=True))(
                        params, im2, pool(fmap), pool(cnet), pool(seed),
                        jnp.arange(b, dtype=jnp.int32),
                        jnp.ones((b,), bool))[0]
        flow = np.asarray(jax.block_until_ready(flow), np.float32)
        check(flow.shape == (b, h, w, 2) and bool(np.isfinite(flow).all()),
              f"{name}: flow of shape {flow.shape}, or not finite")
        out[name] = hashlib.sha256(flow.tobytes()).hexdigest()
        note(**{name: {"sha256": out[name], "program": f"{b}x{h}x{w}",
                       "iters": iters,
                       "mean_abs_flow": round(float(np.abs(flow).mean()), 4),
                       "seconds": round(time.monotonic() - t0, 2)}})
    return out


def phase_flows(meter, sz: Sizes) -> None:
    """``--flows``: the five served programs' flows on a fixed seed, hashed.
    A change to the lookup that moves lanes and multiplies the same products
    gives the same float32 sums, so the same bytes as its parent on the same
    chip and the same jax and libtpu: PR 43 held its five to the parent's
    that way (TUNING.md), as PRs 36 and 38 held their launches.  Nothing is
    compared here: hashes of another build are not comparable."""
    with Phase(meter, "flows") as ph:
        served_flow_hashes(sz, ph.note)


# ------------------------------------------------------------------- train

class ChildWatch(threading.Thread):
    """Samples this process's descendants while the trainer runs and
    records, per pid, its command line and whether libtpu is mapped into
    it.  The decode workers are forkserver children; a process that has
    initialised the TPU backend has libtpu.so in /proc/<pid>/maps, so
    'never mapped, in any sample' is the evidence that no child ever
    touched the chip."""

    def __init__(self):
        super().__init__(name="smoke-childwatch", daemon=True)
        self.seen = {}
        self._halt = threading.Event()

    @staticmethod
    def _descendants(root: int):
        kids = {}
        for pid in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{pid}/stat") as f:
                    ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            except (OSError, ValueError, IndexError):
                continue
            kids.setdefault(ppid, []).append(int(pid))
        out, todo = [], [root]
        while todo:
            for k in kids.get(todo.pop(), []):
                out.append(k)
                todo.append(k)
        return out

    def run(self):
        me = os.getpid()
        while not self._halt.wait(0.25):
            for pid in self._descendants(me):
                try:
                    with open(f"/proc/{pid}/cmdline", "rb") as f:
                        cmd = f.read().replace(b"\0", b" ").decode()[:120]
                    with open(f"/proc/{pid}/maps") as f:
                        tpu = "libtpu" in f.read()
                except OSError:
                    continue
                rec = self.seen.setdefault(pid, {"cmd": cmd, "libtpu": False})
                rec["libtpu"] = rec["libtpu"] or tpu

    def stop(self):
        self._halt.set()
        self.join()
        return self.seen


def phase_train(meter, sz: Sizes) -> None:
    import jax
    import numpy as np

    from raft_tpu import cli, native
    from raft_tpu.config import RAFTConfig, TrainConfig, init_rng
    from raft_tpu.models import init_raft
    from raft_tpu.training import TrainState, make_optimizer
    from raft_tpu.training.checkpoint import (latest_checkpoint,
                                              restore_checkpoint)

    out_dir = os.path.join(WORK, "train")
    th, tw = sz.train_size
    argv = ["-m", "train", "--dataset", "synthetic", "--device-aug",
            "--workers", str(sz.train_workers),
            "--train-size", str(th), str(tw),
            "--batch", str(sz.train_batch), "--accum", str(sz.train_accum),
            "--corr-impl", sz.train_corr, "--iters", str(sz.iters),
            "--num-steps", "3", "--log-every", "1", "--ckpt-every", "3",
            "--out", out_dir]
    with Phase(meter, "train") as ph:
        ph.note(argv=" ".join(argv), accum=sz.train_accum,
                corr_impl=sz.train_corr, fit_analysis=TRAIN_FIT,
                native_io=("libraftio.so" if native.available()
                           else "python fallback"))
        watch = ChildWatch()
        watch.start()
        try:
            rc = cli.main(argv)
        finally:
            children = watch.stop()
        check(rc == 0, f"-m train exited {rc}")
        ph.note(children=len(children),
                children_with_libtpu=[c for c in children.values()
                                      if c["libtpu"]])
        check(len(children) >= sz.train_workers,
              f"saw {len(children)} child process(es), fewer than the "
              f"{sz.train_workers} decode workers asked for")
        check(not any(c["libtpu"] for c in children.values()),
              "a child of the trainer mapped libtpu: a decode worker "
              "initialised a backend")

        ckpt_dir = os.path.join(out_dir, "checkpoints")
        steps, run_end = [], None
        with open(os.path.join(ckpt_dir, "metrics.jsonl")) as f:
            for ln in f:
                rec = json.loads(ln)
                if rec.get("event") == "run_end":
                    run_end = rec
                elif "step" in rec and "loss" in rec:
                    steps.append(rec)
        losses = [float(r["loss"]) for r in steps]
        ph.note(losses=[round(x, 4) for x in losses])
        check(len(losses) == 3 and all(np.isfinite(losses)),
              f"want 3 finite step losses, got {losses}")
        check(run_end is not None and run_end["final_step"] == 3,
              f"run_end record: {run_end and run_end.get('final_step')}")
        errors = run_end["metrics"].get("raft_data_errors_total", 0)
        errors = errors.get("value", errors) if isinstance(errors, dict) \
            else errors
        check(not errors, f"raft_data_errors_total = {errors}")

        # the checkpoint, read back into a fresh state template
        path = latest_checkpoint(ckpt_dir)
        check(path is not None, f"no checkpoint under {ckpt_dir}")
        config = RAFTConfig.full(iters=sz.iters)
        tx = make_optimizer(TrainConfig.for_stage("synthetic"))
        template = TrainState.create(init_raft(init_rng(), config), tx)
        restored = restore_checkpoint(path, template)
        ph.note(checkpoint=os.path.basename(str(path)),
                restored_step=int(restored.step))
        check(int(restored.step) == 3,
              f"checkpoint restores to step {int(restored.step)}, not 3")
        check(all(np.isfinite(np.asarray(x)).all()
                  for x in jax.tree.leaves(restored.params)),
              "restored params are not finite")


# ---------------------------------------------------------------- 4 chips

def fleet_before_jax(sz: Sizes, replicas: int = 4) -> dict:
    """(c) ``-m serve_fleet --replicas 4`` — run BEFORE this process
    initialises any backend, as a child through the real launcher: four
    one-chip replicas behind the router.  Each replica answers one POST
    /v1/flow directly and the router answers four more; every flow must be
    bit-identical to replica 0's (same weights, same executable — loaded
    from the fleet's shared engine cache — one chip each)."""
    import re

    import numpy as np

    out_dir = os.path.join(WORK, "fleet")
    os.makedirs(out_dir, exist_ok=True)
    bh, bw = sz.bucket
    argv = [sys.executable, "-m", "raft_tpu.cli", "-m", "serve_fleet",
            "--replicas", str(replicas), "--max-replicas", str(replicas),
            "--port", "0", "--buckets", f"{bh}x{bw}",
            "--corr-impl", "pallas", "--gru-impl", "pallas",
            "--max-batch", "1", "--max-sessions", "1", "--out", out_dir]
    if sz.iters != 12:
        argv += ["--iters", str(sz.iters)]
    t0 = time.monotonic()
    log_path = os.path.join(out_dir, "launcher.log")
    im1, im2 = (fit_to(im, sz.bucket) for im in read_pair())
    body = npz_body(image1=im1, image2=im2)
    with open(log_path, "w") as log_f:
        proc = subprocess.Popen(argv, cwd=ROOT, stdout=log_f,
                                stderr=subprocess.STDOUT)
    try:
        router, urls = None, []
        while router is None:
            check(proc.poll() is None, f"the fleet launcher exited "
                  f"{proc.returncode} before it was ready: "
                  f"{open(log_path).read()[-1500:]}")
            check(time.monotonic() - t0 < 900, "fleet not ready in 900 s")
            time.sleep(0.5)
            m = re.search(r"\[fleet\] router listening on (\S+)\s+"
                          r"replicas=(\d+) (\[[^\]]*\])",
                          open(log_path).read())
            if m:
                router, urls = m.group(1), json.loads(
                    m.group(3).replace("'", '"'))
        ready_s = time.monotonic() - t0
        check(len(urls) == replicas, f"{len(urls)} replicas came up: {urls}")
        flows = []
        for url in urls:                  # each chip's replica, directly
            st, payload, _ = http_call(url, "POST", "/v1/flow", body)
            check(st == 200, f"replica {url} POST /v1/flow -> {st}")
            flows.append(npz_load(payload)["flow"])
        served_by = []
        for _ in range(replicas):         # and through the front door
            st, payload, hdr = http_call(router, "POST", "/v1/flow", body)
            check(st == 200, f"router POST /v1/flow -> {st}")
            flows.append(npz_load(payload)["flow"])
            served_by.append(hdr.get("X-Raft-Replica"))
        for i, fl in enumerate(flows):
            check(np.isfinite(fl).all(), f"fleet flow {i} is not finite")
            check(np.array_equal(fl, flows[0]),
                  f"fleet flow {i} differs from replica 0's: max|d| "
                  f"{float(np.abs(fl - flows[0]).max()):.3e}")
        return {"phase": "fleet", "ok": True,
                "seconds": round(time.monotonic() - t0, 2),
                "ready_seconds": round(ready_s, 2), "replicas": urls,
                "router_served_by": served_by,
                "flows_identical": len(flows)}
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def assert_on_all_devices(tree, n: int, what: str) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        check(len(leaf.sharding.device_set) == n,
              f"{what}: a leaf of shape {leaf.shape} lives on "
              f"{len(leaf.sharding.device_set)} device(s), not {n}")
    for d in jax.local_devices()[:n]:
        stats = d.memory_stats()          # None on the CPU rehearsal
        check(stats is None or stats.get("bytes_in_use", 0) > 0,
              f"{what}: device {d.id} holds no bytes")


# Cross-chip comparisons are made at a depth cut to one iteration, for the
# reason given at SERVE_REF_ITERS: an untrained raft-things amplifies any
# rounding through its 12 iterations, and on the chip two differently
# compiled f32 programs do round differently (the MXU's default precision
# feeds it bf16-rounded operands, and a last-bit difference flips that
# rounding; kernels phase, GRU_F32_TOL).  Each cross-chip program ALSO runs at
# the full depth, where it is held to finite values, step counts and the
# placement of its arrays, and its full-depth difference is printed.
CROSS_CHIP_REF_ITERS = 1
# dp=4 vs one device: the loss is a mean over ~1.5M pixel errors, which
# averages per-pixel rounding noise (~1e-3 after one iteration) well below
# 1e-3; a sample that reached the wrong device moves it by percents.
DP_REL_TOL = 1e-3
# spatial-4 vs one device, per-pixel: the sharded program sums its norms and
# its correlation in another order (psum over 4 slabs, ring-passed
# correlation).  Mean end-point difference over mean flow magnitude.
SPATIAL_REL_TOL = 2e-2


def phase_dp(meter, sz: Sizes, n: int = 4) -> None:
    """(a) the trainer's data-parallel step (``make_pjit_train_step``) on
    dp=4 against the one-device step, same global batch and seed, 2 steps.
    BatchNorm is frozen (the things/sintel/kitti stage setting), so the loss
    does not depend on how the batch is grouped into devices (dp) or
    micro-batches (one device needs --accum to fit)."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.config import RAFTConfig, TrainConfig, init_rng
    from raft_tpu.models import init_raft
    from raft_tpu.parallel.data_parallel import make_pjit_train_step
    from raft_tpu.parallel.mesh import make_mesh, replicated, shard_batch
    from raft_tpu.training import (Batch, TrainState, make_optimizer,
                                   make_train_step)

    with Phase(meter, "dp4") as ph:
        H, W = sz.train_size
        B = sz.dp_batch
        full = RAFTConfig.full(iters=sz.iters, corr_impl=sz.dp_corr)
        cut = dc.replace(full, iters=min(CROSS_CHIP_REF_ITERS, sz.iters))
        rng = np.random.RandomState(0)
        host = Batch(image1=rng.rand(B, H, W, 3).astype(np.float32),
                     image2=rng.rand(B, H, W, 3).astype(np.float32),
                     flow=(rng.randn(B, H, W, 2) * 4).astype(np.float32),
                     valid=np.ones((B, H, W), np.float32))
        key = jax.random.PRNGKey(1)
        mesh = make_mesh(devices=jax.devices()[:n])

        def two_steps(config, dp: bool):
            tconfig = TrainConfig.for_stage(
                "synthetic", num_steps=10, batch_size=B, image_size=(H, W),
                accum_steps=1 if dp else n, freeze_bn=True)
            tx = make_optimizer(tconfig)
            state = TrainState.create(init_raft(init_rng(), config), tx)
            if dp:
                step = make_pjit_train_step(config, tconfig, tx, mesh)
                batch = shard_batch(mesh, host)
                state = jax.device_put(state, replicated(mesh))
                assert_on_all_devices(batch, n, "dp=4 batch")
            else:
                step = jax.jit(make_train_step(config, tconfig, tx),
                               donate_argnums=0)
                batch = jax.tree.map(jnp.asarray, host)
            losses = []
            for _ in range(2):
                state, metrics = step(state, batch, key)
                losses.append(float(metrics["loss"]))
            if dp:
                assert_on_all_devices(state.params, n, "dp=4 params")
            check(int(state.step) == 2, f"step {int(state.step)} after 2")
            check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
            return losses

        one, dp4 = two_steps(cut, dp=False), two_steps(cut, dp=True)
        rel = max(abs(a - b) / abs(a) for a, b in zip(one, dp4))
        ph.note(global_batch=B, corr_impl=sz.dp_corr, ref_iters=cut.iters,
                losses={"one_device": one, "dp4": dp4}, rel=rel,
                rel_tol=DP_REL_TOL)
        check(rel <= DP_REL_TOL, f"dp=4 loss differs from one device by "
              f"{rel:.3e} relative (> {DP_REL_TOL}): {one} vs {dp4}")
        if cut.iters != full.iters:
            ph.note(losses_at_full_depth=two_steps(full, dp=True))


def phase_spatial(meter, sz: Sizes, n: int = 4) -> None:
    """(b) ``make_shard_inference_fn`` (the CLI's ``--spatial 4``) on one
    pair against the one-device forward with the on-demand Pallas
    correlation (a dense level-0 volume at 1024x1920 is 3.8 GB)."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_inference_fn
    from raft_tpu.parallel.spatial import (make_shard_inference_fn,
                                           required_h_multiple)

    with Phase(meter, "spatial4") as ph:
        H, W = sz.spatial_hw
        full = RAFTConfig.full(iters=sz.iters, corr_impl="pallas")
        cut = dc.replace(full, iters=min(CROSS_CHIP_REF_ITERS, sz.iters))
        check(H % required_h_multiple(full, n) == 0,
              f"H={H} not divisible by {required_h_multiple(full, n)}")
        params = init_raft(init_rng(), full)
        rng = np.random.RandomState(0)
        im1 = rng.rand(1, H, W, 3).astype(np.float32)
        im2 = np.clip(im1 + rng.randn(1, H, W, 3).astype(np.float32) * .05,
                      0, 1)
        mesh = Mesh(np.array(jax.devices()[:n]), ("spatial",))
        rows = NamedSharding(mesh, P(None, "spatial"))
        a, b = (jax.device_put(x, rows) for x in (im1, im2))
        assert_on_all_devices((a, b), n, "row-sharded images")

        def sharded(config):
            flow = make_shard_inference_fn(config, mesh)(params, a, b)
            check(len(flow.sharding.device_set) == n, f"sharded flow lives "
                  f"on {len(flow.sharding.device_set)} device(s)")
            shard_rows = sorted(s.data.shape[1]
                                for s in flow.addressable_shards)
            check(shard_rows == [H // n] * n, f"row shards: {shard_rows}")
            flow = np.asarray(flow)
            check(flow.shape == (1, H, W, 2) and np.isfinite(flow).all(),
                  f"sharded flow: shape {flow.shape}, finite "
                  f"{bool(np.isfinite(flow).all())}")
            return flow

        def one_device(config):
            return np.asarray(jax.jit(make_inference_fn(config))(
                params, jnp.asarray(im1), jnp.asarray(im2)))

        def rel_epe(x, ref):
            epe = float(np.linalg.norm(x - ref, axis=-1).mean())
            mag = float(np.linalg.norm(ref, axis=-1).mean())
            return epe, mag, epe / mag

        epe, mag, rel = rel_epe(sharded(cut), one_device(cut))
        ph.note(size=[H, W], shard_rows=[H // n] * n, ref_iters=cut.iters,
                mean_epe_px=round(epe, 6), ref_mean_flow_px=round(mag, 4),
                rel=rel, rel_tol=SPATIAL_REL_TOL)
        check(rel <= SPATIAL_REL_TOL,
              f"spatial-4 flow is {epe:.3e} px from the one-device flow "
              f"after {cut.iters} iteration(s), over {SPATIAL_REL_TOL} x its "
              f"mean magnitude {mag:.4f}")
        if cut.iters != full.iters:
            e, m, r = rel_epe(sharded(full), one_device(full))
            ph.note(at_full_depth={"ref_mean_flow_px": round(m, 2),
                                   "rel": round(r, 4)})


# -------------------------------------------------------------------- main

def run(chips: int, sz: Sizes, flows: bool = False,
        int8: bool = False) -> dict:
    """All phases for ``chips``; returns the device record.  Raises on the
    first failure."""
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK, exist_ok=True)
    from raft_tpu.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    emit(phase="start", chips=chips, compile_cache_dir=cache_dir,
         compile_cache_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         work_dir=WORK)
    if chips == 4:
        from raft_tpu.fleet.manager import local_chip_count
        check(sz.interpret or local_chip_count() == 4,
              f"--chips 4 needs four chips on this host, found "
              f"{local_chip_count()} device file(s)")
        emit(**fleet_before_jax(sz))      # before any backend exists here
    meter = CompileMeter()
    device = phase_device(meter, chips) if not sz.interpret else None
    if flows:
        phase_flows(meter, sz)
    elif int8:
        phase_int8(meter, sz)
    elif chips == 4:
        phase_dp(meter, sz)
        phase_spatial(meter, sz)
    else:
        phase_kernels(meter, sz)
        phase_serve(meter, sz)
        phase_small(meter, sz)
        phase_int8(meter, sz)
        phase_train(meter, sz)
    emit(phase="end", compile_seconds=round(meter.seconds, 2),
         cache_hits=meter.hits, cache_misses=meter.misses,
         compile_cache_dir=cache_dir)
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1 (default): device, kernels, serve, small, int8, train on "
                        "one chip.  4: only the cross-chip phase — fleet of "
                        "four one-chip replicas, dp=4 train step, spatial-4 "
                        "forward — and what each is compared with")
    p.add_argument("--flows", action="store_true",
                   help="no smoke: print the sha256 of the five served "
                        "programs' flows on a fixed seed (one chip), to hold "
                        "a change to the lookup to its parent bit for bit")
    p.add_argument("--int8", action="store_true",
                   help="the int8 phase alone (one chip): open, advance, "
                        "demote, restart on the 256-slot 1080p int8 pool of "
                        "benchmark/configs/raft-things-1080p-stream-int8."
                        "json, for the bring-up of a changed pool format")
    args = p.parse_args(argv)
    alone = args.flows or args.int8
    try:
        device = run(1 if alone else args.chips, Sizes(), args.flows,
                     args.int8)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
