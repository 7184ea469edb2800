#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: the cell, its configuration and
its traffic mix in ``BENCHMARK.json``; the configuration's sizes and serve
arguments in its ``file``; the mix in ``traffic/<mix>.json``; the numbers that
depend on both in ``workloads/<cell>.json``; each per-layer metric's reader in
``layer_metrics/<metric>.json`` (or ``.py``); the chip's peaks in
``peaks.json``; the mix's load driver (what is sent, in what order, and which
input an answer belongs to) in ``drivers/<mix's "driver">.py``, absent:
``pairs``; the configuration's plain reference in ``references/<its
"check.reference">.py``, absent: ``dense``.  A later PR adds cells,
configurations, mixes, metrics, drivers and references by adding files and
appending entries, and edits nothing here.

A run: make inputs and weights from the seed, start the real server in this
process (system.py) and warm it (all of that is ``setup_s``, but for the
seconds JAX takes to bring the device up), have the driver offer the mix's
load for ``--seconds``, read the device's peak memory, stop the server, hold
a seeded sample of the window's answers against the reference as the driver
walks it (check.py), and print one JSON object as the last line of stdout.
With ``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` the last few seconds of the window are captured with the JAX
profiler and its metrics are the cell's per-layer metrics.

No TPU, or fewer chips than the cell asks for: exit code 3, no result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # process start, for setup_s

import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import threading

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

WARM_SECONDS = 1.0                  # request-path warm-up, part of set-up
# the traced seconds: a cell's file may ask for more (``trace_seconds``)
# where a program run is long, so that the capture holds whole runs
TRACE_SHARE, TRACE_S, TRACE_BEFORE_END_S = 0.4, 3.0, 0.5


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


def load_named(bench_dir: str, folder: str, name: str, key: str):
    """The module ``<folder>/<name>.py`` of the benchmark, by the ``name``
    that a data file gives under ``key``: a load driver, a reference."""
    path = os.path.join(bench_dir, folder, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: {key} names {name!r}, and there is no "
                         f"{os.path.join(folder, name + '.py')}")
    spec = importlib.util.spec_from_file_location(
        f"{folder}_{re.sub(r'[^0-9a-zA-Z_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def listed(metric: dict, cell: str, reporting: set) -> bool:
    """Does ``cell`` report ``metric``?  By its ``workloads`` key, else by
    whether the cell reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reporting


def capture_trace(trace_dir: str, delay_s: float, length_s: float, box: dict):
    """Sleep, then profile ``length_s`` seconds of the running window."""
    import jax
    time.sleep(delay_s)
    import tracered
    opts = jax.profiler.ProfileOptions()
    # the Python tracer records every call of every thread: on the chip it
    # made a 58 MB trace of 3 s and stop_trace took 47 s (PERF.md, PR 23)
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    t0 = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t1 = time.monotonic()
    with jax.profiler.TraceAnnotation(tracered.WINDOW_ANNOTATION):
        time.sleep(length_s)
    t2 = time.monotonic()
    jax.profiler.stop_trace()
    box.update(start_s=t1 - t0, window_s=t2 - t1, stop_s=time.monotonic() - t2)


def main(argv=None, bench_dir: str = BENCH_DIR, manifest: str = None,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    repo = os.path.dirname(bench_dir)
    bench = load_json(manifest or os.path.join(repo, "BENCHMARK.json"))
    cell_entry = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], cell_entry["config"], "configuration")
    config = load_json(os.path.join(repo, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell_entry["traffic"] + ".json"))
    cell = load_json(os.path.join(bench_dir, "workloads",
                                  args.workload + ".json"))
    chips = int(cell_entry["chips"])

    # the compile cache lives inside the checkout, at a fixed path, whatever
    # the environment says: the path is part of the cache's key
    cache_dir = os.path.join(bench_dir, ".cache")
    jax_cache = os.path.join(cache_dir, "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = jax_cache
    t_up = time.monotonic()
    import jax
    jax.config.update("jax_compilation_cache_dir", jax_cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    # importing JAX and bringing the device up is the machine's time, not the
    # program's: 9.6 to 20.2 s from one process to the next on one machine,
    # wandering by 3-4 s over a quarter of an hour (my chip runs, PR 23), which
    # no PR can change and which would hide a quarter of the program's own
    # set-up under the bound.  It is logged, and it is not in ``setup_s``.
    device_up_s = time.monotonic() - t_up
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"benchmark: need {chips} TPU chip(s), JAX reports "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3

    import check
    import costs
    import loadgen
    import readers
    import system
    import weights as weights_mod

    driver = load_named(bench_dir, "drivers", traffic.get("driver", "pairs"),
                        f"traffic mix {cell_entry['traffic']!r}'s driver")
    ref_mod = load_named(
        bench_dir, "references", config["check"].get("reference", "dense"),
        f"configuration {cfg_entry['name']!r}'s check.reference")

    peaks = load_json(os.path.join(bench_dir, "peaks.json"))["chips"]
    kind = devices[0].device_kind
    if require_tpu and kind not in peaks:
        print(f"benchmark: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 3

    # ---- set-up: inputs (host thread), weights and server (this thread)
    box = {}
    t_in = threading.Thread(
        target=lambda: box.update(made=driver.make_inputs(args.seed, traffic)),
        name="make-inputs")
    t_in.start()
    t_dev = time.monotonic() - T_START
    mcfg = weights_mod.model_cfg(config)
    wts = jax.block_until_ready(weights_mod.make_weights(args.seed, mcfg))
    t_wts = time.monotonic() - T_START
    sut = system.start(config, wts, cache_dir, cfg_entry["name"])
    t_srv = time.monotonic() - T_START
    t_in.join()
    made = box["made"]
    driver.warm_up(sut, made, args.seed, traffic, cell, WARM_SECONDS)
    t_req = time.monotonic() - T_START
    # the warm-up's last answers come after two device batches or three,
    # as its first requests happened to fall into batches (6.2 or 7.3 s, half
    # the runs each): a cell's file may fix the phase's length, so that the
    # set-up time does not take two levels by the benchmark's own doing
    time.sleep(max(0.0, t_srv + float(cell.get("warm_total_seconds", 0.0))
                   - t_req))
    log(f"setup: server {' '.join(sut.argv)}; executables {sut.executables}; "
        f"engine cache {sut.engine_cache_stats()}; seconds since process "
        f"start: device {t_dev:.1f}, weights {t_wts:.1f}, server warm "
        f"{t_srv:.1f}, requests warm {t_req:.1f}, window "
        f"{time.monotonic() - T_START:.1f}; of these the device's bring-up "
        f"{device_up_s:.1f} (not in setup_s)")

    # ---- the window
    n_keep = int(config["check"].get("sample", 3))
    trace_dir = os.path.join(cache_dir, "trace", args.workload)
    tbox: dict = {}
    tracer = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # the LAST seconds of the window: the profiler halves the host
        # path's rate while it is on (PERF.md, PR 23), so it is kept off the
        # rest of the window, and stop_trace's half minute falls after it
        length = min(float(cell.get("trace_seconds", TRACE_S)),
                     TRACE_SHARE * args.seconds)
        delay = max(0.0, args.seconds - length - TRACE_BEFORE_END_S)
        tracer = threading.Thread(target=capture_trace, name="trace",
                                  args=(trace_dir, delay, length, tbox))
    prom0 = sut.scrape()
    setup_s = time.monotonic() - T_START - device_up_s
    if tracer:
        tracer.start()
    window = driver.run_window(sut, made, args.seed, traffic, cell,
                               args.seconds, n_keep)
    if tracer:
        tracer.join()
    prom = system.diff_prom(prom0, sut.scrape())
    records, summary = window.records, driver.summarize(window)
    # the allocator's peak does not count what the runtime reserves for a
    # program's temporaries (0.74 GB here against 0.2 GB of buffers; looked
    # at on the chip, PR 23): the chip's fullest moment holds both
    mem_peak = max(int(ms.get("peak_bytes_in_use", 0))
                   + int(ms.get("peak_bytes_reserved", 0))
                   for ms in ((d.memory_stats() or {})
                              for d in devices[:max(chips, 1)]))
    log("memory: " + json.dumps(devices[0].memory_stats() or {}))
    sut.stop()
    log("window: " + json.dumps(summary))
    spans = {}
    for r in records:
        for k, v in (r.timings or {}).items():
            spans.setdefault(k, []).append(v)
    log("spans p50 ms: " + json.dumps(
        {k: round(loadgen.percentile(v, 50), 2) for k, v in spans.items()}))

    # ---- the output check, outside the window and outside set-up
    t_chk = time.monotonic()
    answers = driver.kept_answers(window)
    which, iters = [a[1] for a in answers], int(config["iters"])
    refs = driver.reference_answers(
        check.forward(ref_mod, wts, mcfg, iters), made, which)
    own = driver.reference_answers(
        check.forward(ref_mod, wts, mcfg, iters,
                      config["check"]["own_precision"]), made, which)
    verdict = check.compare(answers, refs, own,
                            float(config["check"]["ratio_limit"]), log)
    misses = sum(v for k, v in prom.items() if k.split("{", 1)[0]
                 == "raft_serving_compile_cache_misses_total")
    log(f"check: compile misses in the window {misses:g} limit 0 "
        f"{'ok' if misses == 0 else 'OVER'}")
    checks = dict(verdict["checks"], compile_misses={
        "value": misses, "limit": 0, "ok": misses == 0})
    correct = all(c["ok"] for c in checks.values())
    log(f"check: took {time.monotonic() - t_chk:.1f}s (not part of setup_s)")

    # ---- metrics
    # an end-to-end metric is the set-up time or a number of the driver's
    # summary under the metric's own name
    e2e_values = dict(summary, setup_s=setup_s)
    reporting = {m["name"] for m in bench["end_to_end"]
                 if listed(m, args.workload, set())}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct, "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": {}, "device": device}
    if not args.trace:
        for m in bench["end_to_end"]:
            if m["name"] in reporting and e2e_values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {
                    "value": e2e_values[m["name"]], "unit": m["unit"]}
    else:
        import tracered
        trace = tracered.reduce_trace(tracered.find_xplane(trace_dir))
        h, w = int(traffic["height"]), int(traffic["width"])
        log(f"trace: {json.dumps(tbox)} window_s {trace.window_s:.3f} clipped "
            f"{trace.clipped} devices {trace.n_devices} whole program runs "
            f"{trace.module_runs()}")
        ctx = readers.RunContext(
            config=config, traffic=traffic, cell=cell, records=records,
            summary=summary, prom_window=prom, max_batch=sut.max_batch,
            peak=peaks.get(kind, {}), memory_peak_bytes=int(mem_peak),
            shapes=costs.grid_shapes(config, h + (-h) % 8, w + (-w) % 8),
            trace=trace)
        for m in bench["per_layer"]:
            if not listed(m, args.workload, reporting):
                continue
            v = readers.read_metric(bench_dir, m["name"], ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.top_gaps(10)}
    result["checks"] = checks
    check.report(checks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
