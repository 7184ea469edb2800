#!/usr/bin/env python3
"""The control of the output check for a cell of sessions, at its own size.

    python3 benchmark/control_sessions.py --config <name> --traffic <mix> \\
        --seeds 11 12 13 [--clips 1]

``control.py``'s way, walked over sessions: for each seed the benchmark's
weights and the mix's clips, the configuration's reference walked from frame
0 as the mix's driver walks it (each answer's 1/8 flow projected into the
next call's ``flow_init``) in float32, at the precision the configuration
states, and one step below it ('float8': e4m3 operands with a per-tensor
scale) in the program's place.  Prints, per clip and frame index, the number
the check compares (``precision_ratio``, check.py) as the control reads it.
The check's limit has to lie under the control's readings at a frame index
the mix keeps, and over the largest reading of sound runs of the program
(PERF.md has both).  Runs where it is started: on the chip through
``chiprun``, at a small ``--size`` on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


def readings(config: dict, traffic: dict, seed: int, clips: int,
             frames: list, precision: str = "float8") -> list:
    import check
    import run
    import weights as weights_mod
    mcfg = weights_mod.model_cfg(config)
    wts = weights_mod.make_weights(seed, mcfg)
    driver = run.load_named(BENCH_DIR, "drivers", traffic["driver"],
                            "the mix's driver")
    ref = run.load_named(BENCH_DIR, "references",
                         config["check"]["reference"],
                         "the configuration's check.reference")
    made = driver.make_inputs(seed, dict(traffic, clips=clips,
                                         kept_frames=frames),
                              frames=max(frames) + 1)
    which = [(c, k) for c in range(clips) for k in frames]
    refs, own, low = (
        driver.reference_answers(
            check.forward(ref, wts, mcfg, int(config["iters"]), p), made,
            which)
        for p in ("float32", config["check"]["own_precision"], precision))
    out = []
    for w in which:
        stated = check.rel_epe(own[w], refs[w])
        ctl = check.rel_epe(low[w], refs[w])
        out.append({"seed": seed, "clip": w[0], "frame": w[1],
                    "control": precision, "rel_epe_control": ctl,
                    "rel_epe_stated": stated,
                    "precision_ratio": ctl / stated})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--clips", type=int, default=1)
    ap.add_argument("--frames", type=int, nargs="+", default=None,
                    help="frame indices to read (default: 1 to the mix's "
                         "last kept one)")
    ap.add_argument("--size", type=int, nargs=2, default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(BENCH_DIR, "configs", args.config + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", args.traffic + ".json")) as f:
        traffic = json.load(f)
    if args.size:
        traffic.update(height=args.size[0], width=args.size[1])
    kept = [int(k) for k in traffic["kept_frames"]]
    frames = args.frames or list(range(1, max(kept) + 1))
    limit = float(config["check"]["ratio_limit"])
    rows = []
    for seed in args.seeds:
        for r in readings(config, traffic, seed, args.clips, frames):
            rows.append(r)
            print(json.dumps(dict(r, limit=limit)), flush=True)
    by_frame = {k: [r["precision_ratio"] for r in rows if r["frame"] == k]
                for k in frames}
    # the control fails the check if it is over the limit at ONE kept index
    fails = any(min(v) > limit for k, v in by_frame.items() if k in kept)
    print(json.dumps({"config": args.config, "limit": limit,
                      "control_range_by_frame": {
                          k: [min(v), max(v)] for k, v in by_frame.items()},
                      "kept_frames": kept, "control_fails": fails}))
    return 0 if fails else 1


if __name__ == "__main__":
    sys.exit(main())
