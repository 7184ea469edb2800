#!/usr/bin/env python3
"""The control of the output check, at a cell's own size.

    python3 benchmark/control.py --config <name> --seeds 11 12 13 [--pairs 2]

For each seed: the benchmark's weights and frames, the plain reference in
float32, and the same reference put in the program's place at a LOWER
precision than the configuration states ('float8': e4m3 operands with a
per-tensor scale, the step below bfloat16).  Prints, per pair, the number the
check compares (``precision_ratio``, check.py) as the control reads it.  The
check's limit has to lie under the smallest control reading and over the
largest reading of sound runs of the program (PERF.md has both).
Runs where it is started: on the chip through ``chiprun``, at a small
``--size`` on the CPU for the test under ``tests/``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


def readings(config: dict, seed: int, n_pairs: int, height: int, width: int,
             precision: str = "float8") -> list:
    import check
    import inputs
    import run
    import weights as weights_mod
    mcfg = weights_mod.model_cfg(config)
    wts = weights_mod.make_weights(seed, mcfg)
    pairs = inputs.make_pairs(seed, n_pairs, height, width)
    ref = run.load_named(BENCH_DIR, "references",
                         config["check"].get("reference", "dense"),
                         "the configuration's check.reference")
    refs, own, low = (
        check.reference_flows(wts, pairs, range(n_pairs), mcfg,
                              int(config["iters"]), p, ref)
        for p in ("float32", config["check"]["own_precision"], precision))
    out = []
    for i in range(n_pairs):
        stated = check.rel_epe(own[i], refs[i])
        ctl = check.rel_epe(low[i], refs[i])
        out.append({"seed": seed, "pair": i, "control": precision,
                    "rel_epe_control": ctl, "rel_epe_stated": stated,
                    "precision_ratio": ctl / stated})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--pairs", type=int, default=2)
    ap.add_argument("--size", type=int, nargs=2, default=(436, 1024))
    args = ap.parse_args(argv)
    with open(os.path.join(BENCH_DIR, "configs", args.config + ".json")) as f:
        config = json.load(f)
    limit = float(config["check"]["ratio_limit"])
    rows = []
    for seed in args.seeds:
        for r in readings(config, seed, args.pairs, *args.size):
            rows.append(r)
            print(json.dumps(dict(r, limit=limit)), flush=True)
    ctl = [r["precision_ratio"] for r in rows]
    print(json.dumps({"config": args.config, "limit": limit,
                      "control_min": min(ctl), "control_max": max(ctl),
                      "control_fails": min(ctl) > limit}))
    return 0 if min(ctl) > limit else 1


if __name__ == "__main__":
    sys.exit(main())
