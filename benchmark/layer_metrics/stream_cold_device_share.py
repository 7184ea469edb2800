"""Share of the device's busy time inside the solo ``encode`` and ``stream``
programs: what cold restarts (and opens) take of the chip."""

import glob
import json
import os

import stages
import tracered


def _labels_by_kind(config_name: str, pattern: str = None) -> list:
    """[(kind, {operation label})] of the engine's stage maps of one
    configuration; the kind is the head of the map's file name."""
    pattern = pattern or os.path.join(
        stages.BENCH_DIR, ".cache", "engine", config_name, "*",
        "*.stages.json")
    out = []
    for path in sorted(glob.glob(pattern)):
        try:
            with open(path) as f:
                doc = json.load(f)
            out.append((os.path.basename(path).split("-", 1)[0],
                        {tracered.op_label(rec["text"])
                         for rec in doc["instructions"].values()}))
        except (OSError, ValueError, KeyError, TypeError):
            continue
    return out


def read(ctx, params):
    """100 x the window's device ns in operations of the programs
    ``params["kinds"]`` / its busy ns, or None without a device trace or
    without the maps."""
    tr = ctx.trace
    if stages.busiest(tr) is None or not tr.busy_s():
        return None
    maps = _labels_by_kind(str(ctx.config.get("name", "*")),
                           params.get("maps"))
    ops = [op for op in tr.ops() if op.count > 0
           and not tracered.CONTAINERS.match(op.name)]
    if not maps or not ops:
        return None
    main = max(maps, key=lambda m: sum(op.total_ns for op in ops
                                       if op.label in m[1]))[1]
    solo = set().union(*[labels for kind, labels in maps
                         if kind in params["kinds"]]) - main
    busy_ns = sum(d["busy_ns"] for d in tr.devices.values())
    return 100.0 * sum(op.total_ns for op in ops
                       if op.label in solo) / busy_ns
