"""The comparison that decides ``correct``.

Once the window has closed, the answers kept from it (a sample of request
ordinals drawn from the seed; each answer is a whole ``[H, W, 2]`` flow field
as the timed path returned it over HTTP, computed in whatever device batch
the window put it in) are held against the plain reference, run once over the
same frames with the same weights.  Per answer two distances to the float32
reference are taken, each

    rel_epe(x) = mean |x - reference| / mean |reference|

(end-point error over the mean flow magnitude, over every pixel): the served
answer's, and that of the reference itself computed with its convolution
operands rounded to the precision the configuration STATES (``check.
own_precision``, 'bfloat16' here).  The number compared is their quotient,

    precision_ratio = rel_epe(served) / rel_epe(reference at own precision)

because the plain distance swings threefold with the weights a seed draws (a
draw that amplifies rounding amplifies every rounding alike) while the
quotient does not: a sound bf16 program reads about 1, a step down in
precision (the e4m3 control) reads about ten.  Its limit is the configuration
file's ``check.ratio_limit``; the readings it was set from are in PERF.md.
An answer of the wrong shape or with a non-finite value fails outright, and
so does a window in which the engine compiled.

WHICH reference (the configuration's ``check.reference``, a module under
``references/``) and which inputs it is walked over, in what order (the
traffic mix's driver), are found by name in ``run.py``; this file compares.
Every number compared goes, beside its limit, into ``checks``: the result
line's last key and the last lines on standard error.
"""

from __future__ import annotations

import sys

import numpy as np

import reference        # the dense one: reference_flows' default


def rel_epe(flow: np.ndarray, ref: np.ndarray) -> float:
    epe = np.linalg.norm(flow.astype(np.float64) - ref, axis=-1).mean()
    return float(epe / np.linalg.norm(ref.astype(np.float64), axis=-1).mean())


def forward(ref, weights, cfg: dict, iters: int, precision: str = "float32"):
    """Reference module ``ref``'s forward pass with everything but the frames
    bound: what a driver's ``reference_answers`` walks over its inputs.  What
    a reference carries from one call to the next (a session's previous
    flow) goes through ``carried``, between that driver and that reference."""
    def flow(image1, image2, **carried):
        return ref.flow(weights, image1, image2, cfg, iters, precision,
                        **carried)
    return flow


def reference_flows(weights, pairs, which, cfg: dict, iters: int,
                    precision: str = "float32", ref=None) -> dict:
    """{pair index: reference flow} for the pair indices in ``which``, each
    pair alone; ``ref`` is the reference's module, absent: the dense one.
    ``control.py`` and ``chip_smoke.py`` call it; the benchmark's own runs
    go through the driver's walk."""
    flow = forward(ref or reference, weights, cfg, iters, precision)
    return {i: np.asarray(flow(*pairs[i])) for i in sorted(set(which))}


def compare(answers, refs: dict, own: dict, limit: float, out=print) -> dict:
    """``answers``: [(ordinal, pair index, flow array or None)]; ``refs`` and
    ``own``: the reference flows in float32 and at the configuration's own
    precision.  Prints each number beside its limit;
    -> {"correct", "worst", "numbers", "checks"}."""
    numbers, checks = [], {}
    if not answers:
        out("check: the window kept no answer to compare: not correct")
    for ordinal, pair, flow in answers:
        ref = refs[pair]
        entry = checks[f"precision_ratio.r{ordinal}"] = {
            "value": None, "limit": limit, "ok": False}
        if flow is None:
            out(f"check: request {ordinal} (pair {pair}): no answer kept")
            continue
        flow = np.asarray(flow)
        flow = flow.reshape(flow.shape[-3:])
        if flow.shape != ref.shape or not np.isfinite(flow).all():
            out(f"check: request {ordinal} (pair {pair}): shape {flow.shape} "
                f"against {ref.shape}, finite={bool(np.isfinite(flow).all())}"
                f": not correct")
            continue
        served, stated = rel_epe(flow, ref), rel_epe(own[pair], ref)
        ratio = served / stated
        numbers.append(ratio)
        entry.update(value=ratio, ok=bool(ratio <= limit))
        out(f"check: request {ordinal} (pair {pair}): precision_ratio "
            f"{ratio:.4f} limit {limit:.4f} {'ok' if ratio <= limit else 'OVER'}"
            f"; rel_epe served {served:.6f}, reference at the stated "
            f"precision {stated:.6f}; mean |reference| "
            f"{float(np.linalg.norm(ref, axis=-1).mean()):.4f} px")
    # at least one answer, and none kept without a number
    need = max(1, len(answers))
    checks["answers_compared"] = {
        "value": len(numbers), "limit": need, "ok": len(numbers) >= need}
    return {"correct": all(c["ok"] for c in checks.values()),
            "worst": max(numbers) if numbers else None,
            "numbers": numbers, "checks": checks}


def report(checks: dict) -> None:
    """Every number compared beside its limit, one line each: the run's last
    lines on standard error."""
    for name, c in checks.items():
        print(f"check: {name} {c['value']} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'FAILS'}", file=sys.stderr, flush=True)
