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
"""

from __future__ import annotations

import numpy as np

import reference


def rel_epe(flow: np.ndarray, ref: np.ndarray) -> float:
    epe = np.linalg.norm(flow.astype(np.float64) - ref, axis=-1).mean()
    return float(epe / np.linalg.norm(ref.astype(np.float64), axis=-1).mean())


def reference_flows(weights, pairs, which, cfg: dict, iters: int,
                    precision: str = "float32") -> dict:
    """{pair index: reference flow} for the pair indices in ``which``."""
    return {i: np.asarray(reference.flow(weights, pairs[i][0], pairs[i][1],
                                         cfg, iters, precision))
            for i in sorted(set(which))}


def compare(answers, refs: dict, own: dict, limit: float, out=print) -> dict:
    """``answers``: [(ordinal, pair index, flow array or None)]; ``refs`` and
    ``own``: the reference flows in float32 and at the configuration's own
    precision.  Prints each number beside its limit;
    -> {"correct", "worst", "numbers"}."""
    numbers, ok = [], bool(answers)
    if not answers:
        out("check: the window kept no answer to compare: not correct")
    for ordinal, pair, flow in answers:
        ref = refs[pair]
        if flow is None:
            out(f"check: request {ordinal} (pair {pair}): no answer kept")
            ok = False
            continue
        flow = np.asarray(flow)
        flow = flow.reshape(flow.shape[-3:])
        if flow.shape != ref.shape or not np.isfinite(flow).all():
            out(f"check: request {ordinal} (pair {pair}): shape {flow.shape} "
                f"against {ref.shape}, finite={bool(np.isfinite(flow).all())}"
                f": not correct")
            ok = False
            continue
        served, stated = rel_epe(flow, ref), rel_epe(own[pair], ref)
        ratio = served / stated
        numbers.append(ratio)
        out(f"check: request {ordinal} (pair {pair}): precision_ratio "
            f"{ratio:.4f} limit {limit:.4f} {'ok' if ratio <= limit else 'OVER'}"
            f"; rel_epe served {served:.6f}, reference at the stated "
            f"precision {stated:.6f}; mean |reference| "
            f"{float(np.linalg.norm(ref, axis=-1).mean()):.4f} px")
        ok = ok and ratio <= limit
    return {"correct": ok, "worst": max(numbers) if numbers else None,
            "numbers": numbers}
