"""The default load driver, ``pairs``: stateless pairs of frames.

A traffic mix names its driver (``"driver"``, absent: this one) and
``run.py`` finds ``drivers/<name>.py`` by that name alone.  A driver is the
functions below, called in this order; it owns everything about WHAT is sent
and how an answer is matched to its input, ``run.py`` owns the clock, the
system, the trace and the result line.

``distinct_pairs`` seeded ``uint8`` pairs of the mix's one size, encoded once
as npz bodies; ordinal ``i`` of the window posts body ``(seed + i) % n`` to
the mix's ``endpoint``, and no request depends on an answer.  The loops, the
sample of kept ordinals and the arithmetic are ``loadgen.py``'s, the frames
``inputs.py``'s.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import inputs
import loadgen


@dataclasses.dataclass
class Made:
    pairs: list                  # [(frame1, frame2)] uint8 [H, W, 3]
    bodies: list                 # the same pairs as npz request bodies


@dataclasses.dataclass
class Window:
    records: list                # loadgen.Record, every request of the window
    t0: float
    t1: float
    loop: str
    keep: list                   # the ordinals whose answers were kept


def make_inputs(seed: int, traffic: dict) -> Made:
    """Everything the window sends, from the seed and the mix; runs on a
    thread of its own beside the server's warm-up."""
    pairs = inputs.make_pairs(seed, int(traffic["distinct_pairs"]),
                              int(traffic["height"]), int(traffic["width"]),
                              int(traffic.get("max_shift", 6)))
    return Made(pairs, [inputs.npz_body(image1=a, image2=b)
                        for a, b in pairs])


def _clients(sut, cell: dict) -> int:
    return int(cell.get("clients", 2 * sut.max_batch))


def warm_up(sut, made: Made, seed: int, traffic: dict, cell: dict,
            seconds: float) -> None:
    """The request path's warm-up, part of set-up: the window's own clients
    for ``seconds``, nothing kept."""
    loadgen.run_closed(sut.host, sut.port, traffic["endpoint"], made.bodies,
                       seed, _clients(sut, cell), seconds, keep=())


def run_window(sut, made: Made, seed: int, traffic: dict, cell: dict,
               seconds: float, n_keep: int) -> Window:
    """Offer the mix's load for ``seconds``; keep ``n_keep`` answers by
    ordinals drawn from the seed."""
    path, loop = traffic["endpoint"], traffic["loop"]
    if loop == "closed":
        clients = _clients(sut, cell)
        # past the ramp-up's part batches, and early enough that a window a
        # third as long or a server a third as fast still answers them (at
        # 8 batches' reach a 12 s window kept nothing: my chip run, PR 23)
        keep = loadgen.sample_ordinals(seed, n_keep, clients,
                                       clients + 3 * sut.max_batch)
        records, t0, t1 = loadgen.run_closed(
            sut.host, sut.port, path, made.bodies, seed, clients, seconds,
            keep)
    elif loop == "open":
        due = loadgen.open_schedule(seed, float(cell["rate_per_s"]), seconds,
                                    traffic)
        keep = loadgen.sample_ordinals(seed, n_keep, 0, len(due))
        records, t0, t1 = loadgen.run_open(
            sut.host, sut.port, path, made.bodies, seed, due, seconds,
            int(cell.get("workers", 32)), keep)
    else:
        raise SystemExit(f"traffic loop {loop!r}")
    return Window(records, t0, t1, loop, keep)


def summarize(win: Window) -> dict:
    """``pairs_per_s``, ``attempted``, ``failed``, the latencies: whatever
    the end-to-end metrics and the ``loadgen`` reader take from a window."""
    return loadgen.summarize(win.records, win.t0, win.t1, win.loop)


def kept_answers(win: Window) -> list:
    """[(ordinal, which input, flow array or None)] of the kept ordinals."""
    kept = set(win.keep)
    return [(r.ordinal, r.body,
             inputs.npz_load(r.payload)["flow"] if r.payload else None)
            for r in win.records if r.ordinal in kept]


def reference_answers(forward, made: Made, which) -> dict:
    """{which input: the reference's answer}: each kept pair alone.
    ``forward(image1, image2)`` is the configuration's reference with the
    weights, the sizes and the precision bound (``check.forward``)."""
    return {i: np.asarray(forward(*made.pairs[i])) for i in sorted(set(which))}
