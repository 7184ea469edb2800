"""The reference ``warm_restart``: RAFT's video protocol for a session that
can lose its place on the device.

``references/warm.py`` is the protocol of a session that never pauses (Teed &
Deng, ECCV 2020, arXiv:2003.12039, the warm-start rows of the Sintel
evaluation; upstream ``evaluate.py::create_sintel_submission(warm_start=
True)``): frame k's recurrence starts from frame k-1's 1/8 flow projected
forward along itself.  A service that holds more live sessions than device
slots cannot always do that: a session that paused comes back to find its
previous frame's maps and its seed gone.  What it owes the client then, written
down here and nowhere in the program's words:

* **a cold restart of frame k is the zero-seeded pair (frame k-1, frame k)**,
  whatever came before: both frames encoded anew, the recurrence started at
  ``coords0``, the answer what ``/v1/flow`` gives for the two frames.  Nothing
  of the session's history enters: not the seed it had, not the flow before
  the pause;
* **the advance after it is warm again**, seeded with the projection of the
  RESTART's 1/8 flow (and so on: the protocol goes on from the restart as it
  would from an open);
* the answer says which of the two it was (``warm``), and a walk of the
  reference restarts exactly where the answers said the service did.  A
  service that says warm and restarted, or says cold and used the seed, is
  then off the reference by the seed's whole effect.

``flow`` is ``warm.flow`` with ``restart`` (the seed is dropped); ``walk``
is a session from its open to frame ``last`` with restarts at the frame
indices ``cold``.  Plain ``jax.numpy``, ``numpy`` and ``scipy`` through
``warm.py`` and ``reference.py``; it imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from references import warm
from references.warm import forward_interpolate  # noqa: F401  (the driver's)


def flow(weights, image1, image2, cfg: dict, iters: int,
         precision: str = "float32", flow_init=None, restart: bool = False):
    """``warm.flow``: ``(flow [H, W, 2], flow_lr [h, w, 2])`` of one ``uint8``
    pair with the recurrence started at ``flow_init``; with ``restart`` the
    seed is dropped, whatever it holds: the zero-seeded pair."""
    return warm.flow(weights, image1, image2, cfg, iters, precision,
                     flow_init=None if restart else flow_init)


def walk(forward, frames, last: int, cold=()) -> dict:
    """{k: flow(frame k-1 -> frame k)} for k = 1 .. ``last`` of one session
    opened with ``frames[0]``: index 1 starts from zeros (an open holds no
    flow), every later index from the projection of the answer before it,
    but for the indices in ``cold``, which restart.  ``forward(image1,
    image2, flow_init=..., restart=...)`` is :func:`flow` with the weights,
    the sizes and the precision bound (``check.forward``)."""
    out, flow_init = {}, None
    for k in range(1, last + 1):
        answer, flow_lr = forward(frames[k - 1], frames[k],
                                  flow_init=flow_init, restart=k in cold)
        flow_init = forward_interpolate(np.asarray(flow_lr))
        out[k] = np.asarray(answer)
    return out
