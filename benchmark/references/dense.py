"""The default reference, ``dense``: ``reference.py``'s plain forward pass
with the dense all-pairs volume.

A configuration names its reference (``check.reference``, absent: this one)
and ``run.py`` finds ``references/<name>.py`` by that name alone.  A
reference is one function, ``flow(weights, image1, image2, cfg, iters,
precision)``; it imports nothing of the program.  ``reference.py`` keeps its
place and its bare name because the repo's tier-1 tests and ``chip_smoke.py``
import it so.
"""

from reference import flow  # noqa: F401
