"""Test configuration: run everything on CPU with 8 virtual devices so the
multi-device sharding paths are exercised without TPU hardware (SURVEY.md §4).

The force-CPU recipe lives in _cpu_backend.py at the repo root (shared with
__graft_entry__.dryrun_multichip and the tools' ``--cpu`` flags).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _cpu_backend import force_cpu_backend

# Tests count real compiles (RecompileWatch, the AOT-cache "loads without
# compiling" cases), so JAX's persistent compilation cache is off for the
# test process and, through the environment, for every child it starts.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

force_cpu_backend(8)


def make_sintel_tree(root, split="training", dstype="clean",
                     scenes=("alley_1",), n_frames=3, size=(32, 48),
                     with_gt=None, seed=0):
    """Fabricate the MpiSintel on-disk layout under ``root``:
    <split>/<dstype>/<scene>/frame_XXXX.png (1-based), plus
    <split>/flow/<scene>/frame_XXXX.flo ground truth when ``with_gt``
    (default: split == "training").  One shared builder so the layout
    assumption MpiSintel scans lives in one place across the test suite."""
    import cv2
    import numpy as np

    from raft_tpu.utils.flow_io import write_flo

    if with_gt is None:
        with_gt = split == "training"
    h, w = size
    rng = np.random.RandomState(seed)
    for scene in scenes:
        d = root / split / dstype / scene
        d.mkdir(parents=True, exist_ok=True)
        for i in range(1, n_frames + 1):
            cv2.imwrite(str(d / f"frame_{i:04d}.png"),
                        rng.randint(0, 255, (h, w, 3), np.uint8))
        if with_gt:
            f = root / split / "flow" / scene
            f.mkdir(parents=True, exist_ok=True)
            for i in range(1, n_frames):
                write_flo((rng.randn(h, w, 2) * 2).astype(np.float32),
                          f / f"frame_{i:04d}.flo")
    return root
