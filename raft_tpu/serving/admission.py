"""Does the slot pool fit the chip?  The server's own answer, at start-up.

``InferenceEngine.__init__`` asks :func:`admit_stream` before anything is
compiled or allocated; ``lint/budget.analyze`` reads the same functions for
its report (the server decides, the analyzer reads what it decided:
``tests/test_layering.py``).  Everything here is priced from shapes: the
engine's table of kinds (``serving/engine.Programs``) over real or abstract
params.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def bytes_of(spec) -> int:
    """Device bytes of one abstract array (anything with .shape/.dtype)."""
    return int(np.prod(spec.shape, dtype=np.int64)) * np.dtype(
        spec.dtype).itemsize


def tree_bytes(tree) -> int:
    import jax
    return sum(bytes_of(leaf) for leaf in jax.tree.leaves(tree))


#: Temporaries of the batched stream step (``sbatch``) per input pixel and
#: row, for the full model in bfloat16 with both Pallas kernels: its peak
#: lies in the encoder pass of the batch's frames, and the pool's size and
#: format do not move it.  ``memory_analysis().temp_size_in_bytes`` of
#: ``sbatch-1080-1920-8`` (sandbox compiles for a described v5e, PR 45):
#: 6,379,550,208 B with 33 bfloat16 rows, with 33 int8 rows and with 257 int8
#: rows, 6,379,646,976 B with 257 bfloat16 rows: 384.6 bytes.
STREAM_TEMP_BYTES_PER_PIXEL = 385


def stream_temp_bytes(config, h: int, w: int, b: int) -> Optional[int]:
    """HBM temporaries of the ``sbatch`` executable at ``b`` x ``h`` x ``w``,
    or None for a program no figure was taken from (:func:`lint.budget.pair_temp_bytes`'
    rule: not priced beats priced wrong)."""
    if (config.compute_dtype != "bfloat16" or config.corr_impl != "pallas"
            or config.small or config.gru_impl != "pallas"):
        return None
    return STREAM_TEMP_BYTES_PER_PIXEL * b * h * w


#: What the runtime hands out of a chip's HBM where it has been read
#: (``memory_stats()["bytes_limit"]``): a v5e gives 15.75 GiB of its 16 (my
#: chip runs, PR 45; ``chip_smoke.py``'s TRAIN_FIT note, PR 21).
USABLE_HBM_BYTES = {"tpu-v5e": 16_909_336_064}
#: ``jax`` ``device_kind`` substring -> key of ``USABLE_HBM_BYTES`` (a v5e
#: reports itself as "TPU v5 lite"; ``lint/budget.py`` has the same keys)
_KIND_KEYS = (("v5 lite", "tpu-v5e"), ("v5e", "tpu-v5e"))

#: The share of the usable bytes :func:`stream_footprint` may reach.  It is
#: a lower bound and has read 3.7 % under the chip's own peak with 257 int8
#: rows (11.94 GB for ``peak_hbm_gb`` 12.40) and 4.5 % under it with 33
#: bfloat16 rows (8.71 for 9.12: ledger, PR 44), by what it does not price
#: (the allocator's alignment, an open's rows, the answers being encoded):
#: a pool whose bound leaves under 5 % free does not start.
STREAM_BOUND_SHARE = 0.95


def stream_footprint(programs, h: int, w: int, b: int) -> dict:
    """The chip at its fullest on the stream path of one bucket, from shapes
    alone: ``programs`` is the engine's table of kinds (built over real or
    abstract params), ``b`` the widest batch step.  On the HEAP under the
    batcher's two-deep walk of groups (serving/stream.py): the weights and
    the pool's leaves; the running group's frames and the frames of the
    group placed behind it; the running group's outputs and those of the
    group before it, fetched and committed under this run.  Below the heap
    the runtime RESERVES one region for whichever program runs, as large as
    the largest program's temporaries (``peak_bytes_reserved`` read
    6,374,899,712 B on the chip, the batched step's; my chip run, PR 45):
    the step's where :func:`stream_temp_bytes` prices them, and never under
    the ``b`` rows of the widest leaf that the step gathers and the commit
    quantises.  The pool's size does not enter: both programs address the
    pool a row at a time (PR 46; sandbox compiles for a described v5e:
    ``scommit-1080-1920-8`` holds 580,608 B of temporaries beside 33
    bfloat16 rows and 67,226,112 B, 8 rows of codes, beside 257 int8 rows,
    where the general scatter held a copy of a leaf, 747,835,392 and
    2,199,671,296 B)."""
    import jax

    pool = programs.slot_specs(h, w)
    outputs = (tree_bytes(programs.feature_specs(h, w, b))
               + b * (h * w + (h // 8) * (w // 8)) * 2 * 4)
    frames = b * h * w * 3 * 4
    widest = max(jax.tree.leaves(pool), key=bytes_of)
    pool_b = tree_bytes(pool)
    heap = tree_bytes(programs.params) + pool_b + 2 * frames + 2 * outputs
    reserved = max(stream_temp_bytes(programs.config, h, w, b) or 0,
                   b * (bytes_of(widest) // widest.shape[0]))
    return {"bucket": [h, w], "pool_bytes": pool_b,
            "reserved_bytes": reserved, "peak_bytes": heap + reserved}


def stream_peak(programs, sconfig) -> Tuple[int, int, dict]:
    """(the stream path's fullest moment, the pools' bytes, the footprint of
    the bucket whose programs ask most) over the buckets a server holds:
    every bucket's pool is resident, one bucket's programs run; a ragged
    server has the one arena at the max box."""
    b = max(sconfig.batch_steps)
    buckets = ([tuple(sconfig.max_box)] if getattr(sconfig, "ragged", False)
               else [tuple(bk) for bk in sconfig.buckets])
    foots = [stream_footprint(programs, h, w, b) for (h, w) in buckets]
    worst = max(foots, key=lambda f: f["peak_bytes"] - f["pool_bytes"])
    pool_b = sum(f["pool_bytes"] for f in foots)
    return worst["peak_bytes"] - worst["pool_bytes"] + pool_b, pool_b, worst


def admit_stream(programs, sconfig, device_kind: str) -> None:
    """Start-up's question (``InferenceEngine.__init__``, before anything is
    compiled or allocated): does the pool ``--max-sessions`` asks for fit
    this chip beside the stream programs?  Raises ``ValueError`` naming the
    pool's bytes where :func:`stream_peak` says no: an allocation failure an
    hour in says nothing an operator can act on.  The CPU, and a chip whose
    usable bytes nobody has read, start unasked."""
    key = next((k for sub, k in _KIND_KEYS if sub in device_kind.lower()),
               None)
    if key is None:
        return
    limit = int(STREAM_BOUND_SHARE * USABLE_HBM_BYTES[key])
    peak, pool_b, worst = stream_peak(programs, sconfig)
    if peak > limit:
        (h, w), b = worst["bucket"], max(sconfig.batch_steps)
        raise ValueError(
            f"--max-sessions {sconfig.max_sessions} does not fit {key}: "
            f"the slot pool is {pool_b} B ({pool_b / 1e9:.2f} GB: "
            f"{programs.capacity + 1} rows a bucket, quant="
            f"{programs.config.quant}) and beside it the stream programs at "
            f"{b} x {h}x{w} hold {peak - pool_b} B "
            f"({(peak - pool_b) / 1e9:.2f} GB, {worst['reserved_bytes']} B "
            f"of it the temporaries the runtime reserves): {peak} B "
            f"({peak / 1e9:.2f} GB), over {limit} B, "
            f"{100 * STREAM_BOUND_SHARE:.0f} % of the chip's usable memory; "
            f"fewer sessions, or --quant int8 slots")
