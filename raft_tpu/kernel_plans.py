"""Block plans of the Pallas kernels: how each one tiles itself.

The padding and blocking arithmetic ``ops/corr_pallas.py`` and
``ops/gru_pallas.py`` execute before their ``pallas_call``, and the scoped
VMEM they ask the compiler for, as dataclasses and integer arithmetic.  The
kernels decide here; whoever needs to know what they decided (the static
capacity analyzer ``lint/budget.py``, the tests, ``tools/tune_pallas.py``)
reads the same functions, so the envelope that is checked is the plan that
runs.  Rule B4 of raftlint keeps byte constants from growing anywhere else.

A leaf module beside ``config.py``: pure Python, no ``jax``, no Pallas, so
the linter runs without ``jax`` and a server that loads its executables from
the AOT cache imports no kernel to know a plan.
"""

from __future__ import annotations

import dataclasses

#: TPU vector-lane width: the last dim of every VMEM tile pads to this.
LANE = 128
#: TPU sublane width: the second-minor dim of a float32 tile pads to this.
SUBLANE = 8
#: Scoped-VMEM limit the Pallas kernels ask the compiler for
#: (``vmem_limit_bytes`` of every pallas_call) and the ceiling the static
#: envelopes below are checked against.  The compiler's DEFAULT scoped limit
#: is 16 MiB, and the kernels at their default blocks sit right on it: the
#: level-0 corr lookup (q_blk 128, p_blk 4096, C 256) is accepted at 16 MiB
#: standalone and refused inside the chairs train step ("Scoped allocation
#: with size 16.84M and limit 16.00M"), the fused GRU at f32 I/O, 8 rows x
#: 128 columns is refused ("17.03M").  A v5e TensorCore has 128 MiB of VMEM,
#: so the default is a compiler setting, not the hardware: the kernels
#: request 32 MiB and keep their measured block plan.
VMEM_BYTES = 32 * 1024 * 1024

#: What a kernel whose row blocks outgrow ``VMEM_BYTES`` may ask for instead
#: (:func:`gru_vmem_limit`): most of the 128 MiB a v5e TensorCore has, the
#: rest left to the compiler's own use around the kernel.
VMEM_CEILING_BYTES = 100 * 1024 * 1024

#: Fused-GRU kernel geometry: the pass-1 recompute halo rows, and the
#: separable tap count (1x5 / 5x1 gates).
GRU_HALO = 4
GRU_TAPS = 5


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``x``."""
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class CorrLevelPlan:
    """Block geometry of one ``_lookup_level`` pallas_call."""

    t: int              # queries per program ([T, C] f1 block)
    qp: int             # padded query count (multiple of t)
    w2p: int            # stored row width, lane-padded (multiple of LANE)
    h2_blk: int         # map rows per f2 block
    rows: int           # map rows before padding
    rows_padded: int    # map rows after padding (multiple of h2_blk)
    n_pblocks: int      # f2 row-block count (the k grid dimension)


def corr_level_plan(q: int, h2: int, w2: int, *, q_blk: int,
                    p_blk_target: int) -> CorrLevelPlan:
    """The fused correlation kernel's block plan for one pyramid level —
    the exact padding/blocking arithmetic ``_lookup_level`` executes."""
    if h2 <= 0 or w2 <= 0:
        raise ValueError(f"degenerate level {h2}x{w2}: the kernel "
                         f"short-circuits these to zeros before planning")
    t = q_blk if q >= q_blk else round_up(q, SUBLANE)
    qp = round_up(q, t)
    w2p = round_up(w2, LANE)
    h2_blk = max(1, min(h2, p_blk_target // w2p))
    rows_padded = round_up(h2, h2_blk)
    return CorrLevelPlan(t=t, qp=qp, w2p=w2p, h2_blk=h2_blk, rows=h2,
                         rows_padded=rows_padded,
                         n_pblocks=rows_padded // h2_blk)


#: Lanes a window row takes where the lookup kernel holds a query's taps: a
#: power of two >= 2r + 2 at the radii the models use (3, 4), so that eight
#: window rows fill one 128-lane tile.
TAP_LANES = 16


def corr_tap_tiles(n: int) -> int:
    """128-lane tiles of a query's ``(n+1) x (n+1)`` integer taps
    (``ops/corr_pallas._window_taps``): window row ``j``, column ``i`` at
    lane ``j * TAP_LANES + i``, eight rows a tile (two tiles at n = 9, one
    at n = 7)."""
    if n + 1 > TAP_LANES:
        raise ValueError(f"a window row of {n + 1} taps does not fit "
                         f"{TAP_LANES} lanes")
    return -(-(n + 1) * TAP_LANES // LANE)


def corr_window_vmem(plan: CorrLevelPlan, n: int, out_itemsize: int) -> int:
    """VMEM bytes of how a lookup launch hands over its ``n`` x ``n`` windows
    (``ops/corr_pallas._accumulate``): the float32 scratch ``[T, tiles *
    128]`` the visited row-blocks' taps are summed in (1 KiB a query at
    n = 9; the ``[T, n, n]`` scratch of PRs 29-31 padded to 8 KiB) and the
    output block ``[T, n*n]`` in the consumer's dtype, which the pipeline
    holds twice."""
    scratch = plan.t * corr_tap_tiles(n) * LANE * 4
    out_block = plan.t * round_up(n * n, LANE) * out_itemsize
    return scratch + 2 * out_block


def corr_level_scheduled(plan: CorrLevelPlan) -> bool:
    """THE rule for the lookup's key-block schedule, read from the level's
    plan alone: a level whose map is cut into more than one row-block is
    visited through a per-tile schedule of the blocks its windows touch; a
    level of one block has nothing to leave out and pays for no schedule.
    A (2r+2)-row window band lies in one or two blocks of a plan's 8 to 32
    rows, so even at two blocks a tile leaves one out more often than not:
    on the v5e one launch at batch 32 of 440x1024's level 0 (two blocks;
    tiles visit 61 % of them) fell from 25.9 to 19.6 ms, and at 1080x1920
    (batch 8) level 0 (nine blocks, 19.5 %) from 213 to 56, level 1 (three)
    from 63 to 38, level 2 (two) from 46 to 31 (TUNING.md, PR 26).  No
    level with more than one block lost."""
    return plan.n_pblocks > 1


@dataclasses.dataclass(frozen=True)
class GruRowPlan:
    """Row-block geometry of one fused-GRU pallas_call."""

    hp: int     # padded height (multiple of block_rows)
    wc: int     # conv-output width (aligned row merges: multiple of 8)
    wp: int     # stored width: wc + tap radius of zeros each side
    n_rb: int   # row-block count (the k grid dimension)


def gru_row_plan(h: int, w: int, block_rows: int) -> GruRowPlan:
    """The fused GRU kernel's padding plan — the exact arithmetic
    ``_gru_fused_impl`` executes before its pallas_call."""
    if block_rows < GRU_HALO:
        raise ValueError(f"block_rows must be >= {GRU_HALO} (the pass-1 "
                         f"recompute halo), got {block_rows}")
    hp = round_up(h, block_rows)
    wc = round_up(w, SUBLANE)
    wp = wc + (GRU_TAPS - 1)
    return GruRowPlan(hp=hp, wc=wc, wp=wp, n_rb=hp // block_rows)


#: Scoped VMEM the fused GRU's program takes per (pass-1 row, stored column)
#: at the full model's 128 hidden + 128 motion channels, by the itemsize of
#: its I/O: its float32 intermediates are all live at once, on top of the
#: row blocks it is handed.  An upper envelope of the chip compiler's own
#: figures (v5e, jax 0.9.0) at 16 pass-1 rows: bfloat16 I/O, 244 stored
#: columns, 53.23M inside the 1080x1920 pair program (13.96 KiB a position;
#: the parent's first run of that program on the chip, PR 26, and the same
#: figure from the compiler here) and 39.63M alone; float32 I/O, 244 columns,
#: 78.74M alone (20.65 KiB); float32, 132 columns, 17.03M inside the 440x1024
#: program (8.3 KiB: narrow rows cost less a position, so this over-asks
#: there, which costs nothing).
GRU_SCOPED_BYTES_PER_POSITION = {2: 14 * 1024, 4: 24 * 1024}


def gru_scoped_bytes(plan: GruRowPlan, block_rows: int, itemsize: int) -> int:
    """What the fused GRU's program is expected to need of scoped VMEM."""
    return ((block_rows + 2 * GRU_HALO) * plan.wp
            * GRU_SCOPED_BYTES_PER_POSITION[itemsize])


def gru_vmem_limit(plan: GruRowPlan, block_rows: int, itemsize: int) -> int:
    """The scoped-VMEM limit the fused GRU kernel asks the compiler for at
    this row plan and I/O itemsize: ``VMEM_BYTES`` wherever its program is
    expected to fit that (bfloat16 up to 146 stored columns at 8 rows: the
    440x1024 program is unchanged), else what it is expected to need and a
    sixth more, up to ``VMEM_CEILING_BYTES``.  The kernel holds whole rows,
    so a wider frame is a larger program: 1080x1920 (244 stored columns)
    needs 53.23M."""
    need = gru_scoped_bytes(plan, block_rows, itemsize)
    if need <= VMEM_BYTES:
        return VMEM_BYTES
    return min(round_up(need + need // 6, 1024 * 1024), VMEM_CEILING_BYTES)
