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
    w2: int             # map columns
    w2p: int            # stored row width (:func:`corr_row_lanes`)
    # the map cut on fixed boundaries: the whole map where it fits one step
    # (``n_pblocks`` 1), the all-rows walk, the ragged launch's pages
    h2_blk: int         # map rows per f2 row-block (a multiple of ``pack``)
    rows: int           # map rows before padding
    rows_padded: int    # map rows after padding (multiple of h2_blk)
    n_pblocks: int      # f2 row-block count (the all-rows k grid dimension)
    # the band a query tile fetches where the map holds more rows than a
    # band (all 0 where it does not): ``band_rows`` consecutive map rows
    # from a multiple of ``band_granule`` (:func:`corr_band`)
    band_granule: int = 0       # g: rows of one fetched block of the band
    band_rows: int = 0          # R: a multiple of g, R * w2p <= p_blk_target
    # K: the bands the level has: a tile's entries in the schedule, and
    # the most key steps a launch takes (it takes what its widest tile needs)
    n_bands: int = 0
    band_rows_padded: int = 0   # map rows + the zero rows the last band reads

    @property
    def pack(self) -> int:
        """Map rows that share one 128-lane row of the stored planes: 1
        where a row fills its lanes, 2, 4 or 8 where it is stored 64, 32 or
        16 lanes wide."""
        return max(1, LANE // self.w2p)

    @property
    def banded(self) -> bool:
        """Whether the level is visited through a per-tile schedule of the
        bands its windows touch (:func:`corr_level_plan` has THE rule, read
        from the level's shape alone); a level that is not is one whole-map
        block and pays for no schedule."""
        return self.n_bands > 0

    @property
    def step_rows(self) -> int:
        """Map rows a grid step of the launch that runs multiplies and
        selects over: a band's, or the one block's."""
        return self.band_rows if self.banded else self.h2_blk

    @property
    def band_granules(self) -> int:
        """Granule blocks a band is fetched as (``R / g``)."""
        return self.band_rows // self.band_granule if self.banded else 0


#: Rows a band starts on a multiple of, and is fetched in blocks of.  Fitted
#: on the v5e at both models' shapes (TUNING.md, PR 36): a finer granule
#: wastes fewer rows on the start's rounding but hands the launch more
#: blocks (each costs about 0.05 us a grid step whether it moves or not:
#: g = 2 lost 9-25 % to g = 4 at the same rows), a coarser one (8) needs 24
#: rows where 16 do.
CORR_BAND_GRANULE_ROWS = 4

#: Rows of flow a band allows for beyond what a tile's windows span at rest
#: (its queries moving apart by that many map rows of the level), before the
#: granule rounds the band up.  A tile that spans more takes a second band:
#: what a straddled row-block boundary used to cost every third tile.
CORR_BAND_FLOW_ROWS = 2


def corr_row_lanes(w2: int) -> int:
    """Lanes a map row of ``w2`` columns is stored in: the smallest of 16,
    32, 64 and 128 that holds them, a multiple of 128 above that.  Under 128
    lanes ``128 // lanes`` consecutive map rows share one 128-lane row of
    the planes (a contiguous ``[H2, 64, C]`` map IS ``[H2 / 2, 128, C]``),
    so a pooled level multiplies and selects over its columns and not over
    the zeros that padded each row to a vector register: at 1080x1920
    level 2 is 60 columns in 64 lanes and level 3 is 30 in 32, at 440x1024
    levels 1-3 are 64, 32 and 16 (level 1 at 1080x1920 is 120 of 128 and
    level 0 240 of 256: as they were)."""
    return (round_up(w2, LANE) if w2 > LANE // 2
            else max(TAP_LANES, 1 << (w2 - 1).bit_length()))


def corr_band(t: int, w2: int, w2p: int, cap_rows: int, radius: int,
              grid_w: int):
    """``(g, R)`` of a banded level: the granule a band starts on a multiple
    of and the rows of a band, from shapes alone.  A tile's ``t`` queries
    are consecutive in raster order over a grid ``grid_w`` wide, so they lie
    in ``1 + (t - 2) // grid_w`` query rows more than one; at a level
    ``2^l`` coarser (``grid_w // w2``, floored to a power of two) that is
    ``ceil(. / 2^l)`` map rows, a window is ``2r + 2`` rows, and a band
    that starts on a multiple of ``g`` at or under the first row needs
    ``g - 1`` more: 16 rows at either model's radius at 1080x1920 and
    440x1024.  Both are capped by the positions of one step (``cap_rows =
    p_blk_target // w2p``), and the granule is whole 128-lane rows of the
    planes (a multiple of the rows that share one)."""
    scale = 1 << (max(1, grid_w // w2).bit_length() - 1)
    span = -(-(1 + max(t - 2, 0) // grid_w) // scale)
    g = max(min(CORR_BAND_GRANULE_ROWS, cap_rows), LANE // w2p)
    need = 2 * radius + 2 + span + CORR_BAND_FLOW_ROWS + g - 1
    return g, max(g, min(round_up(need, g), cap_rows // g * g))


def corr_level_plan(q: int, h2: int, w2: int, *, q_blk: int,
                    p_blk_target: int, radius: int,
                    grid_w: int) -> CorrLevelPlan:
    """The fused correlation kernel's block plan for one pyramid level —
    the exact padding/blocking arithmetic ``_lookup_level`` executes.
    ``q`` queries of a grid ``grid_w`` wide look up windows of ``radius`` in
    a map of ``h2`` x ``w2``; ``p_blk_target`` caps the key positions of one
    grid step.  This is the only place that decides how wide a row is
    stored (:func:`corr_row_lanes`), whether a level is banded, and its
    ``R`` and ``g``.

    THE rule for the band schedule, from the level's shape alone: a level
    is banded where its map holds more rows than a band needs (``h2 > R``,
    :func:`corr_band`), so that no tile multiplies over more map rows than
    its windows can touch; a level of ``R`` rows or fewer is one whole-map
    block and pays for no schedule.  At the default 4096 positions ``R`` is
    16 at either model's radius (3 and 4), so that is levels 0-2 of
    1080x1920's grid (135, 67 and 33 rows; level 3 is 16) and levels 0-1 of
    440x1024's (55 and 27 rows; levels 2 and 3 are 13 and 6).  Until PR 43
    the rule was "more than one row-block of ``p_blk_target``", which is
    the same levels at 1080x1920 while rows were stored 128 lanes wide, and
    left 440x1024's level 1 (27 rows) one block of 3456 positions
    (TUNING.md, PR 36 and PR 43)."""
    if h2 <= 0 or w2 <= 0:
        raise ValueError(f"degenerate level {h2}x{w2}: the kernel "
                         f"short-circuits these to zeros before planning")
    t = q_blk if q >= q_blk else round_up(q, SUBLANE)
    qp = round_up(q, t)
    w2p = corr_row_lanes(w2)
    pack = max(1, LANE // w2p)
    # a block is whole 128-lane rows of the planes: a multiple of ``pack``
    cap_rows = max(pack, p_blk_target // w2p // pack * pack)
    h2_blk = min(round_up(h2, pack), cap_rows)
    rows_padded = round_up(h2, h2_blk)
    plan = CorrLevelPlan(t=t, qp=qp, w2=w2, w2p=w2p, h2_blk=h2_blk, rows=h2,
                         rows_padded=rows_padded,
                         n_pblocks=rows_padded // h2_blk)
    g, band = corr_band(t, w2, w2p, cap_rows, radius, grid_w)
    if h2 <= band:
        return plan
    # a band starts at or under the map's last row, on a multiple of g
    return dataclasses.replace(
        plan, band_granule=g, band_rows=band, n_bands=-(-h2 // band),
        band_rows_padded=(h2 - 1) // g * g + band)


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
