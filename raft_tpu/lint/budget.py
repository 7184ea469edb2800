"""Static compile-surface + device-memory budget analyzer (LINT.md B family).

Given a (RAFTConfig, ServeConfig) pair — no device, no compile — this module
answers the three questions nothing else in the repo could before a replica
boots:

* **What will the engine compile?**  It reads the warm-up grid where the
  engine keeps it (``serving/config.enumerate_warmup_grid``, the list
  ``InferenceEngine.warmup`` itself iterates), and what each key's program
  takes and returns from the engine's table of kinds
  (``serving/engine.Programs``, built here over abstract params), so the
  report's key list and signatures are the engine's, not second copies that
  could drift.
* **Does the config fit HBM, and how many sessions per chip?**
  :func:`analyze` computes per-executable and aggregate footprints via
  ``jax.eval_shape`` abstract evaluation (params, per-bucket SlotPool
  buffers, peak live call buffers per kind — donation-aware: the commit
  scatter's donated pool buffers are not double-counted off-CPU) and
  solves max-sessions headroom against the per-device-kind budget.
* **Do the Pallas kernels fit VMEM?**  The kernels' block plans are their
  own (``raft_tpu/kernel_plans.py``: :func:`corr_level_plan` /
  :func:`gru_row_plan`, which ``ops/corr_pallas.py`` and
  ``ops/gru_pallas.py`` execute); the envelopes here price those plans, so
  the VMEM that is checked is the tiling that runs — a hardcoded constant
  bypassing ``kernel_plans`` is what lint rule B4 exists to catch.

Layering: this is the top of the stack and reads downward only.  Module
import is pure stdlib plus ``kernel_plans`` (the linter must run without
jax); whatever brings jax in (``models``, ``serving``, ``ops.corr_pallas``)
is imported lazily inside the functions that evaluate shapes.  The byte
accounting is an I/O-resident lower bound — XLA's internal temporaries
(convolution scratch, fusion buffers) ride on top, so headroom numbers are
optimistic by design and say "cannot fit", never "will surely fit".
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Dict, List, Optional, Tuple

from ..kernel_plans import (GRU_HALO, GRU_TAPS, LANE, SUBLANE, TAP_LANES,
                            VMEM_BYTES, VMEM_CEILING_BYTES, corr_level_plan,
                            corr_tap_tiles, corr_window_vmem, gru_row_plan,
                            gru_scoped_bytes, gru_vmem_limit)

if TYPE_CHECKING:       # serving/ brings jax in: a name for annotations only
    from ..serving.config import Key

#: Per-device-kind capacity budgets the analyzer solves against.  HBM
#: figures are per-chip; "cpu" is a nominal planning budget so the same
#: report works on dev machines (host RAM is not really this scarce).
DEVICE_BUDGETS: Dict[str, Dict[str, int]] = {
    "tpu-v4":  {"hbm_bytes": 32 * 1024**3, "vmem_bytes": VMEM_BYTES},
    "tpu-v5e": {"hbm_bytes": 16 * 1024**3, "vmem_bytes": VMEM_BYTES},
    "cpu":     {"hbm_bytes": 8 * 1024**3,  "vmem_bytes": VMEM_BYTES},
}

#: ``jax`` ``device_kind`` substring -> DEVICE_BUDGETS key (a v5e reports
#: itself as "TPU v5 lite").
_KIND_KEYS = (("v5 lite", "tpu-v5e"), ("v5e", "tpu-v5e"), ("v4", "tpu-v4"))


def budget_key(device_kind: str) -> str:
    """The DEVICE_BUDGETS key of a device as JAX reports it
    (``jax.devices()[0].device_kind``).  A device that is not in the table
    is an error, not a default: a capacity report against the wrong
    chip's HBM is worse than none."""
    kind = device_kind.lower()
    if kind == "cpu":
        return "cpu"
    for sub, key in _KIND_KEYS:
        if "tpu" in kind and sub in kind:
            return key
    raise ValueError(f"no capacity budget known for device_kind "
                     f"{device_kind!r}; add it to lint/budget.py "
                     f"DEVICE_BUDGETS / _KIND_KEYS with its source")


def corr_vmem_envelope(config, bucket: Tuple[int, int],
                       vmem_bytes: int = VMEM_BYTES) -> dict:
    """Static VMEM envelope of the fused correlation kernel at ``bucket``.

    Per level: the pallas_call's resident blocks (f1/coords/f2 in, window
    out), the float32 scratch the windows' taps are summed in, plus the
    program's dominant intermediates (the [T, Pblk] corr tile and the
    [T, 128] lane tiles its taps are gathered through).  The maps are priced
    in the dtypes the kernel holds them in, asked of the function that hands
    them to it (``ops.corr_pallas.f2_terms``): for bfloat16 maps at 'highest' a
    bfloat16 f1 block and one (level 0) or three (pooled levels) bfloat16
    planes of the f2 block, else float32 blocks.  The output block is
    ``[T, n*n]`` in the compute dtype (what ``models/raft.py`` asks the
    kernel to write), the scratch ``[T, tiles * 128]`` float32, both at the
    size of their VMEM tiles (``kernel_plans.corr_window_vmem``).
    Everything else is float32 or int32.
    """
    import jax
    from ..ops.corr_pallas import f2_terms

    map_dtype = config.compute_dtype     # what the feature encoder returns
    h, w = bucket
    h0, w0 = h // 8, w // 8
    q = h0 * w0
    n = 2 * config.corr_radius + 1
    c = config.fnet_dim
    levels = []
    worst = 0
    h2, w2 = h0, w0
    for level in range(config.corr_levels):
        if h2 <= 0 or w2 <= 0:
            levels.append({"level": level, "shape": [h2, w2],
                           "degenerate": True})
            continue
        plan = corr_level_plan(q, h2, w2, q_blk=config.pallas_q_blk,
                               p_blk_target=config.pallas_p_blk,
                               radius=config.corr_radius, grid_w=w0)
        # the key rows of one grid step of the plan that runs: a band's R
        # rows (its R/g granule blocks, each double-buffered: what R rows
        # in one block take) or the one whole-map block, each row in the
        # lanes it is stored in (16 to 128, or a multiple of 128)
        step_rows = plan.step_rows
        pblk = step_rows * plan.w2p
        # An upper envelope of the least scoped limit the chip's compiler
        # accepts for a launch (v5e, jax 0.9.0, found by bisection in the
        # sandbox, PR 32, for blocks of 4096 positions): 15.14 MiB at three
        # bfloat16 planes of 4096 x 256 (priced 17.4), 12.93 at 440x1024's
        # level 1 (priced 15.1), 10.55 for RAFT-S's 128-channel planes.
        # The pipeline double-buffers every grid-indexed block; the body
        # keeps the MXU product and the term being added to it, a packed
        # tile for every eight rows of the block, the taps and their
        # candidates, and a dozen [T, 128] index and mask planes.
        planes = jax.eval_shape(
            lambda x: f2_terms(map_dtype, x, config.corr_precision),
            jax.ShapeDtypeStruct(
                (1, h2, w2, c), map_dtype if level == 0 else "float32"))
        n_terms, map_bytes = planes.shape[0], planes.dtype.itemsize
        map_blocks = map_bytes * (plan.t * c             # f1 block
                                  + n_terms * pblk * c)  # f2 key rows
        f32_blocks = plan.t * 2              # coords block
        window = corr_window_vmem(      # scratch + double-buffered output
            plan, n, 2 if config.compute_dtype == "bfloat16" else 4)
        tap_tiles = corr_tap_tiles(n)
        lane_tiles = (-(-step_rows * TAP_LANES // LANE)     # packed rows
                      + 2 * tap_tiles + 1    # taps and their candidates
                      + 12)                  # index and mask planes
        floats = (2 * f32_blocks             # double-buffered pipeline
                  + 2 * plan.t * pblk        # corr tile + a term's product
                  + plan.t * LANE * lane_tiles)
        bytes_ = 2 * map_blocks + window + 4 * floats
        worst = max(worst, bytes_)
        levels.append({"level": level, "shape": [h2, w2],
                       "f2_planes": [n_terms, str(planes.dtype)],
                       "block_bytes": bytes_,
                       "fits": bytes_ <= vmem_bytes,
                       "plan": dataclasses.asdict(plan)})
        h2, w2 = h2 // 2, w2 // 2            # avg_pool2d(2, 2) per level
    checks = []
    if config.pallas_q_blk % SUBLANE:
        checks.append(f"pallas_q_blk={config.pallas_q_blk} is not a "
                      f"multiple of the {SUBLANE}-row sublane")
    active = config.corr_impl == "pallas"
    overflow = [lv for lv in levels if lv.get("block_bytes", 0) > vmem_bytes]
    if overflow:
        checks.append(
            f"corr kernel level(s) {[lv['level'] for lv in overflow]} "
            f"need {max(lv['block_bytes'] for lv in overflow)} B of VMEM "
            f"(> {vmem_bytes}); shrink pallas_p_blk or pallas_q_blk")
    return {"active": active, "worst_block_bytes": worst,
            "vmem_bytes": vmem_bytes, "fits": not overflow,
            "levels": levels, "checks": checks}


def gru_vmem_envelope(config, bucket: Tuple[int, int], motion_dim: int,
                      vmem_bytes: int = VMEM_BYTES) -> dict:
    """Static VMEM envelope of the fused GRU kernel at ``bucket``.

    Resident per program: 3 row-picks (prev/cur/next) of the [h|motion]
    map and both hoisted-context stacks at the activation dtype, the six
    fused gate-weight blocks at f32, and the output row block.  The
    recompute-halo arithmetic (``GRU_HALO`` extra pass-1 rows per block)
    is inside :func:`gru_row_plan`'s padding, which this shares with the
    kernel.
    """
    h, w = bucket
    hg, wg = h // 8, w // 8
    t = config.gru_block_rows
    checks = []
    if t < GRU_HALO:
        checks.append(f"gru_block_rows={t} < the {GRU_HALO}-row recompute "
                      f"halo — the kernel rejects this at call time")
        t = GRU_HALO
    plan = gru_row_plan(hg, wg, t)
    hidden = config.hidden_dim
    act_itemsize = 2 if config.compute_dtype == "bfloat16" else 4
    hm_ch = hidden + motion_dim
    ctx_ch = 3 * hidden                      # z/r/q hoisted terms stacked
    act = (3 * t * plan.wp * hm_ch           # hm prev/cur/next blocks
           + 2 * 3 * t * plan.wp * ctx_ch    # c1 + c2 prev/cur/next
           + t * plan.wc * hidden)           # output block
    weights = 2 * GRU_TAPS * (hm_ch * 2 * hidden      # wzr{1,2}
                              + hidden * hidden        # wqh{1,2}
                              + motion_dim * hidden)   # wqm{1,2}
    bytes_ = act * act_itemsize + weights * 4
    active = config.gru_impl == "pallas" and not config.small
    if bytes_ > vmem_bytes:
        checks.append(f"gru kernel row blocks need {bytes_} B of VMEM "
                      f"(> {vmem_bytes}); shrink gru_block_rows")
    # the program around those blocks: what it is expected to need, and what
    # the kernel will therefore ask the compiler for at this width
    scoped = gru_scoped_bytes(plan, t, act_itemsize)
    limit = gru_vmem_limit(plan, t, act_itemsize)
    if scoped > VMEM_CEILING_BYTES:
        checks.append(f"gru kernel's program needs about {scoped} B of "
                      f"scoped VMEM (> {VMEM_CEILING_BYTES}); shrink "
                      f"gru_block_rows")
    return {"active": active, "block_bytes": bytes_,
            "scoped_bytes": scoped, "vmem_limit": limit,
            "vmem_bytes": vmem_bytes,
            "fits": bytes_ <= vmem_bytes and scoped <= VMEM_CEILING_BYTES,
            "motion_dim": motion_dim, "plan": dataclasses.asdict(plan),
            "checks": checks}


# ---------------------------------------------------------------------------
# eval_shape memory model (jax imported lazily from here down).
# ---------------------------------------------------------------------------

def bytes_of(spec) -> int:
    """Device bytes of one abstract array (anything with .shape/.dtype)."""
    import numpy as np
    n = 1
    for d in spec.shape:
        n *= int(d)
    return n * np.dtype(spec.dtype).itemsize


def tree_bytes(tree) -> int:
    import jax
    return sum(bytes_of(leaf) for leaf in jax.tree.leaves(tree))


def _resolved_config(config, sconfig):
    if sconfig.iters_policy is not None:
        config = dataclasses.replace(config,
                                     iters_policy=sconfig.iters_policy)
    return config


def param_specs(config):
    """Abstract shapes/dtypes of the full parameter tree — eval_shape over
    the real initializer, so a variant or dtype change flows through."""
    import jax

    from ..config import init_rng
    from ..models.raft import init_raft
    specs = jax.eval_shape(lambda k: init_raft(k, config), init_rng(0))
    if config.quant_weights:
        # quant='bf16w': the engine stores the fnet/cnet encoder weights
        # in bf16 (models/raft.cast_encoder_weights) — price them that way
        import jax.numpy as jnp

        def bf16(s):
            return (jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                    if s.dtype == jnp.float32 else s)
        specs = dict(specs)
        for k in ("fnet", "cnet"):
            if k in specs:
                specs[k] = jax.tree.map(bf16, specs[k])
    return specs


def _motion_dim(pspecs, config) -> int:
    """Motion-feature channel count, derived from the gate-conv input
    width exactly as the kernels derive it (hx = [h, ctx, motion])."""
    gru = pspecs["update_block"]["gru"]
    conv = gru.get("convz1", gru.get("convz"))
    return int(conv["w"].shape[2]) - config.hidden_dim - config.context_dim


def kind_footprint(programs, key: Key) -> dict:
    """Per-executable device-memory footprint of ``key``, priced from the
    engine's own table of kinds (``programs``, a ``serving.engine.Programs``
    built over abstract params): the arguments ``engine._compile_traced``
    lowers and the outputs ``jax.eval_shape`` gives them, counters and all.

    ``transient_bytes`` is what one call of this executable holds LIVE
    beyond the steady-state residents (params + pool buffers): its
    non-resident inputs plus its outputs, with donated buffers aliased
    away (a scommit's output pool buffers reuse the donated inputs'
    memory off-CPU; on the CPU backend donation is off and the scatter
    really is a copy — a table built with ``donate=False`` models that).
    A ``pair`` call is priced with a second set of inputs and outputs
    (``staged_bytes``) beside the running one: the batcher places the next
    batch while this one runs, and dispatches it before it has fetched
    this one's flow (serving/batcher.py).
    """
    import jax

    kind, h, w = key[:3]
    prog = programs.program(key)
    out = jax.eval_shape(prog.fn, *prog.specs)
    in_b = tree_bytes([s for i, s in enumerate(prog.specs)
                       if i not in prog.resident
                       and s is not programs.params])
    out_b = tree_bytes(out)
    don_b = tree_bytes([prog.specs[i] for i in prog.donated])
    staged = in_b + out_b if kind == "pair" else 0
    if kind == "szero":
        # builds the resident pool buffers themselves: nothing transient
        transient = 0
    else:
        transient = in_b + max(0, out_b - don_b) + staged
    return {"key": list(key), "input_bytes": in_b, "output_bytes": out_b,
            "donated_bytes": don_b, "staged_bytes": staged,
            "transient_bytes": transient,
            "pool_bytes": tree_bytes(programs.slot_specs(h, w))
            if prog.resident or kind == "szero" else 0}


#: Temporaries of one pair executable per input pixel and pair: what the
#: compiler keeps in HBM while the program runs (encoder activations, the
#: update loop's carries, the upsampling mask), for the full model in
#: bfloat16 with both Pallas kernels, which never build a correlation
#: volume.  From the chip compiler's ``memory_analysis()`` of the served
#: programs (sandbox compiles for a described v5e): 5.63 GB at 32 x 440x1024
#: (PR 23) and 6.48 GB at 8 x 1080x1920 (PR 26) are 390.5 and 390.6 bytes.
#: Re-read in PR 29, whose lookup no longer returns a padded float32 window
#: (57.7 MB a pair and level at 440x1024): 5,631,585,280 and 6,479,531,008
#: bytes, 390.60 both.  The figure did not move because the program's peak
#: lies in the encoders; the update loop's buffers fit under it.
PAIR_TEMP_BYTES_PER_PIXEL = 391


#: The same for RAFT-S (``config.small``; bfloat16, Pallas lookup, the 3x3
#: ConvGRU as XLA convolutions), per input pixel and pair where nothing is
#: padded.  Its peak also lies in the feature encoder, at HALF resolution:
#: the stem's and layer1's ``bf16[2B, H/2, W/2, 32]`` maps of both frames
#: (and the bottleneck's 8-channel ones), three or so alive at once.  The
#: chip stores such a map with either its 32 channels or its 2B images in
#: the 128 lanes (:func:`small_lane_padding`), so the bytes a pixel depend
#: on the batch: ``memory_analysis().temp_size_in_bytes`` of the served
#: programs (sandbox compiles for a described v5e, PR 31) read 102.2 B a
#: pixel at 64 x 440x1024 (128 images fill the lanes: the figure of PERF.md's
#: older note), 198.6 at 32 x 440x1024, 390.2 at 16 x 1080x1920 and 422.5 at
#: 8 x 1080x1920 (7,007,982,080 B, the benchmark's cell); 424.1 at 8 x
#: 440x1024 is the largest of seven readings.  107 prices them all from
#: above: +4.7, +7.8, +9.7 and +1.3 % for the four named here.
SMALL_PAIR_TEMP_BYTES_PER_PIXEL = 107
_SMALL_STEM_CHANNELS = 32


def small_lane_padding(b: int) -> float:
    """How much larger than its contents RAFT-S's widest temporary is on the
    chip at batch ``b``: the compiler puts the smaller padding of the two in
    the 128 lanes, the 32 channels (4 x) or the ``2 b`` images of both
    frames (up to the next multiple of 128)."""
    images = 2 * b
    by_batch = -(-images // LANE) * LANE / images
    return min(LANE / _SMALL_STEM_CHANNELS, by_batch)


def pair_temp_bytes(config, h: int, w: int, b: int) -> Optional[int]:
    """HBM temporaries of the ``pair`` executable at ``b`` x ``h`` x ``w``,
    or None for a program no figure was taken from (a float32 one, a lookup
    that stores its volume, the full model without its GRU kernel): not
    priced beats priced wrong.  Which model it is the code can see
    (``config.small``); each has the figure read from its own served
    program."""
    if config.compute_dtype != "bfloat16" or config.corr_impl != "pallas":
        return None
    if config.small:
        return int(SMALL_PAIR_TEMP_BYTES_PER_PIXEL * small_lane_padding(b)
                   * b * h * w)
    if config.gru_impl != "pallas":
        return None
    return PAIR_TEMP_BYTES_PER_PIXEL * b * h * w


def stream_limit(device_key: str) -> int:
    """The bytes the server's own admission (``serving/admission.py``: the
    server decides, the analyzer reads what it decided) lets the stream
    path reach on a chip of ``DEVICE_BUDGETS``: ``STREAM_BOUND_SHARE`` of
    what its runtime hands out (of the table's figure where that has not
    been read)."""
    from ..serving.admission import STREAM_BOUND_SHARE, USABLE_HBM_BYTES
    usable = USABLE_HBM_BYTES.get(device_key,
                                  DEVICE_BUDGETS[device_key]["hbm_bytes"])
    return int(STREAM_BOUND_SHARE * usable)


def config_signature(config, sconfig, stream: bool, chaos: bool) -> dict:
    """What the committed-baseline comparison keys on: every knob that
    changes the compile surface or the footprint model."""
    from ..serving.config import resolved_policy
    return {
        "small": config.small,
        "compute_dtype": config.compute_dtype,
        "quant": config.quant,
        "buckets": [list(b) for b in sconfig.buckets],
        "batch_steps": list(sconfig.batch_steps),
        "max_sessions": sconfig.max_sessions,
        "stream": stream,
        "chaos": chaos,
        "policy": resolved_policy(config, sconfig),
        "ragged": bool(getattr(sconfig, "ragged", False)),
    }


def analyze(config, sconfig, device_kind: Optional[str] = None,
            stream: Optional[bool] = None, chaos: Optional[bool] = None,
            donation: Optional[bool] = None) -> dict:
    """The full static capacity report (the BUDGET.json payload).

    ``device_kind`` is a DEVICE_BUDGETS key; left None it is derived from
    the device this process runs on (:func:`budget_key`).  ``donation``
    defaults to the device kind's behavior: the engine turns
    buffer donation off on the CPU backend, so the cpu model counts the
    scatter outputs as real copies.
    """
    import jax  # fail here, loudly, if jax is unavailable

    from ..serving.config import enumerate_warmup_grid
    from ..serving.engine import Programs

    if device_kind is None:
        device_kind = budget_key(jax.devices()[0].device_kind)
    if device_kind not in DEVICE_BUDGETS:
        raise ValueError(f"unknown device kind {device_kind!r}; "
                         f"options: {sorted(DEVICE_BUDGETS)}")
    budget = DEVICE_BUDGETS[device_kind]
    if stream is None:
        stream = sconfig.max_sessions > 0
    if chaos is None:
        chaos = sconfig.chaos is not None
    if donation is None:
        donation = device_kind != "cpu"
    rconfig = _resolved_config(config, sconfig)
    ragged = bool(getattr(sconfig, "ragged", False))
    keys = enumerate_warmup_grid(rconfig, sconfig, stream=stream,
                                 chaos=chaos)
    capacity = max(1, sconfig.max_sessions)
    pspecs = param_specs(rconfig)
    programs = Programs(rconfig, pspecs, capacity, ragged=ragged,
                        donate=donation)
    params_b = tree_bytes(pspecs)
    motion = _motion_dim(pspecs, rconfig)

    by_kind: Dict[str, int] = {}
    for k in keys:
        by_kind[k[0]] = by_kind.get(k[0], 0) + 1

    buckets = []
    resident = params_b
    peak_transient = 0
    peak_pair_temp = 0        # stays 0 where pair_temp_bytes prices nothing
    session_row_b = 0
    violations: List[str] = []
    # ragged: exactly ONE pool arena (and one executable family) exists,
    # at the max box — pricing each declared bucket would multiply the
    # resident pool by a factor that never materializes on the device
    a_buckets = ([tuple(sconfig.max_box)] if ragged
                 else [tuple(b) for b in sconfig.buckets])
    for (bh, bw) in a_buckets:
        pool = programs.slot_specs(bh, bw)
        pool_b = tree_bytes(pool)
        row_b = sum(bytes_of(s) // (capacity + 1)
                    for s in jax.tree.leaves(pool))
        kinds = [kind_footprint(programs, k)
                 for k in keys if (k[1], k[2]) == (bh, bw)]
        bucket_peak = max((f["transient_bytes"] for f in kinds), default=0)
        peak_transient = max(peak_transient, bucket_peak)
        temps = [pair_temp_bytes(rconfig, bh, bw, k[3]) for k in keys
                 if k[0] == "pair" and (k[1], k[2]) == (bh, bw)]
        if temps and None not in temps:
            peak_pair_temp = max(peak_pair_temp, max(temps))
        if stream:
            resident += pool_b
            session_row_b += row_b
        corr_env = corr_vmem_envelope(rconfig, (bh, bw),
                                      budget["vmem_bytes"])
        gru_env = gru_vmem_envelope(rconfig, (bh, bw), motion,
                                    budget["vmem_bytes"])
        for env, name in ((corr_env, "corr_pallas"), (gru_env,
                                                      "gru_pallas")):
            if env["active"] and not env["fits"]:
                violations.append(f"{name} @ {bh}x{bw}: " +
                                  "; ".join(env["checks"]))
        buckets.append({
            "bucket": [bh, bw],
            "pool_bytes": pool_b if stream else 0,
            "per_session_bytes": row_b if stream else 0,
            "peak_transient_bytes": bucket_peak,
            "kinds": kinds,
            "pallas": {"corr": corr_env, "gru": gru_env},
        })

    peak = resident + peak_transient
    headroom = budget["hbm_bytes"] - peak
    stream_peak_b = stream_sessions_fit = None
    max_sessions_fit = None
    if stream and session_row_b > 0:
        # resident(S) = params + sum_b (S+1) * row_b; solve the largest S
        # with resident(S) + peak_transient <= hbm (transient is
        # S-independent: pool buffers enter calls as residents)
        free = (budget["hbm_bytes"] - params_b - peak_transient
                - session_row_b)                       # the scratch rows
        max_sessions_fit = max(0, free // session_row_b)
        # and with the stream path's fullest moment (stream_peak): beside
        # the pool everything it holds is the same for every capacity
        from ..serving.admission import STREAM_BOUND_SHARE, stream_peak
        limit = stream_limit(device_kind)
        stream_peak_b, pool_b, _ = stream_peak(programs, sconfig)
        stream_sessions_fit = max(
            0, (limit - (stream_peak_b - pool_b)) // session_row_b - 1)
        if stream_peak_b > limit:
            violations.append(
                f"the slot pool ({pool_b} B) and the stream programs beside "
                f"it hold {stream_peak_b} B, over {limit} B "
                f"({100 * STREAM_BOUND_SHARE:.0f} % of the {device_kind}'s "
                f"usable memory): at most {stream_sessions_fit} session(s) "
                f"fit beside them")
        if sconfig.max_sessions > max_sessions_fit:
            violations.append(
                f"max_sessions={sconfig.max_sessions} does not fit "
                f"{device_kind}: at most {max_sessions_fit} session(s) "
                f"leave room for params + peak call buffers")
    if headroom < 0:
        violations.append(
            f"estimated peak {peak} B exceeds the {device_kind} HBM "
            f"budget {budget['hbm_bytes']} B by {-headroom} B")

    return {
        "version": 1,
        "device_kind": device_kind,
        "donation": donation,
        "config_signature": config_signature(rconfig, sconfig, stream,
                                             chaos),
        "grid": {"size": len(keys), "by_kind": by_kind,
                 "keys": [list(k) for k in keys]},
        "params_bytes": params_b,
        "buckets": buckets,
        "totals": {
            "resident_bytes": resident,
            "peak_transient_bytes": peak_transient,
            "peak_bytes": peak,
            # with the largest pair executable's temporaries on top, which
            # is what a batch server's chip holds at its fullest (7.57 GB
            # against 7.54-7.60 read at 1080x1920, batch 8; 6.58 against
            # 6.54-6.58 at 440x1024, batch 32: PERF.md, PR 27)
            "peak_with_pair_temps_bytes": (peak + peak_pair_temp
                                           if peak_pair_temp else None),
            # the stream path's fullest moment: the pool, a batched step
            # with its temporaries, the frames staged behind it and the
            # outputs being committed (stream_footprint)
            "peak_with_stream_temps_bytes": stream_peak_b,
            "max_sessions_fit_stream": stream_sessions_fit,
            "hbm_budget_bytes": budget["hbm_bytes"],
            "headroom_bytes": headroom,
            "per_session_bytes": session_row_b or None,
            "max_sessions_fit": max_sessions_fit,
        },
        "violations": violations,
    }
