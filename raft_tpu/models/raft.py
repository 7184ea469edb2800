"""RAFT: the full model, TPU-first.

Re-design of reference networks/RAFT.py:78-134 (``network_graph``):

* the 20x statically-unrolled update loop (reference RAFT.py:91, which copies
  the graph 20 times) becomes a single ``jax.lax.scan`` over iterations, with
  optional per-iteration rematerialization for training memory;
* every iteration's *upsampled* flow is emitted for the sequence loss — the
  reference discarded intermediates (RAFT.py:109, SURVEY.md §3.6 capability
  gap);
* iteration count, batch and resolution are free (fixing reference
  readme.md:13 and the frozen placeholder shapes at RAFT.py:45-51);
* correlation can run dense, blockwise (on-demand), or via the fused Pallas
  kernel (config.corr_impl).

Inputs are float images in [0, 1], NHWC; channel order must match the loaded
weights (reference preprocessing: RAFT.py:53-59, BGR note at RAFT.py:13; the
CLI and converter handle the RGB/BGR stem swap).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import RAFTConfig, parse_iters_policy
from ..lint.contracts import contract
from ..ops import spmd
from ..ops.coords import coords_grid, upflow8
from ..ops.corr import (as_precision, build_pyramid, fmap2_pyramid,
                        lookup_blockwise_onehot, lookup_dense,
                        lookup_dense_onehot, lookup_ondemand,
                        mask_ragged_rows, ragged_pyramid)
from ..ops.upsample import convex_upsample_flow
from ..telemetry.trace import stage
from ..telemetry.watchdogs import nan_guard
from .encoders import apply_encoder, init_encoder
from .update import (apply_basic_update_block, apply_small_update_block,
                     init_basic_update_block, init_small_update_block,
                     precompute_gru_ctx)


class RAFTOutput(NamedTuple):
    flow: jax.Array                      # [B, H, W, 2] final full-res flow
    flow_iters: Optional[jax.Array]      # [iters, B, H, W, 2] or None
    flow_lr: jax.Array                   # [B, H/8, W/8, 2] final low-res flow
    # [B] int32: GRU iterations each sample actually spent ACTIVE (= `iters`
    # under iters_policy='fixed'; < iters for samples that hit the converge
    # early-exit).  Under early exit, flow_iters entries past iters_used[b]
    # repeat sample b's frozen flow — never stale intermediates — so the
    # sequence loss and --dump-flow stay correct.
    iters_used: Optional[jax.Array] = None
    # int32 [visited, possible, tiles, steps, stored, live]: over every
    # iteration run, the (query tile, band of key rows) grid steps the fused
    # correlation kernel did work in, what a walk of every band would take,
    # the (query tile, level) pairs it looked up, the grid steps its launches
    # took, and the key positions those steps multiplied over as stored and
    # as the maps hold them
    # (ops/corr_pallas.schedule_keyblocks) — None off the dense Pallas
    # lookup.
    corr_keyblocks: Optional[jax.Array] = None


def _validate_loop_config(config: RAFTConfig):
    """Validate every update-loop knob up front (no-silent-fallback
    contract: a typo'd policy/impl raises, never quietly runs the other
    implementation) and reject unsupported sharding combinations BEFORE
    any compute traces.  Shared by :func:`raft_forward` and
    :func:`_iterate_flow` (the feature-reuse entries).  Returns the
    parsed ``(policy, eps, min_iters)``."""
    policy, eps, min_iters = parse_iters_policy(config.iters_policy)
    if policy == "converge" and spmd.spatial_axis() is not None:
        raise NotImplementedError(
            "iters_policy='converge:...' under row-sharded (spatial) "
            "execution is not wired: each shard would measure ‖Δflow‖ on "
            "its local slab only and freeze samples at different "
            "iterations; use iters_policy='fixed'.")
    if config.gru_impl not in ("xla", "pallas"):
        # same silent-fallback hazard as corr_lookup: a typo must not
        # quietly run the other GRU implementation
        raise ValueError(f"gru_impl must be 'xla' or 'pallas', "
                         f"got {config.gru_impl!r}")
    if config.gru_impl == "pallas" and config.small:
        raise ValueError(
            "gru_impl='pallas' covers the full model's SepConvGRU; the "
            "small variant's 3x3 ConvGRU has no hand kernel — use "
            "gru_impl='xla'.")
    if config.gru_impl == "pallas" and spmd.spatial_axis() is not None:
        raise NotImplementedError(
            "gru_impl='pallas' under row-sharded (spatial) execution is not "
            "wired: the kernel's row halo does not exchange across shards; "
            "use gru_impl='xla' (conv2d halo-exchanges automatically).")
    if config.corr_lookup not in ("gather", "onehot"):
        # validated for every impl, not just dense — a typo must not fall
        # back silently to the gather path
        raise ValueError(f"corr_lookup must be 'gather' or 'onehot', "
                         f"got {config.corr_lookup!r}")
    if config.corr_precision not in ("highest", "default"):
        # same silent-fallback hazard: a typo must not quietly degrade the
        # corr matmuls to bf16 MXU inputs
        raise ValueError(f"corr_precision must be 'highest' or 'default', "
                         f"got {config.corr_precision!r}")
    if config.scan_unroll < 1:
        raise ValueError(f"scan_unroll must be >= 1, got {config.scan_unroll}")
    return policy, eps, min_iters


def init_raft(key: jax.Array, config: RAFTConfig) -> Dict[str, dict]:
    kf, kc, ku = jax.random.split(key, 3)
    corr_dim = config.corr_feature_dim
    if config.small:
        return {
            "fnet": init_encoder(kf, config.fnet_dim, "instance", small=True),
            "cnet": init_encoder(kc, config.cnet_dim, "none", small=True),
            "update_block": init_small_update_block(
                ku, corr_dim, config.hidden_dim, config.context_dim),
        }
    return {
        "fnet": init_encoder(kf, config.fnet_dim, "instance", small=False),
        "cnet": init_encoder(kc, config.cnet_dim, "batch", small=False),
        "update_block": init_basic_update_block(
            ku, corr_dim, config.hidden_dim, config.context_dim),
    }


def _preprocess(image: jax.Array, config: RAFTConfig) -> jax.Array:
    # [0,1] -> [-1,1] (reference RAFT.py:53-59)
    x = 2.0 * image - 1.0
    if config.compute_dtype == "bfloat16":
        x = x.astype(jnp.bfloat16)
    return x


@contract(image1="*[B,H,W,3]", image2="*[B,H,W,3]",
          flow_init="*[B,HL,WL,2]")
def raft_forward(params: Dict[str, dict], image1: jax.Array, image2: jax.Array,
                 config: RAFTConfig, iters: Optional[int] = None,
                 train: bool = False, axis_name: Optional[str] = None,
                 flow_init: Optional[jax.Array] = None,
                 all_flows: Optional[bool] = None,
                 rng: Optional[jax.Array] = None,
                 freeze_bn: bool = False,
                 sizes: Optional[jax.Array] = None
                 ) -> Tuple[RAFTOutput, Dict[str, dict]]:
    """Run RAFT; returns (output, params-with-updated-BN-stats).

    all_flows defaults to ``train`` — training needs every iteration's
    upsampled flow for the sequence loss; inference only the last.

    ``sizes`` ([B, 2] int32, optional) switches on RAGGED mixed-resolution
    mode: each item is a corner-anchored ``(h_b, w_b)`` crop living in the
    shared ``[H, W]`` max box, correlation runs the ragged page-scheduled
    path (one executable for every declared resolution), and the images are
    re-masked in-graph so dead regions are deterministic zeros whatever the
    caller embedded.  Output rows are valid inside each item's crop; the
    caller slices ``flow[b, :h_b, :w_b]``.  None = the dense paths,
    bit-for-bit unchanged.

    ``freeze_bn`` (only meaningful with ``train=True``) runs batch norm in
    eval mode — running statistics used and not updated — while everything
    else trains: the official finetune recipe (freeze_bn() for every stage
    after chairs; TrainConfig.for_stage wires it).  Affine BN parameters
    keep receiving gradients, matching torch ``.eval()`` semantics.
    """
    iters = config.iters if iters is None else iters
    all_flows = train if all_flows is None else all_flows
    cnet_norm = "none" if config.small else "batch"
    # full config validation BEFORE the encoders: a typo'd policy/impl (or
    # an unsupported sharding combination) must raise here, not after the
    # fnet has already traced under a sharded context
    policy_spec = _validate_loop_config(config)

    orig_params = params
    params = _cast_params(params, config)

    B, H, W, _ = image1.shape
    if H % 8 or W % 8:
        raise ValueError(
            f"RAFT requires H and W divisible by 8, got {(H, W)}; pad or "
            f"resize the inputs (see data.pipeline.pad_to_multiple).")
    if image2.shape != image1.shape:
        raise ValueError(f"image shapes differ: {image1.shape} vs {image2.shape}")
    if sizes is not None:
        # dead regions become exact zeros regardless of what the caller
        # embedded — every downstream value is then a deterministic function
        # of (crop pixels, sizes), the batch-independence contract the
        # ragged serving equality tests rely on
        image1 = mask_ragged_rows(image1, sizes)
        image2 = mask_ragged_rows(image2, sizes)

    rngs = jax.random.split(rng, 2) if rng is not None else (None, None)
    with stage("raft/preprocess"):
        x1 = _preprocess(image1, config)
        x2 = _preprocess(image2, config)
        # Shared-weight feature encoder on both frames (reference
        # RAFT.py:79-80): batch the two frames through one encoder call so
        # XLA sees 2B-sized convs.
        x12 = jnp.concatenate([x1, x2], axis=0)
    with stage("raft/fnet"):
        fmaps, _ = apply_encoder(params["fnet"], x12, "instance",
                                 small=config.small,
                                 train=train, axis_name=axis_name,
                                 dropout=config.dropout, rng=rngs[0])
    fmaps = nan_guard(fmaps, "raft/fnet")
    fmap1, fmap2 = fmaps[:B], fmaps[B:]

    with stage("raft/cnet"):
        cnet, new_cnet_params = apply_encoder(
            params["cnet"], x1, cnet_norm, small=config.small, train=train,
            axis_name=axis_name, dropout=config.dropout, rng=rngs[1],
            bn_train=train and not freeze_bn)
        net = jnp.tanh(cnet[..., :config.hidden_dim])
        inp = jax.nn.relu(cnet[..., config.hidden_dim:])

    sizes8 = None if sizes is None else sizes.astype(jnp.int32) // 8
    out = _iterate_flow(params, fmap1, fmap2, net, inp, config,
                        iters=iters, train=train, all_flows=all_flows,
                        flow_init=flow_init, policy_spec=policy_spec,
                        sizes8=sizes8)

    new_params = dict(orig_params)
    if train and not config.small and not freeze_bn:
        # BN running stats updated in the cnet; restore original leaf dtypes.
        # Under freeze_bn the ORIGINAL tree is returned untouched — the
        # cast-down/cast-up round trip would otherwise bake bf16 rounding
        # (~0.4% relative) into the frozen stats under
        # compute_dtype='bfloat16', violating the left-untouched contract.
        new_params["cnet"] = jax.tree.map(
            lambda new, old: new.astype(old.dtype),
            new_cnet_params, orig_params["cnet"])
    return out, new_params


def _iterate_flow(params, fmap1: jax.Array, fmap2: jax.Array,
                  net: jax.Array, inp: jax.Array, config: RAFTConfig,
                  iters: int, train: bool, all_flows: bool,
                  flow_init: Optional[jax.Array],
                  policy_spec=None,
                  active: Optional[jax.Array] = None,
                  sizes8: Optional[jax.Array] = None) -> RAFTOutput:
    """The recurrent core of RAFT, from encoder features to flow.

    Shared by :func:`raft_forward` (which computes the features) and
    :func:`forward_from_features` (which receives them precomputed — the
    streaming serving path caches the previous frame's maps so each new
    frame costs one encoder pass).  ``params`` must already carry the
    compute-dtype cast; ``fmap1``/``fmap2`` are fnet outputs in any dtype
    (correlation accumulates in float32), ``net``/``inp`` the split
    context activations at the 1/8 grid.  ``policy_spec`` is the parsed
    ``(policy, eps, min_iters)`` from :func:`_validate_loop_config` —
    public entries validate once, before their encoders, and pass it
    down; None validates here (direct/test callers).

    ``active`` ([B] bool, optional) marks real rows in a slot-padded
    batch (the batched streaming step): inactive rows start CONVERGED
    under an adaptive policy — they never prolong the whole-batch
    while_loop and report ``iters_used == 0`` — and their outputs are
    discarded by the caller.  None (the default) = all rows real, and
    every existing path is bit-for-bit unchanged.

    ``sizes8`` ([B, 2] int32, optional) selects RAGGED mixed-resolution
    correlation: per-item live (h, w) extents at the 1/8 query grid, items
    corner-anchored in the shared max box.  corr_impl='pallas' rides the
    page-scheduled ragged kernel; 'dense'/'blockwise' ride its exact XLA
    twin (masked max-box streams through ``lookup_blockwise_onehot``).
    """
    policy, eps, min_iters = (policy_spec if policy_spec is not None
                              else _validate_loop_config(config))
    adaptive = policy == "converge"
    if config.small:
        update_fn = apply_small_update_block
    else:
        update_fn = functools.partial(apply_basic_update_block,
                                      gru_impl=config.gru_impl,
                                      gru_block_rows=config.gru_block_rows)
    cdt = jnp.bfloat16 if config.compute_dtype == "bfloat16" else jnp.float32
    B, h, w, _ = fmap1.shape

    # correlation products exact, accumulated in float32 (numerics policy):
    # the XLA paths multiply float32 casts; the Pallas kernels take the maps
    # in the dtype the encoder produced and choose their MXU passes from it
    # (ops/corr_pallas.corr_terms)
    fmap1c = fmap1.astype(jnp.float32)
    fmap2c = fmap2.astype(jnp.float32)

    corr_prec = as_precision(config.corr_precision)
    counts_keyblocks = False      # only the dense Pallas lookup has them

    if sizes8 is not None:
        # ragged mixed-resolution batch: ONE lookup closure serves every
        # declared crop of the max box (page-scheduled Pallas kernel, or its
        # exact masked XLA twin off-kernel)
        if spmd.spatial_axis() is not None:
            raise NotImplementedError(
                "ragged mixed-resolution batches under row-sharded (spatial) "
                "execution are not wired: per-item page schedules would "
                "straddle shard slabs; use the dense bucket path.")
        if config.corr_impl == "pallas":
            try:
                from ..ops.corr_pallas import make_ragged_fused_lookup
            except ImportError as e:
                raise NotImplementedError(
                    "corr_impl='pallas' requires ops/corr_pallas.py (the "
                    "fused TPU kernel); use 'dense' or 'blockwise'.") from e
            lookup = make_ragged_fused_lookup(
                fmap1, fmap2, sizes8, config.corr_levels,
                config.corr_radius, corr_precision=corr_prec,
                q_blk=config.pallas_q_blk, p_blk_target=config.pallas_p_blk,
                out_dtype=cdt)
        else:
            # 'dense' and 'blockwise' share the masked blockwise twin — the
            # dense (HW)^2 volume has no ragged form worth building, and the
            # twin is the kernel's own correctness reference
            f1m = mask_ragged_rows(fmap1c, sizes8)
            f2_levels = ragged_pyramid(fmap2c, sizes8, config.corr_levels)
            lookup = functools.partial(lookup_blockwise_onehot, f1m,
                                       f2_levels, radius=config.corr_radius,
                                       precision=corr_prec)
    elif spmd.spatial_axis() is not None:
        # row-sharded run (make_shard_inference_fn): correlation must see the
        # full fmap2, which lives sharded across devices -> ring pass; with
        # corr_impl='pallas' each slab's partial rides the fused kernel
        from ..parallel.spatial import make_ring_lookup_local
        if config.corr_impl == "pallas":
            try:
                from ..ops import corr_pallas  # noqa: F401 — availability check
            except ImportError as e:
                raise NotImplementedError(
                    "corr_impl='pallas' requires ops/corr_pallas.py (the "
                    "fused TPU kernel); use 'dense' or 'blockwise'.") from e
        lookup = make_ring_lookup_local(
            fmap1c, fmap2c, config.corr_levels, config.corr_radius,
            spmd.spatial_axis(), precision=corr_prec,
            kernel="pallas" if config.corr_impl == "pallas" else "onehot",
            pallas_opts=dict(q_blk=config.pallas_q_blk,
                             p_blk_target=config.pallas_p_blk))
    elif config.corr_impl == "dense":
        lookup_fn = (lookup_dense_onehot if config.corr_lookup == "onehot"
                     else lookup_dense)
        with stage("raft/corr_pyramid"):
            pyramid = build_pyramid(fmap1c, fmap2c, config.corr_levels,
                                    precision=corr_prec)
        lookup = functools.partial(lookup_fn, pyramid, radius=config.corr_radius)
    elif config.corr_impl == "blockwise":
        f2_levels = fmap2_pyramid(fmap2c, config.corr_levels)
        if config.corr_lookup == "onehot":
            lookup = functools.partial(lookup_blockwise_onehot, fmap1c,
                                       f2_levels, radius=config.corr_radius,
                                       precision=corr_prec)
        else:
            lookup = functools.partial(lookup_ondemand, fmap1c, f2_levels,
                                       radius=config.corr_radius,
                                       precision=corr_prec)
    elif config.corr_impl == "pallas":
        try:
            from ..ops.corr_pallas import make_fused_lookup
        except ImportError as e:
            raise NotImplementedError(
                "corr_impl='pallas' requires ops/corr_pallas.py (the fused "
                "TPU kernel); use 'dense' or 'blockwise'.") from e
        with stage("raft/corr_pyramid"):      # fmap2's pooled levels
            lookup = make_fused_lookup(
                fmap1, fmap2, config.corr_levels, config.corr_radius,
                corr_precision=corr_prec, q_blk=config.pallas_q_blk,
                p_blk_target=config.pallas_p_blk, out_dtype=cdt)
        counts_keyblocks = True
    else:
        raise ValueError(config.corr_impl)

    coords0 = coords_grid(B, h, w)
    if spmd.spatial_axis() is not None:
        # local slab -> global pixel coordinates (queries address the global
        # correlation plane)
        off = jax.lax.axis_index(spmd.spatial_axis()) * h
        coords0 = coords0.at[..., 1].add(off.astype(coords0.dtype))
    coords1 = coords0 if flow_init is None else coords0 + flow_init

    def upsample(flow_lr: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
        if config.small:
            return upflow8(flow_lr.astype(jnp.float32), rescale=True)
        return convex_upsample_flow(flow_lr.astype(jnp.float32),
                                    mask.astype(jnp.float32))

    gru_ctx = None
    if config.gru_ctx_hoist or config.gru_impl == "pallas":
        # context terms of the gate convs are iteration-invariant: one conv
        # each here instead of a third of every in-loop gate contraction.
        # gru_impl='pallas' requires them regardless of the hoist flag (the
        # fused kernel never contracts the context channels in-loop).
        with stage("raft/gru_ctx"):
            gru_ctx = precompute_gru_ctx(params["update_block"]["gru"], inp,
                                         config.hidden_dim,
                                         small=config.small)

    kb0 = jnp.zeros((6,), jnp.int32)

    def gru_step(net, coords1):
        """One GRU update — shared by every loop form below.  Returns the
        updated (net, coords1, mask), the per-sample mean L2 norm of the
        flow update at the 1/8 grid (the converge-policy criterion) and the
        lookup's band counts (zeros where it has none)."""
        coords1 = jax.lax.stop_gradient(coords1)   # reference RAFT.py:93 / official
        kb = kb0
        with stage("raft/corr_lookup"):
            if counts_keyblocks:
                # the schedules the kernels are given are the ones counted;
                # the kernels write cdt themselves (out_dtype above), so the
                # cast is for the lookups that do not
                sched = lookup.schedules(coords1)
                corr = lookup(coords1, sched).astype(cdt)
                kb = lookup.keyblocks(sched)
            else:
                corr = lookup(coords=coords1).astype(cdt)
        corr = nan_guard(corr, "raft/corr_lookup")
        flow = (coords1 - coords0).astype(cdt)
        with stage("raft/update"):
            net, mask, delta_flow = update_fn(params["update_block"], net, inp,
                                              corr, flow, gru_ctx=gru_ctx)
        delta_flow = nan_guard(delta_flow, "raft/update")
        coords1 = coords1 + delta_flow.astype(jnp.float32)
        dn = jnp.sqrt(jnp.sum(jnp.square(delta_flow.astype(jnp.float32)),
                              axis=-1)).mean(axis=(1, 2))        # [B]
        return net, coords1, mask, dn, kb

    def emit(coords1, mask):
        if not all_flows:
            return None
        with stage("raft/upsample"):
            return upsample(coords1 - coords0, mask)

    mask0 = None if config.small else jnp.zeros((B, h, w, 64 * 9), cdt)

    if not adaptive:
        # -- fixed policy: the plain scan, structurally unchanged ---------
        def step(carry, _):
            net, coords1, _, kbs = carry
            net, coords1, mask, _, kb = gru_step(net, coords1)
            return (net, coords1, mask, kbs + kb), emit(coords1, mask)

        if config.remat_iters and train:
            step = jax.checkpoint(step)

        (net, coords1, mask, kbs), ys = jax.lax.scan(
            step, (net, coords1, mask0, kb0), None, length=iters,
            unroll=min(config.scan_unroll, iters))
        iters_used = jnp.full((B,), iters, jnp.int32)
        if active is not None:             # padding rows spent nothing real
            iters_used = jnp.where(active, iters_used, 0)
    else:
        # -- converge policy: per-sample masked freeze, static shapes -----
        # A sample whose update norm drops below eps is FROZEN: its carry
        # entries (net, coords, mask) keep their current values through all
        # remaining iterations, so later emitted flows repeat the frozen
        # flow exactly.  Shapes never depend on the data — one executable
        # serves every difficulty mix (raftlint R2 discipline).
        def masked_iter(i, net, coords1, mask, converged, nused, kbs):
            active = ~converged                                    # [B]
            net2, coords2, mask2, dn, kb = gru_step(net, coords1)

            def keep(new, old):
                a = active.reshape((B,) + (1,) * (new.ndim - 1))
                return jnp.where(a, new, old)

            net = keep(net2, net)
            coords1 = keep(coords2, coords1)
            mask = keep(mask2, mask) if mask2 is not None else None
            # eps=0 never fires (a norm is never < 0): bit-exact 'fixed'
            converged = converged | (active & (dn < eps)
                                     & (i + 1 >= min_iters))
            nused = nused + active.astype(jnp.int32)
            return net, coords1, mask, converged, nused, kbs + kb

        # padding rows of a slot-batched step start converged: they can
        # never extend the while_loop past the hardest REAL sample, and
        # nused stays 0 for them (the padding-exclusion contract the
        # serving metrics rely on)
        conv0 = (jnp.zeros((B,), bool) if active is None else ~active)
        used0 = jnp.zeros((B,), jnp.int32)
        if train or all_flows:
            # differentiable form: masked scan over all `iters` iterations
            # (reverse-mode through while_loop is undefined); frozen
            # samples cost no numerics change, and remat/unroll compose
            # exactly as for 'fixed'
            def step(carry, i):
                carry = masked_iter(i, *carry)
                return carry, emit(carry[1], carry[2])

            if config.remat_iters and train:
                step = jax.checkpoint(step)

            (net, coords1, mask, _, iters_used, kbs), ys = jax.lax.scan(
                step, (net, coords1, mask0, conv0, used0, kb0),
                jnp.arange(iters), unroll=min(config.scan_unroll, iters))
        else:
            # inference fast path: whole-batch early exit — the loop stops
            # as soon as EVERY sample has converged (or at `iters`), so
            # wall-clock tracks the hardest sample in the batch, not the
            # declared maximum
            def w_cond(carry):
                i = carry[0]
                converged = carry[4]
                return (i < iters) & ~jnp.all(converged)

            def w_body(carry):
                return (carry[0] + 1, *masked_iter(*carry))

            (_, net, coords1, mask, _, iters_used, kbs) = jax.lax.while_loop(
                w_cond, w_body,
                (jnp.int32(0), net, coords1, mask0, conv0, used0, kb0))
            ys = None

    flow_lr = coords1 - coords0
    if all_flows:
        flow_iters = ys                      # [iters, B, H, W, 2]
        flow = flow_iters[-1]
    else:
        flow_iters = None
        with stage("raft/upsample"):
            flow = upsample(flow_lr, mask)

    return RAFTOutput(flow=flow, flow_iters=flow_iters, flow_lr=flow_lr,
                      iters_used=iters_used,
                      corr_keyblocks=kbs if counts_keyblocks else None)


def _cast_params(params: Dict[str, dict], config: RAFTConfig):
    if config.compute_dtype == "bfloat16":
        # One cast at the top; correlation and upsampling stay float32.
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                            if a.dtype == jnp.float32 else a, params)
    if config.quant_weights:
        # quant='bf16w' stores the encoder weights bf16 on device
        # (cast_encoder_weights); f32 compute up-casts them in-graph so
        # conv dtypes match — the numerics are exactly "bf16-rounded
        # weights, f32 math".
        return jax.tree.map(lambda a: a.astype(jnp.float32)
                            if a.dtype == jnp.bfloat16 else a, params)
    return params


def cast_encoder_weights(params: Dict[str, dict], config: RAFTConfig):
    """``quant='bf16w'``: cast the fnet/cnet ENCODER weights to bf16 for
    device storage — halves the encoder half of param HBM (the update
    block stays f32).  Applied ONCE at load time by the serving engine;
    :func:`_cast_params` up-casts in-graph for f32 compute, so serving
    numerics equal bf16-rounded weights under the configured compute
    dtype.  No-op for other quant modes."""
    if not config.quant_weights:
        return params
    out = dict(params)
    for k in ("fnet", "cnet"):
        if k in out:
            out[k] = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                                  if a.dtype == jnp.float32 else a, out[k])
    return out


def quantize_rows(rows: jax.Array):
    """Symmetric per-channel int8 quantization of feature rows
    ``[..., H, W, C]`` -> ``(int8 vals [..., H, W, C], f32 scales
    [..., C])`` with the absmax over the spatial dims mapped to 127.

    The SlotPool storage format under ``quant='int8'``: encoder outputs
    (fmap/cnet rows) quantize on scatter (serving/session.py
    ``make_slot_commit_fn``) and dequantize on gather
    (:func:`make_stream_batch_step_fn`), shrinking the cached per-session
    rows ~4x against float32 maps and 2x against bfloat16 ones (what
    ``--dtype bfloat16`` serves: a 1080p slot goes from 33.44 to 16.85 MB,
    1.98x, the float32 seed staying) so more sessions fit one chip.  The
    scale floor keeps an all-zero channel from dividing by zero (it
    round-trips to exact 0)."""
    rows = rows.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(rows), axis=(-3, -2))
    scales = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.round(rows / scales[..., None, None, :])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scales


def dequantize_rows(vals: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_rows` (f32 output)."""
    return vals.astype(jnp.float32) * scales[..., None, None, :]


def slot_row(buf: jax.Array, slot) -> jax.Array:
    """Row ``slot`` of a slot-pool leaf ``[cap+1, ...]``, as ``[1, ...]``:
    ONE ``dynamic_slice``.  ``slot`` lies in ``[0, cap]`` by construction
    (a session's slot or the scratch row), so nothing normalises it: an index
    outside the leaf is CLAMPED to its nearest row, where ``buf[slot]``
    wrapped a negative one."""
    return jax.lax.dynamic_slice_in_dim(buf, slot, 1, axis=0,
                                        allow_negative_indices=False)


def gather_slot_rows(buf: jax.Array, slots: jax.Array) -> jax.Array:
    """``buf[slots]`` a row at a time: ``b`` one-row slices (``b`` is static:
    the engine compiles a width) joined in order.  The program then moves
    ``b`` rows whatever the pool holds: the chip's compiler turns the general
    gather into slices of the WHOLE leaf by 128-channel halves, and
    cross-program-prefetches a leaf that fits (PERF.md §6, PR 46)."""
    return jnp.concatenate([slot_row(buf, slots[i])
                            for i in range(slots.shape[0])])


@contract(image="*[B,H,W,3]")
def encode_frame(params: Dict[str, dict], image: jax.Array,
                 config: RAFTConfig) -> Tuple[jax.Array, jax.Array]:
    """Encode ONE frame for sequential (video) inference: returns
    ``(fmap, cnet)`` — the fnet feature map and the raw context-encoder
    output, both at the 1/8 grid.

    This is the cacheable per-frame state of the streaming serving path
    (serving/session.py): ``fmap`` feeds correlation as frame 2 on this
    step and as frame 1 on the next advance; ``cnet`` becomes the context
    source when this frame is frame 1.  Inference-mode only (BN running
    stats, no dropout) — exactly what :func:`raft_forward` computes for a
    frame at ``train=False``, so flows built from cached maps match the
    pairwise path.
    """
    H, W = image.shape[1], image.shape[2]
    if H % 8 or W % 8:
        raise ValueError(
            f"RAFT requires H and W divisible by 8, got {(H, W)}; pad or "
            f"resize the inputs (see data.pipeline.pad_to_multiple).")
    params = _cast_params(params, config)
    x = _preprocess(image, config)
    with stage("raft/fnet"):
        fmap, _ = apply_encoder(params["fnet"], x, "instance",
                                small=config.small, train=False)
    fmap = nan_guard(fmap, "raft/fnet")
    cnet_norm = "none" if config.small else "batch"
    with stage("raft/cnet"):
        cnet, _ = apply_encoder(params["cnet"], x, cnet_norm,
                                small=config.small, train=False)
    return fmap, cnet


@contract(fmap1="*[B,HL,WL,C]", fmap2="*[B,HL,WL,C]", cnet1="*[B,HL,WL,D]",
          flow_init="*[B,HL,WL,2]")
def forward_from_features(params: Dict[str, dict], fmap1: jax.Array,
                          fmap2: jax.Array, cnet1: jax.Array,
                          config: RAFTConfig, iters: Optional[int] = None,
                          flow_init: Optional[jax.Array] = None,
                          active: Optional[jax.Array] = None,
                          sizes8: Optional[jax.Array] = None
                          ) -> RAFTOutput:
    """Run the recurrent flow core from PRECOMPUTED encoder features.

    ``fmap1``/``fmap2`` are :func:`encode_frame` fnet maps for the two
    frames; ``cnet1`` is frame 1's raw context-encoder output.  With the
    maps cached across a video session, flow(prev -> cur) costs one
    encoder pass (the current frame's) instead of two, and ``flow_init``
    (ops/warmstart.warm_start_seed of the previous low-res flow) lets a
    ``converge:eps`` policy exit in a fraction of the cold iterations.
    Inference-only: the equivalent of ``raft_forward(train=False,
    all_flows=False)`` on the frames the features came from.  ``active``
    ([B] bool) marks real rows of a slot-padded batch (see
    :func:`_iterate_flow`); None = all rows real.  ``sizes8`` ([B, 2]
    int32) selects ragged mixed-resolution correlation (per-item live
    extents at the 1/8 grid; see :func:`_iterate_flow`).
    """
    policy_spec = _validate_loop_config(config)
    params = _cast_params(params, config)
    net = jnp.tanh(cnet1[..., :config.hidden_dim])
    inp = jax.nn.relu(cnet1[..., config.hidden_dim:])
    return _iterate_flow(params, fmap1, fmap2, net, inp, config,
                         iters=config.iters if iters is None else iters,
                         train=False, all_flows=False, flow_init=flow_init,
                         policy_spec=policy_spec, active=active,
                         sizes8=sizes8)


# The four factories below are the functions of the served programs
# (serving/engine.py's table of kinds gives each its arguments and names its
# outputs).  The three that run the lookup take a trailing ``sizes`` ([B, 2]
# int32 full-res live extents): left None — a Python-level branch while the
# program is traced — the program is the dense one; given, it is the RAGGED
# mixed-resolution one, whose images are corner-anchored crops zero-embedded
# in one max box and re-masked in-graph (deterministic dead regions), so one
# executable serves every declared resolution and row b's outputs are valid
# on ``[:sizes[b,0], :sizes[b,1]]``.


def make_encode_fn(config: RAFTConfig):
    """A jittable (params, image) -> (fmap, cnet) single-frame encoder —
    the session-open / cold-restart half of the streaming serving path."""
    def fn(params, image):
        return encode_frame(params, image, config)
    return fn


def _stream_outputs(out: RAFTOutput, fmap_cur, cnet_cur, adaptive: bool,
                    keyblocks: bool) -> tuple:
    """What every stream step returns: ``(flow, flow_lr, fmap_cur,
    cnet_cur[, iters_used][, corr_keyblocks])``."""
    res = (out.flow, out.flow_lr, fmap_cur, cnet_cur)
    if adaptive:
        res += (out.iters_used,)
    if keyblocks:
        res += (out.corr_keyblocks,)
    return res


def _sizes8(sizes):
    """A ragged step's live extents at the 1/8 grid (None for a dense one)."""
    return None if sizes is None else sizes.astype(jnp.int32) // 8


def make_stream_step_fn(config: RAFTConfig, iters: Optional[int] = None,
                        keyblocks: bool = False):
    """A jittable streaming step: ``(params, image, fmap_prev, cnet_prev,
    flow_init, sizes=None) -> (flow, flow_lr, fmap_cur, cnet_cur
    [, iters_used][, corr_keyblocks])``.

    ONE device call advances a video session by one frame: encode the
    current frame (one fnet + one cnet pass — the previous frame's maps
    arrive cached), run the recurrent core with correlation
    fmap_prev x fmap_cur and context from cnet_prev, and hand the current
    frame's maps back for the session cache (with ``sizes``, max-box rows a
    ragged arena stores verbatim).  ``iters_used`` is appended
    under an adaptive ``iters_policy`` (the serving engine's counted-
    executable convention, engine.py), and with ``keyblocks``
    ``RAFTOutput.corr_keyblocks`` last, as :func:`make_inference_fn` has
    it (dense Pallas lookup only)."""
    from ..config import adaptive_iters
    adaptive = adaptive_iters(config.iters_policy)

    def fn(params, image, fmap_prev, cnet_prev, flow_init, sizes=None):
        if sizes is not None:
            image = mask_ragged_rows(image, sizes)
        fmap_cur, cnet_cur = encode_frame(params, image, config)
        out = forward_from_features(params, fmap_prev, fmap_cur, cnet_prev,
                                    config, iters=iters, flow_init=flow_init,
                                    sizes8=_sizes8(sizes))
        return _stream_outputs(out, fmap_cur, cnet_cur, adaptive, keyblocks)
    return fn


def make_stream_batch_step_fn(config: RAFTConfig,
                              iters: Optional[int] = None,
                              keyblocks: bool = False):
    """A jittable CONTINUOUS-BATCHED streaming step over a device-resident
    slot pool: ``(params, images [b,H,W,3], fmap_buf [cap+1,h,w,C],
    cnet_buf [cap+1,h,w,D], flow_buf [cap+1,h,w,2], slots [b] int32,
    active [b] bool, sizes=None) -> (flow [b,H,W,2], flow_lr [b,h,w,2],
    fmap_cur [b,h,w,C], cnet_cur [b,h,w,D][, iters_used [b]]
    [, corr_keyblocks])`` (``keyblocks`` as in :func:`make_stream_step_fn`).

    ONE device call advances ``b`` *different* sessions by one frame
    each (LLM-continuous-batching applied to RAFT's cached maps — the
    Ragged-Paged-Attention recipe from PAPERS.md): each row gathers its
    session's cached previous-frame maps and warm-start seed from its
    batch slot (:func:`gather_slot_rows`: ``b`` one-row slices, ``slots`` in
    ``[0, cap]``), the current frames encode at batch
    width ``b`` (one fnet pass per frame, exactly as the solo step), and
    the recurrent core runs once for the whole batch.  Padding rows
    carry ``active=False``: they point at the pool's scratch slot, start
    converged under an adaptive policy (never extending the while_loop),
    and report ``iters_used == 0``.  The updated maps come back as ROWS
    — the caller commits the finite ones into the pool with the
    scatter executable (serving/session.py ``make_slot_commit_fn``)
    AFTER the host-side non-finite sentinel, so a poisoned row can
    never be cached.  With ``sizes`` the buffers are a single max-box arena
    (every slot row max-box shaped, each session live only on its
    corner-anchored crop), so sessions of DIFFERENT resolutions share one
    stream batch and one executable per batch step.
    """
    from ..config import adaptive_iters
    adaptive = adaptive_iters(config.iters_policy)
    quant = config.quant_slots

    def fn(params, images, fmap_buf, cnet_buf, flow_buf, slots, active,
           sizes=None):
        if sizes is not None:
            images = mask_ragged_rows(images, sizes)
        fmap_cur, cnet_cur = encode_frame(params, images, config)
        with stage("raft/stream/gather"):
            if quant:
                # quant='int8': fmap_buf/cnet_buf arrive as (int8 vals,
                # per-channel f32 scales) 2-leaf pytrees — dequant on
                # gather; the flow seed buffer stays f32
                fmap_q = [gather_slot_rows(leaf, slots) for leaf in fmap_buf]
                cnet_q = [gather_slot_rows(leaf, slots) for leaf in cnet_buf]
                with stage("dequant"):
                    fmap_prev = dequantize_rows(*fmap_q).astype(
                        fmap_cur.dtype)
                    cnet_prev = dequantize_rows(*cnet_q).astype(
                        cnet_cur.dtype)
            else:
                fmap_prev = gather_slot_rows(fmap_buf, slots)
                cnet_prev = gather_slot_rows(cnet_buf, slots)
            flow_init = gather_slot_rows(flow_buf, slots)
        out = forward_from_features(params, fmap_prev, fmap_cur, cnet_prev,
                                    config, iters=iters,
                                    flow_init=flow_init, active=active,
                                    sizes8=_sizes8(sizes))
        return _stream_outputs(out, fmap_cur, cnet_cur, adaptive, keyblocks)
    return fn


def make_inference_fn(config: RAFTConfig, iters: Optional[int] = None,
                      counted: bool = False, keyblocks: bool = False):
    """A jittable ``(params, image1, image2, sizes=None)`` -> final flow
    function.  ``counted`` -> (flow, iters_used): the per-sample GRU
    iteration count ([B] int32), the adaptive-compute observable behind the
    ``raft_iters_used`` histogram.  ``keyblocks`` appends
    ``RAFTOutput.corr_keyblocks`` last, for the serving engine's band
    counters (dense Pallas lookup only).  With neither the flow comes
    back bare, not in a tuple."""
    def fn(params, image1, image2, sizes=None):
        out, _ = raft_forward(params, image1, image2, config, iters=iters,
                              train=False, all_flows=False, sizes=sizes)
        res = (out.flow,)
        if counted:
            res += (out.iters_used,)
        if keyblocks:
            res += (out.corr_keyblocks,)
        return res if len(res) > 1 else out.flow
    return fn
