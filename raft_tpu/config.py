"""Model / runtime configuration for raft-tpu.

The reference hardcodes its hyperparameters as constructor attributes on the
model class (reference networks/RAFT.py:26-43) and freezes the iteration count
at 20 for both variants (RAFT.py:33) even though the paper's eval protocol uses
12 (small) / 32 (full).  Here every knob is a real config field.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


def parse_iters_policy(spec: str):
    """Parse an iteration policy spec into ``(kind, eps, min_iters)``.

    ``"fixed"``                    -> ``("fixed", None, None)``
    ``"converge:EPS"``             -> ``("converge", EPS, 1)``
    ``"converge:EPS:MIN_ITERS"``   -> ``("converge", EPS, MIN_ITERS)``

    ``EPS`` is the early-exit threshold in pixels at the 1/8 recurrence
    grid: a sample is *converged* once the per-sample mean L2 norm of the
    GRU's flow update ``‖Δflow‖`` drops below it (after at least
    ``MIN_ITERS`` iterations).  ``converge:0`` never triggers (the norm is
    never < 0), so it is the bit-exact twin of ``fixed`` — what the
    equivalence tests pin.  A malformed spec raises ValueError — same
    no-silent-fallback contract as ``corr_lookup``/``gru_impl``.
    """
    if spec == "fixed":
        return ("fixed", None, None)
    parts = spec.split(":")
    if parts[0] != "converge" or len(parts) not in (2, 3):
        raise ValueError(
            f"iters_policy must be 'fixed' or 'converge:eps[:min_iters]', "
            f"got {spec!r}")
    try:
        eps = float(parts[1])
    except ValueError:
        raise ValueError(f"iters_policy {spec!r}: eps {parts[1]!r} is not "
                         f"a number")
    if not eps >= 0.0:          # also rejects NaN
        raise ValueError(f"iters_policy {spec!r}: eps must be >= 0")
    min_iters = 1
    if len(parts) == 3:
        try:
            min_iters = int(parts[2])
        except ValueError:
            raise ValueError(f"iters_policy {spec!r}: min_iters "
                             f"{parts[2]!r} is not an integer")
        if min_iters < 1:
            raise ValueError(f"iters_policy {spec!r}: min_iters must "
                             f"be >= 1")
    return ("converge", eps, min_iters)


def adaptive_iters(spec: str) -> bool:
    """True when ``spec`` enables the per-sample early exit (validates as a
    side effect) — the one test every policy consumer needs, so a future
    policy kind means touching this helper, not every call site."""
    return parse_iters_policy(spec)[0] == "converge"


def init_rng(seed: int = 0):
    """The one sanctioned source of init randomness.

    Every weight-init / template-init site goes through here instead of
    scattering ``jax.random.PRNGKey(0)`` across call sites (which raftlint
    R3 flags: paths seeded independently with the same literal silently
    draw the SAME stream).  jax is imported lazily so config stays
    importable without it (the linter itself depends on that).
    """
    import jax
    return jax.random.PRNGKey(seed)


@dataclasses.dataclass(frozen=True)
class RAFTConfig:
    """Static hyperparameters of the RAFT model.

    Mirrors the capability surface of reference networks/RAFT.py:26-43 (full
    vs --small variants) with the hardcoded values promoted to fields.
    """

    small: bool = False
    hidden_dim: int = 128
    context_dim: int = 128
    corr_levels: int = 4
    corr_radius: int = 4
    iters: int = 32
    dropout: float = 0.0
    # NOTE: input channel order (BGR per the reference's cv2 path, reference
    # RAFT.py:13, vs RGB for the official weights) is a property of the DATA
    # and the loaded WEIGHTS, not of the model graph — it lives in the CLI
    # (--rgb) and the weight converter (swap_input_channels), not here.
    # Correlation implementation: 'dense' materializes per-level volumes
    # (reference model_utils.py:199-221 semantics), 'blockwise' chunks over
    # query pixels and never materializes the full (HW)^2 volume, 'pallas'
    # uses the fused TPU kernel (the CUDA-extension equivalent the reference
    # never wrote, reference readme.md:12).
    corr_impl: str = "dense"
    # Window-lookup formulation for the dense impl: 'gather'
    # (take_along_axis, the reference's SampleCorr semantics) or 'onehot'
    # (separable one-hot interpolation matmuls — MXU work instead of
    # gathers).  Default 'onehot' from measured data: TPU v5e 18.09 vs
    # 11.42 pairs/s (round 2, TUNING.md); identical values (parity-tested
    # vs gather).
    corr_lookup: str = "onehot"
    # MXU precision of the fused kernel's correlation matmul.  'highest' =
    # every product of the operands' values, summed in float32: for
    # bfloat16 feature maps one MXU pass at level 0 and three at the pooled
    # levels, for float32 maps the MXU's six-pass matmul
    # (ops/corr_pallas.corr_terms counts the terms from the dtypes).
    # 'default' = one pass over operands the MXU rounds to bf16, matching
    # the dense/blockwise einsum default.  Bilinear-interpolation matmuls
    # always run at highest.
    corr_precision: str = "highest"
    # Fused-kernel block sizes (corr_impl='pallas'): queries per program and
    # target level-0 tile width (rows of fmap2 per program x padded W2).
    # Defaults chosen from the measured sweep on TPU v5e — tools/tune_pallas.py,
    # table in TUNING.md — not guesses.
    pallas_q_blk: int = 128
    pallas_p_blk: int = 4096
    # Compute dtype for conv/matmul-heavy paths ('float32' or 'bfloat16');
    # the correlation itself always accumulates in float32.  The library
    # default stays float32 (numerics-first; bf16 is emulated and slower on
    # CPU); the CLI resolves its own default to bfloat16 on TPU for
    # inference/eval, where the cost is measured and negligible: held-out
    # EPE 1.0007 (f32) vs 1.0016 (bf16) on the trained flagship checkpoint,
    # +0.0009 EPE for ~1.5x measured TPU throughput (PERF.md round 5).
    compute_dtype: str = "float32"
    # Iteration policy for the recurrent update loop (parse_iters_policy):
    # 'fixed' runs exactly `iters` GRU iterations; 'converge:eps[:min_iters]'
    # adds a per-sample early-exit criterion — a sample whose mean 1/8-grid
    # flow update ‖Δflow‖ drops below eps (pixels) is FROZEN in place
    # (masked carry update; shapes stay static so raftlint R2 holds and one
    # executable serves every difficulty mix), and inference takes a
    # whole-batch lax.while_loop fast path that stops once every sample has
    # converged (or at `iters`, whichever first).  Train/differentiable
    # paths keep the masked lax.scan form (reverse-mode through while_loop
    # is undefined), composing with remat_iters and scan_unroll.
    # 'converge:0' is the bit-exact twin of 'fixed'.  PERF.md round 8.
    iters_policy: str = "fixed"
    # Rematerialize each GRU iteration during backprop (memory/FLOPs trade).
    remat_iters: bool = True
    # lax.scan unroll factor for the GRU iteration loop (1 = no unrolling).
    # Unrolling lets XLA fuse/overlap across adjacent iterations at the cost
    # of code size; measured on hardware before changing the default.
    scan_unroll: int = 1
    # Hoist the context contribution out of the GRU gate convolutions: every
    # gate conv reads [h, inp, motion] and `inp` (the context features) is
    # iteration-invariant, so its input-channel block is convolved ONCE
    # before the scan and added per iteration — an exact rewrite (conv is
    # linear over input-channel blocks) that removes 1/3 of the gate-conv
    # FLOPs inside the loop (~26% for the small variant).  XLA does not do
    # this itself (loop-invariant code motion moves whole ops, not partial
    # contractions).  Identical values (forward + gradient torch-oracle
    # parity tested).  Default ON: a pure FLOP cut, so it can only help
    # where the gate convs dominate (round-2 TPU attribution).  Not timed
    # on the chip against the un-hoisted form.
    gru_ctx_hoist: bool = True
    # Which implementation executes the SepConvGRU iteration (full model
    # only — the small variant's 3x3 ConvGRU has no hand kernel yet):
    # 'xla' = the conv formulation above (with optional ctx hoisting);
    # 'pallas' = the fused update-block kernel (ops/gru_pallas.py): one
    # grid pass per iteration keeps h, motion, the hoisted context terms
    # and all gate weights VMEM-resident — the 1x5 and 5x1 gate passes,
    # nonlinearities and blends never round-trip HBM.  Implies the ctx
    # hoist (the kernel consumes precomputed context terms).  Off-TPU the
    # kernel's XLA twin runs (same fused weights, f32-compute policy —
    # measured faster than the emulated-bf16 conv path on CPU, PERF.md r6);
    # interpret mode covers the literal kernel body in tests.
    gru_impl: str = "xla"
    # Output rows per grid program of the fused GRU kernel (the pass-1
    # recompute halo is 4 rows, so larger blocks amortize more halo
    # recompute at more VMEM).  Sweep: tools/tune_pallas.py --kernel gru;
    # hardware numbers pending (TUNING.md round 6).
    gru_block_rows: int = 8
    # Post-training quantization of the serving plane (SERVING.md "Cold
    # start & cache"): 'int8' stores the streaming SlotPool's fmap/cnet
    # rows as int8 with a per-channel f32 scale (dequant-on-gather inside
    # the sbatch step, quantize-on-scatter inside scommit — the flow seed
    # row stays f32); 'bf16w' casts the fnet/cnet ENCODER weights to
    # bfloat16 at load (halves encoder param HBM; the update block stays
    # f32); 'int8+bf16w' composes both.  Quantization changes the pool
    # buffer pytree, so it is part of the engine's compile keys and of
    # lint/budget's config signature — tools/envelope_check.py gates the
    # EPE delta.  Default 'none' = today's f32 behavior, bit-for-bit.
    quant: str = "none"

    def __post_init__(self):
        allowed = ("none", "int8", "bf16w", "int8+bf16w")
        if self.quant not in allowed:
            # no-silent-fallback contract, same as parse_iters_policy
            raise ValueError(f"quant must be one of {allowed}, "
                             f"got {self.quant!r}")

    @property
    def quant_slots(self) -> bool:
        """True when the SlotPool stores int8 fmap/cnet rows."""
        return "int8" in self.quant

    @property
    def quant_weights(self) -> bool:
        """True when the fnet/cnet encoder weights are cast to bf16."""
        return "bf16w" in self.quant

    @property
    def fnet_dim(self) -> int:
        return 128 if self.small else 256

    @property
    def cnet_dim(self) -> int:
        return self.hidden_dim + self.context_dim

    @property
    def corr_feature_dim(self) -> int:
        return self.corr_levels * (2 * self.corr_radius + 1) ** 2

    @staticmethod
    def full(**overrides) -> "RAFTConfig":
        """raft-things variant (reference RAFT.py:28-35)."""
        return RAFTConfig(**{**dict(small=False), **overrides})

    @staticmethod
    def small_model(**overrides) -> "RAFTConfig":
        """raft-small variant (reference RAFT.py:37-41)."""
        defaults = dict(small=True, hidden_dim=96, context_dim=64, corr_radius=3, iters=12)
        return RAFTConfig(**{**defaults, **overrides})


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training recipe (absent from the reference — SURVEY.md §3.6; realizes
    the stubbed --optimizer choices at reference infer_raft.py:62-63)."""

    num_steps: int = 100_000
    batch_size: int = 6
    # micro-batch gradient accumulation inside the jitted step: the batch is
    # split into accum_steps sequential slices (lax.scan), cutting peak
    # activation memory by that factor while the optimizer sees the averaged
    # full-batch gradient — the single-chip fit knob for the official
    # batch 10-12 x (368,496) x many-iteration recipes.  batch_size (and,
    # under data-parallel, the per-device batch) must divide evenly.
    accum_steps: int = 1
    image_size: Tuple[int, int] = (368, 496)
    lr: float = 4e-4
    weight_decay: float = 1e-5   # reference RAFT.py:14 (declared, unused there)
    adamw_eps: float = 1e-8
    clip_norm: float = 1.0
    gamma: float = 0.8           # sequence-loss decay (RAFT paper eq. 7)
    # Sequence-loss denominator: 'total' = official RAFT's element-count
    # mean ((valid * i_loss).mean() — invalid pixels still count in the
    # denominator, so sparse-valid stages like the kitti finetune keep the
    # official effective LR); 'valid' = per-valid-pixel mean (2-4x larger
    # on KITTI-like ~25-50%-valid masks; compensate lr if selected).  See
    # training/loss.py:sequence_loss.
    loss_normalization: str = "total"
    optimizer: str = "adamw"     # adam | adamw | sgd | sgd_cyclic | sgd_1cycle
    schedule: str = "one_cycle"  # one_cycle | constant | cyclic
    pct_start: float = 0.05
    max_flow: float = 400.0      # exclude ground-truth flows beyond this
    # Freeze batch norm during training (official recipe for every stage
    # after chairs): running stats are used and left untouched; BN affine
    # params still train.  Irrelevant for the small variant (no BN).
    freeze_bn: bool = False
    # Failure detection/containment (SURVEY.md §5 listed 'none' for the
    # reference): drop updates with non-finite grads (optax.apply_if_finite),
    # and the loop halts with a clear error if the loss itself goes
    # non-finite at a logged step (halt_on_nonfinite).
    skip_nonfinite_updates: bool = True
    halt_on_nonfinite: bool = True
    seed: int = 0
    log_every: int = 100
    ckpt_every: int = 5000
    ckpt_dir: str = "checkpoints"
    # Retention: keep only the newest N step-numbered checkpoints, pruning
    # the oldest AFTER each successful atomic save (None = keep all).
    # Resume pairs with this: restore_latest_with_fallback skips a
    # corrupt/truncated newest file instead of crashing.
    keep_checkpoints: Optional[int] = None
    # Async checkpointing (training/resilience.py): the step loop snapshots
    # device state to host at the step boundary and hands it to a bounded
    # background writer — serialization, fsync, verify-after-write and
    # retention pruning never block a step.  False (--sync-ckpt) restores
    # the historical inline save, bit-for-bit.
    async_checkpointing: bool = True
    # Divergence rollback: a non-finite loss/grad-norm at any step restores
    # the last finite checkpoint snapshot, re-randomizes the PRNG stream
    # (retry count folded into the key) and continues past the offending
    # data window; the run aborts after this many CONSECUTIVE rollbacks.
    # 0 disables (the halt_on_nonfinite streak logic applies instead), as
    # does halt_on_nonfinite=False (the explicit ride-through opt-out).
    # Single-host only — under multi-host training the sentinel is off.
    max_rollbacks: int = 3

    @staticmethod
    def for_stage(stage: str, **overrides) -> "TrainConfig":
        """Official RAFT curriculum presets (paper §4 / official repo
        train_standard.sh): chairs -> things -> sintel/kitti finetune.
        Explicit overrides win."""
        presets = {
            "chairs":    dict(num_steps=100_000, lr=4e-4, batch_size=10,
                              image_size=(368, 496), weight_decay=1e-4),
            "things":    dict(num_steps=100_000, lr=1.25e-4, batch_size=6,
                              image_size=(400, 720), weight_decay=1e-4,
                              freeze_bn=True),
            "sintel":    dict(num_steps=100_000, lr=1.25e-4, batch_size=6,
                              image_size=(368, 768), weight_decay=1e-5,
                              gamma=0.85, freeze_bn=True),
            "kitti":     dict(num_steps=50_000, lr=1e-4, batch_size=6,
                              image_size=(288, 960), weight_decay=1e-5,
                              gamma=0.85, freeze_bn=True),
            "synthetic": dict(image_size=(96, 128), batch_size=4,
                              log_every=10, ckpt_every=100),
        }
        if stage not in presets:
            raise ValueError(f"unknown stage {stage!r}; "
                             f"options: {sorted(presets)}")
        return TrainConfig(**{**presets[stage], **overrides})
