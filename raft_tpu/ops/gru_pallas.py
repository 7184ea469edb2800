"""Fused SepConvGRU update-block iteration as a Pallas TPU kernel.

Round-2 hardware attribution (PERF.md) showed RAFT inference at serving
batch sizes is GRU-bound, not corr-bound: the ~10 small convolutions of the
recurrent update operator on a 54x128 latent grid dominate the per-iteration
cost (MFU 0.032), and each one is a separate XLA op that round-trips ``h``,
``motion`` and the gate activations through HBM even though the whole
iteration state is a few MB.  This kernel executes ONE full SepConvGRU
iteration fused — the 1x5 horizontal z/r/q gate pass, the 5x1 vertical
pass, the sigmoid/tanh nonlinearities and the ``(1-z)*h + z*q`` blends —
with ``h``, the motion features, the hoisted context terms
(``models.update.precompute_gru_ctx``) and all gate weights VMEM-resident
for the whole iteration.  Nothing but the input row blocks and the output
``h`` block crosses HBM.

Design:

* Grid ``(B, row-blocks)`` over the latent grid.  Each program computes
  ``block_rows`` output rows.  The separable 5-taps need halo: the vertical
  q-gate reads ``r2 * h1`` two rows out, and ``r2``'s own conv reads two
  more, so pass 1 is recomputed on a 4-row halo (``_HALO``) fetched from
  the neighbor row blocks (clamped index maps + validity masking — the
  flash-attention-style overlap trick, same as ``corr_pallas``'s p-blocks).
  Width is zero-padded by the tap radius OUTSIDE the kernel, so horizontal
  taps are static in-VMEM slices and the zero columns reproduce
  ``ops.conv.conv2d``'s symmetric zero padding exactly.
* Each 5-tap separable conv runs as 5 shifted ``[rows*W, Cin] @ [Cin, Cout]``
  MXU matmuls.  Per direction, z and r (which read the same ``[h, motion]``
  input) share one fused matmul, and the q gate's motion columns are a
  second small matmul that does not wait on ``r`` — only the q gate's
  ``r*h`` contraction is sequential, and it contracts ``hidden`` channels
  instead of ``hidden + motion`` (the same FLOP count as the hoisted XLA
  formulation; see ``fuse_gru_weights``).
* Numerics: the kernel computes in float32 regardless of the I/O dtype
  (matmuls accumulate f32 via ``preferred_element_type``; bf16 inputs are
  upcast once in VMEM) — the same fp32-core policy as the corr kernel.
  Output dtype mirrors ``h``.  "float32" here means f32 accumulation: the
  dots carry no ``precision``, so on the chip the MXU rounds their f32
  operands to bf16, exactly as XLA's default does for the convs this kernel
  replaces (measured on a v5e, PR 21: kernel and XLA twin 1.0e-3 apart, both
  1.6e-2 from a HIGHEST-precision oracle).
* The context terms come PRE-HOISTED: ``gru_impl='pallas'`` implies the
  ``gru_ctx_hoist`` rewrite (models/raft.py precomputes the terms even when
  the config flag is off), so the kernel never contracts the
  iteration-invariant context channels.
* Off-TPU the same schedule runs as a plain-XLA twin
  (``sep_conv_gru_xla``, f32-compute policy included) — measurably faster
  on the compute-bound CPU backend than the bf16-emulated conv path — and
  the Pallas kernel itself runs under ``interpret=True`` for the parity
  suite (tests/test_gru_pallas.py), so the exact kernel code is exercised
  off-hardware.  Backward delegates to the twin via ``custom_vjp`` (the
  corr_pallas pattern: forward rides the kernel, gradients ride XLA).

The motion encoder's 1x1 ``convc1`` and the flow head are NOT folded in yet
(they read/write different channel plans; candidate for a follow-up once
the chip is back to rank it) — this kernel covers the SepConvGRU core, the
largest slice of the update block.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kernel_plans import GRU_HALO, GRU_TAPS, gru_row_plan, gru_vmem_limit
from ..lint.contracts import contract
from ..telemetry.trace import stage
from .conv import conv2d

# Kernel geometry constants live in kernel_plans.py, where the static VMEM
# analyzer reads them too (lint rule B4).
_HALO = GRU_HALO   # pass-1 recompute halo rows: q2 reads r2*h1 at +-2,
#                    r2's conv +-2
_K = GRU_TAPS      # separable tap count (1x5 / 5x1)
_CTX2_HALO = 2     # pass-2 ctx terms are needed at the r2 rows only (+-2)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ weight prep

def fuse_gru_weights(p: Dict[str, dict], hidden: int, ctx_dim: int) -> dict:
    """Tap-major gate weights with the context input-channel block removed.

    For each pass ``s`` (1 = horizontal 1x5, 2 = vertical 5x1):

    * ``wzr{s}`` [5, hidden+motion, 2*hidden] — z and r fused on the output
      axis (same input, one matmul; exact, like ``apply_conv_fused``);
    * ``wqh{s}`` [5, hidden, hidden] — the q gate's ``r*h`` columns;
    * ``wqm{s}`` [5, motion, hidden] — the q gate's motion columns, which do
      not depend on ``r`` and therefore run alongside the z/r matmul.

    Loop-invariant (pure slicing/concat of the param dict), so XLA hoists
    the prep out of the GRU ``lax.scan``; checkpoint format untouched.
    The gate biases are NOT included — they ride the hoisted context terms
    (``precompute_gru_ctx`` folds them in), exactly as in the XLA path.
    """
    lo, hi = hidden, hidden + ctx_dim
    out = {}
    for s in ("1", "2"):
        def taps(name: str, s=s) -> jax.Array:
            w = p[name + s]["w"]                      # [kh, kw, hx, hidden]
            return w[0] if s == "1" else w[:, 0]      # [5, hx, hidden]

        def loop_cols(w: jax.Array) -> jax.Array:     # drop the ctx block
            return jnp.concatenate([w[:, :lo], w[:, hi:]], axis=1)

        wq = taps("convq")
        out["wzr" + s] = jnp.concatenate(
            [loop_cols(taps("convz")), loop_cols(taps("convr"))], axis=2)
        out["wqh" + s] = wq[:, :lo]
        out["wqm" + s] = wq[:, hi:]
    return out


def _ctx_cat(ctx: Dict[str, jax.Array], s: str) -> jax.Array:
    """Hoisted context terms of pass ``s`` as one [B, H, W, 3*hidden] array
    (z | r | q) — one fetch stream instead of three."""
    return jnp.concatenate([ctx["convz" + s], ctx["convr" + s],
                            ctx["convq" + s]], axis=-1)


# ---------------------------------------------------------------- kernel

def _gru_kernel(hm_p, hm_c, hm_n, c1_p, c1_c, c1_n, c2_p, c2_c, c2_n,
                wzr1, wqh1, wqm1, wzr2, wqh2, wqm2, out_ref, *,
                T: int, H: int, hidden: int):
    """One (batch, row-block) program: full SepConvGRU iteration in VMEM.

    Row coordinate frames (E = ``_HALO``):

    * ``ext``  — [T + 2E] rows, global rows [k*T - E, k*T + T + E): the
      pass-1 domain (h1 must exist 4 rows beyond the output block).
    * ``mid``  — ext[2 : T+6], the r2/rh2 domain (output rows +-2).
    * center — ext[E : E+T], the T output rows.

    Width frame: inputs arrive zero-padded to Wp = Wc + 4 (Wc = padded-out
    width, multiple of 8); horizontal conv outputs live at width Wc, column
    j of which is real column j (left pad = tap radius = 2).
    """
    k = pl.program_id(1)
    E = _HALO

    def ext(prev, cur, nxt):
        # neighbor blocks are index-map-CLAMPED at the grid edges, so halo
        # rows outside [0, H) carry garbage; masking them to zero both
        # fixes that and reproduces conv2d's zero row-padding.
        x = jnp.concatenate([prev[0, T - E:], cur[0], nxt[0, :E]], axis=0)
        rows = (jax.lax.broadcasted_iota(jnp.int32, (T + 2 * E, 1, 1), 0)
                + k * T - E)
        return jnp.where((rows >= 0) & (rows < H),
                         x.astype(jnp.float32), 0.0)

    hm = ext(hm_p, hm_c, hm_n)                       # [T+2E, Wp, hid+mot]
    c1 = ext(c1_p, c1_c, c1_n)                       # [T+2E, Wp, 3*hid]
    c2 = ext(c2_p, c2_c, c2_n)[E - _CTX2_HALO: E + T + _CTX2_HALO]

    Wp = hm.shape[1]
    Wc = Wp - (_K - 1)                               # conv-output width

    def hconv(x, w):
        """1x5 conv: x [R, Wx, Ci] -> [R, Wx-4, Co], 5 shifted MXU matmuls."""
        R, Wx, Ci = x.shape
        Wo = Wx - (_K - 1)
        acc = None
        for d in range(_K):
            xd = x[:, d:d + Wo, :].reshape(R * Wo, Ci)
            t = jax.lax.dot_general(xd, w[d], (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = t if acc is None else acc + t
        return acc.reshape(R, Wo, -1)

    def vconv(x, w, r0, rout):
        """5x1 conv: output row m (m in [0, rout)) = sum_d x[r0+m+d-2] @ w[d]."""
        _, Wx, Ci = x.shape
        acc = None
        for d in range(_K):
            lo = r0 - 2 + d
            xd = x[lo:lo + rout].reshape(rout * Wx, Ci)
            t = jax.lax.dot_general(xd, w[d], (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = t if acc is None else acc + t
        return acc.reshape(rout, Wx, -1)

    f32 = lambda ref: ref[...].astype(jnp.float32)  # noqa: E731

    # ---- pass 1: horizontal (1x5), computed on the full ext row range
    h0 = hm[:, 2:2 + Wc, :hidden]                    # conv-output-aligned
    mot = hm[:, 2:2 + Wc, hidden:]
    c1c = c1[:, 2:2 + Wc]
    zr1 = hconv(hm, f32(wzr1))
    z1 = jax.nn.sigmoid(zr1[..., :hidden] + c1c[..., :hidden])
    r1 = jax.nn.sigmoid(zr1[..., hidden:] + c1c[..., hidden:2 * hidden])
    qm1 = hconv(hm[:, :, hidden:], f32(wqm1))        # motion cols: no r dep
    rh1 = r1 * h0
    # re-pad r*h to Wp so its taps see the same zero columns conv2d would
    zc = jnp.zeros((rh1.shape[0], 2, hidden), jnp.float32)
    rh1 = jnp.concatenate([zc, rh1, zc], axis=1)
    q1 = jnp.tanh(hconv(rh1, f32(wqh1)) + qm1 + c1c[..., 2 * hidden:])
    h1 = (1.0 - z1) * h0 + z1 * q1                   # [T+2E, Wc, hidden]

    # ---- pass 2: vertical (5x1) on the center rows
    hm2 = jnp.concatenate([h1, mot], axis=2)
    zr2 = vconv(hm2, f32(wzr2), r0=_CTX2_HALO, rout=T + 2 * _CTX2_HALO)
    c2c = c2[:, 2:2 + Wc]                            # rows align with zr2
    r2 = jax.nn.sigmoid(zr2[..., hidden:] + c2c[..., hidden:2 * hidden])
    z2 = jax.nn.sigmoid(zr2[_CTX2_HALO:_CTX2_HALO + T, :, :hidden]
                        + c2c[_CTX2_HALO:_CTX2_HALO + T, :, :hidden])
    rh2 = r2 * h1[E - _CTX2_HALO: E + T + _CTX2_HALO]
    qh2 = vconv(rh2, f32(wqh2), r0=_CTX2_HALO, rout=T)
    qm2 = vconv(mot, f32(wqm2), r0=E, rout=T)
    q2 = jnp.tanh(qh2 + qm2
                  + c2c[_CTX2_HALO:_CTX2_HALO + T, :, 2 * hidden:])
    h2 = (1.0 - z2) * h1[E:E + T] + z2 * q2          # [T, Wc, hidden]
    out_ref[0] = h2.astype(out_ref.dtype)


def _pallas_gru(hm: jax.Array, c1: jax.Array, c2: jax.Array, fw: dict,
                hidden: int, T: int, H: int, interpret: bool,
                vmem_limit: int) -> jax.Array:
    """hm/c1/c2 [B, Hp, Wp, *] (row/width pre-padded) -> [B, Hp, Wc, hidden]."""
    B, Hp, Wp, _ = hm.shape
    n_rb = Hp // T
    Wc = Wp - 4

    def rowblock_spec(arr, pick):
        return pl.BlockSpec((1, T, Wp, arr.shape[-1]),
                            lambda b, k, pick=pick: (b, pick(k), 0, 0))

    prev = lambda k: jnp.maximum(k - 1, 0)           # noqa: E731
    cur = lambda k: k                                # noqa: E731
    nxt = lambda k: jnp.minimum(k + 1, n_rb - 1)     # noqa: E731
    in_specs = [rowblock_spec(a, pick)
                for a in (hm, c1, c2) for pick in (prev, cur, nxt)]
    weights = [fw["wzr1"], fw["wqh1"], fw["wqm1"],
               fw["wzr2"], fw["wqh2"], fw["wqm2"]]
    in_specs += [pl.BlockSpec(w.shape, lambda b, k: (0, 0, 0))
                 for w in weights]

    return pl.pallas_call(
        functools.partial(_gru_kernel, T=T, H=H, hidden=hidden),
        grid=(B, n_rb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, T, Wc, hidden),
                               lambda b, k: (b, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hp, Wc, hidden), hm.dtype),
        interpret=interpret,
        # f32 I/O at 8 rows x 128 columns needs 17.03M of scoped VMEM, over
        # the compiler's 16 MiB default; whole rows of a wider frame need
        # more (kernel_plans.gru_vmem_limit reads it from the row plan)
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
    )(hm, hm, hm, c1, c1, c1, c2, c2, c2, *weights)


# ------------------------------------------------------------- XLA twin

@stage("update/gru_xla_twin")
@contract(h="*[B,H,W,C]", motion="*[B,H,W,M]", _returns="*[B,H,W,C]")
def sep_conv_gru_xla(p: Dict[str, dict], h: jax.Array, motion: jax.Array,
                     ctx: Dict[str, jax.Array]) -> jax.Array:
    """The kernel's computation executed by plain XLA.

    Same fused weights (z/r one conv; the q gate's motion columns split off
    the ``r*h`` contraction), same f32-compute policy — the off-TPU fast
    path (on the compute-bound CPU backend, f32 convs beat the
    emulated-bf16 conv path by ~15-20%; PERF.md round 6) and the backward
    delegate of the kernel (fully differentiable, no Pallas in the grad
    path).  The 5-tap decomposition itself is a Mosaic layout constraint,
    not a semantic one, so here each gate runs as one ``conv2d``.
    """
    io_dtype = h.dtype
    hidden = h.shape[-1]
    ctx_dim = p["convz1"]["w"].shape[2] - hidden - motion.shape[-1]
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    fw = f32(fuse_gru_weights(p, hidden, ctx_dim))
    hf = h.astype(jnp.float32)
    mot = motion.astype(jnp.float32)
    c1 = _ctx_cat(ctx, "1").astype(jnp.float32)
    c2 = _ctx_cat(ctx, "2").astype(jnp.float32)

    for s, to4 in (("1", lambda w: w[None]), ("2", lambda w: w[:, None])):
        cs = c1 if s == "1" else c2
        zr = conv2d(jnp.concatenate([hf, mot], -1), to4(fw["wzr" + s]))
        z = jax.nn.sigmoid(zr[..., :hidden] + cs[..., :hidden])
        r = jax.nn.sigmoid(zr[..., hidden:] + cs[..., hidden:2 * hidden])
        q = jnp.tanh(conv2d(r * hf, to4(fw["wqh" + s]))
                     + conv2d(mot, to4(fw["wqm" + s]))
                     + cs[..., 2 * hidden:])
        hf = (1.0 - z) * hf + z * q
    return hf.astype(io_dtype)


# ------------------------------------------------------------- dispatch

def _gru_fused_impl(p, h, motion, ctx, block_rows, interpret, impl):
    if impl == "auto":
        # kernel on TPU; elsewhere the XLA twin, unless interpret mode is
        # explicitly requested (tests exercise the literal kernel body)
        impl = "kernel" if (jax.default_backend() == "tpu" or interpret) \
            else "xla"
    if impl == "xla":
        return sep_conv_gru_xla(p, h, motion, ctx)

    B, H, W, hidden = h.shape
    T = block_rows
    ctx_dim = p["convz1"]["w"].shape[2] - hidden - motion.shape[-1]
    io_dtype = h.dtype
    # weights ride at f32 whatever the activation dtype — the same policy
    # as the XLA twin, so kernel and twin (= the backward path) see
    # bit-identical weights even when params and activations differ in
    # dtype.  They are small (a few hundred KB), so the VMEM cost is
    # noise next to the row blocks.
    fw = jax.tree.map(lambda a: a.astype(jnp.float32),
                      fuse_gru_weights(p, hidden, ctx_dim))

    # the kernel's padding plan (kernel_plans.py; the static VMEM analyzer
    # prices the same one):
    # Hp multiple of T, Wc the aligned conv-output width, Wp = Wc + the
    # tap radius of zeros each side
    plan = gru_row_plan(H, W, T)
    Hp, Wc, Wp = plan.hp, plan.wc, plan.wp
    pad = ((0, 0), (0, Hp - H), (2, Wp - W - 2), (0, 0))
    hm = jnp.pad(jnp.concatenate([h, motion.astype(io_dtype)], -1), pad)
    c1 = jnp.pad(_ctx_cat(ctx, "1").astype(io_dtype), pad)
    c2 = jnp.pad(_ctx_cat(ctx, "2").astype(io_dtype), pad)

    interp = _use_interpret() if interpret is None else interpret
    out = _pallas_gru(hm, c1, c2, fw, hidden, T, H, interp,
                      gru_vmem_limit(plan, T, jnp.dtype(io_dtype).itemsize))
    return out[:, :H, :W]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gru_fused(p, h, motion, ctx, block_rows, interpret, impl):
    return _gru_fused_impl(p, h, motion, ctx, block_rows, interpret, impl)


def _gru_fused_fwd(p, h, motion, ctx, block_rows, interpret, impl):
    return (_gru_fused_impl(p, h, motion, ctx, block_rows, interpret, impl),
            (p, h, motion, ctx))


def _gru_fused_bwd(block_rows, interpret, impl, residuals, g):
    # gradients ride the XLA twin (same schedule, fully differentiable) —
    # training with gru_impl='pallas' never differentiates through Pallas
    p, h, motion, ctx = residuals
    _, vjp = jax.vjp(sep_conv_gru_xla, p, h, motion, ctx)
    return vjp(g)


_gru_fused.defvjp(_gru_fused_fwd, _gru_fused_bwd)


@contract(h="*[B,H,W,C]", motion="*[B,H,W,M]", _returns="*[B,H,W,C]")
def sep_conv_gru_pallas(p: Dict[str, dict], h: jax.Array, motion: jax.Array,
                        ctx: Dict[str, jax.Array], *, block_rows: int = 8,
                        interpret: bool | None = None,
                        impl: str = "auto") -> jax.Array:
    """One fused SepConvGRU iteration (the ``gru_impl='pallas'`` hot path).

    p: the ``update_block.gru`` param dict (convz1..convq2 — layout
    untouched); h [B, H, W, hidden]; motion [B, H, W, M] (the motion-encoder
    features, i.e. the non-context part of the GRU input); ctx: the hoisted
    context terms from ``precompute_gru_ctx`` (bias included).  Exact-parity
    with ``apply_sep_conv_gru(p, h, concat([inp, motion]))`` up to f32
    round-off (tests/test_gru_pallas.py pins it at the corr_pallas
    tolerance).

    impl: 'kernel' forces the Pallas kernel (interpret mode off-TPU unless
    ``interpret`` says otherwise), 'xla' the twin, 'auto' picks per backend.
    block_rows: output rows per grid program (tools/tune_pallas.py
    ``--kernel gru`` sweeps it; must be >= the 4-row recompute halo).
    """
    if impl not in ("auto", "kernel", "xla"):
        # same silent-fallback hazard as corr_lookup: a typo must not
        # quietly run the other implementation
        raise ValueError(f"impl must be 'auto', 'kernel' or 'xla', "
                         f"got {impl!r}")
    if block_rows < _HALO:
        raise ValueError(f"block_rows must be >= {_HALO} (the pass-1 "
                         f"recompute halo), got {block_rows}")
    return _gru_fused(p, h, motion, ctx, block_rows, interpret, impl)
