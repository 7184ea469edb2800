"""The main path's Pallas kernels, compiled for a TPU v5e that is DESCRIBED,
not attached (on-chip-measurement guide §2, rehearsal 3).

Interpret mode cannot see what Mosaic refuses — a slice off the tiling, a
shape cast it has no layout for, more scoped VMEM than a kernel may use.
These cases ask the chip's own compiler, at the real widths (Sintel 440x1024:
55x128 queries, and full HD 1080x1920: 135x240; C=256; hidden 128), and cost
~2 s each and no chip time.
They call the kernels' own entry points with ``interpret=False`` /
``impl='kernel'`` — no program option exists for this.

All of them live in THIS file: the worker that runs it loads the TPU library
and keeps it; a second file could land on another worker, whose fixture
would then skip.  The topology is described inside a module-scoped fixture
(never at import), and JAX's persistent compilation cache is off around the
compiles (an entry written for a described device cannot be read back here
and only warns).
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from raft_tpu.kernel_plans import corr_level_plan
from raft_tpu.ops.corr_pallas import (_lookup_level, _ragged_lookup_level,
                                      level_schedule)

P = jax.lax.Precision
H, W, C = 55, 128, 256          # Sintel bucket 440x1024 at the 1/8 grid
HD = (135, 240)                 # 1080x1920 at the 1/8 grid
CROP = (46, 62)                 # the 368x496 training crop at the 1/8 grid
RADIUS = 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *specs):
    """Compile for the described chip; returns the optimized HLO text."""
    return jax.jit(fn).lower(*specs).compile().as_text()


def _corr_specs(sd, level: int, batch: int = 1, f1=jnp.float32,
                f2=jnp.float32, grid=(H, W), c=C):
    h, w = grid
    s = functools.partial(jax.ShapeDtypeStruct, sharding=sd)
    return (s((batch, h * w, c), f1),
            s((batch, h // 2 ** level, w // 2 ** level, c), f2),
            s((batch, h * w, 2), jnp.float32))


def _scheduled_level(f1, f2_level, coords, *, level, p_blk_target, grid_w,
                     radius=RADIUS, **kw):
    """``_lookup_level`` under the band schedule of its own coords: each
    query tile fetches the band of key rows its windows touch."""
    h2, w2 = f2_level.shape[-3:-1]
    plan = corr_level_plan(f1.shape[1], h2, w2, q_blk=128,
                           p_blk_target=p_blk_target, radius=radius,
                           grid_w=grid_w)
    return _lookup_level(
        f1, f2_level, coords, radius, level, q_blk=128,
        p_blk_target=p_blk_target, interpret=False, grid_w=grid_w,
        schedule=level_schedule(coords, plan, level, radius), **kw)


BF16_L0 = dict(f1=jnp.bfloat16, f2=jnp.bfloat16)   # the encoder's own maps
BF16_X3 = dict(f1=jnp.bfloat16, f2=jnp.float32)    # a level pooled in float32


@pytest.mark.parametrize("name,level,kw,dtypes", [
    ("highest", 0, dict(corr_precision=P.HIGHEST, p_blk_target=4096), {}),
    ("default", 0, dict(corr_precision=P.DEFAULT, p_blk_target=4096), {}),
    ("scheduled", 0, dict(corr_precision=P.DEFAULT, p_blk_target=1024,
                          scheduled=True), {}),
    ("coarsest-level", 3, dict(corr_precision=P.HIGHEST,
                               p_blk_target=4096), {}),
    # bfloat16 maps at HIGHEST (ops/corr_pallas.corr_terms): a bfloat16 NT
    # matmul over one (16,128)-tiled f2 plane at level 0, three planes split
    # from a float32 pooled level at the others
    ("bf16-level0", 0, dict(corr_precision=P.HIGHEST, p_blk_target=4096),
     BF16_L0),
    ("bf16x3-level1", 1, dict(corr_precision=P.HIGHEST, p_blk_target=4096),
     BF16_X3),
    ("bf16x3-level3", 3, dict(corr_precision=P.HIGHEST, p_blk_target=4096),
     BF16_X3),
    ("bf16-scheduled", 0, dict(corr_precision=P.HIGHEST, p_blk_target=1024,
                               scheduled=True), BF16_L0),
    # 1080x1920: the levels the plan bands at the served 4096 positions (a
    # band of 16 rows as four blocks of 4: 4 x 256 lanes at level 0, 4 x 128
    # at levels 1 and 2), and level 3, which is one block
    ("hd-level0", 0, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                          scheduled=True, grid=HD), BF16_L0),
    ("hd-level1", 1, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                          scheduled=True, grid=HD), BF16_X3),
    ("hd-level2", 2, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                          scheduled=True, grid=HD), BF16_X3),
    ("hd-level3", 3, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                          grid=HD), BF16_X3),
    # 368x496, float32 as the train step holds its maps: rows of 62 and 31
    # columns stored in 64 and 32 lanes, two and four map rows to a 128-lane
    # row of the planes (PR 43); both levels hold more rows (46, 23) than a
    # band of 20
    ("crop-level0", 0, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                            scheduled=True, grid=CROP), {}),
    ("crop-level1", 1, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                            scheduled=True, grid=CROP), {}),
    # the window written in the update block's dtype (every case above
    # writes float32): a bfloat16 [T, 81] block, (16,128)-tiled, filled by
    # nine masked stores at static lane offsets; scheduled and not, at the
    # three grids
    ("out-bf16-level0", 0, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                                scheduled=True, out_dtype=jnp.bfloat16),
     BF16_L0),
    ("out-bf16-level3", 3, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                                out_dtype=jnp.bfloat16), BF16_X3),
    ("hd-out-bf16-level0", 0, dict(corr_precision=P.HIGHEST,
                                   p_blk_target=4096, scheduled=True,
                                   grid=HD, out_dtype=jnp.bfloat16), BF16_L0),
    ("hd-out-bf16-level1", 1, dict(corr_precision=P.HIGHEST,
                                   p_blk_target=4096, scheduled=True,
                                   grid=HD, out_dtype=jnp.bfloat16), BF16_X3),
    ("hd-out-bf16-level3", 3, dict(corr_precision=P.HIGHEST,
                                   p_blk_target=4096, grid=HD,
                                   out_dtype=jnp.bfloat16), BF16_X3),
    ("crop-out-bf16-level0", 0, dict(corr_precision=P.HIGHEST,
                                     p_blk_target=4096, scheduled=True,
                                     grid=CROP, out_dtype=jnp.bfloat16), {}),
    ("crop-out-bf16-level1", 1, dict(corr_precision=P.HIGHEST,
                                     p_blk_target=4096, scheduled=True,
                                     grid=CROP, out_dtype=jnp.bfloat16), {}),
    # RAFT-S at 1080x1920 (PR 31's cell): a 7x7 window, so eight taps a side
    # fill a float32 sublane tile exactly, and 128-channel maps
    ("small-hd-level0", 0, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                                scheduled=True, grid=HD, radius=3, c=128,
                                out_dtype=jnp.bfloat16), BF16_L0),
    ("small-hd-level1", 1, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                                scheduled=True, grid=HD, radius=3, c=128,
                                out_dtype=jnp.bfloat16), BF16_X3),
    ("small-hd-level2", 2, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                                scheduled=True, grid=HD, radius=3, c=128,
                                out_dtype=jnp.bfloat16), BF16_X3),
    ("hd-out-bf16-level2", 2, dict(corr_precision=P.HIGHEST,
                                   p_blk_target=4096, scheduled=True,
                                   grid=HD, out_dtype=jnp.bfloat16), BF16_X3),
    ("small-hd-level3", 3, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                                grid=HD, radius=3, c=128,
                                out_dtype=jnp.bfloat16), BF16_X3),
    # rows that share their 128 lanes (PR 43), as served at 440x1024: level
    # 1 (27 x 64, two to a row, banded: four blocks of [4 x 64] positions),
    # level 2 (13 x 32, four to a row, one block), level 3 (6 x 16, eight to
    # a row, one block: "coarsest-level" above, in float32); and a level
    # whose eight-to-a-row map is banded, which no served grid has
    ("packed-level1-banded", 1, dict(corr_precision=P.HIGHEST,
                                     p_blk_target=4096, scheduled=True,
                                     out_dtype=jnp.bfloat16), BF16_X3),
    ("packed-level2", 2, dict(corr_precision=P.HIGHEST, p_blk_target=4096,
                              out_dtype=jnp.bfloat16), BF16_X3),
    ("small-packed-level1-banded", 1, dict(
        corr_precision=P.HIGHEST, p_blk_target=4096, scheduled=True,
        radius=3, c=128, out_dtype=jnp.bfloat16), BF16_X3),
    ("packed-by-eight-banded", 3, dict(
        corr_precision=P.HIGHEST, p_blk_target=4096, scheduled=True,
        grid=(320, 128), out_dtype=jnp.bfloat16), BF16_X3),
])
def test_corr_kernel_compiles_for_v5e(one_chip, name, level, kw, dtypes):
    kw = dict(kw)
    grid = kw.pop("grid", (H, W))
    scheduled = kw.pop("scheduled", False)
    radius, c = kw.pop("radius", RADIUS), kw.pop("c", C)
    plan = corr_level_plan(grid[0] * grid[1], grid[0] >> level,
                           grid[1] >> level, q_blk=128,
                           p_blk_target=kw["p_blk_target"], radius=radius,
                           grid_w=grid[1])
    if grid != (H, W):  # the case is the program's: the plan bands the
        assert plan.banded == scheduled, name    # level or it does not
    else:               # (55x128's levels also as the all-rows walk)
        assert plan.banded or not scheduled, name
    if name.startswith(("packed", "small-packed")):
        assert plan.pack == (8 if "eight" in name else 1 << level), name
    if scheduled:
        fn = functools.partial(_scheduled_level, level=level, radius=radius,
                               grid_w=grid[1], **kw)
    else:
        fn = functools.partial(_lookup_level, radius=radius, level=level,
                               q_blk=128, interpret=False, grid_w=grid[1],
                               **kw)
    text = _compile(fn, *_corr_specs(one_chip, level, grid=grid, c=c,
                                     **dtypes))
    assert "tpu_custom_call" in text
    # the launch itself returns the lane-dense window in the dtype asked for
    out = "bf16" if kw.get("out_dtype") == jnp.bfloat16 else "f32"
    window = (2 * radius + 1) ** 2
    assert re.search(rf"= {out}\[1,{plan.qp},{window}\]\S* custom-call\(",
                     text), name
    # a banded launch's third grid dimension is an operand, the int32 scalar
    # ``schedule_steps`` reduced from the schedule that follows it ([B, Qb*K]
    # with the plan's K as its stride); the all-rows walk has a static grid
    dynamic = re.search(r"operand_layout_constraints=\{s32\[\], "
                        r"s32\[1,(\d+)\]", text)
    assert bool(dynamic) == scheduled, name
    if scheduled:
        assert int(dynamic.group(1)) == plan.qp // plan.t * plan.n_bands, name
    if dtypes:
        # the kernel was handed bfloat16 planes: nothing widened them first
        planes = 1 if dtypes is BF16_L0 else 3
        assert f"bf16[{planes},1," in text, name


@pytest.mark.parametrize("name,model,bucket,level", [
    ("sintel-level1", "things", (440, 1024), 1),
    ("hd-level0", "things", (1080, 1920), 0),
    ("hd-level1", "things", (1080, 1920), 1),
    ("small-hd-level1", "small", (1080, 1920), 1),
    ("crop-f32-level0", "things-f32", (368, 496), 0),
])
def test_corr_kernel_fits_the_envelope_the_analyzer_prices(
        one_chip, monkeypatch, name, model, bucket, level):
    """``lint/budget.corr_vmem_envelope`` is an upper envelope of what the
    chip's compiler wants of scoped VMEM for a launch: handed the analyzer's
    figure for the level as its limit, in place of the 32 MiB the kernels
    ask for, the compiler accepts the launch.  (By bisection here, PR 32,
    the least limit it accepts is 15.14 MiB where the analyzer prices 17.63,
    12.93 for 15.13, 10.55 for 11.38.)  Half the figure is refused where
    the three bfloat16 planes of a 4096 x 256 block alone take more: the
    limit binds."""
    from jax.experimental.pallas import tpu as pltpu

    from raft_tpu.config import RAFTConfig
    from raft_tpu.lint import budget
    from raft_tpu.ops import corr_pallas

    config = {"things": RAFTConfig.full(corr_impl="pallas",
                                        compute_dtype="bfloat16"),
              "things-f32": RAFTConfig.full(corr_impl="pallas"),
              "small": RAFTConfig.small_model(corr_impl="pallas",
                                              compute_dtype="bfloat16"),
              }[model]
    priced = budget.corr_vmem_envelope(config, bucket)["levels"][level]
    grid = (bucket[0] // 8, bucket[1] // 8)
    maps = jnp.dtype(config.compute_dtype)
    dtypes = dict(f1=maps, f2=maps if level == 0 else jnp.float32)
    specs = _corr_specs(one_chip, level, grid=grid, c=config.fnet_dim,
                        **dtypes)
    kw = dict(level=level, p_blk_target=config.pallas_p_blk,
              radius=config.corr_radius, corr_precision=P.HIGHEST,
              out_dtype=maps, grid_w=grid[1])
    if priced["plan"]["n_bands"] > 0:                # banded, as served
        launch = _scheduled_level
    else:
        launch = functools.partial(_lookup_level, q_blk=128, interpret=False)

    def compiles(limit: int) -> bool:
        monkeypatch.setattr(corr_pallas, "_COMPILER_PARAMS",
                            pltpu.CompilerParams(vmem_limit_bytes=limit))
        try:        # a fresh function: the limit is read as the call traces
            _compile(lambda *a: launch(*a, **kw), *specs)
        except Exception as e:  # noqa: BLE001 — the compiler's refusal
            assert "vmem" in str(e).lower(), e
            return False
        return True

    assert compiles(priced["block_bytes"]), (name, priced["block_bytes"])
    if name == "hd-level1":
        assert not compiles(priced["block_bytes"] // 2)


@pytest.mark.parametrize("level,dtypes,out_dtype", [
    (0, {}, jnp.float32), (0, BF16_L0, jnp.float32),
    (1, BF16_X3, jnp.float32), (0, BF16_L0, jnp.bfloat16),
    (1, BF16_X3, jnp.bfloat16)],
    ids=["f32", "bf16-level0", "bf16x3-level1", "out-bf16-level0",
         "out-bf16-level1"])
def test_ragged_corr_kernel_compiles_for_v5e(one_chip, level, dtypes,
                                             out_dtype):
    """The ``sizes``-operand (mixed-resolution) kernel at batch 2."""
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    f1, f2, coords = _corr_specs(one_chip, level, batch=2, **dtypes)
    fn = functools.partial(_ragged_lookup_level, radius=RADIUS, level=level,
                           q_blk=128, p_blk_target=4096, interpret=False,
                           grid_w=W, out_dtype=out_dtype)
    text = _compile(fn, f1, f2, coords, s((2, H * W), jnp.bool_),
                    s((2,), jnp.int32))
    out = "bf16" if out_dtype == jnp.bfloat16 else "f32"
    assert re.search(rf"= {out}\[1,{2 * H * W},81\]\S* custom-call\(", text)


@pytest.mark.parametrize("grid,dtype", [((H, W), jnp.float32),
                                        ((H, W), jnp.bfloat16),
                                        (HD, jnp.float32),
                                        (HD, jnp.bfloat16)],
                         ids=["f32-io", "bf16-io", "hd-f32-io", "hd-bf16-io"])
def test_gru_kernel_compiles_for_v5e(one_chip, grid, dtype):
    """Fused SepConvGRU at 55x128x128 and 135x240x128, ``gru_block_rows=8``.
    The f32 case at 55x128 needs 17.03M of scoped VMEM — over the compiler's
    16 MiB default; whole rows of 244 stored columns need 39.63M (bf16 I/O,
    alone; 53.23M inside the served 1080x1920 program, which the chip's
    compiler refused under 32 MiB: PR 26).  The kernel asks for what its row
    plan needs (kernel_plans.gru_vmem_limit)."""
    from raft_tpu.models.update import init_sep_conv_gru, precompute_gru_ctx
    from raft_tpu.ops.gru_pallas import sep_conv_gru_pallas

    def spec(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, dtype if jnp.issubdtype(a.dtype, jnp.floating)
                else a.dtype, sharding=one_chip), tree)

    hid = 128
    p = spec(jax.eval_shape(
        lambda: init_sep_conv_gru(jax.random.PRNGKey(0), hid, 256)))
    h = spec(jax.ShapeDtypeStruct((2, *grid, hid), dtype))
    ctx = spec(jax.eval_shape(
        lambda pp, i: precompute_gru_ctx(pp, i, hid), p, h))
    fn = functools.partial(sep_conv_gru_pallas, block_rows=8,
                           interpret=False, impl="kernel")
    assert "tpu_custom_call" in _compile(fn, p, h, h, ctx)


@pytest.fixture(scope="module")
def served_program(one_chip):
    """The one whole-program compile: the served pair executable's model —
    raft-things, bf16, both kernels, 12 iterations, 1x440x1024.  The
    backend check is steered here, in the test (this process's default
    backend is the CPU), not through a program option."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_inference_fn

    config = RAFTConfig.full(iters=12, compute_dtype="bfloat16",
                             corr_impl="pallas", gru_impl="pallas")
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_raft(jax.random.PRNGKey(0), config)))
    img = jax.ShapeDtypeStruct((1, 440, 1024, 3), jnp.float32,
                               sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        # both kernels ask jax.default_backend() whether to interpret (corr)
        # or to run the XLA twin (GRU, impl='auto')
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return jax.jit(make_inference_fn(config)).lower(
            params, img, img).compile()


def test_whole_inference_program_compiles_for_v5e(served_program):
    # 4 pyramid-level corr kernels + the fused GRU, inside the scan body
    assert served_program.as_text().count("tpu_custom_call") >= 5
    assert served_program.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


@pytest.mark.parametrize("pattern,launches", [(r"^corr_lookup\.", 4),
                                              (r"^gru\.", 1)])
def test_kernels_keep_the_names_the_benchmark_finds_them_by(
        served_program, pattern, launches):
    """The chip's trace names an operation by its instruction, and the
    compiler names a kernel's instruction after the innermost ``stage()``
    around it: ``benchmark/layer_metrics/{corr_lookup,gru}_roofline.json``
    match these patterns, so a renamed scope would silently read nothing."""
    from raft_tpu.telemetry.trace import instruction_stages
    insts = instruction_stages(served_program.as_text())
    kernels = [n for n, rec in insts.items() if re.search(pattern, n)
               and " custom-call(" in rec["text"]]
    assert len(kernels) == launches, kernels


def test_stage_map_of_the_served_program(served_program):
    """What the engine writes beside its AOT cache entry, for the program
    the benchmark's cell serves: each pyramid level's lookup under a scope
    of its own and every model stage that a per-layer metric reads."""
    from raft_tpu.telemetry.trace import instruction_stages
    insts = instruction_stages(served_program.as_text())
    stages = {rec["stage"] for rec in insts.values()}
    for level in range(4):
        scope = f"raft/corr_lookup/l{level}/corr_lookup"
        under = [n for n, rec in insts.items() if rec["stage"] == scope]
        assert any(n.startswith("corr_lookup.") for n in under), scope
    for scope in ("raft/preprocess", "raft/fnet", "raft/cnet",
                  "raft/corr_pyramid", "raft/gru_ctx", "raft/update",
                  "raft/upsample"):
        assert any(st == scope or st.startswith(scope + "/")
                   for st in stages), scope


def test_lookup_launches_hand_over_what_the_update_block_consumes(
        served_program):
    """The four launches of an iteration return ``bf16[1,7040,81]``: the
    compute dtype, a query's window side by side in the lanes.  Nothing
    under ``raft/corr_lookup`` converts or reshapes a ``[.., 9, 9]`` array
    any more (PR 29: four converts of a 25x-padded float32 array and the
    reshape of their result were 21-25 % of the served program's run)."""
    from raft_tpu.telemetry.trace import instruction_stages
    insts = instruction_stages(served_program.as_text())
    under = {n: rec["text"] for n, rec in insts.items()
             if (rec["stage"] or "").startswith("raft/corr_lookup")}
    launches = [t for n, t in under.items() if n.startswith("corr_lookup.")
                and " custom-call(" in t]
    assert len(launches) == 4
    for text in launches:
        assert re.search(r"= bf16\[1,7040,81\]\S* custom-call\(", text), text
    assert not [n for n in under if n.startswith("convert")], under.keys()
    assert not [n for n, t in under.items() if ",9,9]" in t], under.keys()
    assert "f32[1,7040,9,9]" not in served_program.as_text()


# ------------------------------------------ the served small program (PR 31)

@pytest.fixture(scope="module")
def served_small_program(one_chip):
    """RAFT-S as ``benchmark/configs/raft-small-1080p.json`` serves it: the
    configuration's own serve arguments through ``cli.parse_args`` /
    ``_make_config``, the engine's pair function (key-block counts beside
    the flow) at 8 x 1080x1920 — the batch the cell
    ``small-1080p-b8-closed`` times.  About a minute."""
    import json

    from raft_tpu import cli
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_inference_fn

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "raft-small-1080p.json")) as f:
        serve_args = [str(a) for a in json.load(f)["serve_args"]]
    args = cli.parse_args(["-m", "serve"] + serve_args)
    config = cli._make_config(args)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_raft(jax.random.PRNGKey(0), config)))
    img = jax.ShapeDtypeStruct((args.max_batch, 1080, 1920, 3), jnp.float32,
                               sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(make_inference_fn(
            config, iters=args.iters, keyblocks=True)).lower(
                params, img, img).compile()
    return config, compiled


def test_small_served_program_launches_the_radius_3_lookup(
        served_small_program):
    """Four ``corr_lookup.<n>`` launches an iteration, each returning a
    query's 7x7 window side by side in the lanes, ``bf16[8,32512,49]``
    (32,400 queries padded to whole tiles of 128); and no ``%gru.`` launch:
    the 3x3 ConvGRU runs as XLA convolutions."""
    from raft_tpu.telemetry.trace import instruction_stages
    _, compiled = served_small_program
    insts = instruction_stages(compiled.as_text())
    launches = [rec["text"] for n, rec in insts.items()
                if re.search(r"^corr_lookup\.", n)
                and " custom-call(" in rec["text"]]
    assert len(launches) == 4, launches
    for text in launches:
        assert re.search(r"= bf16\[8,32512,49\]\S* custom-call\(", text), text
    assert not [n for n in insts if re.search(r"^gru\.", n)]
    assert all(rec["loop"] == 1 for n, rec in insts.items()
               if n.startswith("corr_lookup."))


@pytest.mark.parametrize("scope", [
    "raft/update/update/gru", "raft/update/update/motion_encoder",
    "raft/update/update/heads", "raft/corr_lookup/l0/corr_lookup",
    "raft/corr_lookup/l1/corr_lookup", "raft/corr_lookup/l2/corr_lookup",
    "raft/corr_lookup/l3/corr_lookup", "raft/fnet", "raft/cnet",
    "raft/upsample", "raft/gru_ctx"])
def test_stage_map_of_the_small_served_program(served_small_program, scope):
    """What the three metrics of PR 31 and the stage readers the cell shares
    match on: the ConvGRU, the motion encoder and the flow head each under a
    scope of their own inside ``raft/update``, each level's lookup, both
    encoders, the upsampling."""
    from raft_tpu.telemetry.trace import instruction_stages
    _, compiled = served_small_program
    insts = instruction_stages(compiled.as_text())
    under = [n for n, rec in insts.items()
             if rec["stage"] == scope or rec["stage"].startswith(scope + "/")]
    assert under, scope
    if "corr_lookup" in scope:
        assert all(insts[n]["loop"] == 1 for n in under), scope
    if scope.startswith("raft/update/"):
        # inside the update loop, but for the weights' slices and relayouts
        # the compiler hoists out of it
        outside = [insts[n]["text"] for n in under if insts[n]["loop"] != 1]
        assert len(outside) < len(under), scope
        assert not [t for t in outside if "135,240" in t], outside
    if scope.endswith("update/gru"):
        # the gates' convolutions are XLA fusions that return the hidden
        # state's shape (z and r together: 192 channels), not a kernel launch
        texts = [insts[n]["text"] for n in under]
        assert any(re.search(r"= bf16\[8,135,240,192\]\S* fusion\(", t)
                   for t in texts), texts
        assert any(re.search(r"= bf16\[8,135,240,96\]\S* fusion\(", t)
                   for t in texts), texts
        assert not any("custom-call(" in t for t in texts)


def test_analyzer_prices_the_small_served_programs_temporaries(
        served_small_program):
    """``lint/budget.pair_temp_bytes`` against the chip compiler's own
    ``memory_analysis()`` of this very program: within 5 %, and the program
    fits a v5e with a second batch staged."""
    from raft_tpu.lint import budget
    config, compiled = served_small_program
    temp = compiled.memory_analysis().temp_size_in_bytes
    priced = budget.pair_temp_bytes(config, 1080, 1920, 8)
    assert abs(priced - temp) / temp < 0.05, (priced, temp)
    assert temp < 12e9


# ------------------------------- the served stream batch programs (PR 39)

@pytest.mark.parametrize("name,file,key_lanes", [
    # the packed planes the launches are handed (PR 43): positions x
    # channels of a band's granule block or of the one block, by level
    ("things-sintel-b32", "raft-things.json",
     ["bf16[1,32,8704,256]", "bf16[3,32,2560,256]", "bf16[3,32,512,256]",
      "bf16[3,32,128,256]"]),
    ("things-1080p-b8", "raft-things-1080p.json",
     ["bf16[1,8,37888,256]", "bf16[3,8,10240,256]", "bf16[3,8,3072,256]",
      "bf16[3,8,512,256]"]),
])
def test_pair_programs_compile_at_every_configurations_shape(
        one_chip, name, file, key_lanes):
    """The other two ``/v1/flow`` configurations' pair programs at their
    served frame and batch (``served_small_program`` is RAFT-S's,
    ``served_stream_programs`` the stream configurations'): the
    configuration's own serve arguments, key-block counts beside the flow.
    Mosaic takes the four launches, and each is handed its level's planes
    as the plan stores them: 2, 4 or 8 map rows to a 128-lane row where the
    level is 64, 32 or 16 columns wide (PR 43).  About a minute each."""
    import json

    from raft_tpu import cli
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_inference_fn
    from raft_tpu.telemetry.trace import instruction_stages

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", file)) as f:
        serve_args = [str(a) for a in json.load(f)["serve_args"]]
    args = cli.parse_args(["-m", "serve"] + serve_args)
    config = cli._make_config(args)
    h, w = (int(v) for v in args.buckets.split(",")[0].split("x"))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_raft(jax.random.PRNGKey(0), config)))
    img = jax.ShapeDtypeStruct((args.max_batch, h, w, 3), jnp.float32,
                               sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(make_inference_fn(
            config, iters=args.iters, keyblocks=True)).lower(
                params, img, img).compile().as_text()
    insts = instruction_stages(text)
    launches = sorted((rec["stage"], rec["text"]) for n, rec in insts.items()
                      if re.search(r"^corr_lookup\.", n)
                      and " custom-call(" in rec["text"])
    assert len(launches) == 4, launches
    for level, (stage_name, launch) in enumerate(launches):
        assert stage_name == f"raft/corr_lookup/l{level}/corr_lookup"
        assert key_lanes[level] in text, (name, level, key_lanes[level])


def _stream_programs(one_chip, name: str, kinds):
    """``kinds`` of the stream configuration ``benchmark/configs/<name>.json``
    at 1080x1920 as its ``serve_args`` serve them: the engine's own table of
    kinds (key-block counts beside the step's outputs, the pool donated into
    the commit) over a pool of ``--max-sessions`` + 1 rows, the kernels
    really in the step.  About 25 s for ``sbatch``, a second a commit."""
    import json

    from raft_tpu import cli
    from raft_tpu.models import init_raft
    from raft_tpu.serving.engine import Programs

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", name + ".json")) as f:
        serve_args = [str(a) for a in json.load(f)["serve_args"]]
    args = cli.parse_args(["-m", "serve"] + serve_args)
    config = cli._make_config(args)
    params = jax.eval_shape(lambda: init_raft(jax.random.PRNGKey(0), config))
    programs = Programs(config, params, args.max_sessions, iters=args.iters,
                        donate=True)

    def compiled(kind, b):
        prog = programs.program((kind, 1080, 1920, b, "fixed"))
        specs = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), prog.specs)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            return prog.fn.lower(*specs).compile()

    return programs, {key: compiled(*key) for key in kinds}


@pytest.fixture(scope="module")
def served_stream_programs(one_chip):
    """What ``things-stream-sessions`` runs a batched advance with, as
    ``benchmark/configs/raft-things-1080p-stream.json`` serves it: the stream
    batch program at 8 x 1080x1920 over a pool of 32 + 1 bfloat16 slots and
    the slot commit at the same width."""
    _, compiled = _stream_programs(one_chip, "raft-things-1080p-stream",
                                   (("sbatch", 8), ("scommit", 8)))
    return compiled[("sbatch", 8)], compiled[("scommit", 8)]


def test_stream_batch_program_fits_a_v5e_beside_its_pool(
        served_stream_programs):
    """Four lookup launches and the fused GRU in the loop, as the pair
    program has them; the temporaries of 8 advances, the pool and the
    outputs together leave the chip room (the cell reads its peak)."""
    step, commit = served_stream_programs
    assert step.as_text().count("tpu_custom_call") >= 5
    m = step.memory_analysis()
    held = m.temp_size_in_bytes + m.argument_size_in_bytes \
        + m.output_size_in_bytes
    assert 4 * 2 ** 30 < held < 12e9, held
    # the pool is 33 rows of 33.4 MB: two bfloat16 maps and a float32 seed
    pool = 33 * 135 * 240 * (2 * 256 * 2 + 2 * 4)
    assert m.argument_size_in_bytes > pool
    assert commit.memory_analysis().alias_size_in_bytes >= pool - 4096


def test_slot_io_metrics_find_the_gather_and_the_commit_program(
        served_stream_programs):
    """What ``benchmark/stream_metrics.py::slot_io_ms`` joins on: the stream
    batch program's own instructions (the map's ``loop`` 0) under
    ``raft/stream/gather`` hold the rows' slices and what joins them (the
    compiler's ``dynamic-slice`` fusions, the copies and the
    ``concatenate``s: the rows are MATERIALISED under the scope, 8 of them a
    leaf), none of them inside the update loop; and the trace will call the
    commit program ``jit_slot_commit``, not one more ``jit_fn``."""
    from raft_tpu.telemetry.trace import instruction_stages
    step, commit = served_stream_programs
    insts = instruction_stages(step.as_text())
    gather = {n: rec for n, rec in insts.items()
              if re.search(r"(^|/)stream/gather(/|$)", rec["stage"] or "")}
    assert gather and all(rec["loop"] == 0 for rec in gather.values())
    slices = [n for n in gather if "dynamic-slice" in n]
    assert slices and not [n for n in gather if n.startswith("while")], gather
    # each leaf's 8 rows are written under the scope, whole
    for leaf in ("bf16[8,135,240,256]", "f32[8,135,240,2]"):
        assert [n for n, rec in gather.items()
                if f" = {leaf}" in rec["text"]], (leaf, sorted(gather))
    # the lookup's launches are still found by their names, in the loop
    launches = [n for n, rec in insts.items() if re.search(r"^corr_lookup\.",
                n) and " custom-call(" in rec["text"]]
    assert len(launches) == 4 and all(insts[n]["loop"] == 1
                                      for n in launches)
    assert [n for n in insts if re.search(r"^gru\.", n)]
    assert re.search(r"^HloModule jit_slot_commit\b", commit.as_text())
    assert re.search(r"^HloModule jit_fn\b", step.as_text())


# ------------------------------------ the int8 slot pool's programs (PR 45)

@pytest.fixture(scope="module")
def int8_pool_programs(one_chip):
    """The programs of ``raft-things-1080p-stream-int8`` (bfloat16 rows in,
    int8 slots) at 1080x1920 over its pool of 256 slots and the scratch row,
    as ``things-stream-int8-pool`` runs them: the batched step, the commits
    and the chaos arm's poison."""
    return _stream_programs(
        one_chip, "raft-things-1080p-stream-int8",
        (("sbatch", 8), ("scommit", 8), ("scommit", 1), ("spoison", 1)))


@pytest.mark.parametrize("kind,b", [("scommit", 8), ("scommit", 1),
                                    ("spoison", 1)])
def test_int8_scatter_programs_update_the_pool_in_place_on_the_chip(
        int8_pool_programs, kind, b):
    """Every leaf of the pool that goes in is aliased to the output that
    takes its place (``alias_size_in_bytes`` is the outputs' size to the
    runtime's alignment): at 4.33 GB a second copy of the pool would not fit
    beside the batched step."""
    from raft_tpu.lint.budget import tree_bytes
    programs, compiled = int8_pool_programs
    ma = compiled[(kind, b)].memory_analysis()
    pool = programs.slot_specs(1080, 1920)
    want = tree_bytes(pool if kind == "scommit" else pool[0])
    # (a seed row's [240, 2] is laid out in tiles of 128 lanes, a [257, 256]
    # float32 leaf in tiles of eight rows: 0.1 % over the shapes' bytes)
    assert want <= ma.alias_size_in_bytes <= want + want // 500
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes < 64 * 1024


@pytest.mark.parametrize("kind,b", [("scommit", 8), ("scommit", 1)])
def test_int8_commits_move_their_rows_and_not_the_pool(int8_pool_programs,
                                                       kind, b):
    """A commit is ``b`` one-row ``dynamic-update-slice``s into the donated
    leaf (PR 46): its temporaries are the batch's quantised rows (8 rows of
    codes, 66 MB, beside a leaf of 2.13 GB), nothing slices a whole leaf
    (PR 45 pinned the general scatter's ``mini-gather-slice`` and its copy of
    a leaf here), and the pool is still updated in place."""
    from raft_tpu.lint.budget import bytes_of, tree_bytes
    programs, compiled = int8_pool_programs
    pool = programs.slot_specs(1080, 1920)
    leaf = bytes_of(pool[0][0])
    assert leaf == 257 * 135 * 240 * 256
    ma, text = compiled[(kind, b)].memory_analysis(), compiled[
        (kind, b)].as_text()
    assert ma.temp_size_in_bytes < leaf // 16
    assert "dynamic-update-slice" in text
    assert "mini-gather-slice" not in text
    assert not re.search(r"\[257,135,240,128\]", text)
    assert ma.alias_size_in_bytes >= tree_bytes(pool)


def _configs(text: str):
    """name -> (result shape, op_name, backend_config) of every instruction
    of a compiled text that carries the TPU compiler's ``backend_config``."""
    import json
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ", line)
        if not m or "backend_config={" not in line:
            continue
        try:
            cfg, _ = json.JSONDecoder().raw_decode(
                line[line.index("backend_config=") + len("backend_config="):])
        except ValueError:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        out[m.group(1)] = (m.group(2), op.group(1) if op else "", cfg)
    return out


def _encoder_windows(text: str) -> list:
    """The encoders' windowed instructions in program order: (op_name,
    result shape, the window the compiler chose, its estimate)."""
    keys = ("kernel_window_bounds", "output_window_bounds",
            "input_window_bounds", "iteration_bounds", "estimated_cycles")
    return [(op, shape.split("{")[0]) + tuple(
        str(cfg["window_config"].get(k)) for k in keys)
        for shape, op, cfg in _configs(text).values()
        if re.search(r"/raft/(fnet|cnet)/", op) and "window_config" in cfg]


@pytest.fixture(scope="module")
def stream_batch_texts(served_stream_programs, int8_pool_programs):
    """The batched step's compiled text beside 33 bfloat16 rows (sessions,
    churn) and beside 257 int8 rows (the int8 cell)."""
    return {33: served_stream_programs[0].as_text(),
            257: int8_pool_programs[1][("sbatch", 8)].as_text()}


@pytest.mark.parametrize("rows", [33, 257])
def test_no_pool_leaf_is_parked_in_the_batched_steps_scoped_memory(
        stream_batch_texts, rows):
    """PR 46's finding.  The general gather ``buf[slots]`` made its operand a
    cross-program-prefetch candidate: at 33 rows the seed leaf (9.1 MB in its
    tiling) was parked at the bottom of memory space 1 for the program's
    life, every fusion's ``scoped_memory_configs`` began at offset 9,125,888
    of 16,777,216, and the half-resolution 3x3 convolution 96->96 fell to
    output windows of 3x6 (``estimated_cycles`` 259.6 M for 4.0 M; two
    ``convert_reduce`` fusions 214.9 M each).  Row-wise slices are no
    candidate at any pool size: nothing of the pool is prefetched, every
    fusion plans in the whole scoped memory, and no instruction has a whole
    leaf's shape."""
    text = stream_batch_texts[rows]
    parked = [ln.strip()[:120] for ln in text.splitlines()
              if "cross_program_prefetch_index" in ln
              and re.search(rf"\[{rows},", ln)]
    assert not parked, parked
    configs = _configs(text)
    offsets = {int(c["offset"]) for _, _, cfg in configs.values()
               for c in cfg.get("scoped_memory_configs", [])}
    assert offsets and max(offsets) < 2 ** 20, sorted(offsets)
    assert not re.search(rf"\[{rows},135,240,128\]", text)
    assert "mini-gather" not in text
    convs = {n: int(cfg["window_config"]["estimated_cycles"])
             for n, (shape, op, cfg) in configs.items()
             if shape.startswith("bf16[8,270,480,96]")
             and op.endswith("/layer2/conv_general_dilated")
             and "window_config" in cfg}
    assert len(convs) >= 3 and max(convs.values()) < 10e6, convs
    slow = {n: int(cfg["window_config"]["estimated_cycles"])
            for n, (_, op, cfg) in configs.items()
            if re.search(r"/raft/(fnet|cnet)/", op) and "window_config" in cfg
            and int(cfg["window_config"]["estimated_cycles"]) > 25e6}
    assert not slow, slow


def test_the_encoder_compiles_the_same_beside_either_pool(stream_batch_texts):
    """The two 33-row cells and the int8 cell run one encoder: the same
    instructions with the same windows, window for window (before PR 46 the
    33-row program's were the squeezed ones)."""
    small, large = (_encoder_windows(stream_batch_texts[r])
                    for r in (33, 257))
    assert len(small) > 40
    assert small == large


def test_int8_commit_on_the_chip_files_its_quantiser_under_its_scope(
        int8_pool_programs):
    from raft_tpu.telemetry.trace import instruction_stages
    _, compiled = int8_pool_programs
    for key in (("scommit", 8), ("scommit", 1)):
        insts = instruction_stages(compiled[key].as_text())
        stages = {rec["stage"] for rec in insts.values()}
        assert "raft/stream/commit/quant" in stages, key
        assert all(st.startswith("raft/stream/commit")
                   for st in stages if st), key
