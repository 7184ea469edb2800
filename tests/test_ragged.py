"""Ragged mixed-resolution serving tests (tier-1, CPU): kernel-level parity
of the ragged fused lookup against per-crop dense lookups, the max-box
arena slot pool, the cross-resolution batcher policy on a stub engine, the
warmup-grid collapse the lint budget prices, and a live mixed-resolution
server whose answers must equal each resolution's solo run bit-for-bit.

The live fixture is module-scoped so its (one-arena) warmup grid compiles
once; everything else never compiles a model.
"""

import dataclasses
import json
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.serving import (FlowServer, MicroBatcher, Request,
                              RequestQueue, ServeConfig)


# ------------------------------------------------ kernel: ragged lookup --

def _ragged_case(sizes, Hm, Wm, C, seed=0):
    """Zero-embedded feature stacks + per-item crops for the parity checks."""
    rng = np.random.RandomState(seed)
    B = len(sizes)
    f1 = np.zeros((B, Hm, Wm, C), np.float32)
    f2 = np.zeros((B, Hm, Wm, C), np.float32)
    crops1, crops2 = [], []
    for b, (h, w) in enumerate(sizes):
        c1 = rng.randn(h, w, C).astype(np.float32)
        c2 = rng.randn(h, w, C).astype(np.float32)
        f1[b, :h, :w], f2[b, :h, :w] = c1, c2
        crops1.append(c1)
        crops2.append(c2)
    flow = rng.randn(B, Hm, Wm, 2).astype(np.float32) * 3.0
    from raft_tpu.ops.coords import coords_grid
    coords = np.asarray(coords_grid(B, Hm, Wm)) + flow
    return f1, f2, crops1, crops2, coords


@pytest.mark.parametrize("sizes,Hm,Wm,C,levels,radius", [
    ([(16, 24), (8, 8), (13, 19)], 16, 24, 32, 3, 4),   # odd extent included
    ([(12, 16), (12, 16)], 12, 16, 16, 3, 3),           # all items at the box
    ([(8, 8)], 10, 14, 8, 2, 2),                        # solo, odd max box
])
def test_ragged_lookup_matches_dense_per_item(sizes, Hm, Wm, C, levels,
                                              radius):
    """Each row of the ragged lookup must equal the standalone dense lookup
    on that row's crop (corner-anchored zero embedding + per-level
    re-masking reproduces each crop's own pyramid), and the dead region
    beyond every extent must be exact zeros."""
    from raft_tpu.ops.corr_pallas import (make_fused_lookup,
                                          make_ragged_fused_lookup)

    f1, f2, crops1, crops2, coords = _ragged_case(sizes, Hm, Wm, C)
    lookup = make_ragged_fused_lookup(jnp.asarray(f1), jnp.asarray(f2),
                                      jnp.asarray(np.asarray(sizes, np.int32)),
                                      levels, radius)
    out = np.asarray(lookup(jnp.asarray(coords)))
    for b, (h, w) in enumerate(sizes):
        dl = make_fused_lookup(jnp.asarray(crops1[b][None]),
                               jnp.asarray(crops2[b][None]), levels, radius)
        dense = np.asarray(dl(jnp.asarray(coords[b:b + 1, :h, :w])))
        np.testing.assert_allclose(out[b, :h, :w], dense[0],
                                   rtol=1e-4, atol=1e-4)
        dead = out[b].copy()
        dead[:h, :w] = 0
        assert np.abs(dead).max() == 0.0, f"item {b} dead region nonzero"


def test_ragged_lookup_bf16_inputs():
    """bf16 feature inputs go through the maker's f32 accumulation policy:
    close to the f32-input run, never NaN/garbage."""
    from raft_tpu.ops.corr_pallas import make_ragged_fused_lookup

    sizes = [(16, 24), (13, 19)]
    f1, f2, _, _, coords = _ragged_case(sizes, 16, 24, 16, seed=2)
    sz = jnp.asarray(np.asarray(sizes, np.int32))
    out = np.asarray(make_ragged_fused_lookup(
        jnp.asarray(f1), jnp.asarray(f2), sz, 3, 4)(jnp.asarray(coords)))
    bf = [jnp.asarray(f).astype(jnp.bfloat16) for f in (f1, f2)]
    out_bf = make_ragged_fused_lookup(*bf, sz, 3, 4)(jnp.asarray(coords))
    assert np.isfinite(np.asarray(out_bf)).all()
    np.testing.assert_allclose(np.asarray(out_bf), out, rtol=0.05, atol=0.05)
    # written in the update block's dtype: those float32 sums rounded once,
    # the dead region exact zeros still
    written = make_ragged_fused_lookup(
        *bf, sz, 3, 4, out_dtype=jnp.bfloat16)(jnp.asarray(coords))
    assert written.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(written).view(np.uint16),
        np.asarray(out_bf.astype(jnp.bfloat16)).view(np.uint16))
    assert not np.asarray(written)[1, 13:].view(np.uint16).any()


@pytest.mark.parametrize("out", [jnp.float32, jnp.bfloat16],
                         ids=["out-f32", "out-bf16"])
def test_ragged_lookup_gradients_masked(out):
    """The custom_vjp backward must be finite everywhere and EXACTLY zero on
    dead-region fmap rows — the mask sits upstream of the kernel, so no
    gradient can leak into a crop's embedding.  With a bfloat16 output the
    cotangent arrives in bfloat16 and is raised to float32 for the twin."""
    from raft_tpu.ops.corr_pallas import make_ragged_fused_lookup

    sizes = [(16, 24), (8, 8), (13, 19)]
    f1, f2, _, _, coords = _ragged_case(sizes, 16, 24, 16, seed=3)
    sz = jnp.asarray(np.asarray(sizes, np.int32))

    def loss(a, c):
        lk = make_ragged_fused_lookup(a, jnp.asarray(f2), sz, 3, 4,
                                      out_dtype=out)
        return jnp.sum(jnp.sin(lk(c).astype(jnp.float32)))

    g1, gc = jax.grad(loss, argnums=(0, 1))(jnp.asarray(f1),
                                            jnp.asarray(coords))
    g1, gc = np.asarray(g1), np.asarray(gc)
    assert np.isfinite(g1).all() and np.isfinite(gc).all()
    assert np.abs(g1).max() > 0                   # gradient actually flows
    for b, (h, w) in enumerate(sizes):
        dead = g1[b].copy()
        dead[:h, :w] = 0
        assert np.abs(dead).max() == 0.0, f"item {b} dead grad nonzero"


# ------------------------------------------------- model: solo == mixed --

def test_ragged_model_solo_vs_mixed_and_garbage_embed():
    """One ragged inference fn serving two resolutions at once: each row
    must match its own solo run (solo jits a batch-1 program, so only
    reduction reassociation separates them), and garbage written into the
    dead embedding must not change outputs AT ALL — same executable, so
    the in-graph re-mask is a bitwise determinism contract."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models.raft import init_raft, make_inference_fn

    config = RAFTConfig.small_model(iters=2, corr_impl="pallas")
    params = init_raft(init_rng(0), config)
    fn = jax.jit(make_inference_fn(config, iters=2))

    Hm, Wm = 32, 48
    rng = np.random.RandomState(1)
    sizes = np.array([[32, 48], [16, 24]], np.int32)
    ims = np.zeros((2, 2, Hm, Wm, 3), np.float32)      # [frame, b, H, W, 3]
    for b, (h, w) in enumerate(sizes):
        for f in range(2):
            ims[f, b, :h, :w] = rng.rand(h, w, 3)

    flow = np.asarray(fn(params, jnp.asarray(ims[0]), jnp.asarray(ims[1]),
                         jnp.asarray(sizes)))
    assert flow.shape == (2, Hm, Wm, 2)
    for b, (h, w) in enumerate(sizes):
        solo = np.asarray(fn(params, jnp.asarray(ims[0, b:b + 1]),
                             jnp.asarray(ims[1, b:b + 1]),
                             jnp.asarray(sizes[b:b + 1])))
        np.testing.assert_allclose(solo[0, :h, :w], flow[b, :h, :w],
                                   rtol=1e-3, atol=1e-3)

    ims_g = ims.copy()
    for b, (h, w) in enumerate(sizes):
        dead = np.ones((Hm, Wm), bool)
        dead[:h, :w] = False
        for f in range(2):
            ims_g[f, b][dead] = rng.rand(int(dead.sum()), 3)
    flow_g = np.asarray(fn(params, jnp.asarray(ims_g[0]),
                           jnp.asarray(ims_g[1]), jnp.asarray(sizes)))
    for b, (h, w) in enumerate(sizes):
        err = np.abs(flow_g[b, :h, :w] - flow[b, :h, :w]).max()
        assert err == 0.0, (b, err)


@pytest.mark.parametrize("kind", ["pair", "stream", "sbatch"])
def test_full_box_sizes_agree_with_the_dense_program(kind):
    """``sizes`` is what makes a served program the ragged one: with every
    row live on the whole box, the ragged program of each lookup kind and
    the dense program of the same factory give the same flow (and the same
    maps back), to the tolerance solo and mixed rows are held to."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models.raft import (encode_frame, init_raft,
                                      make_inference_fn,
                                      make_stream_batch_step_fn,
                                      make_stream_step_fn)

    config = RAFTConfig.small_model(iters=2, corr_impl="pallas")
    params = init_raft(init_rng(0), config)
    b, (h, w) = 2, (32, 48)
    rng = np.random.RandomState(3)
    im1, im2 = (jnp.asarray(rng.rand(b, h, w, 3).astype(np.float32))
                for _ in range(2))
    full = jnp.tile(jnp.asarray([[h, w]], jnp.int32), (b, 1))
    if kind == "pair":
        fn, args = make_inference_fn(config), (im1, im2)
    else:
        fmap, cnet = encode_frame(params, im1, config)
        seed = jnp.asarray(rng.randn(b, h // 8, w // 8, 2)
                           .astype(np.float32))
        if kind == "stream":
            fn, args = make_stream_step_fn(config), (im2, fmap, cnet, seed)
        else:
            # a pool of b slots and the scratch row, the rows in slots 1, 0
            pool = [jnp.concatenate([x, jnp.zeros_like(x[:1])])
                    for x in (fmap, cnet, seed)]
            fn = make_stream_batch_step_fn(config)
            args = (im2[::-1], *pool, jnp.asarray([1, 0], jnp.int32),
                    jnp.ones((b,), bool))
    fn = jax.jit(fn)
    dense = fn(params, *args)
    ragged = fn(params, *args, full)
    assert jax.tree.structure(dense) == jax.tree.structure(ragged)
    for d, r in zip(jax.tree.leaves(dense), jax.tree.leaves(ragged)):
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.asarray(d, np.float32),
                                   rtol=1e-3, atol=1e-3)


# --------------------------------------------------- embed + slot arena --

def test_embed_to_shape_round_trip():
    from raft_tpu.data.pipeline import embed_to_shape

    rng = np.random.RandomState(7)
    im = rng.rand(1, 13, 19, 3).astype(np.float32)
    out = embed_to_shape(im, (16, 24))
    assert out.shape == (1, 16, 24, 3)
    np.testing.assert_array_equal(out[:, :13, :19], im)
    assert np.abs(out[:, 13:]).max() == 0.0 and np.abs(out[:, :, 19:]).max() == 0.0
    with pytest.raises(ValueError):
        embed_to_shape(im, (13, 18))


def test_slot_pool_arena_round_trip():
    """Every routed bucket maps onto ONE shared arena free-list: cross-
    bucket allocs draw from the same capacity, extents track live pixels,
    and free() returns the slot to every bucket's view."""
    from raft_tpu.serving.session import SlotPool

    arena = (32, 48)
    pool = SlotPool(2, arena=arena)
    s0 = pool.alloc((16, 24))
    s1 = pool.alloc((32, 48))                     # different routed bucket
    assert s0 is not None and s1 is not None and s0 != s1
    assert pool.alloc((24, 32)) is None           # shared capacity exhausted
    assert pool.in_use((16, 24)) == pool.in_use((32, 48)) == 2

    pool.set_extent((16, 24), s0, (16, 24))
    pool.set_extent((32, 48), s1, (32, 48))
    assert pool.extent((16, 24), s0) == (16, 24)
    assert pool.used_pixels(arena) == 16 * 24 + 32 * 48

    pool.free((16, 24), s0)                       # extent cleared with slot
    assert pool.used_pixels(arena) == 32 * 48
    assert pool.in_use((24, 32)) == 1
    s2 = pool.alloc((24, 32))                     # freed slot reusable from
    assert s2 == s0                               # any routed bucket

    # buffers installed under one bucket key are visible under all of them
    pool.install(arena, {"fmap": np.zeros((2, 4, 6, 8), np.float32)})
    assert pool.buffers((16, 24)) is pool.buffers((24, 32))


def test_slot_pool_dense_mode_unchanged():
    """arena=None keeps the per-bucket free-list semantics (dense serving)."""
    from raft_tpu.serving.session import SlotPool

    pool = SlotPool(1)
    a = pool.alloc((16, 24))
    b = pool.alloc((32, 48))                      # independent bucket
    assert a is not None and b is not None
    assert pool.in_use((16, 24)) == 1 and pool.in_use((32, 48)) == 1


# -------------------------------------------- batcher: ragged coalesce --

class _RaggedStubEngine:
    """Records (bucket, padded, rbuckets-tuple) per device call."""

    def __init__(self):
        self.calls = []

    def run(self, bucket, im1, im2, sizes):
        self.calls.append((bucket, im1.shape[0],
                           tuple(map(tuple, np.asarray(sizes).tolist()))))
        return np.zeros(im1.shape[:3] + (2,), np.float32)


def _ragged_request(rbucket, box=(32, 48), deadline_s=30.0):
    bh, bw = box
    h, w = rbucket
    im = np.zeros((1, bh, bw, 3), np.float32)
    return Request(im, im, box, (0, bh - h, 0, bw - w),
                   deadline=time.monotonic() + deadline_s, rbucket=rbucket)


def test_batcher_ragged_coalesces_across_resolutions():
    """Under --ragged, requests routed to DIFFERENT buckets queue under the
    one max-box key and ride one device call, with per-row sizes handed to
    the engine (padding rows repeat the last row's size)."""
    eng = _RaggedStubEngine()
    q = RequestQueue(16)
    b = MicroBatcher(q, eng.run, lambda n: {1: 1, 2: 2, 3: 4, 4: 4}[n],
                     4, 10_000.0, ragged=True)
    b.start()
    rbs = [(16, 24), (32, 48), (24, 32), (16, 24)]
    reqs = [_ragged_request(rb) for rb in rbs]
    for r in reqs:
        q.submit(r)
    flows = [r.wait(timeout=10) for r in reqs]
    assert [f.shape for f in flows] == [rb + (2,) for rb in rbs]  # unpadded
    assert len(eng.calls) == 1                    # cross-resolution coalesce
    bucket, padded, sizes = eng.calls[0]
    assert bucket == (32, 48) and padded == 4
    assert sizes == ((16, 24), (32, 48), (24, 32), (16, 24))
    q.close()
    b.join(5)


def test_batcher_ragged_footprint_chunks():
    """ragged_batch_pixels caps a batch's LIVE pixels: a full-batch pop is
    greedily split by each row's routed-resolution footprint (not row
    count), so mixing tiny and huge frames can't balloon one device
    call."""
    eng = _RaggedStubEngine()
    q = RequestQueue(16)
    b = MicroBatcher(q, eng.run, lambda n: {1: 1, 2: 2, 3: 4, 4: 4}[n],
                     4, 10_000.0, ragged=True,
                     ragged_batch_pixels=2 * 32 * 48)
    b.start()
    # live pixels 1536 + 384 + 384 fit the 3072 budget; the second full
    # box would overflow it -> the 4-row pop splits 3 + 1
    rbs = [(32, 48), (16, 24), (16, 24), (32, 48)]
    reqs = [_ragged_request(rb) for rb in rbs]
    for r in reqs:
        q.submit(r)
    for r in reqs:
        r.wait(timeout=10)
    # 3 live rows padded to step 4 (padding repeats the last row's size),
    # then the overflowed full box rides alone
    assert [(p, s) for _, p, s in eng.calls] == [
        (4, ((32, 48), (16, 24), (16, 24), (16, 24))),
        (1, ((32, 48),))], eng.calls
    q.close()
    b.join(5)


def test_batcher_chunks_helper_edge_cases():
    q = RequestQueue(4)
    b = MicroBatcher(q, lambda *a: None, lambda n: n, 4, 5.0,
                     ragged=True, ragged_batch_pixels=10)
    one = _ragged_request((32, 48))               # 1536 px >> budget
    assert b._chunks([one]) == [[one]]            # never splits below a row
    pair = [_ragged_request((32, 48)), _ragged_request((16, 24))]
    assert b._chunks(pair) == [[pair[0]], [pair[1]]]
    b.ragged_batch_pixels = 0
    assert b._chunks(pair) == [pair]              # 0 = unbounded
    q.close()


# ------------------------------------------------ budget: grid collapse --

def test_budget_grid_collapses_under_ragged():
    """The lint budget prices ONE executable family at the max box under
    --ragged: >= 3x fewer warmup keys at 3 declared buckets, every key at
    the arena shape, and the budget baseline signature records the mode."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.lint.budget import config_signature
    from raft_tpu.serving.config import enumerate_warmup_grid

    mconfig = RAFTConfig.small_model(iters=1)
    mk = lambda ragged: ServeConfig(
        buckets=((16, 24), (24, 32), (32, 48)), max_batch=2,
        max_sessions=2, ragged=ragged, port=0)
    dense, ragged = mk(False), mk(True)
    gd = enumerate_warmup_grid(mconfig, dense)
    gr = enumerate_warmup_grid(mconfig, ragged)
    assert len(gd) == 3 * len(gr)                 # the >=3x collapse
    assert {(h, w) for _, h, w, _, _ in gr} == {(32, 48)}
    sig = lambda sc: config_signature(mconfig, sc, True, False)
    assert sig(dense)["ragged"] is False
    assert sig(ragged)["ragged"] is True


# ------------------------------------- live server: mixed-res one arena --

@pytest.fixture(scope="module")
def ragged_server():
    """A ragged live server over three declared resolutions sharing one
    32x48 arena.  max_wait 150ms so concurrent posts coalesce; pallas corr
    so the ragged kernel path (not just the XLA twin) is what serves."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=2, corr_impl="pallas")
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=((16, 24), (24, 32), (32, 48)),
                          max_batch=2, max_wait_ms=150.0, queue_depth=16,
                          default_deadline_ms=30_000.0, port=0,
                          max_sessions=2, session_ttl_s=600.0, ragged=True)
    server = FlowServer(config, params, sconfig)
    server.start()
    yield server, config, params
    server.stop()


def test_ragged_warmup_one_executable_family(ragged_server):
    """Acceptance criterion: one executable per (kind, batch-step, policy)
    serves every declared resolution — the warmup grid holds ONLY max-box
    keys, exactly the set the lint budget enumerated, and its dense twin
    would have been 3x larger."""
    from raft_tpu.serving.config import enumerate_warmup_grid

    server, config, _ = ragged_server
    eng = server.engine
    keys = eng.keys()
    assert {(h, w) for _, h, w, _, _ in keys} == {(32, 48)}
    assert sorted(keys) == sorted(enumerate_warmup_grid(config,
                                                        server.sconfig))
    dense_twin = dataclasses.replace(server.sconfig, ragged=False)
    assert len(enumerate_warmup_grid(config, dense_twin)) == 3 * len(keys)
    assert eng.compile_misses == 0


def test_ragged_mixed_equals_solo(ragged_server):
    """THE parity criterion: three resolutions served concurrently through
    shared batches must each match the same request served alone.  Norms
    run over the max box either way, so the only difference is the padded
    batch step (1 solo vs 2 mixed) reassociating reductions."""
    from concurrent.futures import ThreadPoolExecutor

    server, _, _ = ragged_server
    rng = np.random.RandomState(11)
    sizes = [(15, 20), (22, 30), (30, 44)]        # route to all 3 buckets
    pairs = [(rng.rand(h, w, 3).astype(np.float32),
              rng.rand(h, w, 3).astype(np.float32)) for h, w in sizes]
    solo = [np.asarray(server.infer(a, b).result) for a, b in pairs]
    misses = server.engine.compile_misses
    with ThreadPoolExecutor(max_workers=3) as ex:
        futs = [ex.submit(server.infer, a, b) for a, b in pairs]
        mixed = [np.asarray(f.result().result) for f in futs]
    for (h, w), s, m in zip(sizes, solo, mixed):
        assert s.shape == m.shape == (h, w, 2)
        np.testing.assert_allclose(s, m, rtol=1e-3, atol=1e-3)
    assert server.engine.compile_misses == misses  # zero post-warmup compiles


def _post(server, path, payload):
    req = urllib.request.Request(
        server.url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_ragged_stream_mixed_resolutions(ragged_server):
    """Two streams at different resolutions share the one arena: both stay
    warm across advances, the first advance equals the pairwise answer on
    the same frames, and nothing compiles."""
    server, _, _ = ragged_server
    eng = server.engine
    misses = eng.compile_misses
    rng = np.random.RandomState(12)
    sessions = {}
    for hw in [(15, 20), (30, 44)]:
        frames = [rng.rand(hw[0], hw[1], 3).astype(np.float32)
                  for _ in range(3)]
        sid = _post(server, "/v1/stream",
                    {"image": frames[0].tolist()})["session"]
        sessions[hw] = (sid, frames)
    for hw, (sid, frames) in sessions.items():
        r1 = _post(server, "/v1/stream",
                   {"session": sid, "image": frames[1].tolist()})
        assert r1["meta"]["warm"] is True
        flow1 = np.asarray(r1["flow"], np.float32)
        assert flow1.shape == hw + (2,)
        pw = _post(server, "/v1/flow", {"image1": frames[0].tolist(),
                                        "image2": frames[1].tolist()})
        np.testing.assert_allclose(flow1, np.asarray(pw["flow"], np.float32),
                                   rtol=1e-4, atol=1e-2)
        r2 = _post(server, "/v1/stream",
                   {"session": sid, "image": frames[2].tolist()})
        assert r2["meta"]["warm"] is True
        assert np.isfinite(np.asarray(r2["flow"])).all()
    assert eng.compile_misses == misses
    for sid, _ in sessions.values():
        _post(server, "/v1/stream", {"op": "close", "session": sid})


def test_ragged_demoted_session_restarts_at_the_place(ragged_server):
    """A session that lost its slot while parked comes back as a row of its
    group's batched call at the shared max box: re-seated at the place with
    its ROUTED bucket's extent and ``sizes``, answered ``warm: false`` with
    the pairwise answer on the same frames, beside a warm row of another
    resolution; no solo step, nothing compiles, and the next advance is
    warm."""
    from concurrent.futures import ThreadPoolExecutor

    server, _, _ = ragged_server
    eng, streams, pool = server.engine, server.streams, server.streams.pool
    misses = eng.compile_misses
    rng = np.random.RandomState(14)
    sizes = [(15, 20), (30, 44)]
    clips = [[rng.rand(h, w, 3).astype(np.float32) for _ in range(4)]
             for h, w in sizes]
    sids = [streams.open(c[0], None)["session"] for c in clips]

    def both(t):
        with ThreadPoolExecutor(2) as ex:
            return list(ex.map(lambda k: streams.advance(
                sids[k], clips[k][t], None), (0, 1)))

    assert [r["meta"]["warm"] for r in both(1)] == [True, True]
    small = streams.store.get(sids[0])
    assert small.bucket == (16, 24)
    streams.store.demote(small, "lru")          # LRU, while it was parked
    assert pool.used_pixels(small.bucket) == 32 * 48
    calls = eng.encode_calls, eng.stream_calls
    batched = streams.metrics["restarts_batched"].value
    cold, warm = both(2)
    assert (cold["meta"]["warm"], warm["meta"]["warm"]) == (False, True)
    assert cold["meta"]["batch_real"] == warm["meta"]["batch_real"] == 2
    # one encoder pass of the kept frame, two rows of one batched step
    assert (eng.encode_calls - calls[0], eng.stream_calls - calls[1]) \
        == (1, 2)
    assert streams.metrics["restarts_batched"].value - batched == 1
    # its page is back in the arena at its own extent
    assert pool.extent(small.bucket, small.slot) == (16, 24)
    assert pool.used_pixels(small.bucket) == 16 * 24 + 32 * 48
    assert cold["flow"].shape == (15, 20, 2)
    pair = server.infer(clips[0][1], clips[0][2]).result
    np.testing.assert_allclose(cold["flow"], pair, rtol=1e-4, atol=1e-2)
    after = both(3)
    assert [r["meta"]["warm"] for r in after] == [True, True]
    assert all(np.isfinite(r["flow"]).all() for r in after)
    assert eng.compile_misses == misses
    for sid in sids:
        streams.close(sid)


def test_ragged_metrics_waste_and_arena(ragged_server):
    """The padding-waste histogram fills from both pairwise and stream
    batches, and the arena live-pixel gauge is exposed (mixed resolutions
    make the waste strictly positive)."""
    server, _, _ = ragged_server
    with urllib.request.urlopen(server.url + "/metrics") as r:
        text = r.read().decode()
    assert "raft_batch_padding_waste_ratio_count" in text
    count = sum(float(line.split()[-1])
                for line in text.splitlines()
                if line.startswith("raft_batch_padding_waste_ratio_count"))
    total = sum(float(line.split()[-1])
                for line in text.splitlines()
                if line.startswith("raft_batch_padding_waste_ratio_sum"))
    assert count > 0 and total > 0                # mixed res -> real waste
    assert "raft_stream_arena_live_pixels" in text
