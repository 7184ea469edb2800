"""The stream path addresses its slot pool a ROW at a time (PR 46): ``b``
one-row slices in (``models/raft.gather_slot_rows``), ``b`` one-row in-place
writes back (``serving/session.make_slot_commit_fn``).  Held here, bit for
bit, to the forms they replaced: ``buf[slots]`` and
``buf.at[slots].set(where(mask, rows, buf[slots]))``, at every width the
engine compiles (1, 2, 4, 8), plain and int8 (both leaves), with padding rows
that share the scratch slot, a masked real row and a full batch; and the
batched step, dense and ragged, with the general gather put back in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.models import raft

CAP = 9                                 # slots 0..8; row 9 is the scratch slot
WIDTHS = (1, 2, 4, 8)


def _slots_and_mask(b: int, case: str):
    """A batch's slot vector and mask.  ``padding``: the first half real and
    kept, the rest padding rows that share the scratch slot (at width 1 the
    one row is a padding row); ``masked``: real rows out of order, the first
    of them rejected, and a quarter of the batch padding; ``full``: ``b``
    real rows, all kept."""
    real = [7, 2, 5, 0, 8, 3, 1, 6][:b]             # unique, not ascending
    if case == "full":
        return real, [True] * b
    pads = (b + 1) // 2 if case == "padding" else b // 4
    keep = [True] * (b - pads) + [False] * pads
    if case == "masked":
        keep[0] = False
    return real[:b - pads] + [CAP] * pads, keep


def _pool(rng, quant: bool, h=3, w=5, c=8):
    """A pool of CAP + 1 rows in which every row, the scratch row too, holds
    something: a write-back that moved a row would show."""
    def maps():
        if quant:
            return (jnp.asarray(rng.integers(-127, 128, (CAP + 1, h, w, c)),
                                jnp.int8),
                    jnp.asarray(rng.uniform(0.1, 1.0, (CAP + 1, c)),
                                jnp.float32))
        return jnp.asarray(rng.standard_normal((CAP + 1, h, w, c)),
                           jnp.bfloat16)
    return maps(), maps(), jnp.asarray(
        rng.standard_normal((CAP + 1, h, w, 2)), jnp.float32)


def _scatter_commit(quant: bool):
    """The commit as it was before PR 46: one general scatter over one
    general gather."""
    def commit(fmap_buf, cnet_buf, flow_buf, slots, fmap_rows, cnet_rows,
               seed_rows, mask):
        def put(buf, rows):
            keep = mask.reshape((-1,) + (1,) * (rows.ndim - 1))
            return buf.at[slots].set(jnp.where(keep, rows, buf[slots]))

        def put_q(buf, rows):
            vals, scales = raft.quantize_rows(rows)
            return (put(buf[0], vals), put(buf[1], scales))

        put_maps = put_q if quant else put
        return (put_maps(fmap_buf, fmap_rows), put_maps(cnet_buf, cnet_rows),
                put(flow_buf, seed_rows))
    return commit


@pytest.mark.parametrize("case", ["padding", "masked", "full"])
@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("b", WIDTHS)
def test_row_wise_commit_leaves_the_pool_as_the_scatter_did(b, quant, case):
    from raft_tpu.serving.session import make_slot_commit_fn
    rng = np.random.default_rng(100 * b + 10 * quant + len(case))
    bufs = _pool(rng, quant)
    slots, mask = _slots_and_mask(b, case)
    dtype = jnp.float32 if quant else jnp.bfloat16
    frows, crows = (jnp.asarray(rng.standard_normal((b, 3, 5, 8)), dtype)
                    for _ in range(2))
    # what a rejected row holds: NaNs; a padding row: anything
    bad = jnp.asarray([not k for k in mask]).reshape(b, 1, 1, 1)
    frows = jnp.where(bad, jnp.nan, frows)
    crows = jnp.where(bad, 777.0, crows).astype(dtype)
    srows = jnp.asarray(rng.standard_normal((b, 3, 5, 2)), jnp.float32)
    args = (*bufs, jnp.asarray(slots, jnp.int32), frows, crows, srows,
            jnp.asarray(mask))
    new = jax.tree.leaves(jax.jit(make_slot_commit_fn(quant=quant))(*args))
    old = jax.tree.leaves(jax.jit(_scatter_commit(quant))(*args))
    before = jax.tree.leaves(bufs)
    assert len(new) == len(old) == (5 if quant else 3)
    written = sorted(s for s, k in zip(slots, mask) if k)
    for n, o, was in zip(new, old, before):
        assert n.dtype == o.dtype == was.dtype and n.shape == was.shape
        n, o, was = (np.asarray(x.astype(jnp.float32)) for x in (n, o, was))
        assert np.array_equal(n, o, equal_nan=True)
        rest = [r for r in range(CAP + 1) if r not in written]
        assert np.array_equal(n[rest], was[rest])
        assert np.isfinite(n).all()
        for s in written:
            assert not np.array_equal(n[s], was[s])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8, jnp.float32],
                         ids=["bfloat16", "int8", "float32"])
@pytest.mark.parametrize("b", WIDTHS)
def test_row_wise_gather_reads_what_the_index_read(b, dtype):
    """``b`` one-row slices joined in order are ``buf[slots]``: rows out of
    order, and padding rows that read the scratch slot more than once."""
    rng = np.random.default_rng(b)
    buf = jnp.asarray(rng.integers(-127, 128, (CAP + 1, 3, 5, 8)), dtype)
    scales = jnp.asarray(rng.uniform(0.1, 1.0, (CAP + 1, 8)), jnp.float32)
    for case in ("padding", "masked", "full"):
        slots = jnp.asarray(_slots_and_mask(b, case)[0], jnp.int32)
        for leaf in (buf, scales):
            got = jax.jit(raft.gather_slot_rows)(leaf, slots)
            assert got.dtype == leaf.dtype and got.shape == (b,) + leaf.shape[1:]
            assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(leaf[slots].astype(jnp.float32)))


def test_a_slot_outside_the_pool_is_clamped_not_wrapped():
    """Slots lie in ``[0, capacity]`` by construction, and nothing in the
    programs normalises them; where one did not, a slice reads (and the
    commit writes) the nearest row: ``buf[-1]`` read the scratch row."""
    from raft_tpu.serving.session import make_slot_commit_fn
    buf = jnp.arange(CAP + 1, dtype=jnp.float32).reshape(CAP + 1, 1, 1, 1)
    slots = jnp.asarray([-1, CAP + 5, 4], jnp.int32)
    got = jax.jit(raft.gather_slot_rows)(buf, slots)
    assert np.asarray(got).ravel().tolist() == [0.0, float(CAP), 4.0]
    rows = jnp.full((3, 1, 1, 1), 100.0) + jnp.arange(3.0).reshape(3, 1, 1, 1)
    out = jax.jit(make_slot_commit_fn())(buf, buf, buf, slots, rows, rows,
                                         rows, jnp.ones((3,), bool))
    want = np.arange(CAP + 1, dtype=np.float32)
    want[[0, CAP, 4]] = [100.0, 101.0, 102.0]
    for leaf in out:
        assert np.asarray(leaf).ravel().tolist() == want.tolist()


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
@pytest.mark.parametrize("quant", ["none", "int8"], ids=["plain", "int8"])
def test_batched_step_with_the_general_gather_put_back(quant, ragged,
                                                       monkeypatch):
    """The whole batched step (the arena's ``sizes`` form too) over a pool of
    four slots, two real rows out of order and two padding rows: every output
    is what the same program gives with ``buf[slots]`` in the gather's
    place."""
    from raft_tpu.config import RAFTConfig, init_rng
    config = RAFTConfig.small_model(iters=2, quant=quant)
    params = raft.init_raft(init_rng(0), config)
    (h, w), cap, b = (32, 48), 4, 4
    rng = np.random.default_rng(5)
    prev = jnp.asarray(rng.random((cap + 1, h, w, 3)), jnp.float32)
    fmap, cnet = raft.encode_frame(params, prev, config)
    if config.quant_slots:
        fmap, cnet = raft.quantize_rows(fmap), raft.quantize_rows(cnet)
    seeds = jnp.asarray(rng.standard_normal((cap + 1, h // 8, w // 8, 2)),
                        jnp.float32)
    images = jnp.asarray(rng.random((b, h, w, 3)), jnp.float32)
    args = (params, images, fmap, cnet, seeds,
            jnp.asarray([3, 1, cap, cap], jnp.int32),
            jnp.asarray([True, True, False, False]))
    if ragged:
        args += (jnp.asarray([[h, w], [24, 40], [h, w], [h, w]], jnp.int32),)
    new = jax.jit(raft.make_stream_batch_step_fn(config))(*args)
    monkeypatch.setattr(raft, "gather_slot_rows",
                        lambda buf, slots: buf[slots])
    old = jax.jit(raft.make_stream_batch_step_fn(config))(*args)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for n, o in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert n.dtype == o.dtype
        assert np.array_equal(np.asarray(n[:2].astype(jnp.float32)),
                              np.asarray(o[:2].astype(jnp.float32)))
