"""``--quant int8`` slots on the stream path, end to end (PR 45): the
configuration ``raft-things-1080p-stream-int8`` and its cell
``things-stream-int8-pool``.

What a slot holds under ``--quant int8`` is written down once, in
``benchmark/references/warm_restart_int8.py`` (one float32 scale a channel
and row, the absmax to 127, round half to even), and held here at every
level on the CPU at tiny sizes: (a) the program's quantiser and the
reference's give the same codes and scales; (b) a session served through the
real coordinator and batcher, with a forced LRU demotion, is the reference's
walk and NOT the unquantising reference's; (c) a commit's padding and
masked-out rows leave every live slot's codes and scales alone; (d) a
poisoned scale row is caught by the per-row sentinel and the session heals
cold; (e) the static budget admits 256 int8 slots at 1080p and refuses 256
bfloat16 ones, before anything is allocated; (f) the commit and poison
programs donate every leaf of the pool; (g) the new scopes, gauges and
counter are where the per-layer metrics read them; (h) the new readers on
synthetic scrapes and traces, and the cell's place in the manifest.
"""

from __future__ import annotations

import http.client
import importlib.util
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL, CHURN = "things-stream-int8-pool", "things-stream-churn"
CONFIG = "raft-things-1080p-stream-int8"
MIX = "davis1080p-sessions-churn"
NEW_METRICS = {"slot_pool_gb": "engine", "slot_dequant_ms": "kernels",
               "slot_quant_ms": "kernels", "slot_io_int8_roofline": "kernels",
               "slot_fill": "engine"}
SEED, H, W, FRAMES = 4_500_000_011, 64, 96, 5


@pytest.fixture(scope="module")
def bench_modules():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import check
    import inputs
    import pool_metrics
    import readers
    import stages
    import system
    import weights
    return types.SimpleNamespace(check=check, inputs=inputs, system=system,
                                 pool_metrics=pool_metrics, readers=readers,
                                 stages=stages, weights=weights)


@pytest.fixture(scope="module")
def run(bench_modules):
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cell(run):
    bench = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = run.find(bench["workloads"], CELL, "workload")
    cfg_entry = run.find(bench["configs"], entry["config"], "configuration")
    return {
        "bench": bench, "entry": entry, "cfg_entry": cfg_entry,
        "config": run.load_json(os.path.join(REPO, cfg_entry["file"])),
        "churn": run.load_json(os.path.join(BENCH, "configs",
                                            CONFIG[:-4] + "churn.json")),
        "traffic": run.load_json(os.path.join(BENCH, "traffic",
                                              entry["traffic"] + ".json")),
        "file": run.load_json(os.path.join(BENCH, "workloads",
                                           CELL + ".json")),
    }


@pytest.fixture(scope="module")
def int8_ref(run):
    return run.load_named(BENCH, "references", "warm_restart_int8",
                          "the test's reference")


@pytest.fixture(scope="module")
def plain_ref(run):
    return run.load_named(BENCH, "references", "warm_restart",
                          "the test's reference")


# --------------------------------- (a) one quantiser, written down twice

def _maps(case: str) -> np.ndarray:
    """A seeded ``[6, 10, 8]`` map with the case's trap in it."""
    rng = np.random.default_rng(45)
    x = rng.standard_normal((6, 10, 8)).astype(np.float32)
    if case == "ties":
        # a channel whose absmax is 127 exactly has a scale of 1: halves
        # are exact ties, which round to the even code
        x[..., 0] = np.linspace(-29.5, 29.5, 60).reshape(6, 10)
        x[0, 0, 0] = 127.0
        x[..., 1] = np.linspace(-59.5, 59.5, 60).reshape(6, 10) * 2.0
        x[0, 0, 1] = -254.0
    elif case == "zero-channel":
        x[..., 3] = 0.0
    elif case == "corner-absmax":
        x[..., 5] = 0.25 * x[..., 5]
        x[5, 9, 5] = -9.75          # the last position holds the absmax
    elif case == "bfloat16-rows":
        x = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    return x


@pytest.mark.parametrize("case", ["seeded", "ties", "zero-channel",
                                  "corner-absmax", "bfloat16-rows"])
def test_the_programs_quantiser_is_the_references(case, int8_ref):
    """``models/raft.quantize_rows`` / ``dequantize_rows`` over a batch of
    rows and the reference's ``quantise`` / ``stored`` over each row alone:
    the same int8 codes, the same float32 scales and the same values read
    back, bit for bit."""
    from raft_tpu.models.raft import dequantize_rows, quantize_rows
    rows = np.stack([_maps(case), 3.0 * _maps(case)[::-1]])
    # op by op, and compiled (where the compiler turns the division by 127
    # into a product with its reciprocal, on both sides alike)
    for wrap in (lambda f: f, jax.jit):
        vals, scales = wrap(quantize_rows)(jnp.asarray(rows))
        back = wrap(dequantize_rows)(vals, scales)
        assert vals.dtype == jnp.int8 and scales.dtype == jnp.float32
        for i, row in enumerate(rows):
            codes, s = wrap(int8_ref.quantise)(jnp.asarray(row))
            assert codes.dtype == jnp.int8
            assert (np.asarray(codes) == np.asarray(vals[i])).all()
            assert (np.asarray(s) == np.asarray(scales[i])).all()
            assert (np.asarray(wrap(int8_ref.stored)(jnp.asarray(row)))
                    == np.asarray(back[i])).all()
    vals, scales = quantize_rows(jnp.asarray(rows))
    back = dequantize_rows(vals, scales)
    v = np.asarray(vals)
    assert v.min() >= -127 and v.max() <= 127
    if case == "ties":
        # scale exactly 1: 0.5 -> 0, 1.5 -> 2, 2.5 -> 2 (half to even)
        assert float(scales[0, 0]) == 1.0 and float(scales[0, 1]) == 2.0
        col = rows[0][..., 0].ravel()
        ties = np.flatnonzero(np.abs(col * 2) % 2 == 1)
        assert len(ties) > 20
        assert (v[0][..., 0].ravel()[ties] % 2 == 0).all()
    if case == "zero-channel":
        assert (v[..., 3] == 0).all() and (np.asarray(back)[..., 3] == 0).all()
        assert float(scales[0, 3]) == np.float32(1e-12) / np.float32(127.0)
    if case == "corner-absmax":
        assert v[0, 5, 9, 5] == -127
        assert float(scales[0, 5]) == np.float32(9.75) / np.float32(127.0)


def test_the_reference_quantises_at_every_precision_and_on_a_restart(
        int8_ref, plain_ref, bench_modules, cell):
    """``flow`` differs from the unquantising reference's at 'float32', at
    the configuration's 'bfloat16' and at the control's 'float8'; a restart
    drops the seed and nothing else: it is the zero-seeded call, not the
    unquantised one."""
    w = bench_modules.weights
    mcfg = w.model_cfg(cell["config"])
    wts = w.make_weights(SEED, mcfg)
    rng = np.random.default_rng(3)
    a, b = (rng.integers(0, 255, (H, W, 3), dtype=np.uint8) for _ in "ab")
    seed = rng.standard_normal((H // 8, W // 8, 2)).astype(np.float32)
    for precision in ("float32", "bfloat16", "float8"):
        got, _ = int8_ref.flow(wts, a, b, mcfg, 3, precision, flow_init=seed)
        plain, _ = plain_ref.flow(wts, a, b, mcfg, 3, precision,
                                  flow_init=seed)
        assert np.isfinite(np.asarray(got)).all()
        assert np.abs(np.asarray(got) - np.asarray(plain)).max() > 1e-6
    cold, _ = int8_ref.flow(wts, a, b, mcfg, 3, flow_init=seed, restart=True)
    zero, _ = int8_ref.flow(wts, a, b, mcfg, 3)
    plain_cold, _ = plain_ref.flow(wts, a, b, mcfg, 3, restart=True)
    assert (np.asarray(cold) == np.asarray(zero)).all()
    assert np.abs(np.asarray(cold) - np.asarray(plain_cold)).max() > 1e-6


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(BENCH, "references",
                             "warm_restart_int8.py")).read()
    assert not re.search(r"^\s*(from|import)\s+raft_tpu", text, re.M)
    assert 'jax.default_matmul_precision("highest")' in text
    assert "ONE\ndeparture" in text and "DEPARTURE" in text
    imports = re.findall(r"^(?:from|import) (\S+)", text, re.M)
    assert set(imports) <= {"__future__", "functools", "jax", "jax.numpy",
                            "numpy", "reference", "references.warm",
                            "references.warm_restart"}


# ------------------------- (c) a commit writes the rows it is told to write

def _pool(rng, rows=4, h=3, w=5, c=8):
    def leaf():
        return (jnp.asarray(rng.integers(-127, 128, (rows, h, w, c)),
                            jnp.int8),
                jnp.asarray(rng.uniform(0.1, 1.0, (rows, c)), jnp.float32))
    return leaf(), leaf(), jnp.asarray(
        rng.standard_normal((rows, h, w, 2)), jnp.float32)


@pytest.mark.parametrize("slots,mask,written", [
    ([0, 3], [True, False], [0]),            # a part batch: a row, a padding
    ([1, 3, 3, 3], [True, False, False, False], [1]),     # three paddings
    ([0, 2], [False, True], [2]),            # a row the sentinel rejected
    ([0, 1], [False, False], []),            # nothing passes
    ([2, 0], [True, True], [0, 2])])
def test_padding_and_masked_rows_leave_live_slots_alone(slots, mask,
                                                        written, int8_ref):
    """The quantising commit over a pool of three slots and the scratch row
    (3): the rows masked in hold the new maps' codes and scales and their
    seeds; every other row of EVERY leaf, codes and scales, is what it was,
    bit for bit — a padding row's huge values move no scale of a live
    slot."""
    from raft_tpu.serving.session import make_slot_commit_fn
    rng = np.random.default_rng(7)
    fbuf, cbuf, seeds = _pool(rng)
    b = len(slots)
    frows = rng.standard_normal((b, 3, 5, 8)).astype(np.float32)
    crows = rng.standard_normal((b, 3, 5, 8)).astype(np.float32)
    for i, keep in enumerate(mask):
        if not keep:                 # what a padding row may hold: anything
            frows[i] *= 1e4
            crows[i] = 777.0
    srows = rng.standard_normal((b, 3, 5, 2)).astype(np.float32)
    commit = jax.jit(make_slot_commit_fn(quant=True))
    out = commit(fbuf, cbuf, seeds, jnp.asarray(slots, jnp.int32),
                 jnp.asarray(frows), jnp.asarray(crows), jnp.asarray(srows),
                 jnp.asarray(mask))
    before = jax.tree.leaves((fbuf, cbuf, seeds))
    after = jax.tree.leaves(out)
    assert [a.dtype for a in after] == [b_.dtype for b_ in before]
    untouched = [r for r in range(4) if r not in written]
    for old, new in zip(before, after):
        assert (np.asarray(old)[untouched] == np.asarray(new)[untouched]).all()
    for i, slot in enumerate(slots):
        if not mask[i]:
            continue
        for buf, rows in ((out[0], frows), (out[1], crows)):
            codes, s = jax.jit(int8_ref.quantise)(jnp.asarray(rows[i]))
            assert (np.asarray(buf[0][slot]) == np.asarray(codes)).all()
            assert (np.asarray(buf[1][slot]) == np.asarray(s)).all()
        assert (np.asarray(out[2][slot]) == srows[i]).all()


# ------------------------------ the served session (b), (d), (g): fixtures

def _serve_args(config, **replace):
    argv = [str(a) for a in config["serve_args"]]
    for flag, value in replace.items():
        argv[argv.index("--" + flag.replace("_", "-")) + 1] = value
    return argv


def _tiny_config(cell, **serve):
    """The configuration at a 64x96 bucket in float32, three updates, XLA's
    GRU, batches of 2, its ``--quant int8`` kept; ``serve``: further flags
    exchanged."""
    config = dict(cell["config"])
    config["serve_args"] = _serve_args(config, **dict(dict(
        buckets="64x96", iters="3", dtype="float32", max_batch="2",
        gru_impl="xla"), **serve))
    config.update(iters=3, program={"small": False, "quant": "int8",
                                    "compute_dtype": "float32"})
    return config


def _post(bm, conn, path, **arrays):
    conn.request("POST", path, body=bm.inputs.npz_body(**arrays),
                 headers={"Content-Type": "application/octet-stream",
                          "Accept": "application/octet-stream"})
    resp = conn.getresponse()
    payload = resp.read()
    assert resp.status == 200, payload[:300]
    return bm.inputs.npz_load(payload)


def _program_projection(flow_lr):
    from raft_tpu.utils.frame_utils import forward_interpolate
    return forward_interpolate(np.asarray(flow_lr, np.float32))


@pytest.fixture(scope="module")
def clip(cell, bench_modules, run):
    driver = run.load_named(BENCH, "drivers", "sessions_churn",
                            "the test's driver")
    mcfg = bench_modules.weights.model_cfg(cell["config"])
    return {"weights": bench_modules.weights.make_weights(SEED, mcfg),
            "mcfg": mcfg,
            "frames": driver.sessions.make_clip(SEED, 0, FRAMES, H, W, 2),
            "other": driver.sessions.make_clip(SEED, 1, FRAMES, H, W, 2)}


@pytest.fixture(scope="module")
def served(cell, bench_modules, clip, tmp_path_factory):
    """One slot, two sessions, ``--quant int8``, through the real server: an
    open and four advances of session a, session b's open taking the slot
    between advances 2 and 3 (a forced LRU demotion: advance 3 restarts
    cold); then a's slot poisoned (the chaos arm's NaN scale row) before a
    fifth advance, and a sixth after it."""
    bm = bench_modules
    config = _tiny_config(cell, max_sessions="1")
    config["serve_args"].append("--no-warmup")
    sut = bm.system.start(config, clip["weights"],
                          str(tmp_path_factory.mktemp("int8")), "int8-cpu")
    out = {"flows": {}, "warm": {}}
    try:
        conn = http.client.HTTPConnection(sut.host, sut.port, timeout=600)
        frames = clip["frames"]
        before = sut.scrape()
        a = str(_post(bm, conn, "/v1/stream", image=frames[0])["session"])

        def advance(k, image=None):
            got = _post(bm, conn, "/v1/stream", session=np.asarray(a),
                        image=frames[k] if image is None else image)
            out["flows"][k], out["warm"][k] = got["flow"], bool(got["warm"])

        advance(1)
        advance(2)
        b = str(_post(bm, conn, "/v1/stream",
                      image=clip["other"][0])["session"])
        advance(3)
        advance(4)
        out["walk"] = bm.system.diff_prom(before, sut.scrape())
        out["absolute"] = sut.scrape()
        engine, store = sut.server.engine, sut.server.streams.store
        out["specs"] = engine.programs.slot_specs(H, W)
        # (d): a's slot poisoned as the chaos arm poisons it
        s = store.get(a)
        assert s.slot is not None
        mid = sut.scrape()
        engine.poison_slot((H, W), s.slot)
        scales = np.asarray(engine.pool.buffers((H, W))[0][1])
        out["poisoned_scales_nan"] = bool(np.isnan(scales[s.slot]).all())
        advance(5, clip["other"][1])
        advance(6, clip["other"][2])
        out["drill"] = bm.system.diff_prom(mid, sut.scrape())
        for sid in (a, b):
            _post(bm, conn, "/v1/stream", op=np.asarray("close"),
                  session=np.asarray(sid))
        conn.close()
    finally:
        sut.stop()
    return out


def _walks(bm, ref, clip, monkeypatch, cold=(3,)):
    with monkeypatch.context() as mp:
        # both sides fill the projection's holes alike (on an 8 x 12 grid a
        # tenth of the pixels are holes): what is left is the maps' format
        mp.setattr(sys.modules["references.warm_restart"],
                   "forward_interpolate", _program_projection)
        return ref.walk(bm.check.forward(ref, clip["weights"], clip["mcfg"],
                                         3), clip["frames"], 4, cold=cold)


# ------------------------ (b) the served session is the quantising walk

def test_a_served_int8_session_is_the_quantising_references_walk(
        served, bench_modules, clip, int8_ref, plain_ref, monkeypatch):
    """Open, two warm advances, a demotion, the restart and the advance
    seeded from it: every answer is ``warm_restart_int8.walk``'s to 2e-3 of
    the flow (float32 program: what is left is the encoders' round-off, which
    moves a code here and there), and at least five times NEARER to it than
    to ``warm_restart.walk``'s: a pool that stopped quantising, or a
    reference that did, fails one of the two."""
    check = bench_modules.check
    assert served["warm"] == {1: True, 2: True, 3: False, 4: True,
                              5: False, 6: True}
    quantising = _walks(bench_modules, int8_ref, clip, monkeypatch)
    plain = _walks(bench_modules, plain_ref, clip, monkeypatch)
    for k in (1, 2, 3, 4):
        flow = served["flows"][k]
        assert np.linalg.norm(quantising[k], axis=-1).mean() > 0.03
        near = check.rel_epe(flow, quantising[k])
        far = check.rel_epe(flow, plain[k])
        assert near < 2e-3, (k, near)
        assert far > 5 * near, (k, near, far)


def test_the_restart_under_quant_is_not_the_unbroken_walk(
        served, bench_modules, clip, int8_ref, monkeypatch):
    check = bench_modules.check
    whole = _walks(bench_modules, int8_ref, clip, monkeypatch, cold=())
    assert check.rel_epe(served["flows"][3], whole[3]) > 1e-3


# ------------------------------------- (d) a poisoned int8 slot is caught

def test_a_poisoned_int8_slot_is_caught_and_the_session_restarts_cold(served):
    """``spoison`` under quant NaNs the slot's SCALE row (int8 codes cannot
    hold a NaN); the next advance's gather dequantises it to NaN maps, the
    per-row sentinel rejects the row, the session is degraded and healed
    through the solo cold restart in the same advance (200, ``warm:
    false``), and the advance after it is warm again."""
    assert served["poisoned_scales_nan"]
    assert served["warm"][5] is False and served["warm"][6] is True
    assert np.isfinite(served["flows"][5]).all()
    assert np.isfinite(served["flows"][6]).all()
    drill = served["drill"]
    assert drill["raft_nonfinite_outputs_total"] == 1
    assert drill["raft_stream_degraded_total"] == 1
    assert drill['raft_stream_cold_restarts_total{cause="degraded"}'] == 1
    assert drill['raft_stream_evictions_total{reason="degraded"}'] == 1
    assert drill['raft_serving_requests_total{status="ok"}'] == 2


# --------------------- (g) gauges and the counter, where the readers read

def test_the_pools_gauges_are_the_specs_bytes_and_rows_are_counted(served):
    """``raft_stream_pool_bytes{leaf=}`` are the bytes of the engine's own
    ``slot_specs`` leaf by leaf; rows quantised over the walk = frames +
    opens + restarts (4 + 2 + 1); the solo heal's attach counts its row
    too."""
    from raft_tpu.lint.budget import bytes_of
    (fv, fs), (cv, cs), seed = served["specs"]
    prom = served["absolute"]
    assert fv.dtype == cv.dtype == jnp.int8 and fs.dtype == jnp.float32
    assert prom['raft_stream_pool_bytes{leaf="vals"}'] \
        == bytes_of(fv) + bytes_of(cv)
    assert prom['raft_stream_pool_bytes{leaf="scales"}'] \
        == bytes_of(fs) + bytes_of(cs)
    assert prom['raft_stream_pool_bytes{leaf="seed"}'] == bytes_of(seed)
    assert prom['raft_stream_slot_capacity{bucket="64x96"}'] == 1
    assert prom['raft_stream_slots_in_use{bucket="64x96"}'] == 1
    walk = served["walk"]
    restarts = walk["raft_stream_restarts_batched_total"]
    assert (walk["raft_stream_frames_total"], walk["raft_stream_opens_total"],
            restarts) == (4, 2, 1)
    assert walk["raft_stream_rows_quantized_total"] == 4 + 2 + 1
    # the drill: advance 5's heal attaches one row, advance 6 commits one
    assert served["drill"]["raft_stream_rows_quantized_total"] == 2


def test_an_unquantised_server_counts_no_quantised_row(cell, bench_modules,
                                                       clip, tmp_path):
    bm = bench_modules
    config = _tiny_config(cell, max_sessions="1")
    argv = config["serve_args"]
    del argv[argv.index("--quant"):argv.index("--quant") + 2]
    config["program"]["quant"] = "none"
    config["serve_args"].append("--no-warmup")
    sut = bm.system.start(config, clip["weights"], str(tmp_path), "bf-cpu")
    try:
        conn = http.client.HTTPConnection(sut.host, sut.port, timeout=600)
        a = str(_post(bm, conn, "/v1/stream",
                      image=clip["frames"][0])["session"])
        _post(bm, conn, "/v1/stream", session=np.asarray(a),
              image=clip["frames"][1])
        prom = sut.scrape()
        conn.close()
    finally:
        sut.stop()
    assert prom["raft_stream_rows_quantized_total"] == 0
    assert prom['raft_stream_pool_bytes{leaf="scales"}'] == 0
    maps = 2 * (H // 8) * (W // 8) * 256 * 4 * 2
    assert prom['raft_stream_pool_bytes{leaf="vals"}'] == maps


# ----------------- (f), (g) the programs: donation and scopes, from shapes

@pytest.fixture(scope="module")
def tiny_programs():
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import init_raft
    from raft_tpu.serving.engine import Programs
    config = RAFTConfig.full(iters=2, quant="int8", gru_impl="xla")
    params = jax.eval_shape(lambda: init_raft(jax.random.PRNGKey(0), config))
    return Programs(config, params, 3, iters=2, donate=True)


@pytest.mark.parametrize("kind,b,leaves", [("scommit", 2, 5),
                                           ("scommit", 1, 5),
                                           ("spoison", 1, 2)])
def test_the_scatter_programs_donate_every_leaf_of_the_pool(tiny_programs,
                                                            kind, b, leaves):
    """Each leaf of the pool that goes into a commit or a poison (int8
    codes and float32 scales of both maps, the seeds) is donated and aliased
    to the output that takes its place: no output the size of the pool is a
    second copy of it."""
    from raft_tpu.lint.budget import kind_footprint, tree_bytes
    key = (kind, 64, 96, b, "fixed")
    prog = tiny_programs.program(key)
    text = prog.fn.lower(*prog.specs).as_text()
    assert len(re.findall(r"tf\.aliasing_output", text)) == leaves
    assert prog.donated == prog.resident
    out = jax.eval_shape(prog.fn, *prog.specs)
    donated = [prog.specs[i] for i in prog.donated]
    assert [(s.shape, s.dtype) for s in jax.tree.leaves(out)] \
        == [(s.shape, s.dtype) for s in jax.tree.leaves(donated)]
    foot = kind_footprint(tiny_programs, key)
    assert foot["donated_bytes"] == foot["output_bytes"] == tree_bytes(out)


def test_the_batched_step_returns_rows_and_no_pool(tiny_programs):
    """The gather hands the step ``b`` rows: nothing it returns has the
    pool's rows, and nothing is donated to it (the pool stays the
    pool's)."""
    prog = tiny_programs.program(("sbatch", 64, 96, 2, "fixed"))
    out = jax.eval_shape(prog.fn, *prog.specs)
    rows = tiny_programs.capacity + 1
    assert rows == 4 and prog.resident == (2, 3, 4) and prog.donated == ()
    assert all(s.shape[0] in (2, 6) for s in jax.tree.leaves(out))


@pytest.mark.parametrize("kind,b,scope", [
    ("sbatch", 2, "raft/stream/gather/dequant"),
    ("scommit", 2, "raft/stream/commit/quant"),
    ("scommit", 1, "raft/stream/commit/quant")])
def test_the_new_scopes_are_in_the_stage_map(tiny_programs, kind, b, scope):
    """What the engine writes beside its AOT cache entry
    (``instruction_stages`` of the compiled text) files instructions under
    the scope that ``slot_dequant_ms`` / ``slot_quant_ms`` read, inside the
    scope ``slot_io_ms`` reads."""
    from raft_tpu.telemetry.trace import instruction_stages
    prog = tiny_programs.program((kind, 64, 96, b, "fixed"))
    insts = instruction_stages(prog.fn.lower(*prog.specs).compile().as_text())
    stages = {rec["stage"] for rec in insts.values()}
    assert scope in stages, sorted(s for s in stages if "stream" in s)
    parent = scope.rsplit("/", 1)[0]
    assert parent in stages
    if kind == "scommit":
        assert all(st.startswith("raft/stream/commit") for st in stages if st)


# --------------------------- (e) the budget, before anything is allocated

def _serve_configs(cell, drop_quant=False):
    from raft_tpu import cli
    from raft_tpu.serving.config import ServeConfig, parse_buckets
    argv = [str(a) for a in cell["config"]["serve_args"]]
    if drop_quant:
        del argv[argv.index("--quant"):argv.index("--quant") + 2]
    args = cli.parse_args(["-m", "serve"] + argv)
    return cli._make_config(args), ServeConfig(
        buckets=parse_buckets(args.buckets), max_batch=args.max_batch,
        max_sessions=args.max_sessions)


@pytest.fixture(scope="module")
def admission(cell):
    """``serving/admission.admit_stream`` (the server's own answer, which
    ``lint/budget`` reads) over the configuration's own serve arguments and
    over its bfloat16 twin, on abstract params: shapes alone."""
    from raft_tpu.lint import budget
    from raft_tpu.serving import admission
    from raft_tpu.serving.engine import Programs
    out = {}
    for name, drop in (("int8", False), ("bfloat16", True)):
        config, sconfig = _serve_configs(cell, drop_quant=drop)
        programs = Programs(config, budget.param_specs(config),
                            sconfig.max_sessions, donate=True)
        foot = admission.stream_footprint(programs, 1080, 1920, 8)
        try:
            admission.admit_stream(programs, sconfig, "TPU v5 lite")
            refusal = None
        except ValueError as e:
            refusal = str(e)
        out[name] = (foot, refusal, programs, sconfig)
    return out


def test_the_budget_admits_256_int8_slots_at_1080p(admission):
    """257 rows of int8 maps, scales and seeds are 4,330,462,336 B (a slot
    16,850,048 B); with two groups' frames and outputs on the heap and the
    batched step's temporaries reserved below it the bound is 11.94 GB, 70 %
    of what a v5e's runtime hands out (the chip read 12.40: PERF.md)."""
    foot, refusal, programs, _ = admission["int8"]
    assert refusal is None
    assert foot["pool_bytes"] == 257 * 16_850_048 == 4_330_462_336
    from raft_tpu.lint.budget import bytes_of
    assert sorted(bytes_of(s) for s in jax.tree.leaves(
        programs.slot_specs(1080, 1920))) == sorted(
        [257 * 135 * 240 * 256] * 2 + [257 * 256 * 4] * 2
        + [257 * 135 * 240 * 2 * 4])
    from raft_tpu.lint import budget
    # the 8 rows a program holds of a leaf (66 MB) lie far under the step's
    # temporaries: no program holds a copy of a pool leaf (PR 46)
    assert "commit_copy_row_bytes" not in foot
    assert foot["reserved_bytes"] == 385 * 8 * 1080 * 1920 \
        > 8 * 135 * 240 * 256
    assert foot["peak_bytes"] == 11_936_732_800 < budget.stream_limit(
        "tpu-v5e") == int(0.95 * 16_909_336_064)


def test_the_budget_refuses_the_bfloat16_twin_and_names_the_pool(admission):
    foot, refusal, _, _ = admission["bfloat16"]
    assert foot["pool_bytes"] == 257 * 33_436_800 == 8_593_257_600
    from raft_tpu.lint import budget
    assert foot["peak_bytes"] == 16_199_528_064 > budget.stream_limit(
        "tpu-v5e")
    assert refusal is not None
    assert "8593257600 B" in refusal and "8.59 GB" in refusal
    assert "--max-sessions 256" in refusal and "--quant int8" in refusal


@pytest.mark.parametrize("kind,raises", [("cpu", False), ("TPU v9", False),
                                         ("TPU v4", False)])
def test_the_admission_starts_where_it_has_no_budget_or_room(admission, kind,
                                                             raises):
    """The CPU, an unknown chip and a v4 (whose runtime's limit nobody has
    read here) start unasked."""
    from raft_tpu.serving.admission import admit_stream
    _, _, programs, sconfig = admission["bfloat16"]
    admit_stream(programs, sconfig, kind)


def test_the_engine_asks_before_it_compiles_or_allocates(admission,
                                                         monkeypatch):
    """``InferenceEngine.__init__`` on a (pretended) v5e with the twin's
    arguments raises the budget's ValueError: no executable, no buffer."""
    from raft_tpu.serving import engine as engine_mod
    config, sconfig = (admission["bfloat16"][2].config,
                       admission["bfloat16"][3])
    params = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                          admission["bfloat16"][2].params)
    fake = types.SimpleNamespace(device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(ValueError, match="8593257600 B"):
        engine_mod.InferenceEngine(config, params, sconfig, iters=12,
                                   stream=True)


def test_analyze_prices_the_stream_path_and_says_how_many_fit(cell):
    """The full report: no violation for the configuration (500 sessions
    would fit beside the stream programs), one for its twin (251 would)."""
    from raft_tpu.lint import budget
    config, sconfig = _serve_configs(cell)
    rep = budget.analyze(config, sconfig, device_kind="tpu-v5e")
    assert rep["violations"] == []
    assert rep["totals"]["per_session_bytes"] == 16_850_048
    assert rep["totals"]["peak_with_stream_temps_bytes"] \
        == rep["totals"]["resident_bytes"] + 385 * 8 * 1080 * 1920 \
        + 2 * 199_065_600 \
        + 2 * 8 * (135 * 240 * (512 * 2 + 2 * 4) + 1080 * 1920 * 2 * 4)
    assert rep["totals"]["max_sessions_fit_stream"] == 500
    config, sconfig = _serve_configs(cell, drop_quant=True)
    rep = budget.analyze(config, sconfig, device_kind="tpu-v5e")
    assert len(rep["violations"]) == 1 and "8593257600 B" in rep[
        "violations"][0]
    assert rep["totals"]["max_sessions_fit_stream"] == 251


# ------------------------------------------------- the benchmark's new files

def test_the_cell_is_in_the_benchmark_and_only_appended_to_it(cell):
    bench = cell["bench"]
    assert [c["name"] for c in bench["configs"]].count(CONFIG) == 1
    assert bench["configs"][-1] is cell["cfg_entry"]
    assert bench["workloads"][-1] is cell["entry"]
    assert bench["run_seconds"] == 40 and len(bench["workloads"]) == 6
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW_METRICS)
    for rel in ("configs/" + CONFIG + ".json", "traffic/" + MIX + ".json",
                "workloads/" + CELL + ".json", "drivers/sessions_churn.py",
                "references/warm_restart_int8.py", "pool_metrics.py"):
        assert os.path.isfile(os.path.join(BENCH, rel)), rel


@pytest.mark.parametrize("what,want", [
    ("config", CONFIG), ("traffic", MIX), ("chips", 1)])
def test_the_cell_is_the_one_the_issue_names(cell, what, want):
    assert cell["entry"][what] == want
    for entry in (cell["entry"], cell["cfg_entry"]):
        assert 0 < len(entry["why"]) <= 200
    assert 0 < len(cell["cfg_entry"]["source"]) <= 200
    assert cell["cfg_entry"]["reduced"] == cell["config"]["reduced"] == []


def test_the_cells_file(cell):
    f = cell["file"]
    assert (f["clients"], f["live_sessions"], f["trace_seconds"]) \
        == (24, 288, 12.0)
    # the least multiple of 5 s over 1.2 x the longest population build the
    # chip showed (288 opens: PERF.md section 4)
    assert f["warm_total_seconds"] % 5 == 0 and f["warm_total_seconds"] >= 15
    d = cell["config"]["deployment"]
    assert (d["live_sessions"], d["slots"], d["playing"]) == (288, 256, 24)


@pytest.mark.parametrize("key", ["small", "fnet_dim", "hidden_dim",
                                 "context_dim", "corr_levels", "corr_radius",
                                 "iters", "parameters", "weights"])
def test_the_configuration_keeps_the_published_widths(cell, key):
    assert cell["config"][key] == cell["churn"][key]
    assert (cell["config"]["fnet_dim"], cell["config"]["parameters"]) \
        == (256, 5257536)


def test_the_configuration_is_churns_with_a_larger_int8_pool(cell):
    cfg, churn = cell["config"], cell["churn"]
    want = [str(a) for a in churn["serve_args"]]
    want[want.index("--max-sessions") + 1] = "256"
    assert [str(a) for a in cfg["serve_args"]] == want + ["--quant", "int8"]
    assert cfg["program"] == dict(churn["program"], quant="int8")
    assert cfg["precision"].startswith(churn["precision"])
    assert cfg["precision"].endswith(
        "slot rows int8, one float32 scale a channel")
    assert cfg["guarantees"].startswith(churn["guarantees"])
    assert "int8 with one float32 scale a channel and row" in cfg["guarantees"]
    assert cfg["check"]["reference"] == "warm_restart_int8"
    assert cfg["check"]["own_precision"] == "bfloat16"
    # ONE kept answer, the warm one: a check session parked 12 places from
    # the queue's head comes back before LRU has worked through the 232
    # parked sessions that hold a slot in front of it (136 s of demotions at
    # 1.7 a second), so the driver's kinds B and C cannot be had in a 40 s
    # window on a pool this size (PERF.md section 4)
    assert cfg["check"]["sample"] == 1
    assert 1.0 < cfg["check"]["ratio_limit"] <= 3.0
    assert len(cfg["assumed"]) >= 6


def test_the_programs_config_is_what_the_file_declares(cell):
    config, sconfig = _serve_configs(cell)
    for k, v in cell["config"]["program"].items():
        assert getattr(config, k) == v, k
    assert config.quant_slots and sconfig.max_sessions == 256
    assert sconfig.batch_steps[-1] == 8


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_the_new_metrics_list_the_new_cell_alone(cell, run, metric):
    entry = run.find(cell["bench"]["per_layer"], metric, "metric")
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == NEW_METRICS[metric]
    assert entry["moves"] == "pairs_per_s"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    base = os.path.join(BENCH, "layer_metrics", metric)
    assert os.path.exists(base + ".json") and os.path.exists(base + ".py")


def test_the_cell_joins_every_list_churn_is_on_but_the_bf16_roofline(cell,
                                                                     run):
    """Appended LAST to ``pairs_per_s`` and to each per-layer list that
    lists ``things-stream-churn``, but ``slot_io_roofline``: its cost prices
    a row at the compute dtype's width."""
    bench = cell["bench"]
    joined = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", ())
        if CHURN in cells and m["name"] != "slot_io_roofline":
            assert cells[-2:] == [CHURN, CELL], m["name"]
            joined.append(m["name"])
        elif m["name"] not in NEW_METRICS:
            assert CELL not in cells, m["name"]
    assert "pairs_per_s" in joined and "slot_io_ms" in joined
    assert len(joined) == 30
    reporting = {m["name"] for m in bench["end_to_end"]
                 if run.listed(m, CELL, set())}
    assert reporting == {"pairs_per_s", "setup_s"}
    roofline = run.find(bench["per_layer"], "slot_io_roofline", "metric")
    assert not run.listed(roofline, CELL, reporting)


# ------------------------------------- (h) the readers, on synthetic input

def _ctx(bm, cell, **kw):
    base = dict(config=cell["config"], traffic=cell["traffic"],
                cell=cell["file"], records=[], summary={}, prom_window={},
                max_batch=8, peak={"flops_per_s": 197e12,
                                   "bytes_per_s": 819e9},
                memory_peak_bytes=0,
                shapes={"h": 135, "w": 240, "q": 32400, "fnet_dim": 256})
    base.update(kw)
    return bm.readers.RunContext(**base)


def _spill(path, *snaps):
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "manifest", "interval_s": 1.0}) + "\n")
        for i, snap in enumerate(snaps):
            f.write(json.dumps({"kind": "sample", "t": float(i),
                                "snap": snap}) + "\n")


def test_pool_gauges_are_read_from_the_historys_last_sample(
        bench_modules, cell, tmp_path):
    bm = bench_modules
    path = str(tmp_path / "metrics_ts.jsonl")
    empty = {"raft_stream_pool_bytes": {"vals": 0, "scales": 0, "seed": 0},
             "raft_stream_slots_in_use": {"1080x1920": 0.0},
             "raft_stream_slot_capacity": {"1080x1920": 256.0}}
    full = {"raft_stream_pool_bytes": {"vals": 4263321600, "scales": 526336,
                                       "seed": 66614400},
            "raft_stream_slots_in_use": {"1080x1920": 256.0},
            "raft_stream_slot_capacity": {"1080x1920": 256.0}}
    _spill(path, empty, full)
    ctx = _ctx(bm, cell)
    pool = {"bytes": "raft_stream_pool_bytes", "history": path}
    fill = {"in_use": "raft_stream_slots_in_use",
            "capacity": "raft_stream_slot_capacity", "history": path}
    assert bm.pool_metrics.slot_pool_gb(ctx, pool) == pytest.approx(4.330462336)
    assert bm.pool_metrics.slot_fill(ctx, fill) == 100.0
    with open(path, "a") as f:
        f.write("{torn")                         # a line cut by a kill
    assert bm.pool_metrics.slot_fill(ctx, fill) == 100.0
    # a program without the gauges (the parent): nothing to read, no raise
    _spill(path, {"raft_stream_slots_in_use": {"1080x1920": 3.0},
                  "raft_stream_slot_capacity": {"1080x1920": 4.0}})
    assert bm.pool_metrics.slot_pool_gb(ctx, pool) is None
    assert bm.pool_metrics.slot_fill(ctx, fill) == 75.0
    missing = dict(pool, history=str(tmp_path / "none.jsonl"))
    assert bm.pool_metrics.slot_pool_gb(ctx, missing) is None
    assert bm.pool_metrics.slot_fill(ctx, dict(fill, history=missing[
        "history"])) is None


def test_the_history_is_found_under_the_configurations_name(bench_modules,
                                                            cell):
    """Where ``system.start`` puts the server's ``--out``; no run has been
    made from this checkout's tests, so there is nothing there."""
    bm = bench_modules
    ctx = _ctx(bm, cell, config=dict(cell["config"], name="no-such-config"))
    assert bm.pool_metrics.last_sample(ctx) is None


def _trace(bm, ops):
    import tracered
    dev = {"busy_ns": 1.0, "gaps": [], "modules": [
        ("jit_slot_commit(1)", 30e6, True), ("jit_slot_commit(1)", 34e6, True),
        ("jit_slot_commit(2)", 1e6, True), ("jit_fn(3)", 600e6, True)],
        "ops": {}}
    for label, total, count in ops:
        op = tracered.Op(label.split(" ")[0], label, total, count)
        dev["ops"][label] = op
    return tracered.Trace(window_s=12.0, devices={0: dev}, host_events=[])


@pytest.fixture()
def stage_maps(tmp_path):
    """Two executables' maps as the engine writes them: the batched step's
    and the batch's commit, and the one-row commit beside them."""
    def write(name, insts):
        (tmp_path / f"{name}.stages.json").write_text(json.dumps(
            {"instructions": {n: {"stage": st, "loop": 0,
                                  "text": f"%{n} = {shape} fusion(%x)"}
                              for n, (st, shape) in insts.items()}}))
    write("sbatch-1080-1920-8", {
        "fusion.567": ("raft/stream/gather", "s8[257,135,240,128]{3,2,1,0}"),
        "multiply_convert_fusion": ("raft/stream/gather/dequant",
                                    "bf16[8,135,240,256]{3,2,1,0}"),
        "multiply_convert_fusion.1": ("raft/stream/gather/dequant",
                                      "bf16[8,135,240,256]{3,0,2,1}"),
        "fusion.233": ("raft/fnet", "bf16[8,270,480,96]{3,2,1,0}")})
    write("scommit-1080-1920-8", {
        "fusion.12": ("raft/stream/commit/quant", "f32[8,256]{1,0}"),
        "fusion.8": ("raft/stream/commit/quant", "f32[8,256]{1,0}"),
        "broadcast_select_fusion.2": ("raft/stream/commit",
                                      "s8[8,135,240,256]{3,2,1,0}")})
    write("scommit-1080-1920-1", {
        "fusion.4": ("raft/stream/commit/quant", "f32[256]{0}"),
        "fusion.9": ("raft/stream/commit", "s8[257,135,240,256]{3,2,1,0}")})
    return str(tmp_path / "*.stages.json")


def test_the_scope_readers_read_their_own_programs_scope(
        bench_modules, cell, stage_maps):
    """``slot_dequant_ms`` is the two dequantising fusions of the batched
    step, each by the mean of its events; ``slot_quant_ms`` the batch's
    commit's two absmax fusions, not the one-row commit's (less time in the
    window) and not the masked select; a trace without them reads None."""
    bm = bench_modules
    bm.stages.load_stage_maps.cache_clear()
    trace = _trace(bm, [
        ("fusion.567 s8[257,135,240,128] fusion", 50e6, 10),
        ("multiply_convert_fusion bf16[8,135,240,256] fusion", 4e6, 10),
        ("multiply_convert_fusion.1 bf16[8,135,240,256] fusion", 6e6, 10),
        ("fusion.233 bf16[8,270,480,96] fusion", 900e6, 10),
        ("fusion.12 f32[8,256] fusion", 2e6, 10),
        ("fusion.8 f32[8,256] fusion", 3e6, 10),
        ("broadcast_select_fusion.2 s8[8,135,240,256] fusion", 9e6, 10),
        ("fusion.4 f32[256] fusion", 0.2e6, 20),
        ("fusion.9 s8[257,135,240,256] fusion", 1e6, 20)])
    ctx = _ctx(bm, cell, trace=trace)
    deq = {"stage": "(^|/)stream/gather/dequant(/|$)", "maps": stage_maps}
    qnt = {"stage": "(^|/)stream/commit/quant(/|$)", "maps": stage_maps}
    assert bm.pool_metrics.scope_ms(ctx, deq) == pytest.approx(1.0)
    assert bm.pool_metrics.scope_ms(ctx, qnt) == pytest.approx(0.5)
    bare = _ctx(bm, cell, trace=_trace(bm, [
        ("fusion.233 bf16[8,270,480,96] fusion", 900e6, 10)]))
    assert bm.pool_metrics.scope_ms(bare, deq) is None
    assert bm.pool_metrics.scope_ms(bare, qnt) is None
    assert bm.pool_metrics.scope_ms(_ctx(bm, cell), qnt) is None
    bm.stages.load_stage_maps.cache_clear()


def test_the_int8_roofline_counts_the_rows_as_stored(bench_modules, cell,
                                                     stage_maps, run):
    """A row through one advance: the gather reads 16,588,800 int8 B, 2,048
    B of scales and 259,200 B of seed and writes 33,177,600 B of bfloat16
    maps, the commit the same the other way: 100,055,296 B, 8 rows 0.977 ms
    at 819 GB/s; over a ``slot_io_ms`` of 5 + 32 ms that is 2.64 %.  The
    bfloat16 cost function of ``slot_io_roofline`` would say 1.31 ms."""
    bm = bench_modules
    spec = importlib.util.spec_from_file_location(
        "slot_io_int8_roofline", os.path.join(
            BENCH, "layer_metrics", "slot_io_int8_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    shapes = {"q": 32400, "fnet_dim": 256, "slot_channels": 256,
              "compute_itemsize": 2}
    cost = mod.slot_io_stored(shapes)
    assert cost == {"ops": 0, "bytes": 2 * (16_588_800 * 3 + 2_048 + 259_200)}
    bm.stages.load_stage_maps.cache_clear()
    trace = _trace(bm, [
        ("fusion.567 s8[257,135,240,128] fusion", 50e6, 10),
        ("fusion.233 bf16[8,270,480,96] fusion", 900e6, 10)])
    prom = {'raft_serving_batch_size_bucket{le="8"}': 10.0,
            'raft_serving_batch_size_bucket{le="+Inf"}': 10.0,
            "raft_serving_batch_size_sum": 79.0,
            "raft_serving_batch_size_count": 10.0}
    ctx = _ctx(bm, cell, trace=trace, prom_window=prom)
    params = {"stage": "(^|/)stream/gather(/|$)", "program": "slot_commit",
              "maps": stage_maps}
    got = mod.read(ctx, params)
    least_ms = 8 * cost["bytes"] / 819e9 * 1e3
    assert least_ms == pytest.approx(0.9773, rel=1e-3)
    assert got == pytest.approx(100.0 * least_ms / (5.0 + 32.0), rel=1e-6)
    assert got < 105.0
    # a configuration whose slots are not int8 is not this metric's
    assert mod.read(_ctx(bm, cell, trace=trace, prom_window=prom,
                         config=cell["churn"]), params) is None
    assert mod.read(_ctx(bm, cell, prom_window=prom), params) is None
    bm.stages.load_stage_maps.cache_clear()


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_read_metric_finds_each_new_reader_by_name(bench_modules, cell,
                                                   metric):
    """``readers.read_metric`` loads the ``.py`` beside the ``.json``; with
    nothing recorded (no trace, no history) each returns None and raises
    nothing: what a run on the parent's program gives."""
    ctx = _ctx(bench_modules, cell,
               config=dict(cell["config"], name="no-such-config"))
    assert bench_modules.readers.read_metric(BENCH, metric, ctx) is None


# ----------------------------- the cell under run.py, on the CPU, tiny sizes

TINY, LIMIT = "tiny-int8-cell", 0.5


@pytest.fixture()
def tiny_cell(tmp_path, cell, int8_ref, monkeypatch):
    """A copy of the benchmark with one more cell: this configuration at a
    64x96 bucket in float32 with batches of 2 and FOUR int8 slots, eight
    sessions live and three playing (``tests/test_benchmark_churn.py``'s
    rehearsal, with this configuration's reference and ``--quant int8``).
    The reference fills the projection's holes as the program does, so what
    is left between a float32 program and the float32 reference is a code
    moved here and there by the encoders' round-off: far under the
    reference's own bfloat16 rounding, which the ratio divides by."""
    import shutil
    monkeypatch.setattr(sys.modules["references.warm_restart"],
                        "forward_interpolate", _program_projection)
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    cfg = _tiny_config(cell, max_sessions="4")
    cfg.update(name="tiny-int8",
               check=dict(cfg["check"], ratio_limit=LIMIT, sample=3))
    (bench / "configs" / "tiny-int8.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-int8.json").write_text(json.dumps(dict(
        cell["traffic"], height=64, width=96, clips=2, max_shift=2,
        session_frames=[6, 8], burst_frames=[2, 4], check_park_place=2)))
    (bench / "workloads" / (TINY + ".json")).write_text(json.dumps(
        {"clients": 3, "live_sessions": 8, "why": "rehearsal"}))
    manifest = json.loads(json.dumps(cell["bench"]))
    manifest["configs"].append({
        "name": "tiny-int8", "source": "rehearsal",
        "file": "benchmark/configs/tiny-int8.json", "reduced": [],
        "why": "x"})
    manifest["workloads"].append({
        "name": TINY, "config": "tiny-int8", "traffic": "tiny-int8",
        "chips": 1, "why": "x"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return bench, tmp_path / "BENCHMARK.json"


def test_the_cell_under_the_harness_is_correct_on_the_cpu(run, tiny_cell,
                                                          capsys):
    """``run.py`` finds the configuration's reference by name and holds the
    three kept answers (warm, the restart, seeded from the restart) of a
    server with int8 slots to it; the pool is full at the window's end and
    its bytes are the leaves'; every row that was committed was quantised;
    the trace's readers find no device plane on the CPU and are left out."""
    bench, manifest = tiny_cell
    rc = run.main(["--workload", TINY, "--seed", "4500000019", "--seconds",
                   "12", "--trace", "1"], bench_dir=str(bench),
                  manifest=str(manifest), require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0, lines[-12:]
    ratios = {n: c for n, c in result["checks"].items()
              if n.startswith("precision_ratio.r")}
    assert len(ratios) == 3 and all(c["ok"] and 0 < c["value"] < LIMIT
                                    for c in ratios.values()), ratios
    assert result["checks"]["compile_misses"]["value"] == 0
    m = result["metrics"]
    assert m["slot_fill"]["value"] == 100.0
    rows = 5 * 8 * 12
    assert m["slot_pool_gb"]["value"] * 1e9 == (
        2 * rows * 256 + 2 * 5 * 256 * 4 + rows * 2 * 4)
    assert 50.0 < m["stream_warm_share"]["value"] < 100.0
    assert m["stream_restart_batched_share"]["value"] == 100.0
    assert 0.0 < m["stream_lru_demotions_per_advance"]["value"] < 1.0
    for name in ("slot_dequant_ms", "slot_quant_ms", "slot_io_int8_roofline",
                 "slot_io_ms", "slot_io_roofline", "gru_roofline"):
        assert name not in m, name
    for name in ("batch_fill", "host_path_ms", "stream_commit_ms",
                 "corr_lane_fill", "peak_hbm_gb"):
        assert name in m or name == "peak_hbm_gb", name
