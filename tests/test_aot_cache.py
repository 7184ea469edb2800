"""AOT executable cache (serving/aot_cache.py): round-trip, invalidation,
corruption fallback — plus the quantized slot-row storage parity the cache
ships alongside (both halves of the cold-start PR).

The module fixture pays the one real compile (raft-small, one bucket, one
batch step); every other engine in the file boots from the directory it
exported, which is exactly the fleet-respawn path being contracted:
load-or-compile, never load-or-crash.
"""

import json
from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from raft_tpu.config import RAFTConfig, init_rng  # noqa: E402
from raft_tpu.models import init_raft  # noqa: E402
from raft_tpu.serving import ServeConfig  # noqa: E402
from raft_tpu.serving.aot_cache import (  # noqa: E402
    KEY_FIELDS, MANIFEST_NAME, EngineCache, cache_identity, key_filename)
from raft_tpu.serving.engine import InferenceEngine  # noqa: E402

BUCKET = (32, 48)


def _sconfig():
    return ServeConfig(buckets=(BUCKET,), max_batch=1, batch_steps=(1,),
                       port=0, max_sessions=0)


def _boom(key):
    raise AssertionError(f"cache-warm engine tried to compile {key}")


@pytest.fixture(scope="module")
def warm_cache(tmp_path_factory):
    """Engine A: cold warmup against an empty cache root — compiles the
    grid once for the whole module and serializes every executable."""
    config = RAFTConfig.small_model(iters=1)
    params = init_raft(init_rng(), config)
    root = tmp_path_factory.mktemp("engine-cache")
    cache = EngineCache(root, config)
    engine = InferenceEngine(config, params, _sconfig(), cache=cache)
    n = engine.warmup(verbose=False)
    rng = np.random.RandomState(0)
    im1 = rng.rand(1, *BUCKET, 3).astype(np.float32)
    im2 = rng.rand(1, *BUCKET, 3).astype(np.float32)
    return SimpleNamespace(config=config, params=params, root=root,
                           cache=cache, engine=engine, n=n,
                           im1=im1, im2=im2)


def test_cold_warmup_compiles_and_exports(warm_cache):
    wc = warm_cache
    assert wc.n > 0
    assert wc.cache.stats.saves == wc.n
    assert wc.cache.stats.hits == 0 and wc.cache.stats.misses == wc.n
    assert wc.engine.warmup_loaded == 0
    manifest = json.loads((wc.cache.dir / MANIFEST_NAME).read_text())
    assert manifest["key_fields"] == list(KEY_FIELDS)
    assert len(manifest["keys"]) == wc.n
    for entry in manifest["entries"]:
        assert (wc.cache.dir / entry).exists()
    # the directory is keyed by the full identity triple
    ident = cache_identity(wc.config)
    assert ident["config_hash"] in wc.cache.dir.name
    assert ident["jax_version"] in wc.cache.dir.name


def test_cached_warmup_loads_bit_identical_without_compiling(warm_cache):
    wc = warm_cache
    cache2 = EngineCache(wc.root, wc.config)
    engine2 = InferenceEngine(wc.config, wc.params, _sconfig(),
                              cache=cache2)
    # the contract under test: a warm directory means warmup never
    # reaches the compiler at all
    engine2._compile = _boom
    n = engine2.warmup(verbose=False)
    assert n == wc.n
    assert engine2.warmup_loaded == wc.n
    assert cache2.stats.hits == wc.n
    assert cache2.stats.misses == 0
    # deserialize_and_load round-trips the executable bit-identically:
    # same inputs, same bytes out
    cold = wc.engine.run(BUCKET, wc.im1, wc.im2)
    warm = engine2.run(BUCKET, wc.im1, wc.im2)
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(warm))


# -- the instruction -> stage() map beside each entry -----------------------

def _stage_maps(cache):
    return {p.name: json.loads(p.read_text())
            for p in sorted(cache.dir.glob("*.stages.json"))}


def _runnable_instructions(text):
    """Names of the instructions of an HLO module's text outside fused
    computations and reducers, found without the program's parser: what is
    called by ``calls=`` or ``to_apply=`` runs inside its caller."""
    import re
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
    names, comp = set(), None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            comp = head.group(1)
        elif line.startswith("}"):
            comp = None
        elif comp is not None and comp not in inner:
            inst = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", line)
            if inst:
                names.add(inst.group(1))
    return names


def test_stage_map_covers_every_instruction_of_the_executable(warm_cache):
    wc = warm_cache
    maps = _stage_maps(wc.cache)
    # one map beside each serialized executable, named after it
    assert sorted(maps) == sorted(
        key_filename(k)[:-len(".bin")] + ".stages.json"
        for k in wc.engine.keys())
    key = wc.engine.keys()[0]
    doc = maps[key_filename(key)[:-len(".bin")] + ".stages.json"]
    assert doc["key"] == dict(zip(KEY_FIELDS, key))
    text = wc.engine._exec[key].as_text()
    insts = doc["instructions"]
    assert set(insts) == _runnable_instructions(text)
    for name, rec in insts.items():
        assert rec["text"].startswith(f"%{name} = ")
    # the model's stage() scopes are what the map's stages are made of
    stages = {rec["stage"] for rec in insts.values()}
    for scope in ("raft/fnet", "raft/cnet", "raft/corr_lookup",
                  "raft/update", "raft/upsample"):
        assert any(st == scope or st.startswith(scope + "/")
                   for st in stages), (scope, sorted(stages))
    # per-level scopes exist only where the lookup is the fused kernel;
    # no stage names how the code was traced
    assert not any(seg in st.split("/") for st in stages
                   for seg in ("while", "body", "closed_call"))


@pytest.mark.parametrize("case", ["op_name", "fusion_root", "operand",
                                  "structural_only", "fused_left_out"])
def test_instruction_stages_rules(case):
    from raft_tpu.telemetry.trace import instruction_stages, stage_path
    text = """HloModule jit_fn, is_scheduled=true

%fused_computation.1 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(fn)/while/body/raft/corr_lookup/l2/mul"}
}

%body.2 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %convert.9 = bf16[4]{0} convert(%fusion.1)
  %add.3 = f32[4]{0} add(%gte.1, %gte.1), metadata={op_name="jit(fn)/while/body/closed_call/add"}
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%gte.0, %add.3)
}

ENTRY %main.3 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %tanh.1 = f32[4]{0} tanh(%x), metadata={op_name="jit(fn)/jit(main)/raft/fnet/encoder/stem/tanh" source_file="a.py"}
  %while.1 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond.1, body=%body.2, metadata={op_name="jit(fn)/while"}
  ROOT %gte.2 = f32[4]{0} get-tuple-element(%while.1), index=1
}
"""
    m = instruction_stages(text)
    if case == "op_name":
        assert m["tanh.1"]["stage"] == "raft/fnet/encoder/stem"
        assert stage_path("jit(fn)/while/body/closed_call/raft/update/"
                          "update/gru/dot_general") == "raft/update/update/gru"
    elif case == "fusion_root":
        # no op_name of its own: a fusion takes its root's stage
        assert m["fusion.1"]["stage"] == "raft/corr_lookup/l2"
    elif case == "operand":
        # a compiler-made convert belongs to what produced its input
        assert m["convert.9"]["stage"] == "raft/corr_lookup/l2"
    elif case == "structural_only":
        # code outside every stage() stays unmapped; a container too
        assert m["add.3"]["stage"] == "" and m["while.1"]["stage"] == ""
    else:
        assert "mul.1" not in m and "p0" not in m
        assert set(m) == {"arg", "gte.1", "fusion.1", "convert.9", "add.3",
                          "tuple.1", "x", "tanh.1", "while.1", "gte.2"}


def test_loaded_executable_with_no_map_gets_one(warm_cache):
    wc = warm_cache
    before = _stage_maps(wc.cache)
    for p in wc.cache.dir.glob("*.stages.json"):
        p.unlink()
    cache2 = EngineCache(wc.root, wc.config)
    engine2 = InferenceEngine(wc.config, wc.params, _sconfig(),
                              cache=cache2)
    engine2._compile = _boom
    assert engine2.warmup(verbose=False) == wc.n
    assert engine2.warmup_loaded == wc.n
    # the loaded executable's own text gives the same map
    assert _stage_maps(cache2) == before


def test_stale_identity_field_invalidates_whole_directory(warm_cache):
    wc = warm_cache
    path = wc.cache.dir / MANIFEST_NAME
    manifest = json.loads(path.read_text())
    tampered = dict(manifest, jax_version="0.0.0-stale")
    path.write_text(json.dumps(tampered))
    try:
        stale = EngineCache(wc.root, wc.config)
        assert not stale.validate()
        assert stale.load(tuple(manifest["keys"][0])) is None
        assert stale.stats.misses == 1 and stale.stats.hits == 0
    finally:
        path.write_text(json.dumps(manifest))


def test_manifest_version_bump_treated_cold(warm_cache):
    wc = warm_cache
    path = wc.cache.dir / MANIFEST_NAME
    manifest = json.loads(path.read_text())
    path.write_text(json.dumps(dict(manifest, version=999)))
    try:
        assert not EngineCache(wc.root, wc.config).validate()
    finally:
        path.write_text(json.dumps(manifest))


def test_config_change_lands_in_a_different_directory(warm_cache):
    wc = warm_cache
    other = EngineCache(wc.root, RAFTConfig.small_model(iters=2))
    assert other.dir != wc.cache.dir
    # fresh directory, no manifest: cold for loading by definition
    assert not other.validate()


def test_corrupt_entry_skipped_and_recompiled(warm_cache, caplog):
    wc = warm_cache
    manifest = json.loads((wc.cache.dir / MANIFEST_NAME).read_text())
    victim = wc.cache.dir / manifest["entries"][0]
    blob = victim.read_bytes()
    victim.write_bytes(b"not a pickle")
    try:
        cache3 = EngineCache(wc.root, wc.config)
        engine3 = InferenceEngine(wc.config, wc.params, _sconfig(),
                                  cache=cache3)
        with caplog.at_level("WARNING"):
            n = engine3.warmup(verbose=False)
        assert n == wc.n
        assert engine3.warmup_loaded == wc.n - 1
        assert cache3.stats.misses == 1
        assert "corrupt entry" in caplog.text
        # the fallback compile still serves
        out = engine3.run(BUCKET, wc.im1, wc.im2)
        assert np.asarray(out).shape == (1, *BUCKET, 2)
    finally:
        victim.write_bytes(blob)


def test_export_cache_prestages_missing_entries(warm_cache, tmp_path):
    """The RollingUpdater path: a warmed engine can export its in-memory
    executables into an empty directory on demand."""
    wc = warm_cache
    cache = EngineCache(tmp_path / "prestage", wc.config)
    engine = InferenceEngine(wc.config, wc.params, _sconfig(), cache=cache)
    engine._compile = _boom          # reuse engine A's executables instead
    engine._exec = dict(wc.engine._exec)
    info = engine.export_cache()
    assert info["exported"] == wc.n
    assert cache.validate()
    follower = EngineCache(tmp_path / "prestage", wc.config)
    assert follower.load(next(iter(wc.engine._exec))) is not None


def test_key_filename_separates_policies():
    a = key_filename(("pair", 32, 48, 1, "fixed"))
    b = key_filename(("pair", 32, 48, 1, "converge:1e-2"))
    assert a != b
    assert key_filename(("pair", 32, 48, 1, "fixed")) == a


def test_nan_sentinel_suppressed_only_inside_context(monkeypatch):
    """Cache-attached engines trace sentinel-free (a jax.debug.callback
    trampoline is a PyCapsule — unpicklable, so it can never round-trip
    through serialize_executable); the switch must restore on exit."""
    from raft_tpu.telemetry import watchdogs as wd
    monkeypatch.setenv("RAFT_TPU_WATCHDOGS", "1")
    assert wd.nan_sentinel_enabled()
    with wd.suppress_nan_sentinel():
        assert not wd.nan_sentinel_enabled()
        with wd.suppress_nan_sentinel():    # reentrant
            assert not wd.nan_sentinel_enabled()
        assert not wd.nan_sentinel_enabled()
    assert wd.nan_sentinel_enabled()


# ------------------------------------------ quantized slot-row storage ----

def test_quantize_rows_roundtrip_parity():
    """int8 per-channel storage must round-trip features within the
    quantization step (absmax/127 per channel) — the gather/scatter
    parity bound the serving slot pool relies on."""
    from raft_tpu.models.raft import dequantize_rows, quantize_rows
    rng = np.random.RandomState(7)
    rows = jnp.asarray(rng.randn(2, 4, 6, 8).astype(np.float32) * 3)
    vals, scales = quantize_rows(rows)
    assert vals.dtype == jnp.int8
    assert scales.shape == (2, 8)
    back = dequantize_rows(vals, scales)
    # worst case error is half a quantization step per element
    step = np.asarray(scales)[:, None, None, :]
    assert np.all(np.abs(np.asarray(back - rows)) <= step * 0.51)
    rel = (np.linalg.norm(np.asarray(back - rows))
           / np.linalg.norm(np.asarray(rows)))
    assert rel < 0.02


def test_quantize_rows_zero_channel_exact():
    from raft_tpu.models.raft import dequantize_rows, quantize_rows
    rows = jnp.zeros((1, 4, 4, 3), jnp.float32)
    back = dequantize_rows(*quantize_rows(rows))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(rows))


def test_quantized_scale_poison_propagates_nan():
    """Slot poisoning under quant NaNs the SCALE row; any gather that
    dequantizes the slot must surface NaN, not plausible features."""
    from raft_tpu.models.raft import dequantize_rows, quantize_rows
    rows = jnp.ones((4, 4, 2), jnp.float32)
    vals, scales = quantize_rows(rows)
    poisoned = dequantize_rows(vals, jnp.full_like(scales, jnp.nan))
    assert np.all(np.isnan(np.asarray(poisoned)))
