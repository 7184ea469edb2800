"""chip_smoke.py's contract off the chip, the compile-cache helper, the
budget key, and one-process-per-chip in the fleet (ISSUE 21)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run(argv, cwd=REPO, **env):
    return subprocess.run(
        [sys.executable] + argv, cwd=cwd, capture_output=True, text=True,
        timeout=300, env={**os.environ, **env})


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_chip_smoke_fails_without_a_tpu(argv):
    """Held to the CPU the script exits non-zero and prints no result
    line — with --chips 4 before it starts a single replica."""
    proc = _run([os.path.join(REPO, "chip_smoke.py")] + argv,
                JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "FAILED" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it: non-zero, no result."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path),
                JAX_PLATFORMS="cpu", PYTHONPATH="")
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


# ------------------------------------------------ compile-cache placement

_CACHE_PROBE = ("import sys; sys.path.insert(0, {repo!r}); "
                "from raft_tpu.compile_cache import configure_compile_cache;"
                " import jax; d = configure_compile_cache(); "
                "print(d); print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_is_fixed_in_the_checkout(tmp_path):
    """Variable unset: <checkout>/.jax_cache — the same path from two
    working directories, and it is what jax.config holds."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    outs = []
    for cwd in (REPO, str(tmp_path)):
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE.format(repo=REPO)],
            cwd=cwd, capture_output=True, text=True, timeout=120, env=env)
        assert proc.returncode == 0, proc.stderr[-500:]
        outs.append(proc.stdout.split())
    want = os.path.join(REPO, ".jax_cache")
    assert outs == [[want, want], [want, want]]


def test_compile_cache_honours_the_environment(tmp_path):
    """Variable set: the helper returns it and sets nothing in code — JAX
    reads the variable itself."""
    placed = str(tmp_path / "placed")
    proc = _run(["-c", _CACHE_PROBE.format(repo=REPO)],
                JAX_COMPILATION_CACHE_DIR=placed)
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.split() == [placed, placed]

    import jax

    from raft_tpu import compile_cache
    calls = []
    orig = jax.config.update
    try:
        jax.config.update = lambda *a, **k: calls.append(a)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = placed
        assert compile_cache.configure_compile_cache() == placed
    finally:
        jax.config.update = orig
        del os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert calls == []


# ------------------------------------------------------------- budget key

def test_budget_key_from_device_kind():
    from raft_tpu.lint.budget import DEVICE_BUDGETS, budget_key

    assert budget_key("TPU v5 lite") == "tpu-v5e"
    assert budget_key("TPU v5e") == "tpu-v5e"
    assert budget_key("TPU v4") == "tpu-v4"
    assert budget_key("cpu") == "cpu"
    assert DEVICE_BUDGETS["tpu-v5e"]["hbm_bytes"] == 16 * 1024 ** 3
    for unknown in ("TPU v5", "TPU v9", "gpu", ""):
        with pytest.raises(ValueError, match="no capacity budget known"):
            budget_key(unknown)


def test_analyze_derives_its_budget_from_the_device():
    """No device_kind argument: the report is for the device this process
    runs on (here the CPU), not for a TPU v4."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.lint import budget
    from raft_tpu.serving import ServeConfig, parse_buckets

    rep = budget.analyze(
        RAFTConfig.small_model(iters=2),
        ServeConfig(buckets=parse_buckets("32x48"), max_batch=1,
                    max_sessions=1))
    assert rep["device_kind"] == "cpu"


# ------------------------------------------------- one process per chip

class _FakeProc:
    def __init__(self):
        self.returncode = None

    def poll(self):
        return self.returncode

    def kill(self):
        self.returncode = -9

    terminate = kill

    def wait(self, timeout=None):
        return self.returncode


@pytest.fixture
def tpu_host(monkeypatch):
    """A host with two chips, as the fleet manager sees one: device files
    counted, JAX_PLATFORMS not pinned to the CPU; Popen captured."""
    from raft_tpu.fleet import manager as mgr

    spawned = []

    def popen(argv, stdout=None, env=None, **kw):
        spawned.append(env)
        # the child's banner, so _default_spawn returns at once
        stdout.write(f"[serve] listening on http://127.0.0.1:"
                     f"{9000 + len(spawned)}  buckets=[]\n")
        stdout.flush()
        return _FakeProc()

    monkeypatch.setattr(mgr, "local_chip_count", lambda: 2)
    monkeypatch.setattr(mgr.subprocess, "Popen", popen)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    return mgr, spawned


def test_default_spawn_gives_each_replica_its_own_chip(tpu_host, tmp_path):
    mgr, spawned = tpu_host
    from raft_tpu.fleet import FleetConfig

    m = mgr.ReplicaManager(FleetConfig(replicas=2, max_replicas=2),
                           str(tmp_path), base_args=["--small"])
    a, b = m._spawn_one(), m._spawn_one()
    assert (a.chip, b.chip) == (0, 1)
    chips = [env["TPU_VISIBLE_CHIPS"] for env in spawned]
    assert chips == ["0", "1"]
    for env in spawned:
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # no chip left: a third replica fails loudly instead of sharing one
    with pytest.raises(RuntimeError, match="no free TPU chip"):
        m._spawn_one()
    # a replica whose process has exited frees its chip for the respawn
    a.proc.kill()
    a.state = "dead"
    assert m._spawn_one().chip == 0


def test_more_replicas_than_chips_is_refused_up_front(tpu_host, tmp_path):
    mgr, _ = tpu_host
    from raft_tpu.fleet import FleetConfig

    with pytest.raises(ValueError, match="one replica per chip"):
        mgr.ReplicaManager(FleetConfig(replicas=3, max_replicas=4),
                           str(tmp_path), base_args=[])
    # CPU replicas (--cpu forwarded) are not placed and not limited
    m = mgr.ReplicaManager(FleetConfig(replicas=3, max_replicas=4),
                           str(tmp_path), base_args=["--cpu"])
    assert m._spawn_one().chip is None


def test_fleet_launcher_initialises_no_accelerator_backend():
    """``-m serve_fleet`` pins its own process to the CPU platform before
    anything asks JAX for a backend — under a JAX_PLATFORMS that names the
    TPU first.  The launcher is cut short at build_fleet; by then the
    config default, the manifest and the seeded weight init have all run."""
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
import jax
from raft_tpu import cli
from raft_tpu.fleet import launch
def stop(args, config, load_params, run_log=None):
    load_params(args, config)            # the launcher's only JAX work
    from jax._src import xla_bridge
    print("BACKENDS", sorted(xla_bridge._backends), jax.config.jax_platforms)
    raise SystemExit(0)
launch.build_fleet = stop
cli.main(["-m", "serve_fleet", "--small", "--replicas", "2",
          "--run-log", "none"])
"""
    proc = _run(["-c", code], JAX_PLATFORMS="tpu,cpu")
    assert proc.returncode == 0, proc.stderr[-800:]
    line = [ln for ln in proc.stdout.splitlines() if "BACKENDS" in ln][-1]
    assert line == "BACKENDS ['cpu'] cpu"


def test_small_phase_rehearsed_on_the_cpu(capsys):
    """``phase_small`` through its own code at a tiny size (2 x 64x96, the
    lookup in interpret mode, all 20 updates): RAFT-S's served program from
    ``benchmark/configs/raft-small-1080p.json``'s serve arguments, held to
    ``benchmark/reference.py`` by the cell's ``precision_ratio``; one JSON
    line, ``ok``.  A check that does not hold raises ``SmokeFailure``."""
    import json

    import chip_smoke
    sz = chip_smoke.Sizes(interpret=True, small_batch=2, small_hw=(64, 96))
    chip_smoke.phase_small(chip_smoke.CompileMeter(), sz)
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["phase"] == "small" and rec["ok"] is True
    assert (rec["program"], rec["iters"]) == ("2x64x96", 20)
    assert rec["dtype"] == "bfloat16"
    assert 0 < rec["precision_ratio"] < rec["limit"] == 3.0
    assert 0 < rec["keyblock_share"] <= 1
    assert 1 <= rec["bands_per_tile"] <= rec["steps_per_tile"]
    # the real sizes are the cell's
    real = chip_smoke.Sizes()
    assert (real.small_batch, real.small_hw) == (8, (1080, 1920))


def test_flows_phase_rehearsed_on_the_cpu(capsys):
    """``phase_flows`` through its own code at a tiny size (2 x 64x96, two
    updates, the lookup in interpret mode): the five served programs of
    ``chip_smoke.FLOW_PROGRAMS`` built from their configurations' serve
    arguments, run on the fixed seed and hashed (``chip_smoke.py --flows``:
    a tool, no phase of the smoke)."""
    import json

    import chip_smoke
    sz = chip_smoke.Sizes(interpret=True, flow_hw=(64, 96), flow_batch=2,
                          flow_iters=2)
    chip_smoke.phase_flows(chip_smoke.CompileMeter(), sz)
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["phase"] == "flows" and rec["ok"] is True
    names = [name for name, *_ in chip_smoke.FLOW_PROGRAMS]
    assert len(names) == 5 and all(n in rec for n in names)
    for name, _, kind, _ in chip_smoke.FLOW_PROGRAMS:
        assert len(rec[name]["sha256"]) == 64 and rec[name]["iters"] == 2
        assert rec[name]["program"] == ("1x64x96" if kind == "stream"
                                        else "2x64x96")
        assert rec[name]["mean_abs_flow"] > 0
    # one model, one seed, one frame size: the two raft-things pair
    # programs answer the same flow; the stream kinds (a zero seed over
    # cached maps) and RAFT-S do not
    shas = [rec[n]["sha256"] for n in names]
    assert shas[0] == shas[1] and len(set(shas)) == 4
    # the real sizes are the cells'
    real = chip_smoke.Sizes()
    assert real.flow_programs == chip_smoke.FLOW_PROGRAMS
    assert not (real.flow_hw or real.flow_batch or real.flow_iters)
    assert [b for *_, b in real.flow_programs] == [32, 8, 8, 8, 1]


def test_int8_phase_rehearsed_on_the_cpu(capsys):
    """``phase_int8`` through its own code at a tiny size (64x96, FOUR
    slots, float32): the int8 configuration's serve arguments through the
    real server, an open and an advance, four more opens that take the
    session's slot, the cold restart and the warm advance after it; the
    pool's bytes are its leaves', every committed row was quantised."""
    import json

    import chip_smoke
    sz = chip_smoke.Sizes(interpret=True, int8_hw=(64, 96), int8_slots=4)
    chip_smoke.phase_int8(chip_smoke.CompileMeter(), sz)
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["phase"] == "int8" and rec["ok"] is True
    assert "--quant int8" in rec["argv"] and "--no-warmup" in rec["argv"]
    rows, q = 5, 8 * 12
    assert rec["pool_bytes"] == {"vals": rows * q * 512,
                                 "scales": rows * 4 * 512,
                                 "seed": rows * q * 8}
    assert (rec["rows_quantized"], rec["frames"], rec["opens"],
            rec["restarts_batched"]) == (9, 3, 5, 1)
    assert all(m > 0 for m in rec["mean_abs_flow"])
    # the real sizes are the configuration's
    real = chip_smoke.Sizes()
    assert (real.int8_hw, real.int8_slots) == ((1080, 1920), 256)
