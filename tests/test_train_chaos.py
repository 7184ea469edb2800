"""Training-plane resilience tests (ISSUE 14): the seeded fault injector,
the async checkpoint writer (verify-after-write, prune-after-confirm,
saturation backpressure), checkpoint fsync durability, the preemption
guard, and the mp loader's bounded respawn self-healing."""

import os
import signal
import time

import numpy as np
import pytest

from raft_tpu.training.faults import (RATE_ARMS, TrainChaosSpec,
                                      TrainFaultInjector, make_train_injector,
                                      parse_train_chaos_spec)
from raft_tpu.training.resilience import (PREEMPT_EXIT_CODE, CheckpointWriter,
                                          PreemptionGuard, save_if_finite)


# ------------------------------------------------------------ spec parse --

def test_parse_train_chaos_spec():
    spec = parse_train_chaos_spec(
        "seed=7,worker_kill=0.02,worker_stall=0.01,nan_loss=0.5,"
        "torn_ckpt=1.0,preempt=40")
    assert spec.seed == 7 and spec.preempt == 40
    assert spec.nan_loss == 0.5 and spec.torn_ckpt == 1.0
    assert spec.armed
    assert not TrainChaosSpec().armed
    assert TrainChaosSpec(preempt=0).armed        # step 0 is a valid target
    # empty spec -> all-zero injector only via make_train_injector("")
    assert make_train_injector(None) is None and make_train_injector("") is None
    assert make_train_injector("seed=1") is not None
    with pytest.raises(ValueError, match="unknown train-chaos arm"):
        parse_train_chaos_spec("engine_error=0.1")   # serving arm, not ours
    with pytest.raises(ValueError, match="rates must be floats"):
        parse_train_chaos_spec("nan_loss=1.5")
    with pytest.raises(ValueError, match="rates must be floats"):
        parse_train_chaos_spec("preempt=-3")
    with pytest.raises(ValueError, match="expected key=value"):
        parse_train_chaos_spec("nan_loss")


def test_injector_deterministic_replay_disarm_force():
    a = TrainFaultInjector(parse_train_chaos_spec("seed=3,nan_loss=0.3"))
    b = TrainFaultInjector(parse_train_chaos_spec("seed=3,nan_loss=0.3"))
    rolls = [a.roll("nan_loss") for _ in range(50)]
    assert rolls == [b.roll("nan_loss") for _ in range(50)]   # replays
    assert any(rolls) and not all(rolls)
    assert a.injected["nan_loss"] == sum(rolls)
    a.disarm()
    assert not any(a.roll("nan_loss") for _ in range(50))
    a.force("nan_loss", [True])                    # forced beats disarm
    assert a.roll("nan_loss") and not a.roll("nan_loss")
    # preempt is step-triggered, never rate-rolled
    c = TrainFaultInjector(TrainChaosSpec(seed=1, preempt=5))
    assert not c.roll("preempt")
    with pytest.raises(ValueError):
        c.force("latency", [1])


def test_corrupt_batch_and_tear(tmp_path):
    inj = TrainFaultInjector(parse_train_chaos_spec("seed=1"))
    batch = (np.ones((2, 4, 4, 3), np.float32),
             np.ones((2, 4, 4, 3), np.float32))
    assert inj.corrupt_batch(batch) is batch       # unarmed: untouched
    inj.force("nan_loss", [True])
    poisoned = inj.corrupt_batch(batch)
    assert np.isnan(poisoned[0]).all()
    np.testing.assert_array_equal(poisoned[1], batch[1])
    np.testing.assert_array_equal(batch[0], 1.0)   # input not mutated

    p = tmp_path / "ckpt_1.npz"
    np.savez(p, w=np.zeros(64))
    size = p.stat().st_size
    assert not inj.tear_checkpoint(p)              # unarmed
    inj.force("torn_ckpt", [True])
    assert inj.tear_checkpoint(p)
    assert p.stat().st_size < size


# ------------------------------------------------- checkpoint durability --

def test_save_checkpoint_fsyncs_file_and_dir(tmp_path, monkeypatch):
    """The atomic rename must be durable: fsync the tmp file BEFORE
    os.replace and the parent directory AFTER it."""
    from raft_tpu.training import checkpoint as ck

    synced = []
    real_fsync = os.fsync
    real_replace = os.replace
    events = []
    monkeypatch.setattr(os, "fsync",
                        lambda fd: (synced.append(fd), events.append("fsync"),
                                    real_fsync(fd))[-1])
    monkeypatch.setattr(os, "replace",
                        lambda a, b: (events.append("replace"),
                                      real_replace(a, b))[-1])
    p = tmp_path / "ckpt_1.npz"
    ck.save_checkpoint(p, {"w": np.arange(8, dtype=np.float32)})
    assert p.exists()
    assert len(synced) == 2                       # tmp file + parent dir
    assert events == ["fsync", "replace", "fsync"]


# --------------------------------------------------- async ckpt writer ----

def _tiny_state(v=0.0):
    return {"w": np.full((4,), v, np.float32)}


def test_writer_confirms_then_prunes(tmp_path):
    from raft_tpu.training.checkpoint import list_checkpoints

    goods = []
    logs = []
    w = CheckpointWriter(log_fn=logs.append, keep=2,
                         on_good=lambda s, st: goods.append(s))
    for step in (1, 2, 3):
        w.submit(tmp_path / f"ckpt_{step}.npz", _tiny_state(step), step)
    w.close()
    assert [s for s, _ in list_checkpoints(tmp_path)] == [2, 3]
    assert goods == [1, 2, 3]                     # promoted in order
    assert w.last_path == tmp_path / "ckpt_3.npz"
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(tmp_path / "ckpt_4.npz", _tiny_state(), 4)


def test_writer_skips_nonfinite_state(tmp_path):
    logs = []
    goods = []

    class _S:
        params = {"w": np.full((3,), np.nan, np.float32)}
        bn_state = {}

    w = CheckpointWriter(log_fn=logs.append,
                         on_good=lambda s, st: goods.append(s))
    w.submit(tmp_path / "ckpt_1.npz", _S(), 1)
    w.close()
    assert not (tmp_path / "ckpt_1.npz").exists()
    assert not goods and w.last_path is None
    assert any("NOT saving" in m for m in logs)


def test_writer_verify_removes_torn_write(tmp_path):
    """The torn_ckpt arm truncates the file post-rename; the async verify
    pass must unlink it so latest_checkpoint never points at garbage —
    and the next clean write still confirms."""
    from raft_tpu.training.checkpoint import (checkpoint_readable,
                                              latest_checkpoint)

    inj = TrainFaultInjector(parse_train_chaos_spec("seed=1"))
    inj.force("torn_ckpt", [True])
    logs = []
    w = CheckpointWriter(log_fn=logs.append, faults=inj)
    w.submit(tmp_path / "ckpt_1.npz", _tiny_state(1.0), 1)
    w.drain()
    assert not (tmp_path / "ckpt_1.npz").exists()
    assert any("verify" in m for m in logs)
    w.submit(tmp_path / "ckpt_2.npz", _tiny_state(2.0), 2)
    w.close()
    latest = latest_checkpoint(tmp_path)
    assert latest == tmp_path / "ckpt_2.npz" and checkpoint_readable(latest)


def test_writer_failure_surfaces_on_submit_or_close(tmp_path):
    """A writer-thread failure (unwritable directory) must fail the run,
    not rot silently."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_bytes(b"")
    w = CheckpointWriter(log_fn=lambda m: None)
    w.submit(blocker / "sub" / "ckpt_1.npz", _tiny_state(), 1)
    with pytest.raises(OSError):
        w.drain()


def test_writer_sync_mode_is_inline(tmp_path):
    w = CheckpointWriter(log_fn=lambda m: None, sync=True)
    assert w._thread is None                      # no writer thread at all
    w.submit(tmp_path / "ckpt_1.npz", _tiny_state(), 1)
    assert (tmp_path / "ckpt_1.npz").exists()     # done before submit returns
    w.close()


def test_save_if_finite_plain_pytree(tmp_path):
    logs = []
    assert save_if_finite(tmp_path / "a.npz", _tiny_state(), logs.append)
    assert not save_if_finite(tmp_path / "b.npz",
                              {"w": np.array([np.inf], np.float32)},
                              logs.append)
    assert not (tmp_path / "b.npz").exists()


# ------------------------------------------------------ preemption guard --

def test_preemption_guard_catches_sigterm():
    assert PREEMPT_EXIT_CODE == 17
    guard = PreemptionGuard().install()
    try:
        assert not guard.requested
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)
        assert guard.requested and guard.signum == signal.SIGTERM
    finally:
        guard.remove()
    # handlers restored: a second guard installs cleanly
    g2 = PreemptionGuard().install()
    g2.remove()


def test_counter_attach_backfills_early_fires():
    """The CLI arms the injector before train() builds the metric registry
    (the loader's feeder/prefetch threads roll worker arms in that window):
    attaching the counter must backfill earlier fires, and later fires must
    count exactly once."""
    from raft_tpu.telemetry.registry import Registry

    inj = TrainFaultInjector(parse_train_chaos_spec("seed=3"))
    inj.force("worker_kill", [1, 1])
    assert inj.roll("worker_kill") and inj.roll("worker_kill")
    reg = Registry()
    inj.counter = reg.counter("raft_fault_injected_total", "fires",
                              labelnames=("arm",))
    assert reg.snapshot()["raft_fault_injected_total"]["worker_kill"] == 2
    inj.force("worker_kill", [1])
    assert inj.roll("worker_kill")
    assert reg.snapshot()["raft_fault_injected_total"]["worker_kill"] == 3


# --------------------------------------------------- loader self-healing --

def _synth_ds(n=64, seed=5):
    from raft_tpu.data.synthetic import SyntheticFlowDataset
    return SyntheticFlowDataset(size=(24, 32), length=n, seed=seed)


def _kill_and_reap(workers):
    """SIGKILL every live worker and wait until each is DEAD before the
    consumer runs again.  A kill can land while a worker is writing to the
    result pipe; the loader survives that torn frame only because it checks
    the exit sentinels BEFORE it reads the queue.  SIGKILL is asynchronous:
    on a loaded machine the victim may not be dead yet when os.kill returns,
    the sentinel check then sees nothing, and the read blocks forever on the
    half-written frame (seen as a hung tier-1 run under six xdist workers).
    The tests below are about what the loader does AFTER a death, so the
    death is established first."""
    for w in workers:
        if w.is_alive():
            os.kill(w.pid, signal.SIGKILL)
    for w in workers:
        w.join(timeout=30)
        assert not w.is_alive()


def _respawns():
    from raft_tpu.telemetry.registry import default_registry
    return default_registry().snapshot().get(
        "raft_data_worker_respawns_total", 0)


def test_loader_heals_worker_kill_with_slot_reclaim():
    """A SIGKILLed worker (chaos arm) is healed by a pool respawn; the shm
    slots the dead worker held return to the free list and the stream keeps
    flowing with zero errors."""
    from raft_tpu.data.mp_loader import MPSampleLoader

    inj = TrainFaultInjector(parse_train_chaos_spec("seed=2"))
    inj.force("worker_kill", [0] * 4 + [1])
    before = _respawns()
    # The path under test is death -> respawn, which is event-driven (the
    # workers' exit sentinels).  The stall detector is only a net against a
    # true deadlock here, so its window is far beyond any cold start: at
    # 10 s a loaded machine (six xdist workers compiling) could take longer
    # than the window to bring the respawned pool to its first sample, the
    # detector then "healed" the healthy pool again, and the respawn budget
    # ran out — a failure of the machine's load, not of the loader.
    loader = MPSampleLoader(_synth_ds(), num_workers=2, seed=0,
                            transport="shm", shm_slots=4, poll_timeout=0.5,
                            stall_timeout=120.0, faults=inj, max_respawns=3)
    it = iter(loader)
    try:
        samples = [tuple(np.copy(f) for f in next(it)) for _ in range(20)]
    finally:
        loader.close()
    assert len(samples) == 20
    assert _respawns() - before >= 1
    assert inj.injected["worker_kill"] == 1
    # slot conservation: free list + the consumer's pending slot == ring
    assert loader._free.qsize() + 1 <= 4


def test_loader_heals_injected_stall():
    """The worker_stall arm parks every worker past the stall window; the
    detector must respawn the pool instead of raising."""
    from raft_tpu.data.mp_loader import MPSampleLoader

    inj = TrainFaultInjector(parse_train_chaos_spec("seed=2"))
    inj.force("worker_stall", [0] * 3 + [1])
    before = _respawns()
    loader = MPSampleLoader(_synth_ds(), num_workers=2, seed=0,
                            poll_timeout=0.3, stall_timeout=1.0,
                            faults=inj, max_respawns=3)
    it = iter(loader)
    try:
        for _ in range(12):
            next(it)
    finally:
        loader.close()
    assert _respawns() - before >= 1


def test_loader_escalates_with_diagnostics_after_budget():
    """Respawn budget spent -> the historical error, now carrying per-worker
    exitcodes + shm free-list depth (OOM-kill vs deadlock postmortems)."""
    from raft_tpu.data.mp_loader import MPSampleLoader

    loader = MPSampleLoader(_synth_ds(), num_workers=2, seed=0,
                            transport="shm", shm_slots=4,
                            poll_timeout=0.3, max_respawns=0)
    it = iter(loader)
    try:
        next(it)
        _kill_and_reap(loader._workers)
        with pytest.raises(RuntimeError) as e:
            for _ in range(100):
                next(it)
        msg = str(e.value)
        assert "died without reporting" in msg
        assert "exitcodes" in msg and "-9" in msg       # signal visible
        assert "free-list depth" in msg                 # shm occupancy
        assert "respawn budget (0" in msg
    finally:
        loader.close()


def test_loader_bounded_run_escalates_after_feeder_done():
    """A worker death on a bounded (epochs=) run after the feeder finished
    is not healable — the queued task tail died with the torn queues and
    cannot be re-fed — so the loader must raise promptly instead of
    respawning a pool that would starve forever (an infinite hang when the
    stall detector is disabled)."""
    from raft_tpu.data.mp_loader import MPSampleLoader

    loader = MPSampleLoader(_synth_ds(8), num_workers=2, seed=0, epochs=1,
                            poll_timeout=0.2, stall_timeout=None,
                            max_respawns=3)
    it = iter(loader)
    try:
        next(it)
        loader._feeder.join(timeout=10)      # tiny dataset: feeder finishes
        assert not loader._feeder.is_alive()
        _kill_and_reap(loader._workers)
        with pytest.raises(RuntimeError,
                           match="not healable|under-delivered"):
            for _ in range(100):
                next(it)
    finally:
        loader.close()


def test_loader_respawn_budget_window():
    """max_respawns bounds events inside the window; old events age out."""
    from raft_tpu.data.mp_loader import MPSampleLoader

    loader = MPSampleLoader(_synth_ds(), num_workers=1, seed=0,
                            max_respawns=2, respawn_window_s=0.2)
    try:
        assert loader._respawn_allowed()
        loader._respawn_times.extend([time.monotonic()] * 2)
        assert not loader._respawn_allowed()
        time.sleep(0.3)
        assert loader._respawn_allowed()                # window slid past
    finally:
        loader.close()


# ----------------------------------------------------------- CLI surface --

def test_cli_rejects_bad_chaos_and_rollback_flags(tmp_path):
    """--chaos-train parse errors and --max-rollbacks validation surface
    before any compile."""
    from raft_tpu.cli import main

    with pytest.raises(ValueError, match="unknown train-chaos arm"):
        main(["-m", "train", "--dataset", "synthetic", "--small",
              "--iters", "2", "--num-steps", "1", "--batch", "2",
              "--train-size", "32", "48", "--out", str(tmp_path),
              "--chaos-train", "bogus=1"])
    rc = main(["-m", "train", "--dataset", "synthetic", "--small",
               "--iters", "2", "--num-steps", "1", "--batch", "2",
               "--train-size", "32", "48", "--out", str(tmp_path),
               "--max-rollbacks", "-1"])
    assert rc == 2


def test_rate_arms_cover_every_hook():
    """Every documented rate arm has a hook consuming it (a new arm must
    come with a hook, and vice versa)."""
    assert set(RATE_ARMS) == {"worker_kill", "worker_stall", "nan_loss",
                              "torn_ckpt"}
