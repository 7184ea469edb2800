"""Multi-process host-side sample loading.

The decode+augment path (datasets._read_image + augment.FlowAugmentor) is
GIL-bound numpy/cv2 work; a single pump thread tops out well below a TPU
step rate at training shapes.  This is the tensorpack-PrefetchDataZMQ analog
(reference dataflow/test_dataflow.py:7, imported there but never used):
worker *processes* each run ``dataset[idx]`` and stream finished samples back
to the main process, so decode/augment scales across cores while the
batching / device staging stays in the main process (pipeline.PrefetchLoader).

Two transports:

* ``transport='pickle'`` — samples are pickled through the bounded result
  queue (the original path).  Simple, but every multi-MB sample pays
  serialize + pipe + deserialize.
* ``transport='shm'`` — workers write sample arrays into a ring of
  ``multiprocessing.shared_memory`` slots (:class:`ShmRing`; layout pinned
  by :class:`SampleSpec`) and send only the slot id through the result
  queue; the main process wraps the slot as zero-copy numpy views.  Slots
  recycle through a free-list queue: a worker takes a free slot *before*
  decoding (backpressure), the consumer returns the previous slot each
  iteration.  **Yielded arrays are views valid only until the next
  iteration** — collate them copy-on-arrival (``pipeline.batched`` with a
  ``BatchBuffers`` collator does) or copy explicitly.

Design notes:
* start method is a knob, default "forkserver": the loader always runs
  inside a JAX process, and JAX is always multithreaded, so a plain fork
  can land while another thread holds a lock and deadlock the child
  (observed twice in one day: worker alive, zero CPU, forever — the
  CPython fork-under-threads warning is not theoretical).  forkserver
  forks workers from a clean early-spawned server instead, at the cost of
  pickling the dataset (file lists + augmentor state — cheap).  "fork"
  remains opt-in for maximal copy-on-write when the caller knows the
  parent is single-threaded; "spawn" is the portable fallback.  Either
  way the workers touch only numpy/cv2, never jax.
* stall detection — death detection catches workers that DIED; a deadlocked
  worker is alive and silent, so the iterator also raises if all workers
  are alive yet nothing arrives for ``stall_timeout`` seconds.
* per-sample determinism — each task carries a seed derived from (loader
  seed, epoch, index) and reseeds the augmentor's RandomState before the
  item is produced, so sample *content* is reproducible even though arrival
  *order* depends on worker scheduling.  (Training consumes a shuffled
  stream, so order nondeterminism is harmless.)  The shm transport changes
  only WHERE bytes land, never what is computed — determinism tests cover
  both transports.
* bounded task/result queues — backpressure instead of unbounded buffering
  (multiprocessing.Pool.imap would eagerly drain the infinite index stream).
* self-healing — a dead (OOM-killed, segfaulted) or stalled worker pool is
  **respawned** instead of aborting the run, up to ``max_respawns`` events
  per ``respawn_window_s`` (then the historical error raises, now carrying
  per-worker exitcodes + the shm free-list depth so postmortems can tell
  an OOM kill from a deadlock).  A respawn quiesces the whole pool and
  rebuilds the mp queues from scratch — a worker killed mid-``put`` can
  leave a queue's shared pipe lock held forever, so the old queues are
  unsalvageable by construction — salvages already-finished samples,
  reclaims the shm slots the dead workers held (free-list reconciliation:
  every slot not referenced by a salvaged sample or the consumer's pending
  view returns to the ring), and restarts deterministically-seeded workers
  (task seeds are content seeds, so reproducibility survives the respawn).
  Counted in ``raft_data_worker_respawns_total``; with ``epochs`` set, the
  tasks in flight at the kill are lost and the epoch under-delivers — the
  stall detector then escalates, which is the intended bound.
"""

from __future__ import annotations

import itertools
import multiprocessing as mp
import os
import queue
import signal
import threading
import time
import traceback
from collections import deque
from multiprocessing import connection as mp_connection
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np

from ..telemetry.log import get_logger
from ..telemetry.registry import default_registry

_log = get_logger("data")

_SENTINEL = None
_STALL = "__stall__"
_SLOT_ALIGN = 64


def _loader_metrics():
    """Counters/gauges on the process-default telemetry registry, shared
    across loader instances (atomic get-or-create: two loaders iterated
    from different threads must not race into a duplicate-metric error)."""
    reg = default_registry()
    return {
        "samples": reg.get_or_counter(
            "raft_data_samples_total",
            "Samples delivered by worker-process loaders"),
        "errors": reg.get_or_counter(
            "raft_data_errors_total",
            "Data loader failures (worker exception, silent death, stall)"),
        "free_slots": reg.get_or_gauge(
            "raft_data_shm_free_slots",
            "Shared-memory transport: slots currently on the free list"),
        "respawns": reg.get_or_counter(
            "raft_data_worker_respawns_total",
            "Worker-pool respawns healing a dead or stalled worker"),
    }


class SampleSpec:
    """Fixed byte layout of one sample inside a shared-memory slot: an
    ordered list of (shape, dtype) fields at 64-byte-aligned offsets.

    The layout is the transport contract — every sample a dataset produces
    must match it exactly (uniform-shape datasets; a mismatch in a worker
    surfaces as a worker error, not silent corruption)."""

    def __init__(self, fields: Sequence[Tuple[Tuple[int, ...], np.dtype]]):
        self.fields = tuple((tuple(int(d) for d in shape), np.dtype(dt))
                            for shape, dt in fields)
        offsets = []
        off = 0
        for shape, dt in self.fields:
            off = -(-off // _SLOT_ALIGN) * _SLOT_ALIGN
            offsets.append(off)
            off += int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        self.offsets = tuple(offsets)
        self.nbytes = off

    @classmethod
    def from_sample(cls, sample) -> "SampleSpec":
        fields = []
        for f in sample:
            arr = np.asarray(f)
            fields.append((arr.shape, arr.dtype))
        return cls(fields)

    def views(self, buf) -> Tuple[np.ndarray, ...]:
        """Zero-copy numpy views of every field over a slot's buffer."""
        return tuple(np.ndarray(shape, dtype=dt, buffer=buf, offset=off)
                     for (shape, dt), off in zip(self.fields, self.offsets))

    def write(self, buf, sample) -> None:
        views = self.views(buf)
        if len(sample) != len(views):
            raise ValueError(f"sample has {len(sample)} fields, "
                             f"slot layout has {len(views)}")
        for dst, src in zip(views, sample):
            # exact-shape only: numpy broadcasting would let a (H, W, 1) or
            # (1, W, C) mis-shaped frame fill the slot 'successfully' —
            # silent corruption instead of the promised worker error
            if np.shape(src) != dst.shape:
                raise ValueError(f"sample field shape {np.shape(src)} != "
                                 f"slot field shape {dst.shape}")
            dst[...] = src


class ShmRing:
    """Owner side of the slot ring: creates ``slots`` shared-memory blocks
    of ``nbytes``.  Workers attach by name.

    Teardown is two-phase.  :meth:`unlink` removes the names but KEEPS the
    owner's mappings valid — the safe default when numpy views of the slots
    may still be live in another thread (touching a view after the segment
    is unmapped is a SIGSEGV, not an exception); the pages fall back to the
    kernel when the process exits.  :meth:`close` additionally unmaps, for
    owners that control every view's lifetime (e.g. loader_bench's local
    ring)."""

    def __init__(self, slots: int, nbytes: int):
        from multiprocessing import shared_memory
        self.shms = []
        self._unlinked = False
        try:
            for _ in range(slots):
                self.shms.append(
                    shared_memory.SharedMemory(create=True, size=nbytes))
        except BaseException:
            self.close()
            raise
        self.names = tuple(s.name for s in self.shms)

    def views(self, spec: SampleSpec, slot: int) -> Tuple[np.ndarray, ...]:
        return spec.views(self.shms[slot].buf)

    def unlink(self) -> None:
        """Remove the segment names; existing mappings (and views over
        them) stay valid until the process exits."""
        if self._unlinked:
            return
        self._unlinked = True
        for s in self.shms:
            try:
                s.unlink()
            except (FileNotFoundError, OSError):
                pass

    def close(self) -> None:
        """Unlink AND unmap — only when no views can still be live."""
        self.unlink()
        for s in self.shms:
            try:
                s.close()
            except OSError:
                pass
        self.shms = []


def _attach_slots(names):
    """Worker-side attach.  The attach re-registers each segment with the
    resource tracker, but workers inherit the OWNER's tracker process
    (forkserver/spawn pass its fd down), where registration is a set-add —
    idempotent — and the owner's ``unlink()`` unregisters exactly once.  Do
    NOT ``resource_tracker.unregister`` here: with a shared tracker that
    would cancel the owner's registration and crash-leak on unlink."""
    from multiprocessing import shared_memory
    return [shared_memory.SharedMemory(name=name) for name in names]


def _worker_loop(dataset, tasks, results, shm=None):
    # cold-start beacon: spawn + dataset unpickling can take seconds, and
    # the first sample additionally pays the first heavy decode — without a
    # readiness signal all of that counts against the consumer's FIRST
    # stall window, false-positiving short stall_timeouts (ADVICE r3).
    # The consumer treats this as progress, not a sample.
    results.put(("ready", None))
    slots = spec = free = None
    if shm is not None:
        names, spec, free = shm
        slots = _attach_slots(names)
    while True:
        task = tasks.get()
        if task is _SENTINEL:
            break
        if isinstance(task, tuple) and task[0] == _STALL:
            # injected stall (chaos arm worker_stall): alive but silent —
            # exactly the deadlock signature the stall detector heals
            time.sleep(float(task[1]))
            continue
        idx, sample_seed = task
        try:
            aug = getattr(dataset, "augmentor", None)
            if aug is not None and hasattr(aug, "rng"):
                aug.rng = np.random.RandomState(sample_seed)
            if shm is None:
                results.put(("ok", dataset[idx]))
            else:
                # take the free slot BEFORE decoding: backpressure lands on
                # the cheap wait, not on a finished sample with nowhere to go
                slot = free.get()
                spec.write(slots[slot].buf, dataset[idx])
                results.put(("ok", slot))
        except BaseException:
            results.put(("error", traceback.format_exc()))
            break


class MPSampleLoader:
    """Iterator of (im1, im2, flow, valid) samples produced by worker
    processes; feed it to pipeline.batched + PrefetchLoader.

    ``transport='shm'`` streams samples through a shared-memory slot ring
    (zero-copy on the consumer side; see module docstring for the
    view-lifetime contract).  ``shm_slots`` sizes the ring (default
    ``2 * num_workers + 2``); ``sample_spec`` pins the layout explicitly,
    otherwise ``dataset[0]`` is probed once."""

    def __init__(self, dataset, num_workers: int = 4, seed: int = 0,
                 shuffle: bool = True, epochs: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 poll_timeout: float = 10.0,
                 stall_timeout: Optional[float] = 300.0,
                 start_method: str = "forkserver",
                 transport: str = "pickle",
                 shm_slots: Optional[int] = None,
                 sample_spec: Optional[SampleSpec] = None,
                 faults=None,
                 max_respawns: int = 3,
                 respawn_window_s: float = 120.0):
        assert num_workers >= 1
        if start_method not in ("fork", "forkserver", "spawn"):
            raise ValueError(f"start_method must be fork/forkserver/spawn, "
                             f"got {start_method!r}")
        if transport not in ("pickle", "shm"):
            raise ValueError(f"transport must be pickle/shm, got {transport!r}")
        self._poll_timeout = poll_timeout
        self._stall_timeout = stall_timeout
        self._start_method = start_method
        self._transport = transport
        self._dataset = dataset
        self._num_workers = num_workers
        self._faults = faults                 # training.faults injector or None
        self._max_respawns = max_respawns     # 0 = historical fail-fast
        self._respawn_window_s = respawn_window_s
        self._respawn_times: deque = deque()
        self._requeued: deque = deque()       # results salvaged over a respawn
        self._pending_slot = None             # shm slot the consumer still views
        self._ctx = ctx = mp.get_context(start_method)
        self._depth = depth = queue_depth or 2 * num_workers
        self._tasks = ctx.Queue(maxsize=depth)
        self._results = ctx.Queue(maxsize=depth)
        self._ring = None
        self._free = None
        self._spec = None
        if transport == "shm":
            self._spec = sample_spec or SampleSpec.from_sample(dataset[0])
            n_slots = shm_slots if shm_slots is not None \
                else 2 * num_workers + 2
            if n_slots < 2:
                raise ValueError(f"shm transport needs >= 2 slots "
                                 f"(1 pending + 1 circulating), got {n_slots}")
            self._ring = ShmRing(n_slots, self._spec.nbytes)
            self._free = ctx.Queue()
            for i in range(n_slots):
                self._free.put(i)
        self._workers = self._spawn_workers()
        self._closed = False
        self._n_tasks = (len(dataset) * epochs) if epochs is not None else None
        self._feeder = threading.Thread(
            target=self._feed, args=(dataset, seed, shuffle, epochs),
            daemon=True)
        self._feeder.start()

    def _spawn_workers(self):
        """Start a fresh worker generation bound to the CURRENT queues
        (also the respawn path — self._tasks/_results/_free may be brand
        new by then)."""
        shm_args = None
        if self._transport == "shm":
            shm_args = (self._ring.names, self._spec, self._free)
        workers = [
            self._ctx.Process(target=_worker_loop,
                              args=(self._dataset, self._tasks,
                                    self._results, shm_args),
                              daemon=True)
            for _ in range(self._num_workers)]
        for w in workers:
            w.start()
        # the exit-sentinel set the consumer polls for silent deaths; a
        # worker that exits cleanly is dropped from it on first detection
        self._sentinels = [w.sentinel for w in workers]
        return workers

    def _put_task(self, task) -> bool:
        """Feeder-side put that can never wedge permanently: a worker
        SIGKILLed inside ``tasks.get()`` dies HOLDING the queue's reader
        lock, after which its items are unreachable and the bounded put's
        semaphore can never be released — a plain blocking put would park
        the feeder forever.  Retrying with a timeout re-reads
        ``self._tasks`` each attempt, so the feeder migrates to the fresh
        queue a respawn installed."""
        while not self._closed:
            try:
                self._tasks.put(task, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _feed(self, dataset, seed, shuffle, epochs):
        rng = np.random.RandomState(seed)
        for epoch in itertools.count():
            if epochs is not None and epoch >= epochs:
                break
            order = np.arange(len(dataset))
            if shuffle:
                rng.shuffle(order)
            for idx in order:
                sample_seed = (seed * 1_000_003 + epoch * 97_003
                               + int(idx)) % (2**31)
                if self._closed:
                    return
                if (self._faults is not None
                        and self._faults.roll("worker_stall")):
                    # every worker draws one stall task and goes silent for
                    # longer than the stall window — the consumer's detector
                    # must heal the pool, not hang
                    dur = (self._stall_timeout or 2.0) * 1.5 + 1.0
                    for _ in range(self._num_workers):
                        self._put_task((_STALL, dur))
                if not self._put_task((int(idx), sample_seed)):
                    return
        for _ in range(self._num_workers):
            self._put_task(_SENTINEL)

    def __iter__(self) -> Iterator:
        served = 0
        metrics = _loader_metrics()
        last_progress = time.monotonic()
        while self._n_tasks is None or served < self._n_tasks:
            # chaos (training.faults worker_kill arm): SIGKILL one live
            # worker — indistinguishable from the OOM killer downstream
            if self._faults is not None and self._faults.roll("worker_kill"):
                victims = [w for w in self._workers if w.is_alive()]
                if victims:
                    victim = victims[self._faults.pick(len(victims))]
                    os.kill(victim.pid, signal.SIGKILL)
                    # SIGKILL is asynchronous; wait for the death so the
                    # sentinel check below sees it BEFORE the queue is read
                    # (a kill mid-put leaves a torn frame — see below)
                    victim.join(timeout=30)
            while True:
                if self._requeued:
                    # samples salvaged from the pre-respawn result queue
                    status, payload = self._requeued.popleft()
                    last_progress = time.monotonic()
                    break
                # a worker killed by the OS (segfault, OOM killer) never
                # queues an 'error' record — detect the death BEFORE
                # draining the queue (a worker SIGKILLed mid-put leaves a
                # torn frame whose recv would block forever), even while
                # its siblings keep producing.  Per-sample cost is one
                # poll(2) over the live workers' exit sentinels; the
                # N-waitpid scan runs only when a sentinel actually fired
                # (exitcode 0 = the normal end-of-epochs exit, never a
                # failure — its sentinel is dropped from the polled set)
                if self._sentinels and mp_connection.wait(self._sentinels,
                                                          timeout=0):
                    dead = [w for w in self._workers
                            if not w.is_alive() and w.exitcode != 0]
                    self._sentinels = [w.sentinel for w in self._workers
                                       if w.is_alive()]
                    if dead:
                        self._heal_or_raise("death", metrics, dead=dead)
                        last_progress = time.monotonic()
                        continue
                try:
                    status, payload = self._results.get(
                        timeout=self._poll_timeout)
                    last_progress = time.monotonic()
                    break
                except queue.Empty:
                    if (self._n_tasks is not None
                            and not self._feeder.is_alive()
                            and not any(w.is_alive()
                                        for w in self._workers)):
                        # bounded run, feeder finished, every worker exited,
                        # nothing queued: the remaining deficit can never
                        # arrive (its tasks were lost with a respawn's torn
                        # queues) — raise instead of polling forever
                        diag = self._diagnostics()
                        self.close()
                        metrics["errors"].inc()
                        raise RuntimeError(
                            f"data pipeline under-delivered: {served}/"
                            f"{self._n_tasks} samples served but the feeder "
                            f"and every worker have exited (queued tasks "
                            f"were lost when a respawn rebuilt the torn "
                            f"queues); {diag}")
                    # a DEADLOCKED worker is alive yet silent (e.g. a fork
                    # taken while the parent's JAX/BLAS threads held locks):
                    # heal — or raise once the respawn budget is spent —
                    # instead of polling forever
                    stalled = time.monotonic() - last_progress
                    if (self._stall_timeout is not None
                            and stalled > self._stall_timeout):
                        self._heal_or_raise("stall", metrics, stalled=stalled)
                        last_progress = time.monotonic()
            if status == "ready":
                # worker finished cold start (the queue get above already
                # reset the stall clock); nothing to serve yet
                continue
            if status == "error":
                self.close()
                metrics["errors"].inc()
                raise RuntimeError(f"data worker failed:\n{payload}")
            served += 1
            metrics["samples"].inc()
            if self._transport == "shm":
                # the consumer has moved past the previous sample (the
                # copy-on-arrival contract): its slot goes back on the ring
                if self._pending_slot is not None:
                    self._free.put(self._pending_slot)
                self._pending_slot = payload
                metrics["free_slots"].set(self._free.qsize())
                yield self._ring.views(self._spec, payload)
            else:
                yield payload
        if self._pending_slot is not None:
            self._free.put(self._pending_slot)
            self._pending_slot = None
        self.close()

    # ------------------------------------------------- self-healing ------

    def _diagnostics(self) -> str:
        """Postmortem context for every loader failure and respawn line:
        per-worker exitcodes (negative = killed by signal, e.g. -9 is the
        OOM killer's SIGKILL; alive = deadlock candidate) and the shm
        free-list depth (0 with live workers = slot leak or all-stuck)."""
        codes = ", ".join(
            f"pid {w.pid}={'alive' if w.is_alive() else w.exitcode}"
            for w in self._workers)
        s = f"worker exitcodes [{codes}]"
        if self._ring is not None:
            s += (f"; shm free-list depth {self._free.qsize()}"
                  f"/{len(self._ring.shms)}")
        return s

    def _respawn_allowed(self) -> bool:
        now = time.monotonic()
        while (self._respawn_times
               and now - self._respawn_times[0] > self._respawn_window_s):
            self._respawn_times.popleft()
        return len(self._respawn_times) < self._max_respawns

    def _heal_or_raise(self, reason: str, metrics,
                       dead=None, stalled: float = 0.0) -> None:
        diag = self._diagnostics()
        if self._n_tasks is not None and not self._feeder.is_alive():
            # bounded run whose feeder already finished: the queued task
            # tail dies with the torn queues and cannot be re-fed, so a
            # respawned pool would starve forever — escalate instead of
            # healing into a hang (endless training streams, epochs=None,
            # always keep a live feeder and heal normally)
            self.close()
            metrics["errors"].inc()
            raise RuntimeError(
                f"data worker {reason} on a bounded run after the feeder "
                f"finished; the remaining task queue was lost and cannot "
                f"be re-fed, so the pool is not healable; {diag}") from None
        if not self._respawn_allowed():
            self.close()
            metrics["errors"].inc()
            if reason == "death":
                raise RuntimeError(
                    f"data worker(s) died without reporting (killed by the "
                    f"OS? check dmesg for OOM) and the respawn budget "
                    f"({self._max_respawns} per {self._respawn_window_s:.0f}s)"
                    f" is spent; {diag}") from None
            hint = ("storage is stalled (raise stall_timeout / "
                    "--stall-timeout, 0 disables)")
            if self._start_method == "fork":
                hint += (", or the fork deadlocked (threads held "
                         "locks at fork time; retry with "
                         "start_method='forkserver' or 'spawn')")
            raise RuntimeError(
                f"data workers alive but produced nothing for "
                f"{stalled:.0f}s — likely {hint}; respawn budget "
                f"({self._max_respawns} per {self._respawn_window_s:.0f}s) "
                f"is spent; {diag}") from None
        self._respawn(reason, metrics, diag)

    def _respawn(self, reason: str, metrics, diag: str) -> None:
        """Quiesce the pool, salvage finished samples, reclaim shm slots,
        rebuild the queues, restart the workers.

        The queues must be REBUILT, not reused: a worker SIGKILLed inside
        ``get()`` or mid-``put`` dies holding an mp.Queue's shared pipe
        lock, wedging every later user.  The feeder's timeout-put retries
        re-read ``self._tasks``, so it migrates to the fresh queue on its
        own."""
        self._respawn_times.append(time.monotonic())
        for w in self._workers:
            w.terminate()
        for w in self._workers:
            w.join(timeout=5)
        # a worker that ignored/deferred SIGTERM (e.g. stalled in disk I/O
        # — exactly the case the stall heal targets) must be SIGKILLed
        # before its shm slot is reclaimed below: with SIGKILL pending it
        # can never return to user space to write a buffer a fresh worker
        # now owns
        survivors = [w for w in self._workers if w.is_alive()]
        for w in survivors:
            w.kill()
        for w in survivors:
            w.join(timeout=5)
        if self._ring is not None and any(w.is_alive()
                                          for w in self._workers):
            # unkillable (kernel-stuck) worker: its in-progress slot cannot
            # be identified, so reclaiming the free list would risk two
            # processes writing one buffer — fail loudly instead of
            # corrupting training data silently
            metrics["errors"].inc()
            self.close()
            raise RuntimeError(
                f"data worker survived SIGKILL during a {reason} respawn "
                f"(kernel-stuck?); shm slots cannot be safely reclaimed; "
                f"{diag}")
        # salvage finished results (decoded samples are too expensive to
        # drop) AND worker 'error' reports — a genuine dataset/decode bug
        # raised just before the respawn must still surface, not vanish
        # with the old queue; a queue torn by the kill stops the salvage,
        # never the heal
        try:
            while True:
                status, payload = self._results.get_nowait()
                if status in ("ok", "error"):
                    self._requeued.append((status, payload))
        except queue.Empty:
            pass
        except Exception:  # noqa: BLE001 — partial pickle from a torn pipe
            pass
        # fresh queues; the old ones may be poisoned beyond recovery (a
        # worker SIGKILLed inside get() dies holding the reader lock, so
        # queued items — and the bounded put semaphore — are lost).  The
        # feeder's timeout-put (_put_task) migrates to the new task queue
        # on its next retry; the old queue's tasks are lost, which an
        # endless training stream never notices.
        self._tasks = self._ctx.Queue(maxsize=self._depth)
        self._results = self._ctx.Queue(maxsize=self._depth)
        if self._ring is not None:
            # free-list reconciliation: every slot not referenced by a
            # salvaged sample or the consumer's pending view returns to the
            # ring — including the slots the dead workers took before
            # decoding and never published
            held = {p for s, p in self._requeued if s == "ok"}
            if self._pending_slot is not None:
                held.add(self._pending_slot)
            self._free = self._ctx.Queue()
            for slot in range(len(self._ring.shms)):
                if slot not in held:
                    self._free.put(slot)
            metrics["free_slots"].set(self._free.qsize())
        self._workers = self._spawn_workers()
        # absorb the new pool's cold start HERE (forkserver spawn + dataset
        # unpickle can exceed a tight stall window, and a window that fires
        # mid-spawn would kill every fresh generation in a loop): wait for
        # each worker's ready beacon, salvaging anything that arrives
        # interleaved, before the caller's stall clock restarts
        deadline = time.monotonic() + 10.0
        ready = 0
        while ready < self._num_workers and time.monotonic() < deadline:
            try:
                status, payload = self._results.get(timeout=0.2)
            except queue.Empty:
                continue
            except Exception:  # noqa: BLE001
                break
            if status == "ready":
                ready += 1
            else:
                self._requeued.append((status, payload))
        metrics["respawns"].inc()
        _log.warning(
            f"respawned {self._num_workers} data worker(s) after {reason} "
            f"({len(self._respawn_times)}/{self._max_respawns} in window); "
            f"{diag}")
        from ..telemetry import events as tlm_events
        run_log = tlm_events.current()
        if run_log is not None:
            run_log.event("worker_respawn", reason=reason,
                          diagnostics=diag,
                          respawns_in_window=len(self._respawn_times))

    def close(self):
        if self._closed:
            return
        self._closed = True
        # unblock the feeder if it is parked in a full-queue put(): drain the
        # task queue so its in-flight put completes, after which its _closed
        # check returns — otherwise every closed loader leaks a live thread
        for _ in range(3):
            try:
                while True:
                    self._tasks.get_nowait()
            except queue.Empty:
                pass
            self._feeder.join(timeout=0.5)
            if not self._feeder.is_alive():
                break
        for w in self._workers:
            w.terminate()
        for w in self._workers:
            w.join(timeout=5)
        if self._ring is not None:
            # unlink ONLY (names gone; mappings stay valid): close() can be
            # invoked while another thread — e.g. a PrefetchLoader pump
            # parked inside this iterator's results.get — still holds slot
            # views; unmapping under it would SIGSEGV the process.  The
            # pages return to the kernel at process exit.
            self._ring.unlink()


def measure_rate(sample_iter, n: int, warmup: int = 2) -> float:
    """Samples/sec of an iterator, after ``warmup`` discarded samples."""
    it = iter(sample_iter)
    for _ in range(warmup):
        next(it)
    t0 = time.time()
    for _ in range(n):
        next(it)
    return n / (time.time() - t0)
