"""Bounded per-session state for the streaming video path (SERVING.md).

A *session* is one client's video stream.  Its state has two tiers:

* **device tier** — a SLOT in the per-bucket batch buffers of the
  :class:`SlotPool`: the previous frame's encoder maps (``fmap`` + raw
  ``cnet`` output) plus the pre-projected warm-start seed, each stored
  as row ``session.slot`` of a ``[capacity+1, h, w, C]`` device-resident
  buffer.  This is what makes the next advance cost ONE encoder pass and
  exit early under a ``converge`` policy — and, because every session's
  maps live *in batch slots* of one buffer, what lets the batcher
  advance many sessions in ONE device call (the continuous-batching
  stream step, models/raft.make_stream_batch_step_fn): a batch of 8 reads
  its rows as 8 one-row slices of each leaf and the commit writes 8 rows
  back in place, in row order — the programs move the batch's rows, never
  the pool (:func:`make_slot_commit_fn`).
* **host tier** — the previous frame's pixels plus bookkeeping.  Cheap,
  and exactly what a cold two-encoder restart needs.

``SessionStore`` keeps the host-side records and the LRU/TTL policy,
mapping session id → slot index.  At most ``max_sessions`` sessions hold
a slot; promoting one past the cap *demotes* the least-recently-used
holder (slot freed back to the pool, host record kept), so an advance on
a demoted session degrades transparently to a cold two-encoder restart —
correct flow, no error, just the pairwise cost.  Session records
themselves are capped at ``RECORD_CAP_FACTOR x max_sessions`` (oldest
records evicted outright) and reaped entirely after ``ttl_s`` idle
seconds — TTL reaping FREES the reaped session's slot too, so a
long-lived server can never strand device capacity behind dead records;
an advance on a reaped/unknown id is a 404 — the client reopens.

Thread model: handler threads open/advance/close under the store lock and
hold the per-session lock across a whole advance (one frame in flight per
session); slot promote/demote runs in the batcher thread (via the store),
and the pool's free-list is guarded by its own leaf lock
(``SlotPool._lock``, taken under the store lock on demote/sweep paths —
see SERVING_LOCK_HIERARCHY).  Device BUFFERS are read and swapped only on
the single batcher thread (the engine's scatter executables), so buffer
refs need the pool lock only to keep reads/swaps atomic against metric
scrapes.  A session may be demoted *between* enqueue and execute — the
coordinator re-checks ``has_features`` at execute time and falls back
cold, which is the designed behavior, not a race.
"""

from __future__ import annotations

import time
import uuid
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np

from ..lint.concurrency import guarded_by
from ..telemetry.watchdogs import watched_lock

# Demoted records (host tier only) are kept for graceful cold restarts up
# to this multiple of max_sessions; beyond it the oldest records are
# evicted outright (reason="capacity") and their ids become unknown.
RECORD_CAP_FACTOR = 4


def _nbytes(a) -> int:
    """Bytes of a device array, from its shape and dtype (a donated array
    still has both); 0 for what a stub engine installs in their place."""
    return int(getattr(a, "nbytes", 0))


def make_slot_commit_fn(quant: bool = False):
    """The slot-pool commit: ``(fmap_buf, cnet_buf, flow_buf, slots [b],
    fmap_rows [b,...], cnet_rows [b,...], seed_rows [b,...], mask [b])
    -> (fmap_buf, cnet_buf, flow_buf)`` — rows with ``mask=True`` replace
    their slot, everything else (padding rows aimed at the scratch slot,
    rows the non-finite sentinel rejected) writes its OLD value back.

    A ROW at a time (``b`` is static: the engine compiles a width): ``b``
    one-row ``dynamic_update_slice``s into the leaf, in row order, row ``i``
    writing ``where(mask[i], new_i, old_i)`` with ``old_i`` that row's own
    one-row slice (models/raft.slot_row), so the program moves ``b`` rows
    whatever the pool holds (the general ``.at[slots].set`` over
    ``buf[slots]`` made the chip's compiler copy a whole leaf: PERF.md §6,
    PR 46).  ``slots`` lie in ``[0, capacity]`` by construction, so nothing
    normalises them: a slice CLAMPS an index outside the leaf to its nearest
    row, where ``buf[slots]`` wrapped a negative one and the scatter dropped
    a row past the end.

    Duplicate discipline: real rows carry unique slot indices (one frame
    in flight per session), and every masked row writes the value its slot
    holds — so duplicate indices (padding rows all share the scratch slot)
    always write identical data and the order of the writes does not show.
    A masked row is NOT aimed at the scratch slot instead: a rejected row's
    NaNs would then sit in the row every padding row reads.  The serving
    engine compiles this per (bucket, width) with the buffers DONATED
    (off-CPU), so a commit is an in-place row update of the pool, not a
    buffer copy.

    With ``quant=True`` (``RAFTConfig.quant='int8'``) the fmap/cnet
    buffers arrive as ``(int8 vals, per-channel f32 scales)`` 2-leaf
    pytrees; the incoming f32 rows are quantized ON COMMIT
    (models/raft.quantize_rows) and both leaves are masked-written.  The
    flow seed buffer stays f32.  Call-site signatures are unchanged —
    jit handles the pytree args.
    """
    import jax
    import jax.numpy as jnp

    from ..models.raft import quantize_rows, slot_row
    from ..telemetry.trace import stage

    # (named: the device trace calls a program by its function,
    # ``jit_slot_commit``, and the stream programs beside it are ``jit_fn``)
    def slot_commit(fmap_buf, cnet_buf, flow_buf, slots, fmap_rows, cnet_rows,
                    seed_rows, mask):
        def put(buf, rows):
            for i in range(rows.shape[0]):
                row = jnp.where(mask[i], rows[i:i + 1],
                                slot_row(buf, slots[i]))
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf, row.astype(buf.dtype), slots[i], axis=0,
                    allow_negative_indices=False)
            return buf

        def put_q(buf, rows):
            # each row quantised by its own absmax (the reduction is over a
            # row's positions, never over the batch), then BOTH leaves under
            # the one mask: a padding row writes back what its slot holds
            with stage("quant"):
                vals, scales = quantize_rows(rows)
            return (put(buf[0], vals), put(buf[1], scales))

        put_maps = put_q if quant else put
        with stage("raft/stream/commit"):
            return (put_maps(fmap_buf, fmap_rows),
                    put_maps(cnet_buf, cnet_rows), put(flow_buf, seed_rows))
    return slot_commit


def make_slot_poison_fn(quant: bool = False):
    """Chaos ``session`` arm, slot-pool form: NaN-poison one slot's fmap
    row in place (``(fmap_buf, slots [1]) -> fmap_buf``) so the poison
    propagates through the correlation volume into the flow output — the
    non-finite sentinel must then catch it and degrade that row cold.

    Under ``quant=True`` the int8 value rows cannot hold a NaN, so the
    poison NaNs the slot's f32 SCALE row instead — dequant-on-gather
    (``vals * NaN``) then yields NaN across the whole row, preserving the
    drill's propagation contract."""
    import jax.numpy as jnp

    def fn(fmap_buf, slots):
        if quant:
            vals_buf, scale_buf = fmap_buf
            return (vals_buf, scale_buf.at[slots].multiply(jnp.nan))
        return fmap_buf.at[slots].multiply(jnp.nan)
    return fn


class SlotPool:
    """Device-resident batch slots for the streaming sessions, per bucket.

    Pure bookkeeping plus buffer references: a free-list of
    ``capacity`` slot indices per bucket (index ``capacity`` is the
    reserved SCRATCH row padding rows of a batched step aim at), and the
    three device buffers (fmap / cnet / warm-start seed) the serving
    engine's warmed executables gather from and scatter into.  The pool
    itself never touches the device — buffers are created by the
    engine's ``szero`` executable at warmup and swapped here after every
    commit (functional update, donated off-CPU).

    Thread model: the free-list mutates under ``_lock`` from the store's
    promote/demote/sweep paths (store lock held — the declared
    store → pool edge) and buffer refs swap on the single batcher
    thread; the lock makes ref reads/swaps atomic for scrape-time
    gauges.

    **Ragged arena mode** (``arena=(max_h, max_w)``): every bucket key
    collapses onto the single max-box arena — sessions of EVERY declared
    resolution share ONE free-list and ONE set of ``[capacity+1, max_h,
    max_w, C]`` buffers, each slot a corner-anchored zero-embedded page
    (ops/corr.mask_ragged_rows is the layout contract).  Callers keep
    passing their *routed* bucket; the pool maps it, so the store/stream
    plumbing is bucket-agnostic.  A slot → extent map records each
    live page's real ``(h, w)`` so scrape-time gauges and the budget
    analyzer can price arena occupancy in live pixels, not box pixels.
    """

    _free = guarded_by("_lock")
    _bufs = guarded_by("_lock")
    _extents = guarded_by("_lock")

    def __init__(self, capacity: int,
                 arena: Optional[Tuple[int, int]] = None):
        if capacity < 1:
            raise ValueError(f"slot pool capacity must be >= 1, "
                             f"got {capacity}")
        self.capacity = capacity
        self.scratch = capacity          # the padding row, never allocated
        self.arena = None if arena is None else (int(arena[0]),
                                                 int(arena[1]))
        self._lock = watched_lock("SlotPool._lock")
        self._free: Dict[Tuple[int, int], list] = {}
        self._bufs: Dict[Tuple[int, int], Optional[tuple]] = {}
        # (mapped bucket, slot) -> live (h, w) of the page in that slot.
        self._extents: Dict[Tuple[Tuple[int, int], int],
                            Tuple[int, int]] = {}

    def _b(self, bucket: Tuple[int, int]) -> Tuple[int, int]:
        """Map a routed bucket to its storage key: identity in dense
        mode, the shared max-box arena in ragged mode."""
        return bucket if self.arena is None else self.arena

    @guarded_by("_lock")
    def _bucket_locked(self, bucket: Tuple[int, int]) -> list:
        bucket = self._b(bucket)
        free = self._free.get(bucket)
        if free is None:
            free = self._free.setdefault(bucket,
                                         list(range(self.capacity - 1,
                                                    -1, -1)))
            self._bufs.setdefault(bucket, None)
        return free

    def alloc(self, bucket: Tuple[int, int]) -> Optional[int]:
        """Pop a free slot index, or None when every slot of this bucket
        is held by an in-flight session (the caller stays cold)."""
        with self._lock:
            free = self._bucket_locked(bucket)
            return free.pop() if free else None

    def free(self, bucket: Tuple[int, int], slot: int) -> None:
        with self._lock:
            self._bucket_locked(bucket).append(slot)
            self._extents.pop((self._b(bucket), slot), None)

    def in_use(self, bucket: Tuple[int, int]) -> int:
        """Slots allocated in this bucket (the raft_stream_slots_in_use
        gauge; scrape-time callback).  In arena mode every bucket maps to
        the shared arena, so any declared bucket reports the arena-wide
        count."""
        with self._lock:
            free = self._free.get(self._b(bucket))
            return 0 if free is None else self.capacity - len(free)

    def set_extent(self, bucket: Tuple[int, int], slot: int,
                   extent: Tuple[int, int]) -> None:
        """Record the live (h, w) of the page now resident in ``slot``
        (stream coordinator, at attach/commit).  Cleared by :meth:`free`."""
        with self._lock:
            self._extents[(self._b(bucket), slot)] = (int(extent[0]),
                                                      int(extent[1]))

    def extent(self, bucket: Tuple[int, int],
               slot: int) -> Optional[Tuple[int, int]]:
        with self._lock:
            return self._extents.get((self._b(bucket), slot))

    def used_pixels(self, bucket: Tuple[int, int]) -> int:
        """Sum of live page pixels resident in this (mapped) bucket's
        buffers — the ragged-occupancy numerator for gauges and the
        budget analyzer; box pixels x in_use is the denominator."""
        b = self._b(bucket)
        with self._lock:
            return sum(h * w for (bk, _), (h, w) in self._extents.items()
                       if bk == b)

    def buffers(self, bucket: Tuple[int, int]):
        """(fmap_buf, cnet_buf, flow_buf) or None before install."""
        with self._lock:
            return self._bufs.get(self._b(bucket))

    def install(self, bucket: Tuple[int, int], bufs: tuple) -> None:
        """Install/swap this bucket's device buffers (batcher thread, or
        engine warmup).  Called after every commit executable: the old
        refs were donated and must never be used again."""
        with self._lock:
            self._bucket_locked(bucket)
            self._bufs[self._b(bucket)] = tuple(bufs)

    def release(self) -> None:
        """Drop the device buffers (a stopped server's: the pool is a third
        of the chip, and what runs after the server in its process, the
        benchmark's reference, needs the room).  The free-lists stay; a
        later stream call would rebuild the buffers zeroed."""
        with self._lock:
            for bucket in self._bufs:
                self._bufs[bucket] = None

    def leaf_bytes(self) -> Dict[str, int]:
        """Device bytes of the installed buffers as they stand, every bucket
        together, by leaf: ``vals`` (the fmap and cnet maps: int8 codes under
        ``quant='int8'``, else the compute dtype), ``scales`` (their float32
        scales; 0 without quant) and ``seed`` (the float32 warm-start seeds)
        — read from the arrays' own shapes and dtypes, 0 before the first
        install (the ``raft_stream_pool_bytes{leaf=}`` gauges; scrape-time
        callback)."""
        out = {"vals": 0, "scales": 0, "seed": 0}
        with self._lock:
            installed = [b for b in self._bufs.values() if b is not None]
        for bufs in installed:
            for buf, leaf in zip(bufs, ("vals", "vals", "seed")):
                if isinstance(buf, tuple):          # (codes, scales)
                    out["vals"] += _nbytes(buf[0])
                    out["scales"] += _nbytes(buf[1])
                else:
                    out[leaf] += _nbytes(buf)
        return out

    def seed_row(self, bucket: Tuple[int, int],
                 slot: int) -> Optional[np.ndarray]:
        """Host copy of one slot's warm-start seed ([1, h, w, 2]) — the
        solo cold/warm paths and tests read it; the batched step gathers
        it in-device instead."""
        bufs = self.buffers(bucket)
        if bufs is None:
            return None
        return np.asarray(bufs[2][slot])[None]


class Session:
    """One client stream's cached state.  Mutated only while its ``lock``
    is held (handler thread) or from the batcher thread during execute.
    Device-tier maps live in the slot pool at row ``slot``; the record
    itself is host-side."""

    __slots__ = ("id", "bucket", "lock", "created_at", "last_used",
                 "frames", "last_image", "slot")

    def __init__(self, sid: str, bucket: Tuple[int, int]):
        self.id = sid
        self.bucket = bucket
        # budget None: the handler deliberately holds this across a whole
        # advance (queue wait + device call) — serializing frames within a
        # session is the lock's JOB, not a hold-time bug
        self.lock = watched_lock("Session.lock", budget_s=None)
        self.created_at = self.last_used = time.monotonic()
        self.frames = 0                  # advances served (pairs)
        self.last_image = None           # [1, BH, BW, 3] float32, host
        self.slot = None                 # pool slot index, or None (cold)

    @property
    def has_features(self) -> bool:
        return self.slot is not None


class SessionStore:
    """LRU + TTL bounded session registry (one per FlowServer), mapping
    sid → host record → pool slot index.

    ``_lock`` guards the registry itself (``_sessions`` order and
    membership); per-``Session`` state is serialized by ``Session.lock``
    plus the single batcher thread (see the module docstring).  The store
    only ever *probes* ``Session.lock.locked()`` under its own lock —
    never acquires it — so the two can't order-invert.  Every slot
    transition (promote / demote / sweep / close / record-cap evict)
    happens under the store lock, so pool accounting can never leak a
    slot behind a dropped record."""

    _sessions = guarded_by("_lock")

    def __init__(self, max_sessions: int, ttl_s: float,
                 pool: Optional[SlotPool] = None):
        if max_sessions < 1:
            raise ValueError(f"max_sessions must be >= 1 to build a store, "
                             f"got {max_sessions}")
        if not ttl_s > 0:
            raise ValueError(f"session_ttl_s must be > 0, got {ttl_s}")
        self.max_sessions = max_sessions
        self.record_cap = RECORD_CAP_FACTOR * max_sessions
        self.ttl_s = ttl_s
        self.pool = pool if pool is not None else SlotPool(max_sessions)
        self._lock = watched_lock("SessionStore._lock")
        self._sessions: "OrderedDict[str, Session]" = OrderedDict()
        # set by make_stream_metrics: a labeled counter with reason=
        # lru (slot demoted), ttl (record reaped), capacity (record
        # evicted outright).  None until wired — the store works bare.
        self.evictions = None
        # and raft_stream_promotions_total{result=}: free, demoted_other,
        # none — counted in :meth:`promote`, where the slot is found or not
        self.promotions = None

    # -- accounting (live gauge callbacks, sampled at scrape time) ---------

    def active_count(self) -> int:
        """Sessions holding a device slot (the --max-sessions bound)."""
        with self._lock:
            return sum(1 for s in self._sessions.values() if s.has_features)

    def resident_count(self) -> int:
        """Session records resident, demoted included."""
        with self._lock:
            return len(self._sessions)

    def _evict(self, reason: str) -> None:
        if self.evictions is not None:
            self.evictions.labels(reason).inc()

    @guarded_by("_lock")
    def _drop_slot_locked(self, s: Session) -> None:
        """Free a session's slot back to the pool (store lock held — the
        declared store → pool hierarchy edge)."""
        if s.slot is not None:
            self.pool.free(s.bucket, s.slot)
            s.slot = None

    # -- lifecycle ---------------------------------------------------------

    def open(self, bucket: Tuple[int, int]) -> Session:
        """Create a fresh session record (a slot attaches on first
        encode).  Enforces the record cap by evicting the oldest
        not-in-flight records outright."""
        s = Session(uuid.uuid4().hex, bucket)
        with self._lock:
            while len(self._sessions) >= self.record_cap:
                victim = self._pop_lru_locked()
                if victim is None:       # everything in flight: admit anyway
                    break
                self._evict("capacity")
            self._sessions[s.id] = s
        return s

    def get(self, sid: str) -> Optional[Session]:
        """Look up + touch (LRU order and TTL clock)."""
        with self._lock:
            s = self._sessions.get(sid)
            if s is not None:
                s.last_used = time.monotonic()
                self._sessions.move_to_end(sid)
            return s

    def close(self, sid: str) -> Optional[Session]:
        """Pop the record and free its slot.  A session closed while its
        advance is still in flight keeps the slot until the handler
        releases the session lock and calls :meth:`reclaim_if_closed` —
        freeing it mid-execute would let a new session's promote reuse a
        row the batcher is about to scatter into."""
        with self._lock:
            s = self._sessions.pop(sid, None)
            if s is not None and not s.lock.locked():
                self._drop_slot_locked(s)
            return s

    def reclaim_if_closed(self, s: Session) -> None:
        """Handler-side epilogue of an advance: if the session was closed
        (or reaped) while its frame was in flight, free the slot the
        deferred close left behind."""
        with self._lock:
            if s.id not in self._sessions and not s.lock.locked():
                self._drop_slot_locked(s)

    def sweep(self, now: Optional[float] = None) -> int:
        """Reap records idle past the TTL (skipping in-flight sessions)
        and FREE their device slots back to the pool — a reaped session
        must never strand slot capacity; called opportunistically from
        the request path — no sweeper thread to leak."""
        now = time.monotonic() if now is None else now
        reaped = 0
        with self._lock:
            for sid in [sid for sid, s in self._sessions.items()
                        if now - s.last_used > self.ttl_s
                        and not s.lock.locked()]:
                self._drop_slot_locked(self._sessions.pop(sid))
                self._evict("ttl")
                reaped += 1
        return reaped

    # -- the device-slot bound --------------------------------------------

    def promote(self, session: Session) -> Optional[int]:
        """Give ``session`` a device slot (batcher thread, at commit
        time): demote LRU slot-holders until a slot is free — the
        device-memory bound the store exists for — then allocate.  A
        session that already holds a slot keeps it (the common advance
        path: its rows are updated in place by the commit scatter).
        Returns the slot, or None when every slot is pinned by an
        in-flight session (the caller stays cold — correct, just the
        pairwise cost)."""
        with self._lock:
            session.last_used = time.monotonic()
            if session.slot is not None:
                return session.slot
            holders = [s for s in self._sessions.values()
                       if s.has_features and s is not session]
            excess = len(holders) + 1 - self.max_sessions
            result = "free"
            for s in holders:            # OrderedDict order = LRU first
                if excess <= 0:
                    break
                if s.lock.locked():      # mid-advance: not a demotion target
                    continue
                self._drop_slot_locked(s)
                self._evict("lru")
                result = "demoted_other"
                excess -= 1
            session.slot = self.pool.alloc(session.bucket)
            if self.promotions is not None:
                self.promotions.labels(
                    "none" if session.slot is None else result).inc()
            return session.slot

    def demote(self, session: Session, reason: str) -> None:
        """Drop one session's device slot (faulted warm step: the
        degrade-to-cold rung of the ladder).  A no-op on an already-cold
        session, so a bucket-wide recovery followed by per-row degrade
        bookkeeping never double-counts an eviction."""
        with self._lock:
            if session.slot is not None:
                self._drop_slot_locked(session)
                self._evict(reason)

    def demote_all(self, reason: str = "degraded") -> int:
        """Drop EVERY session's device slot (records kept): the circuit
        breaker's degrade hook.  When the breaker opens the engine is
        sick — cached per-session device state from before the storm is
        not worth trusting, and dropping it routes every surviving
        session through the transparent cold-restart path once the
        breaker closes (correct flow, pairwise cost, no error).
        In-flight sessions are skipped, same as LRU demotion."""
        n = 0
        with self._lock:
            for s in self._sessions.values():
                if s.has_features and not s.lock.locked():
                    self._drop_slot_locked(s)
                    self._evict(reason)
                    n += 1
        return n

    def demote_bucket(self, bucket: Tuple[int, int],
                      reason: str = "degraded") -> int:
        """Drop EVERY session slot of ONE bucket — in-flight sessions
        INCLUDED.  This is the recovery hook after a failed commit
        scatter rebuilt the bucket's (donated, now-dead) buffers zeroed:
        any session keeping its slot would gather zeros on its next
        advance and serve finite garbage, so the usual skip-the-locked
        convention must not apply.  Safe to override it here: this runs
        only on the single batcher thread — the one thread that gathers
        — so no step can be mid-gather while the slots are dropped;
        queued advances re-check ``has_features`` at execute time and
        fall back cold."""
        n = 0
        with self._lock:
            for s in self._sessions.values():
                if s.bucket == bucket and s.has_features:
                    self._drop_slot_locked(s)
                    self._evict(reason)
                    n += 1
        return n

    @guarded_by("_lock")
    def _pop_lru_locked(self) -> Optional[Session]:
        for sid, s in self._sessions.items():
            if not s.lock.locked():
                s = self._sessions.pop(sid)
                self._drop_slot_locked(s)
                return s
        return None
