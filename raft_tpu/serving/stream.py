"""Sessionful streaming flow: the machinery behind ``POST /v1/stream``.

Protocol (wire format parsed in http.py): a client *opens* a session with
its first frame, *advances* it one frame at a time — each advance returns
flow(prev -> cur) — and *closes* it.  Per advance the server runs ONE
encoder pass (the current frame's; the previous frame's fmap/context maps
are cached device-side in the session's SLOT of the per-bucket batch
buffers — serving/session.SlotPool) and warm-starts the recurrence from
the previous flow forward-projected along itself
(ops/warmstart.warm_start_seed — RAFT's own Sintel video protocol), so a
``converge:eps`` iteration policy exits in a fraction of the cold count.

**Continuous batching** (ROADMAP item 1, the Ragged-Paged-Attention
recipe from PAPERS.md): advances are keyed per BUCKET in the admission
queue, so concurrent stream steps from *different* sessions coalesce —
up to max_batch / max_wait, exactly like pairwise requests — into ONE
batched stream executable (models/raft.make_stream_batch_step_fn) that
gathers each row's cached maps + warm-start seed from its pool slot,
advances every session in one device call, and scatters the updated
rows back.  Rows join and leave the batch every step as sessions open,
advance and close; padding rows are inactive (scratch slot, converged
from iteration 0, excluded from all metrics).  Session opens stay solo
calls (keyed per session): they run the ``encode`` executable, which has no
batch-mates to share.

Stream steps ride the SAME admission queue and batcher thread as
``/v1/flow`` (bounded depth -> 429, deadlines -> 504, graceful drain);
the session lock serializes frames within a session (a concurrent
advance on the same session answers 409 rather than reordering the
recurrence) — which is also why a coalesced group can never hold the
same session twice, so the commit scatter's real slot indices are
always unique.

**The pipelined walk.**  A group of advances is a job of the batcher's
two-deep pipeline (batcher.py ``_pipeline``), walked through the phases a
pair batch is walked through: :meth:`StreamCoordinator.place` (pad, the
frames' ``h2d``) under group n's run; :meth:`~StreamCoordinator.dispatch`
the moment n is ready (never two programs' temporaries at once), over the
pool's buffers, the slots and the live rows as they stand THEN; and only
then n's :meth:`~StreamCoordinator.fetch` and
:meth:`~StreamCoordinator.finish` — sentinel, warm-start projections, the
commit's dispatch, the heals — under n+1's run.  Why the answers are the
blocking walk's, bit for bit: a handler holds ``Session.lock`` across its
whole advance, so a session is in at most one request in flight and the
rows of n+1 are other sessions, in other slots, than n's; a row is resolved
only after its commit was dispatched, its handler sends the next frame only
after the resolve, and the device runs calls in dispatch order.  So the
order on the device is ``sbatch(n+1)``, ``scommit(n)``, ``sbatch(n+2)``:
every gather sees every earlier scatter of ITS slots, and ``sbatch(n+1)``
reads rows that ``scommit(n)`` does not write (the scratch slot is never
served).  A batch of ``/v1/flow`` pairs and an engine with
``run_stream_batch`` alone begin when the running group has been delivered.
An OPEN goes beside a running group: the queue hands it over at once (it
coalesces with nothing), and its encoder pass and its row's commit are
dispatched and never fetched, so they queue on the device behind the group,
whose rows are other sessions' (an LRU demotion the open's promote makes
skips sessions in flight); the group's own commit then scatters into the
buffers as the open's left them.

**A cold restart waits for nothing.**  A row whose session holds no slot
when its group is PLACED (LRU gave it away while the session was parked) is
re-seated there and then (:meth:`StreamCoordinator._reseat`): an open's two
calls with the frame the service kept and a zero seed, dispatched and never
fetched.  The row is then a row of the batch: padded, placed, dispatched,
fetched, checked, projected and committed with its neighbours, and answered
``warm: false`` (it is a cache miss: two encoder passes; at a fixed
iteration count a row seeded with zeros IS the zero-seeded pair).  With a
restart in group n+1 the order on the device is ``sbatch(n)`` (running),
``encode``, ``scommit`` of width 1, ``sbatch(n+1)`` (gathers the slot just
written), ``scommit(n)`` (other sessions' slots): the order an open relies
on.  What is left of the SOLO restart (``_cold_advance``: the kept frame's
encode and a fetched batch-1 step, device calls of their own that run in
``finish`` after the group dispatched behind has run) is the heal of the
fault ladder below, and of a row that could not be re-seated.

Thread model (SERVING.md "Threading model"): the handler thread holds
``Session.lock`` across the WHOLE advance — including ``queue.submit``
(which takes the queue lock) and the blocking wait — which is why the
declared hierarchy orders ``Session.lock`` OUTSIDE
``RequestQueue._lock``.  The coordinator itself holds no lock: session
state is mutated only in :meth:`execute` and the phases of a group
(:meth:`place` ... :meth:`finish`) on the batcher thread, while the
handler's session lock keeps any second frame of the same session out;
slot transitions go through the store (store lock → pool lock, the
declared edge).

Failure containment, per ROW of a batched step: a row that faults — the
batched call raising (at its place, its dispatch or its wait), or that
row's output failing the non-finite sentinel (e.g. a poisoned slot) — is
demoted and healed through the transparent SOLO cold restart
(``_cold_advance``), in the same advance; its co-batched neighbors keep
their results.  This is the stream path's form of poisoned-row
isolation: the pairwise path bisects because it has no finer fallback, the
stream path degrades straight to per-row cold restarts (finer blame,
bounded at two engine calls per row).  A cold attempt that faults is
terminal for that frame only.  A failed commit rebuilds the pool zeroed and
demotes the bucket; the group already in flight was gathered from the
buffers before the rebuild, so its flows are sound and are served, but none
of its rows commits into a slot its session no longer owns (``finish``
compares), and nothing gathered from a rebuilt pool is served: every
session of the bucket restarts cold on its next frame.  A row whose handler
gave up is skipped BEFORE the device call, never after: at the form
(batcher), again at the dispatch (a run later: the row goes inactive), and
before a heal.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..data.pipeline import embed_to_shape, pad_to_shape
from ..ops.warmstart import warm_start_seed
from ..telemetry import events as tlm_events
from ..telemetry import spans as tlm_spans
from ..telemetry.trace import host_stage
from .batcher import NonFiniteOutput, finite_rows, planar_batch
from .queue import (DeadlineExceeded, Draining, RejectedError, Request,
                    RequestQueue)
from .session import Session, SessionStore


# sink of the batcher thread's stages of a stream step: children of
# ``execute`` as the engine's stages are, counted with them by the batcher
_batch_stage = functools.partial(tlm_spans.record_device_stage, "stream")


class UnknownSession(RejectedError):
    """Session id never existed, was closed, or aged out (TTL) — reopen."""
    http_status = 404
    trace_status = tlm_spans.BAD_REQUEST     # the client's, as a 400 is


class SessionBusy(RejectedError):
    """A frame for this session is already in flight (advances are
    strictly sequential: frame t's flow seeds frame t+1)."""
    http_status = 409
    trace_status = tlm_spans.BAD_REQUEST


class StreamRequest(Request):
    """One stream step in flight.  ``bucket`` is the queue key —
    advances key per BUCKET (``("stream", H, W)``) so concurrent steps
    from different sessions coalesce into one batched device call, while
    opens key per session (``("stream-open", sid)``): they run the solo
    encode executable and have nothing to coalesce with.  Neither key
    ever collides with a pairwise ``(H, W)`` bucket."""

    __slots__ = ("session", "stream_op", "frame", "abandoned", "restarted",
                 "batched")

    @property
    def coalesces(self) -> bool:
        return self.stream_op == "advance"

    @property
    def warm(self) -> bool:
        """The answer's ``warm`` member: served from the session's resident
        slot by the batched call.  A row restarted at the place rode that
        call too and is a cache miss all the same; a row healed solo is
        neither."""
        return self.batched and not self.restarted

    def __init__(self, session: Session, op: str, image_padded, pads,
                 deadline: float,
                 qbucket: Optional[Tuple[int, int]] = None):
        # qbucket: the (H, W) the advance key coalesces on — the
        # session's routed bucket in dense mode; under --ragged the
        # coordinator passes the shared max box, so advances from
        # DIFFERENT resolutions land in one FIFO and one batched step.
        kb = tuple(session.bucket if qbucket is None else qbucket)
        key = (("stream",) + kb if op == "advance"
               else ("stream-open", session.id))
        super().__init__(image_padded, None, key, pads, deadline,
                         rbucket=tuple(session.bucket))
        self.session = session
        self.stream_op = op              # "open" | "advance"
        # a demoted session's row re-seated at its group's place (the kept
        # frame's encode and a zero-seeded commit_row): it rides the batched
        # call and answers ``warm: false``
        self.restarted = False
        # the answer came from the group's batched call (warm or restarted)
        # and not from a solo heal: the width its device step reports
        self.batched = False             # set in the group's finish
        self.frame = 0
        # set by the handler when wait() gives up (batcher stalled past
        # the deadline margin): the batcher must then SKIP the step
        # instead of mutating session state after the session lock was
        # released — a late orphaned step would otherwise consume the
        # frame a client retry is about to resubmit
        self.abandoned = False


class _WholeBatchCall:
    """An engine with ``run_stream_batch`` alone as the phases of a stream
    batch's device call (serving/engine.py): ``dispatch`` is the whole
    call."""

    def __init__(self, engine):
        self.run = engine.run_stream_batch

    def place_stream_batch(self, bucket, images, sizes=None) -> list:
        return [bucket, images, sizes]

    def dispatch_stream_batch(self, call: list, slots, active) -> None:
        bucket, images, sizes = call
        call[:] = [self.run(bucket, images, slots, active, sizes=sizes)]

    def ready(self, call: list) -> bool:
        return True

    def wait(self, call: list) -> None:
        pass

    def fetch_stream_batch(self, call: list):
        return call[0]


class GroupCall:
    """One coalesced group of advances between its place and its finish.
    ``warm`` are the group's rows that ride the batched device call (their
    sessions held a slot at the place, or were given one there: a restarted
    row), ``call`` that call while one is
    placed or running, ``live`` / ``slots`` its rows as the dispatch found
    them, ``out`` what its fetch brought."""

    __slots__ = ("group", "engine", "phases", "warm", "bucket", "padded",
                 "call", "live", "slots", "out", "failed")

    def __init__(self, group: List[StreamRequest], engine):
        self.group, self.engine = group, engine
        self.phases = (engine if hasattr(engine, "dispatch_stream_batch")
                       else _WholeBatchCall(engine))
        self.warm: List[int] = []
        self.call = self.live = self.out = None
        self.failed = False

    @property
    def reqs(self) -> List[StreamRequest]:
        return [self.group[i] for i in self.warm]


class StreamCoordinator:
    """Owns the session store + slot pool policy and the stream-step
    device recipe.

    Handler threads call :meth:`open`/:meth:`advance`/:meth:`close`
    (validate, lock the session, enqueue, block); the batcher thread
    calls :meth:`execute` (opens) and the phases of a coalesced group of
    advances (:meth:`place`, :meth:`dispatch`, :meth:`ready`, :meth:`wait`,
    :meth:`fetch`, :meth:`finish`; :meth:`execute_group` is all of them in
    turn) — the only places device state moves.
    """

    def __init__(self, store: SessionStore, sconfig, queue: RequestQueue,
                 metrics: Dict, count_fn, faults=None, nonfinite=None,
                 breaker=None, tracer=None, stage_done=None):
        self.store = store
        self.pool = store.pool
        self.sconfig = sconfig
        self.queue = queue
        self.metrics = metrics           # make_stream_metrics families
        self.count = count_fn            # FlowServer.count_request
        self.faults = faults             # chaos injector (session arm)
        self.nonfinite = nonfinite       # raft_nonfinite_outputs_total
        self.breaker = breaker           # CircuitBreaker or None
        self.tracer = tracer             # telemetry.spans.Tracer or None
        # sink of the handler threads' host stages (FlowServer.stage_done:
        # stage seconds + span), or None: the span alone
        self.stage_done = stage_done
        # the batcher thread's padded frames (planar_batch), grown to the
        # largest stream batch seen
        self._frames = [np.empty(0, np.uint8)]
        # ragged mixed-resolution mode (SERVING.md "Ragged serving"):
        # every device call runs at the shared max-box arena bucket with
        # per-row live sizes; sessions keep their ROUTED bucket for
        # protocol/routing purposes
        self.ragged = bool(getattr(sconfig, "ragged", False))
        self.dev_box = sconfig.max_box if self.ragged else None

    def _dev(self, s: Session) -> Tuple[int, int]:
        """The bucket device calls run at: the session's routed bucket,
        or the shared max-box arena under --ragged."""
        return s.bucket if self.dev_box is None else self.dev_box

    def _mask_seed(self, seed: np.ndarray,
                   bucket: Tuple[int, int]) -> np.ndarray:
        """Zero a warm-start seed outside the session's live 1/8-scale
        extent: warm_start_seed forward-splats flow along itself, so
        un-masked dead-embedding flow could leak into the live region of
        the NEXT step's init (deterministically, but noise all the
        same)."""
        if self.dev_box is None:
            return seed
        bh, bw = bucket
        seed = np.asarray(seed).copy()
        seed[..., bh // 8:, :, :] = 0.0
        seed[..., :, bw // 8:, :] = 0.0
        return seed

    def _demote_shared(self, reason: str = "degraded") -> None:
        """Ragged twin of ``store.demote_bucket``: a failed commit killed
        the ARENA buffers every resolution shares, so every declared
        bucket's sessions must demote (in-flight included — same
        single-batcher-thread safety argument)."""
        for b in self.sconfig.buckets:
            self.store.demote_bucket(tuple(b), reason)

    # -- handler-thread API ------------------------------------------------

    def open(self, image: np.ndarray, deadline_ms: Optional[float],
             trace_id: Optional[str] = None,
             finish_trace: bool = True, trace=None) -> Dict:
        from .http import BadRequest    # circular-free: http imports us not
        self.store.sweep()
        h, w = image.shape[0], image.shape[1]
        bucket = self.sconfig.route(h, w)
        if bucket is None:
            raise BadRequest(
                f"no declared bucket fits ({h}, {w}); buckets: "
                f"{[f'{bh}x{bw}' for bh, bw in self.sconfig.buckets]}")
        s = self.store.open(bucket)
        try:
            with s.lock:
                req = self._run_step(s, "open", image, deadline_ms,
                                     trace_id=trace_id,
                                     finish_trace=finish_trace, trace=trace)
        except BaseException:
            # no half-open sessions — but close AFTER releasing s.lock:
            # store.close takes the store lock, which the hierarchy orders
            # OUTSIDE the session lock (the id never reached the client,
            # so nothing can race the record between release and close)
            self.store.close(s.id)
            raise
        self.metrics["opens"].inc()
        res = {"session": s.id, "frame": 0,
               "meta": {"bucket": list(bucket)}, "_trace": req.trace,
               "_finished_at": req.finished_at}
        if req.trace is not None:
            res["meta"]["trace_id"] = req.trace.trace_id
        return res

    def advance(self, sid: Optional[str], image: np.ndarray,
                deadline_ms: Optional[float],
                trace_id: Optional[str] = None,
                finish_trace: bool = True, trace=None) -> Dict:
        from .http import BadRequest
        self.store.sweep()
        s = self.store.get(sid) if sid else None
        if s is None:
            self.count("unknown_session")
            raise UnknownSession(
                f"unknown session {sid!r} (closed, expired after "
                f"{self.sconfig.session_ttl_s:.0f}s idle, or never "
                f"opened) — open a new one")
        if not s.lock.acquire(blocking=False):
            self.count("session_busy")
            raise SessionBusy(f"session {sid} already has a frame in "
                              f"flight; advances are sequential")
        try:
            h, w = image.shape[0], image.shape[1]
            if self.sconfig.route(h, w) != s.bucket:
                raise BadRequest(
                    f"frame ({h}, {w}) does not route to this session's "
                    f"bucket {s.bucket}; resolution changes mid-stream "
                    f"need a new session")
            req = self._run_step(s, "advance", image, deadline_ms,
                                 trace_id=trace_id,
                                 finish_trace=finish_trace, trace=trace)
        finally:
            s.lock.release()
            # a close() that raced this advance deferred the slot free
            # to us (see SessionStore.close)
            self.store.reclaim_if_closed(s)
        meta = {"bucket": list(s.bucket), "warm": req.warm,
                "batch_real": req.batch_real,
                "batch_padded": req.batch_padded}
        if req.iters_used is not None:
            meta["iters_used"] = req.iters_used
        if req.trace is not None:
            meta["trace_id"] = req.trace.trace_id
        return {"session": s.id, "frame": req.frame, "flow": req.result,
                "meta": meta, "_trace": req.trace,
                "_finished_at": req.finished_at}

    def close(self, sid: Optional[str]) -> Dict:
        s = self.store.close(sid) if sid else None
        if s is None:
            self.count("unknown_session")
            raise UnknownSession(f"unknown session {sid!r}")
        return {"session": sid, "closed": True, "frames": s.frames}

    def _run_step(self, s: Session, op: str, image: np.ndarray,
                  deadline_ms: Optional[float],
                  trace_id: Optional[str] = None,
                  finish_trace: bool = True, trace=None) -> StreamRequest:
        """Pad, enqueue, block until the batcher resolves — the stream
        twin of FlowServer.infer, same deadline/shed/drain accounting and
        the same trace lifecycle: the HTTP handler mints the trace once it
        has read the op (``trace``), a direct caller's is minted here; it
        closes HERE on every failure path (status from the exception); on
        success the HTTP handler finishes it after the respond span
        (``finish_trace=False``), or this method does for direct
        callers."""
        from .http import BadRequest
        tr = trace
        if tr is None and self.tracer is not None:
            tr = self.tracer.start("stream", trace_id)
        try:
            with host_stage("raft.http.admit") as st:
                dl = (self.sconfig.default_deadline_ms if deadline_ms is None
                      else min(deadline_ms,
                               self.sconfig.default_deadline_ms))
                if dl <= 0:
                    raise BadRequest(
                        f"deadline_ms must be positive, got {dl}")
                # (no copy of a frame that is float32 already, as the HTTP
                # edge's are, and none by the pad where the frame is the
                # bucket's size)
                imp, pads = pad_to_shape(
                    image[None].astype(np.float32, copy=False), s.bucket)
                if self.dev_box is not None:
                    # ragged: zero-embed the routed-bucket frame corner-
                    # anchored into the max-box arena and fold the
                    # embedding into pads, so unpad() recovers the original
                    # resolution straight from the max-box flow
                    (bh, bw), (mh, mw) = s.bucket, self.dev_box
                    imp = embed_to_shape(imp, self.dev_box)
                    t, b_, l_, r_ = pads
                    pads = (t, b_ + mh - bh, l_, r_ + mw - bw)
                req = StreamRequest(s, op, imp, pads,
                                    deadline=time.monotonic() + dl / 1000.0,
                                    qbucket=self.dev_box)
                req.trace = tr
            if self.stage_done is not None:
                self.stage_done(st, tr, op=op, session=s.id)
            elif tr is not None:
                tr.span(st.span, st.t0, st.t1, cpu=st.cpu, op=op,
                        session=s.id)
            try:
                self.queue.submit(req)
            except Draining:
                self.count("draining")
                raise
            except Exception:           # QueueFull: overload shed, HTTP 429
                self.count("shed")
                raise
            try:
                req.wait(timeout=dl / 1000.0 + max(30.0, dl / 1000.0))
            except DeadlineExceeded:
                # the step may still be queued (or mid-execution on a
                # stalled device): mark it so the batcher drops it instead
                # of advancing the session after this thread releases its
                # lock
                req.abandoned = True
                if req.error is None:
                    self.count("timeout")
                raise
        except BaseException as e:
            if tr is not None:
                # stamp-if-absent (see FlowServer.infer): never overwrite
                # another request's id on a shared exception instance
                if getattr(e, "trace_id", None) is None:
                    e.trace_id = tr.trace_id
                tr.finish(tlm_spans.status_of(e))
            raise
        if finish_trace and tr is not None:
            tr.finish()
        return req

    # -- batcher-thread API ------------------------------------------------

    def execute(self, req: StreamRequest, engine):
        """Run one SOLO stream step on the device (session open, or a
        lone advance routed outside the group path).  Returns (padded
        flow or None, iters_used or None); all session/cache mutation
        happens here or in a group's phases, on the single thread that
        owns the device."""
        s = req.session
        if req.stream_op == "open":
            with host_stage("raft.batch.pad", _batch_stage):
                image = planar_batch(self._frames, 0, [req.image1], 1)
            fmap, cnet = engine.run_encode(self._dev(s), image)
            self._attach(s, engine, fmap, cnet, flow_lr=None)
            s.last_image = req.image1
            return None, None
        [(flow, iters_used, err)] = self.execute_group([req], engine)
        if err is not None:
            raise err
        return flow, iters_used

    def execute_group(self, group: List[StreamRequest], engine):
        """Advance a coalesced same-bucket group of sessions, every phase in
        turn with nothing beside it (the blocking walk: a lone advance, a
        server whose engine has ``run_stream_batch`` alone).  Returns what
        :meth:`finish` returns."""
        call = self.place(group, engine)
        self.dispatch(call)
        self.wait(call)
        self.fetch(call)
        return self.finish(call)

    # A group of advances, one phase at a time: the phases of the pair
    # engine's device call (place, dispatch, ready, wait, fetch) and a finish
    # of its own, which the batcher walks two deep (serving/batcher.py
    # ``_pipeline``): group n+1 is placed while group n's batched call runs
    # and dispatched the moment it is ready, and group n is fetched and
    # finished under n+1's run.  A batched call that raises, in whichever
    # phase, fails the CALL: its rows heal cold in ``finish``.

    def _guard(self, call: "GroupCall", phase, *args):
        """One phase of ``call``'s batched device call.  A fault there is
        no retry's business: a warm step has a finer fallback than
        re-running the whole group, the cold heal of each row, which also
        isolates a guilty one.  The failed call still counts against the
        breaker: it measures engine-call health, and a 100%-warm-failure
        mode must stay visible even though every advance heals."""
        try:
            return phase(*args)
        except Exception:
            if self.breaker is not None:
                self.breaker.record(False)
            call.failed, call.call = True, None
            return None

    def place(self, group: List[StreamRequest], engine) -> "GroupCall":
        """The group's rows padded into one batch and their frames put on
        the device.  A row whose session holds no slot now (LRU gave it away
        while the session was parked) is re-seated first (:meth:`_reseat`)
        and rides with its neighbours; one that could not be (every slot
        pinned, or the restart's own calls faulted) waits for
        :meth:`finish`'s solo heal.  The pad buffer is free again once this
        returns (``h2d`` waits for the transfer), so one serves the group
        running and the group staged behind it."""
        if self.faults is not None:
            for r in group:
                self.faults.corrupt_session(r.session, engine)
        call = GroupCall(group, engine)
        # (a row whose handler gave up makes no device call; one that holds
        # a slot now and loses it to a restart's failed commit is healed)
        for r in [r for r in group
                  if not r.session.has_features and not r.abandoned]:
            self._reseat(r, engine)
        call.warm = [i for i, r in enumerate(group)
                     if r.session.has_features]
        if call.warm:
            self._guard(call, self._place_warm, call)
        return call

    def _reseat(self, req: StreamRequest, engine) -> None:
        """A cold restart that waits for nothing: an open's two calls, with
        the frame the service kept and a zero seed.  The kept frame's encoder
        pass and the row's width-1 commit are dispatched and never fetched,
        so they queue on the device behind the running group (whose rows are
        other sessions': the promote's LRU demotion skips sessions in
        flight) and ahead of this group's batched call, which then gathers
        the slot just written: at a fixed iteration count a row seeded with
        zeros IS the zero-seeded pair (kept frame, frame).  Session host
        state moves only when the row has passed the sentinel
        (:meth:`_finish_warm`).  A restart that cannot be made here leaves
        the session without a slot, for :meth:`finish`'s solo heal: no slot
        to be had (``promote``), its encode raising (the slot is given
        back), its commit raising (``_attach`` demotes the bucket)."""
        s = req.session
        if self.store.promote(s) is None:
            return
        try:
            with host_stage("raft.stream.cold.encode", _batch_stage,
                            holds=True):
                fmap, cnet = engine.run_encode(self._dev(s), s.last_image)
            with host_stage("raft.stream.cold.attach", _batch_stage,
                            holds=True):
                self._attach(s, engine, fmap, cnet, flow_lr=None)
        except Exception:
            if self.breaker is not None:
                self.breaker.record(False)
            self.store.demote(s, "degraded")
            return
        if s.has_features:
            self.metrics["fnet_misses"].inc()
            self.metrics["cold_restarts"].labels("demoted").inc()
            req.restarted = True

    def _place_warm(self, call: "GroupCall") -> None:
        reqs = call.reqs
        call.bucket = bucket = self._dev(reqs[0].session)
        n = len(reqs)
        call.padded = padded = self.sconfig.pad_batch_to(
            min(n, self.sconfig.max_batch))
        with host_stage("raft.batch.pad", _batch_stage):
            images = planar_batch(self._frames, 0,
                                  [r.image1 for r in reqs], padded)
        sizes = None
        if self.dev_box is not None:
            # per-row live extents: each session's ROUTED bucket (filler
            # rows repeat the last, matching their repeated pixels)
            sizes = np.asarray([r.session.bucket for r in reqs]
                               + [reqs[-1].session.bucket] * (padded - n),
                               np.int32)
        call.call = call.phases.place_stream_batch(bucket, images, sizes)

    def dispatch(self, call: "GroupCall") -> None:
        """Enqueue the placed batch over the pool's buffers, the slots and
        the rows AS THEY STAND NOW: the dispatch can come a whole run after
        the place, and a commit, an open's ``commit_row`` or a bucket's
        demotion in between has moved them.  A row whose handler
        gave up since, or whose session lost its slot, goes inactive (an
        argument of the program, not a shape) and is left to
        :meth:`finish`; with no row left the call is skipped — BEFORE the
        device call, never after."""
        if call.call is None:
            return
        reqs = call.reqs
        call.live = live = [not r.abandoned and r.session.has_features
                            for r in reqs]
        if not any(live):
            call.call = None
            return
        fill = [self.pool.scratch] * (call.padded - len(reqs))
        call.slots = np.asarray(
            [r.session.slot if ok else self.pool.scratch
             for r, ok in zip(reqs, live)] + fill, np.int32)
        self._guard(call, call.phases.dispatch_stream_batch, call.call,
                    call.slots, np.asarray(live + [False] * len(fill), bool))

    def ready(self, call: "GroupCall") -> bool:
        """Has the batched call finished (or is there none)?  Never
        blocks."""
        return call.call is None or call.phases.ready(call.call)

    def wait(self, call: "GroupCall") -> None:
        if call.call is not None:
            self._guard(call, call.phases.wait, call.call)

    def fetch(self, call: "GroupCall") -> None:
        """The finished call's flows on the host; its rows of ``fmap`` /
        ``cnet`` stay on the device for the commit."""
        if call.call is not None:
            call.out = self._guard(call, call.phases.fetch_stream_batch,
                                   call.call)
            call.call = None
            if call.out is not None and self.breaker is not None:
                self.breaker.record(True)

    def finish(self, call: "GroupCall", alone=None):
        """The end of a group: sentinel, warm-start projections and commit
        of the batched call's rows (:meth:`_finish_warm`), then a solo cold
        restart for every row it did not serve — one that could not be
        re-seated at the place, one displaced since, and rows of the call
        that faulted (the per-row degradation ladder — see the module
        docstring).  These heals are device calls of their own: ``alone()``
        (the batcher's) first waits until the batch dispatched behind this
        one has run.  Returns ``[(padded flow,
        iters_used, err)]`` aligned with the group; exactly one of
        flow/err is set per row.  Session host state (frames, last_image)
        moves only for rows that succeeded."""
        group, engine = call.group, call.engine
        results: List[Optional[tuple]] = [None] * len(group)
        if call.warm:
            for i, row in zip(call.warm, self._finish_warm(call)):
                results[i] = row
        heal = [i for i, row in enumerate(results) if row is None]
        if heal and alone is not None:
            with host_stage("raft.stream.cold.wait", _batch_stage,
                            holds=True):
                alone()
        for i in heal:
            r = group[i]
            if r.abandoned:
                # given up since the form: skipped, as at the form, BEFORE
                # the device call (the handler has counted its timeout)
                results[i] = (None, None, DeadlineExceeded(
                    f"stream step {r.id} abandoned by its handler"))
                continue
            try:
                flow, iters_used = self._cold_advance(
                    r.session, r, engine, self._restart_cause(call, i))
                if iters_used is not None:
                    iters_used = int(np.asarray(iters_used).reshape(-1)[0])
                results[i] = (flow, iters_used, None)
            except Exception as e:
                if self.breaker is not None:
                    self.breaker.record(False)
                results[i] = (None, None, e)
        for r, (flow, _iters, err) in zip(group, results):
            if err is None:
                r.session.frames += 1
                r.frame = r.session.frames
                self.metrics["frames"].inc()
        return results

    def _finish_warm(self, call: "GroupCall") -> list:
        """What became of the batched call's rows.  Returns a list aligned
        with ``call.reqs``: ``(padded flow, iters_used, None)`` for rows
        whose output passed the sentinel (committed, where the session
        still owns the slot it was gathered from), or None for rows that
        must heal cold (their slots are dropped; nothing poisoned is ever
        cached).  A row restarted at the place is answered ``warm: false``
        and counts no cache hit."""
        reqs = call.reqs
        n = len(reqs)
        live = call.live or [True] * n
        if call.out is None:
            # the batched call faulted, or no row was left for it: every
            # row it would have served degrades to the cold-restart path
            # (the solo semantics, batched)
            for r, ok in zip(reqs, live):
                if ok and call.failed:
                    self._degrade(r)
            return [None] * n
        flow, flow_lr, fmap_rows, cnet_rows, iters_used = call.out
        bucket, padded, slots = call.bucket, call.padded, call.slots
        h, w = bucket
        with host_stage("raft.stream.sentinel", _batch_stage):
            row_ok = finite_rows(n, flow, flow_lr, live=live)
        # a row commits into the slot it was gathered from, while its
        # session still owns it: a failed commit of the batch in front
        # (dispatched after this one was) has rebuilt the pool and demoted
        # the bucket — this batch's flows are sound and are served, and its
        # sessions restart cold on their next frame
        mask = np.zeros(padded, bool)
        mask[:n] = [ok and r.session.slot == slots[i]
                    for i, (r, ok) in enumerate(zip(reqs, row_ok))]
        # commit BEFORE touching host state, AFTER the sentinel: finite
        # rows scatter their updated maps + next-frame warm-start seed
        # into their slots; rejected and padding rows write their old
        # values back (mask), so a poisoned output can never be cached
        with host_stage("raft.stream.seed", _batch_stage):
            seeds = np.zeros((padded, h // 8, w // 8, 2), np.float32)
            for i in np.flatnonzero(mask):
                seeds[i] = self._mask_seed(
                    warm_start_seed(flow_lr[i:i + 1], (h // 8, w // 8))[0],
                    reqs[i].session.bucket)
        try:
            with host_stage("raft.stream.commit", _batch_stage):
                # dispatch only: it queues on the device behind the batch
                # dispatched after this one, whose rows are other sessions'
                if mask.any():
                    call.engine.commit_stream(
                        bucket, np.where(mask, slots, self.pool.scratch),
                        fmap_rows, cnet_rows, seeds, mask)
        except Exception:
            # a failed commit leaves the (donated) bucket buffers dead;
            # commit_stream already rebuilt them zeroed — now demote
            # EVERY session of the bucket, in-flight/queued ones
            # included (demote_bucket overrides the skip-the-locked
            # convention precisely because a kept slot would gather the
            # zeros and serve finite garbage), then heal this group cold.
            # Under --ragged every resolution shares the arena buffers,
            # so EVERY declared bucket demotes.
            if self.dev_box is not None:
                self._demote_shared()
            else:
                self.store.demote_bucket(bucket)
            for r, ok in zip(reqs, live):
                if ok:
                    self._degrade(r)
            return [None] * n
        out = []
        for i, r in enumerate(reqs):
            if not live[i]:
                out.append(None)
                continue
            if not row_ok[i]:
                if self.nonfinite is not None:
                    self.nonfinite.inc()
                log = tlm_events.current()
                if log is not None:
                    log.event("nonfinite_output", session=r.session.id,
                              warm=True,
                              trace_id=(r.trace.trace_id
                                        if r.trace is not None else None))
                self._degrade(r)
                out.append(None)
                continue
            r.session.last_image = r.image1
            r.batched = True
            # (a restarted row is a miss, answered by the batched call)
            self.metrics["restarts_batched" if r.restarted
                         else "fnet_hits"].inc()
            out.append((flow[i:i + 1],
                        None if iters_used is None else int(iters_used[i]),
                        None))
        return out

    @staticmethod
    def _restart_cause(call: "GroupCall", i: int) -> str:
        """Why row ``i`` of the group restarts cold
        through the solo heal (``raft_stream_cold_restarts_total{cause=}``;
        a restart at the place counts ``demoted`` there): ``demoted`` — its
        session held no slot when the group was placed (LRU took it while
        the session was parked) and could not be given one, or the
        restart's own calls faulted; ``displaced`` — it held one at the
        place and none at the dispatch, a run later (a bucket's demotion in
        between: no policy takes a slot from a session in flight);
        ``degraded`` — it rode the batched call and faulted."""
        if i not in call.warm:
            return "demoted"
        live = call.live
        if live is not None and not live[call.warm.index(i)]:
            return "displaced"
        return "degraded"

    def _degrade(self, req: StreamRequest) -> None:
        """Drop one faulted warm row's slot so its heal (and every later
        advance until re-promotion) runs the transparent cold-restart
        path; the client still gets a 200, the trace says what it cost."""
        self.store.demote(req.session, "degraded")
        self.metrics["degraded"].inc()
        if req.trace is not None:
            # degraded outranks ok and is always recorder-retained
            req.trace.set_status(tlm_spans.DEGRADED)

    def _cold_advance(self, s: Session, req: StreamRequest, engine,
                      cause: str):
        """The SOLO cold restart from the retained previous frame, the heal
        of a row its group's batched call did not serve (a restart that can
        ride the call is :meth:`_reseat`'s) — pairwise cost, correct flow,
        and the device to itself.  It BYPASSES the pool: the kept frame's
        maps go from the encoder straight into the solo step, so under
        ``--quant int8`` this one answer is computed from unquantised maps
        (every answer of a sound window passes through a slot: an open's
        ``commit_row``, a warm advance's gather, a re-seated restart's
        ``commit_row`` and gather; the heal is what a faulted row gets, and
        what it computed is stored, quantised, by the ``_attach`` below).
        Session state (slot, last_image) is
        mutated only AFTER the output passes the non-finite sentinel, so
        a faulted attempt leaves the session exactly where it was.  Three
        host stages (``raft.stream.cold.encode`` / ``.step`` / ``.attach``)
        hold the engine's stages of the solo calls made inside them
        (``holds=True``: the batcher nests those spans under theirs)."""
        ab = self._dev(s)
        H, W = ab
        with host_stage("raft.stream.cold.encode", _batch_stage,
                        holds=True):
            fmap_p, cnet_p = engine.run_encode(ab, s.last_image)
        init = np.zeros((1, H // 8, W // 8, 2), np.float32)
        self.metrics["fnet_misses"].inc()
        self.metrics["cold_restarts"].labels(cause).inc()
        sizes = (np.asarray([s.bucket], np.int32)
                 if self.dev_box is not None else None)
        with host_stage("raft.stream.cold.step", _batch_stage,
                        holds=True):
            flow, flow_lr, fmap_c, cnet_c, iters_used = engine.run_stream(
                ab, req.image1, fmap_p, cnet_p, init, sizes=sizes)
            finite = bool(finite_rows(1, flow, flow_lr)[0])
        if not finite:
            # non-finite OUTPUT sentinel (inputs were validated at the
            # HTTP edge): never cache poisoned maps or a poisoned seed
            if self.nonfinite is not None:
                self.nonfinite.inc()
            log = tlm_events.current()
            if log is not None:
                log.event("nonfinite_output", session=s.id, warm=False,
                          trace_id=(req.trace.trace_id
                                    if req.trace is not None else None))
            raise NonFiniteOutput(
                f"non-finite stream output for session {s.id} on a "
                f"cold step")
        with host_stage("raft.stream.cold.attach", _batch_stage,
                        holds=True):
            self._attach(s, engine, fmap_c, cnet_c, flow_lr)
        s.last_image = req.image1
        return flow, iters_used

    def _attach(self, s: Session, engine, fmap, cnet, flow_lr) -> None:
        """Install fresh maps + the next advance's warm-start seed into
        the session's slot (promoting it — LRU demotion happens inside
        the store if the pool is at capacity).  ``promote`` returning
        None (every slot pinned by an in-flight session) leaves the
        session cold: correct, just the pairwise cost next frame.  A
        FAILED commit must not fail the advance either — the flow is
        already computed and correct — but its donated buffers are dead
        (rebuilt zeroed by the engine), so the whole bucket demotes
        before anything can gather the zeros."""
        slot = self.store.promote(s)
        if slot is None:
            return
        ab = self._dev(s)
        H, W = ab
        seed = self._mask_seed(warm_start_seed(flow_lr, (H // 8, W // 8)),
                               s.bucket)
        try:
            engine.commit_row(ab, slot, fmap, cnet, seed)
            self.pool.set_extent(s.bucket, slot, s.bucket)
        except Exception:
            if self.dev_box is not None:
                self._demote_shared()
            else:
                self.store.demote_bucket(s.bucket)
