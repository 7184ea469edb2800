"""Dynamic micro-batcher: coalesce, pad, execute, deliver — and contain.

One daemon thread pulls same-bucket FIFO runs from the admission queue
(``RequestQueue.take_batch``: full batch, aged ``max_wait_ms``, or drain —
whichever first), pads the group up to the next declared batch step by
repeating the last pair (any filler works — per-sample inference is
independent; repetition keeps values finite for the instance norms), runs
the warm engine, slices real rows back out, unpads each to its request's
original resolution, and resolves the waiting handler threads.

Device batches go through the device two deep (SERVING.md "The batcher's
pipeline") — pairwise batches and coalesced groups of stream advances alike:
with batch n running the thread takes, forms, pads and places n+1; when n is
ready it dispatches n+1 first and then fetches and delivers n.  At most one
batch runs and one is staged.  While a batch runs a take waits for a FULL
bucket or for that batch to be ready; a finished batch is never held for a
batch that is not there, so a lightly loaded server's requests take the
path, and the time, they always took.

The engine is injected: an object with the phases of a device call (``place,
dispatch, ready, wait, fetch``: serving/engine.py), or a callable
``run(bucket, im1, im2) -> flow`` standing for all five, so tests can drive
the batching policy with a stub (slow / counting / failing) engine and
never touch a compile.

Failure containment (SERVING.md "Failure modes & degradation ladder"):

* **Non-finite sentinel** — every flow output is row-checked host-side;
  a NaN/Inf row fails only ITS request (HTTP 500, status ``poisoned``,
  ``raft_nonfinite_outputs_total``) while co-batched neighbors resolve.
* **Poisoned-batch bisection** — an engine exception is first retried
  (transient device errors heal under backoff), then the batch is
  split-and-retried so only the guilty request fails with
  :class:`PoisonedRequest`; innocents succeed.  Sub-groups pad to the
  declared batch steps, so bisection never compiles a new shape.  Total
  engine calls per batch are capped by a budget (~2x the group size per
  attempt), so a sick engine cannot trap the thread in retry storms.
* **Crash surface** — an exception escaping the loop itself fails any
  in-flight requests and is handed to the server's supervisor, which
  restarts the thread (``server.BatcherSupervisor``).  KeyboardInterrupt/
  SystemExit are re-raised after failing the batch, never swallowed.

Streaming steps (serving/stream.py) share this thread — ONE owner of the
device.  Session OPENS execute solo via the injected ``stream_fn`` (the
queue keys them per session id: an open runs the encode executable and
has nothing to coalesce with); ADVANCES key per bucket and coalesce
across *different* sessions exactly like pairwise work — a popped run
of them goes to ``stream_group_fn`` (the coordinator's continuous-
batched step: one device call advances the whole group, per-row
non-finite sentinel + degrade-to-cold heal inside).  A popped run is
always homogeneous: all-pairwise, all-advances (one bucket), or one
open — the keys guarantee it.  A group of advances is a job of the SAME
two-deep pipeline (:meth:`MicroBatcher._pipeline`): the coordinator presents
the phases the pair engine presents (``place, dispatch, ready, wait,
fetch``) and a ``finish`` of its own (sentinel, warm-start projections, the
commit's dispatch, the cold heals), so group n+1 is padded, placed and
dispatched before group n is fetched and finished, and the host chain of a
batched advance lies under the next one's run.  Its host stages are the
pairwise path's (``raft.batch.form`` here, ``raft.batch.pad`` and the
engine's inside the coordinator, ``raft.batch.deliver`` here).  A lone step,
a batch of the other kind and a group whose executor is one blocking call
begin when the running batch has been delivered; an open goes beside a
running group (its device calls are dispatched and never fetched) and
behind a running pairwise batch.

Thread model (SERVING.md "Threading model"): the batcher deliberately
holds **no lock of its own** — single ownership IS its synchronization.
``batches``/``served``/``timed_out`` and ``_inflight_batch`` are written
only on the loop thread (``restart()`` builds a new thread only after
the old one has died, so single-writer holds across restarts); other
threads only ever read them (serve_cli's exit line, /healthz, tests); the
same holds for ``_running`` and the pad buffers,
which is why raftlint's C1/C6 — scoped to lock-HOLDING classes — do not
apply here.  Everything shared it touches synchronizes on the owner's
lock: the queue's (take_batch), the breaker's (record), the store's
(attach/demote, inside stream_fn) — always one at a time, so the
batcher thread can never hold two locks and can never deadlock.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

from ..data.pipeline import unpad
from ..telemetry import events as tlm_events
from ..telemetry import spans as tlm_spans
from ..telemetry.trace import SENTINEL, host_stage, set_batch
from .queue import DeadlineExceeded, RequestQueue


class PoisonedRequest(RuntimeError):
    """The bisected-guilty request of a failing batch: the engine fails
    whenever this request is present, after retries (HTTP 500, error
    class ``poisoned``)."""
    trace_status = tlm_spans.POISONED


class NonFiniteOutput(RuntimeError):
    """The engine produced NaN/Inf flow for this request's row (HTTP 500,
    error class ``poisoned``) — inputs were validated at the HTTP edge
    (http.py), so a non-finite *output* is an engine-side failure."""
    trace_status = tlm_spans.POISONED


class BatcherCrashed(RuntimeError):
    """The batcher thread died while this request was in flight; the
    supervisor restarts the loop — retry the request."""
    trace_status = tlm_spans.ERROR


def _fresh_error(e: BaseException) -> BaseException:
    """Clone a group-wide failure per waiter: the HTTP layer stamps the
    request's trace id onto the exception it receives, so a SHARED
    instance would cross-wire ids between co-batched clients.  A
    constructor that rejects its own args (kwarg-only shutdown wrappers)
    falls back to the shared instance — still a correct failure;
    stamp-if-absent keeps the first trace id."""
    try:
        return type(e)(*e.args)
    except Exception:
        return e


def finite_rows(n: int, *outputs, live=None) -> np.ndarray:
    """The non-finite OUTPUT sentinel, row by row: bool ``[n]``, True where
    row ``i`` of every array of ``outputs`` (a device call's flow, and its
    ``flow_lr`` on the stream path) is finite throughout, and ``live[i]``
    where given.  Rows past ``n`` (padding) are not looked at.  One pass and
    one boolean temporary of a ROW at a time: the form the stream path
    always had.  ``_deliver``'s own, ``np.isfinite(flows[:n].reshape(n,
    -1)).all(axis=1)`` with a boolean temporary of the whole batch (33 MB at
    eight 1080p rows), read 216-269 ms a batch on the benchmark's host where
    this form read 8.8-9.0 over the same bytes (ledger, PR 42:
    ``deliver_sentinel_ms`` / ``stream_sentinel_ms``)."""
    return np.array([(live is None or bool(live[i]))
                     and all(bool(np.isfinite(out[i]).all())
                             for out in outputs) for i in range(n)], bool)


def planar_batch(bufs: list, i: int, frames: list,
                 padded: int) -> np.ndarray:
    """``frames`` ([1, H, W, C] each), and the last one again up to
    ``padded`` rows, written into the byte buffer ``bufs[i]`` (grown to the
    largest batch seen: fresh ones of a third of a GB a batch cost more in
    page faults than the copy); returns its [padded, H, W, C] view.

    The buffer is channel-planar ([n, C, H, W]): planar is how the chip
    keeps an image (W on the lanes, no padded channel), so the runtime only
    tiles what it is handed.  From an interleaved array it gathers every
    third float in chunks, and writes an event for each chunk into a
    profiler capture: 2.3 M a batch, minutes of ``stop_trace`` (PERF.md
    §5)."""
    h, w, c = frames[0].shape[1:]
    dtype = frames[0].dtype
    nbytes = padded * c * h * w * dtype.itemsize
    if bufs[i].size < nbytes:
        bufs[i] = np.empty(nbytes, np.uint8)
    buf = bufs[i][:nbytes].view(dtype).reshape(padded, c, h, w)
    for k, f in enumerate(frames):
        np.copyto(buf[k], f[0].transpose(2, 0, 1))
    buf[len(frames):] = buf[len(frames) - 1]
    return buf.transpose(0, 2, 3, 1)


class _BlockingCall:
    """A plain ``run(bucket, im1, im2[, sizes]) -> flow`` callable (or a
    stream group's ``run(group) -> outcomes``) as the phases of a device
    call: ``dispatch`` is the whole call, so nothing is ever running while
    the batcher looks for the next batch, and it walks the serial path by
    itself."""

    def __init__(self, run_fn: Callable):
        self.run_fn = run_fn

    def place(self, *args) -> list:
        return [args, None]

    def dispatch(self, call: list) -> None:
        call[1] = self.run_fn(*call[0])

    def ready(self, call: list) -> bool:
        return True

    def wait(self, call: list) -> None:
        pass

    def fetch(self, call: list):
        return call[1]

    def finish(self, call: list, alone=None):
        return call[1]


class _Job:
    """One device batch between its form and its deliver: a pairwise batch,
    or a coalesced group of stream advances.  ``engine`` has the phases of
    its device call, ``place`` and ``settle`` are the batcher's methods
    that begin and end a batch of its kind."""

    __slots__ = ("group", "n", "padded", "ordinal", "engine", "place",
                 "settle", "budget", "traced", "stages", "images", "call",
                 "out", "err", "attempts", "t_exec0", "ahead")

    def __init__(self, group, ordinal: int, engine, place, settle,
                 budget: Optional[list] = None):
        self.group, self.n = group, len(group)
        self.ordinal = ordinal            # MicroBatcher.device_batches
        self.engine, self.place, self.settle = engine, place, settle
        self.budget = budget              # engine calls left, a 1-list
        self.traced = [r for r in group if r.trace is not None]
        self.stages: list = []            # the engine's stages of its calls
        self.images = self.call = self.out = self.err = None
        self.attempts = 0
        # placed before the batch in front of it was ready
        self.ahead = False


class MicroBatcher:
    def __init__(self, queue: RequestQueue, run_fn: Callable,
                 pad_batch_to: Callable[[int], int], max_batch: int,
                 max_wait_ms: float, metrics: Optional[Dict] = None,
                 stream_fn: Optional[Callable] = None,
                 stream_group_fn: Optional[Callable] = None,
                 breaker=None, faults=None, retries: int = 1,
                 retry_backoff_s: float = 0.02, on_crash=None,
                 ragged: bool = False, ragged_batch_pixels: int = 0):
        self.queue = queue
        # the pair engine: an object with the phases of a device call
        # (place, dispatch, ready, wait, fetch: serving/engine.py), which
        # the loop overlaps two deep, or one blocking callable
        self.engine = (run_fn if hasattr(run_fn, "dispatch")
                       else _BlockingCall(run_fn))
        # ragged mixed-resolution mode (SERVING.md "Ragged serving"):
        # every pairwise request is queued under the shared max-box
        # bucket (so the FIFO coalesces across resolutions for free) and
        # run_fn is called with a 4th arg — per-row [b, 2] int32 live
        # sizes built from each request's routed bucket (Request.rbucket).
        # ragged_batch_pixels > 0 bounds one device batch's LIVE-pixel
        # footprint: a popped run is greedily chunked so co-batched live
        # pixels never exceed the budget (a 1080p row can't starve a
        # group of thumbnails); 0 = unbounded.
        self.ragged = ragged
        self.ragged_batch_pixels = ragged_batch_pixels
        # streaming steps (serving/stream.py) ride the same queue and the
        # same device-owning thread: stream_fn takes ONE StreamRequest
        # (session open / solo fallback) and returns (padded flow or
        # None, iters_used or None); stream_group_fn advances a coalesced
        # LIST of same-bucket sessions to per-row (flow, iters_used, err)
        # tuples (the continuous-batched path)
        self.stream_fn = stream_fn
        # the group executor, like the pair engine: an object with the
        # phases of a group's batched call and its ``finish``
        # (stream.StreamCoordinator's, behind server._stream_engine), which
        # the loop overlaps two deep, or one blocking callable
        self.stream_engine = (
            stream_group_fn if stream_group_fn is None
            or hasattr(stream_group_fn, "dispatch")
            else _BlockingCall(stream_group_fn))
        self.pad_batch_to = pad_batch_to
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1000.0
        self.metrics = metrics or {}
        self.breaker = breaker            # CircuitBreaker or None
        self.faults = faults              # FaultInjector or None (chaos)
        self.retries = retries            # same-group retries before bisect
        self.retry_backoff_s = retry_backoff_s
        self.on_crash = on_crash          # supervisor hook: (exception) ->
        self.batches = 0
        self.device_batches = 0           # ordinal of the last device batch
        # the stage families' recorder (trace.StageCounters), or None
        self._stages = self.metrics.get("stages")
        self.served = 0
        self.timed_out = 0
        self._inflight_batch = None       # the popped-but-unresolved batch
        self._running: Optional[_Job] = None   # dispatched, not fetched
        # the padded batch's two host buffers, grown to the largest batch
        # seen and viewed at each batch's shape (made on first use)
        self._pad_bytes = [np.empty(0, np.uint8), np.empty(0, np.uint8)]
        self._thread = self._new_thread()

    def _new_thread(self) -> threading.Thread:
        return threading.Thread(target=self._thread_main, daemon=True,
                                name="raft-serving-batcher")

    def start(self) -> None:
        self._thread.start()

    def restart(self) -> None:
        """Supervisor hook: bring up a fresh loop thread after a crash."""
        self._thread = self._new_thread()
        self._thread.start()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def _observe(self, name: str, *args) -> None:
        m = self.metrics.get(name)
        if m is None:
            return
        if args and hasattr(m, "observe"):
            m.observe(args[0])
        elif hasattr(m, "labels") and len(args) == 2:
            m.labels(args[0]).inc(args[1])
        elif hasattr(m, "inc"):
            m.inc(*args)

    def _stage_done(self, st) -> None:
        """Sink of the batcher thread's own host stages: the stage's wall
        and CPU seconds."""
        if self._stages is not None:
            self._stages.record(st.label, st.wall, st.cpu)

    def _next_device_batch(self) -> None:
        """A device batch begins: its ordinal rides on every host stage this
        thread opens from here on (``batch=<n>`` of the annotations), and the
        slot for the engine's stages is opened."""
        self.device_batches += 1
        set_batch(self.device_batches)
        tlm_spans.set_device_slot([])

    def _device_call(self, real: int, padded: int) -> None:
        """One device call of ``padded`` rows, ``real`` of them requests."""
        self._observe("device_calls")
        self._observe("device_rows", "real", real)
        self._observe("device_rows", "padded", padded)

    def _take_device_stages(self) -> list:
        """The engine's stages of this batch's device calls (h2d, dispatch,
        wait, fetch), counted into the stage seconds once; the callers turn
        them into child spans of ``execute`` on each traced request."""
        calls = tlm_spans.take_device_slot() or []
        if self._stages is not None:
            for _kind, _span, label, t0, t1, cpu, _holds in calls:
                self._stages.record(label, t1 - t0, cpu)
        return calls

    def _count_stages(self, job: "_Job") -> None:
        """:meth:`_take_device_stages` for a pairwise batch, whose stages
        have gathered in its own slot while another batch's interleaved."""
        tlm_spans.set_device_slot(job.stages)
        self._take_device_stages()

    @staticmethod
    def _device_spans(tr, calls, parent: str) -> None:
        """``calls`` as child spans of ``parent``.  A stage that ``holds``
        (``trace.host_stage``) is a container: the stages that ran inside
        its time are children of ITS span, so every level of the tree adds
        up to the span above it; the flat ``timings_ms`` view leaves them
        out (``held_by``, the holding span's name)."""
        holders = [(t0, t1, span, tr.span(span, t0, t1, parent=parent,
                                          call=kind, cpu=cpu))
                   for kind, span, _label, t0, t1, cpu, holds in calls
                   if holds]
        for kind, span, _label, t0, t1, cpu, holds in calls:
            if holds:
                continue
            name, sid = next(((n, sid) for a, b, n, sid in holders
                              if a <= t0 and t1 <= b), (None, None))
            held = {} if sid is None else {"held_by": name}
            tr.span(span, t0, t1, parent=sid or parent, call=kind, cpu=cpu,
                    **held)

    def _observe_waste(self, group, padded: int) -> None:
        """raft_batch_padding_waste_ratio: the fraction of one device
        batch's pixels that is padding — batch-fill rows plus, under
        --ragged, each row's dead embedding beyond its routed resolution
        (``pads`` are relative to the device box in both modes, so live
        pixels fall straight out of them).  Dense same-bucket batches
        report only the batch-fill share; the ragged sweep compares the
        two."""
        bh, bw = group[0].bucket[-2:]
        box = float(bh * bw)
        live = sum((bh - p[0] - p[1]) * (bw - p[2] - p[3])
                   for p in (r.pads for r in group))
        self._observe("padding_waste", (padded * box - live) / (padded * box))

    def _chunks(self, batch):
        """Split a popped run so one chunk's live-pixel footprint stays
        under ``ragged_batch_pixels`` (never splitting below one row);
        identity in dense mode or with the budget unset."""
        if not self.ragged or self.ragged_batch_pixels <= 0 \
                or len(batch) < 2:
            return [batch]
        out, cur, acc = [], [], 0
        for r in batch:
            h, w = r.rbucket
            px = h * w
            if cur and acc + px > self.ragged_batch_pixels:
                out.append(cur)
                cur, acc = [], 0
            cur.append(r)
            acc += px
        if cur:
            out.append(cur)
        return out

    def _fail_expired(self, expired) -> None:
        now = time.monotonic()
        for r in expired:
            self.timed_out += 1
            self._observe("requests", "timeout", 1)
            if r.trace is not None:
                # the whole life of an expired request WAS queue wait
                r.trace.span("queue_wait", r.enqueued_at, now,
                             status=tlm_spans.TIMEOUT)
            r.fail(DeadlineExceeded(
                f"deadline exceeded after "
                f"{time.monotonic() - r.enqueued_at:.3f}s in queue"))

    def _execute_stream(self, r) -> None:
        """One SOLO sessionful step — session opens (keyed per session:
        nothing to coalesce with) and the no-group-executor fallback.
        It observes the stream-step families at its real width (batch 1,
        occupancy 1.0); coalesced advances are jobs of the pipeline
        (:meth:`_form_stream`, :meth:`_settle_stream`), which also fold into
        the shared batch-size/occupancy histograms."""
        if self.stream_fn is None:
            r.fail(RuntimeError("stream request on a batcher without a "
                                "stream executor"))
            return
        if r.abandoned:
            # the handler gave up waiting (already counted status=timeout)
            # and released the session lock: executing now would mutate
            # session state a retry may be racing — drop the step instead
            r.fail(DeadlineExceeded(
                f"stream step {r.id} abandoned by its handler"))
            return
        tr = r.trace
        self._next_device_batch()
        with host_stage("raft.batch.form", self._stage_done) as form:
            self._device_call(1, 1)
        if tr is not None:
            tr.span("queue_wait", r.enqueued_at, r.dequeued_at)
            tr.span(form.span, r.dequeued_at, form.t1, group=1, cpu=form.cpu)
        t0 = form.t1
        err, flow, iters_used = None, None, None
        try:
            flow, iters_used = self.stream_fn(r)
        except BaseException as e:
            # the stream executor already retried cold internally; a step
            # failing here is terminal for this frame.  Never swallow a
            # shutdown signal: fail the request, then let KeyboardInterrupt
            # / SystemExit keep propagating.
            err = e
        calls = self._take_device_stages()
        t1 = time.monotonic()
        self._observe("stream_steps")
        self._observe("stream_step_seconds", t1 - t0)
        self._observe("stream_step_batch", 1.0)
        self._observe("stream_step_occupancy", 1.0)
        with host_stage("raft.batch.deliver", self._stage_done) as st:
            if tr is not None:
                # spans BEFORE resolve/fail: the handler wakes on either and
                # finishes the trace — a late span would hit a closed trace
                eid = tr.span("execute", t0, t1,
                              status=(tlm_spans.OK if err is None
                                      else tlm_spans.status_of(err)),
                              batch_real=1, batch_padded=1)
                self._device_spans(tr, calls, eid)
            if err is not None:
                if self.breaker is not None:
                    self.breaker.record(False)
                self._observe("requests", "error", 1)
                r.fail(err)
                if not isinstance(err, Exception):
                    raise err
                return
            if self.breaker is not None:
                self.breaker.record(True)
            r.batch_real = r.batch_padded = 1
            if iters_used is not None:
                r.iters_used = int(np.asarray(iters_used).reshape(-1)[0])
                self._observe("iters_used", float(r.iters_used))
            if flow is not None:             # (a session open has no pair)
                self._observe("pairs", 1.0)
                flow = unpad(flow[:1], r.pads)[0]
            now = time.monotonic()
            self._observe("request_latency", now - r.enqueued_at)
            self._observe("requests", "ok", 1)
            self.served += 1
            if tr is not None:
                tr.span(st.span, t1, now, row=0,
                        cpu=time.thread_time() - st.c0)
            r.resolve(flow)

    # -- continuous-batched stream advances --------------------------------

    def _form_stream(self, batch) -> Optional["_Job"]:
        """A coalesced run of same-bucket stream ADVANCES becomes a device
        batch: one batched device call for the whole group (serving/stream.py
        — per-row sentinel and degrade-to-cold heal in its ``finish``),
        walked through :meth:`_pipeline` as a pairwise batch is."""
        group = []
        for r in batch:
            if r.abandoned:
                # the handler gave up waiting (already counted
                # status=timeout) and released the session lock:
                # executing now would mutate session state a retry may
                # be racing — drop the row, keep its batch-mates
                r.fail(DeadlineExceeded(
                    f"stream step {r.id} abandoned by its handler"))
                continue
            group.append(r)
        if not group:
            return None
        self.device_batches += 1
        job = _Job(group, self.device_batches, self.stream_engine,
                   self._place_stream, self._settle_stream)
        job.padded = self.pad_batch_to(min(job.n, self.max_batch))
        self._work_on(job)
        with host_stage("raft.batch.form", self._stage_done) as form:
            self._observe_waste(group, job.padded)
            self._device_call(job.n, job.padded)
        for r in job.traced:
            r.trace.span("queue_wait", r.enqueued_at, r.dequeued_at)
            r.trace.span(form.span, r.dequeued_at, form.t1, group=job.n,
                         cpu=form.cpu)
        job.t_exec0 = form.t1
        return job

    def _place_stream(self, job: "_Job") -> None:
        """The group's frames go to the device (its pad stage is the
        coordinator's, inside the place)."""
        job.call = self._phase(job, job.engine.place, job.group)

    def _settle_stream(self, job: "_Job", beside: Optional["_Job"] = None
                       ) -> None:
        """The end of a group of advances: its ``finish`` (sentinel,
        warm-start projections, the commit's dispatch, the cold heals — with
        the device to themselves: ``beside``, dispatched behind this group,
        has run first), then per-row resolve/fail.  A row is resolved only
        after its commit was dispatched, so its session's next frame can
        only be gathered after it.  Folds into the SAME batch-size/occupancy
        histograms as pairwise batches and reports the
        ``raft_stream_step_*`` families at the group's real width."""
        group, n, padded = job.group, job.n, job.padded

        def alone():
            if beside is not None:
                self._wait(beside)
                self._work_on(job)

        # the group executor contains per-row failures itself; an exception
        # escaping it is a crash — fail every row (fresh same-type instance
        # each: the HTTP layer stamps per-request trace ids)
        outcomes = self._phase(job, job.engine.finish, job.call, alone)
        err = job.err
        self._work_on(job)
        self._count_stages(job)
        t0, t1 = job.t_exec0, time.monotonic()
        self._observe("stream_step_seconds", t1 - t0)
        if err is None:
            # honest device-step accounting: only rows whose result came
            # from the batched call report its width (r.batched, set by the
            # coordinator: warm rows and rows restarted at the place); rows
            # healed solo report width-1 steps — raft_stream_step_batch and
            # the shared batch histograms can never claim coalescing the
            # device didn't actually do
            batched_rows = sum(1 for r in group if r.batched)
            solo_rows = n - batched_rows
            if batched_rows:
                self._observe("stream_steps")
                self._observe("stream_step_batch", float(batched_rows))
                self._observe("stream_step_occupancy", batched_rows / padded)
                self._observe("batch_size", float(batched_rows))
                self._observe("batch_occupancy", batched_rows / padded)
            if solo_rows:
                self._observe("stream_steps", solo_rows)
                for _ in range(solo_rows):
                    self._observe("stream_step_batch", 1.0)
                    self._observe("stream_step_occupancy", 1.0)
        exec_sid = tlm_spans.new_span_id()

        def _exec_span(tr, status):
            tr.span("execute", t0, t1, status=status, span_id=exec_sid,
                    batch_real=n, batch_padded=padded)
            self._device_spans(tr, job.stages, exec_sid)

        if err is not None:
            for r in group:
                if r.trace is not None:
                    _exec_span(r.trace, tlm_spans.status_of(err))
                self._observe("requests", "error", 1)
                r.fail(_fresh_error(err))
            return
        with host_stage("raft.batch.deliver", self._stage_done) as st:
            served = 0
            for i, (r, (flow, iters_used, rerr)) in enumerate(
                    zip(group, outcomes)):
                r.batch_real, r.batch_padded = n, padded
                if rerr is not None:
                    self._observe("request_latency",
                                  time.monotonic() - r.enqueued_at)
                    if r.trace is not None:
                        _exec_span(r.trace, tlm_spans.status_of(rerr))
                    if not isinstance(rerr, DeadlineExceeded):
                        # (an abandoned row's handler counted its timeout)
                        self._observe(
                            "requests",
                            "poisoned" if getattr(rerr, "trace_status", None)
                            == tlm_spans.POISONED else "error", 1)
                    r.fail(rerr)
                    continue
                if iters_used is not None:
                    r.iters_used = int(iters_used)
                    self._observe("iters_used", float(r.iters_used))
                flow = unpad(flow[:1], r.pads)[0]
                now = time.monotonic()
                self._observe("request_latency", now - r.enqueued_at)
                if r.trace is not None:
                    # spans BEFORE resolve, and a row's deliver from the end
                    # of execute to its OWN resolve, as _deliver has them
                    _exec_span(r.trace, tlm_spans.OK)
                    r.trace.span(st.span, t1, now, row=i,
                                 cpu=time.thread_time() - st.c0)
                self._observe("requests", "ok", 1)
                self.served += 1
                served += 1
                r.resolve(flow)
            if served:
                self._observe("pairs", float(served))

    # -- pairwise execution: retry -> bisect -> sentinel -------------------

    def _bisect_budget(self, n: int) -> int:
        """Engine-call cap for one batch's recovery: a full binary
        bisection of an all-poisoned group of n costs 2n-1 calls; allow
        that at every retry attempt, nothing more."""
        return (self.retries + 1) * 2 * n

    def _execute(self, batch) -> None:
        op = getattr(batch[0], "stream_op", None)
        if op == "advance" and self.stream_engine is not None:
            if isinstance(self.stream_engine, _BlockingCall):
                # one blocking call, commit and heals inside: it begins
                # when the running batch has been delivered
                self._finish_running()
            job = self._form_stream(batch)
            if job is not None:
                self._pipeline(job)
            return
        if op is not None:
            running = self._running
            if op != "open" or running is None \
                    or running.engine is self.engine:
                # a lone step (no group executor), and an open behind a
                # pairwise batch, come after everything dequeued before them
                self._finish_running()
            # (an open behind a running GROUP goes beside it: its encoder
            # pass and its row's commit are dispatched and never fetched, so
            # they queue on the device behind the group, whose rows are other
            # sessions' — no bubble a renewed session)
            for r in batch:
                self._execute_stream(r)
            return
        with host_stage("raft.batch.form", self._stage_done):
            for r in batch:
                if r.trace is not None:
                    r.trace.span("queue_wait", r.enqueued_at, r.dequeued_at)
            groups = self._chunks(batch)
        for group in groups:
            self._pipeline(
                self._form(group, [self._bisect_budget(len(group))]))

    def _pipeline(self, job: "_Job") -> None:
        """The good path of a device batch — a pairwise group or a group of
        stream advances — two deep: place it while the batch before it runs,
        dispatch it the moment that one is ready, and only then fetch and
        settle that one.  A call that failed is recovered (the job's
        ``settle``) with the device to itself: the batch before it before
        this one is dispatched, this one after the batch before it is
        delivered.  A batch of the other kind begins when the running batch
        has been delivered: nothing mixes the two kinds closely enough to be
        worth an overlap."""
        running = self._running
        if running is not None and running.engine is not job.engine:
            self._finish_running()
            running = None
        job.place(job)
        if running is not None:
            job.ahead = job.err is None \
                and not running.engine.ready(running.call)
            self._wait(running)
            if running.err is not None:
                running.settle(running)
                self._running = running = None
        self._dispatch(job)
        if running is not None:
            self._fetch(running)
            running.settle(running, job)
        self._running = job if job.err is None else None
        if job.err is not None:
            job.settle(job)

    def _finish_running(self) -> None:
        """Wait for the running batch, if there is one, and deliver it."""
        running = self._running
        if running is not None:
            self._wait(running)
            self._fetch(running)
            running.settle(running)
            self._running = None

    def _run_group(self, group, budget) -> None:
        """One half of a bisected group from pad to deliver, nothing beside
        it.  ``budget`` is the batch-wide engine-call allowance (mutable
        1-list)."""
        job = self._form(group, budget, formed=True)
        self._call(job)
        self._settle(job)

    def _form(self, group, budget, formed: bool = False) -> "_Job":
        """A device batch begins: its ordinal, its form and pad stages.
        ``formed`` marks a bisection's sub-group (batch size and the
        batch_form span are recorded once, on the original group)."""
        self.device_batches += 1
        job = _Job(group, self.device_batches, self.engine, self._place,
                   self._settle, budget)
        job.padded = self.pad_batch_to(min(job.n, self.max_batch))
        self._work_on(job)
        with host_stage("raft.batch.form", self._stage_done) as form:
            if not formed:
                self._observe("batch_size", float(job.n))
                self._observe("batch_occupancy", job.n / job.padded)
                self._observe_waste(group, job.padded)
        pad = self._pad(job)
        # the spans after both stages, so that nothing lies between them: a
        # request's batch_form runs from ITS dequeue to the form stage's end
        # (everything between take and pad: the loop's bookkeeping and
        # _execute's are under the same annotation), and its execute begins
        # where its pad ends
        for r in job.traced:
            if not formed:
                r.trace.span(form.span, r.dequeued_at, form.t1, group=job.n,
                             cpu=form.cpu)
            r.trace.span(pad.span, pad.t0, pad.t1, padded=job.padded,
                         cpu=pad.cpu)
        job.t_exec0 = pad.t1
        return job

    def _pad(self, job: "_Job"):
        """Write the group's pairs, and the last one again up to the batch
        step, into the two buffers this batcher keeps
        (:func:`planar_batch`).  They hold a batch from here until its
        ``place`` has returned."""
        with host_stage("raft.batch.pad", self._stage_done) as st:
            job.images = [
                planar_batch(self._pad_bytes, i,
                             [getattr(r, attr) for r in job.group],
                             job.padded)
                for i, attr in enumerate(("image1", "image2"))]
        return st

    def _work_on(self, job: "_Job") -> None:
        """This thread's stages are ``job``'s from here on: its ordinal
        rides on every host stage opened (``batch=<n>`` of the
        annotations), and the engine's stages land in its slot."""
        set_batch(job.ordinal)
        tlm_spans.set_device_slot(job.stages)

    def _phase(self, job: "_Job", fn, *args):
        """One phase of ``job``'s device call, skipped once the call has
        failed.  An exception fails the call, not the thread."""
        if job.err is not None:
            return None
        self._work_on(job)
        try:
            return fn(*args)
        except Exception as e:
            # transient device errors heal under a short backoff;
            # persistent ones fall through to bisection (_settle)
            if self.breaker is not None:
                self.breaker.record(False)
            job.err = e
        except BaseException as e:
            # shutdown (KeyboardInterrupt/SystemExit): fail the group
            # so no handler hangs, then keep propagating — swallowing
            # it here would eat Ctrl-C.  Same type per waiter, but a
            # FRESH instance each (_fresh_error)
            t_x = time.monotonic()
            self._count_stages(job)
            sid = tlm_spans.new_span_id()
            for r in job.group:
                if r.trace is not None:
                    r.trace.span("execute", job.t_exec0, t_x,
                                 status=tlm_spans.ERROR, span_id=sid,
                                 batch_real=job.n, batch_padded=job.padded)
                self._observe("requests", "error", 1)
                r.fail(_fresh_error(e))
            raise
        return None

    def _place(self, job: "_Job") -> None:
        """An attempt begins: the padded pair goes to the device."""
        if job.budget[0] <= 0:
            job.err = RuntimeError("bisection budget exhausted before this "
                                   "sub-group could execute")
            return
        job.attempts += 1
        job.budget[0] -= 1
        self._device_call(job.n, job.padded)
        args = (job.group[0].bucket, *job.images)
        if self.ragged:
            # per-row live sizes from each request's routed bucket;
            # filler rows repeat the last request's, to match its
            # repeated pixels
            rb = ([r.rbucket for r in job.group]
                  + [job.group[-1].rbucket] * (job.padded - job.n))
            args += (np.asarray(rb, np.int32),)
        job.call = self._phase(job, job.engine.place, *args)

    def _dispatch(self, job: "_Job") -> None:
        self._phase(job, job.engine.dispatch, job.call)
        if job.err is None:
            self._observe("batches_staged",
                          "ahead" if job.ahead else "late", 1)

    def _wait(self, job: "_Job") -> None:
        self._phase(job, job.engine.wait, job.call)

    def _fetch(self, job: "_Job") -> None:
        job.out = self._phase(job, job.engine.fetch, job.call)

    def _call(self, job: "_Job") -> None:
        """One attempt with nothing beside it: every phase in turn."""
        job.place(job)
        self._dispatch(job)
        self._wait(job)
        self._fetch(job)

    def _settle(self, job: "_Job", beside: Optional["_Job"] = None) -> None:
        """The end of a pairwise batch: deliver its rows — after the retries
        of a failed call, and the bisection of one that keeps failing, so
        that only the guilty request(s) fail; ``beside``, dispatched behind
        this batch, has run before any of that begins."""
        group, n, padded, budget = job.group, job.n, job.padded, job.budget
        if job.err is not None and beside is not None:
            self._wait(beside)
        while (job.err is not None and 0 < job.attempts <= self.retries
               and budget[0] > 0):
            time.sleep(self.retry_backoff_s)
            job.err, job.ahead = None, False
            self._work_on(job)
            self._pad(job)            # the buffers have held a batch since
            self._call(job)
        err, attempts = job.err, job.attempts
        self._work_on(job)
        self._count_stages(job)
        t_exec1 = time.monotonic()
        # co-batched requests SHARE one execute span id (the join key
        # across their traces); each trace holds its own copy with its
        # own queue spans around it
        exec_sid = tlm_spans.new_span_id()

        def _exec_span(tr, status):
            tr.span("execute", job.t_exec0, t_exec1, status=status,
                    span_id=exec_sid, batch_real=n, batch_padded=padded,
                    attempts=attempts)
            self._device_spans(tr, job.stages, exec_sid)

        if err is not None:
            if n == 1 and attempts:
                # bisected down to the guilty request: the 'poisoned'
                # error class — co-batched neighbors already succeeded
                if group[0].trace is not None:
                    _exec_span(group[0].trace, tlm_spans.POISONED)
                self._observe("requests", "poisoned", 1)
                group[0].fail(PoisonedRequest(
                    f"request {group[0].id} poisons its batch: engine "
                    f"failed after {attempts} attempt(s): {err}"))
                return
            if budget[0] <= 0:
                # retry budget exhausted mid-bisection: the engine is
                # sick, not one request — fail the remainder as plain
                # errors (the breaker is already counting these).  Each
                # request gets its OWN exception instance: the HTTP
                # layer stamps the request's trace id onto it, and a
                # shared instance would cross-wire ids between
                # co-batched clients
                for r in group:
                    if r.trace is not None:
                        _exec_span(r.trace, tlm_spans.ERROR)
                    self._observe("requests", "error", 1)
                    r.fail(RuntimeError(
                        f"engine failing across requests (retry budget "
                        f"exhausted): {err}"))
                return
            # the failed attempt stays visible in every trace (status
            # "retry"); the sub-groups record their own execute spans
            for r in job.traced:
                _exec_span(r.trace, "retry")
            mid = n // 2
            self._run_group(group[:mid], budget)
            self._run_group(group[mid:], budget)
            return
        if self.breaker is not None:
            self.breaker.record(True)
        with host_stage("raft.batch.deliver", self._stage_done) as st:
            served = self._deliver(group, job.out, padded, t_exec1, st,
                                   _exec_span)
            if served:
                self._observe("pairs", float(served))

    def _deliver(self, group, out, padded: int, t_exec1: float,
                 st, exec_span) -> int:
        """Sentinel, unpad and resolve the rows of a finished device batch,
        one after another, inside the deliver stage ``st``; returns how many
        were served.  A request's ``deliver`` span runs from the end of
        ``execute`` to its OWN resolve: the rows before it are part of what
        it waited for."""
        n = len(group)
        # converge-policy engines return (flows, per-row iters_used); only
        # REAL rows are accounted — padding rows repeat the last request
        # and would skew the raft_iters_used distribution
        iters_used = None
        flows = out
        if isinstance(flows, tuple):
            flows, iters_used = flows
        # non-finite OUTPUT sentinel: inputs were validated at the HTTP
        # edge, so a NaN/Inf row here is the engine's failure — fail that
        # row alone, its neighbors are fine (per-sample independence).  One
        # pass over the batch's flow, counted under a label of its own
        # INSIDE batch.deliver (no annotation: trace.SENTINEL)
        t0, c0 = time.monotonic(), time.thread_time()
        flows = np.asarray(flows)
        row_ok = finite_rows(n, flows)
        if self._stages is not None:
            self._stages.record(SENTINEL, time.monotonic() - t0,
                                time.thread_time() - c0)
        served = 0
        for i, r in enumerate(group):
            r.batch_real, r.batch_padded = n, padded
            if iters_used is not None:
                r.iters_used = int(iters_used[i])
                self._observe("iters_used", float(iters_used[i]))
            if row_ok[i]:
                flow = unpad(flows[i:i + 1], r.pads)[0]
                now = time.monotonic()
                self._observe("request_latency", now - r.enqueued_at)
                if r.trace is not None:
                    # spans BEFORE resolve: the handler wakes on it and
                    # finishes the trace — a late span would hit it closed
                    exec_span(r.trace, tlm_spans.OK)
                    r.trace.span(st.span, t_exec1, now, row=i,
                                 cpu=time.thread_time() - st.c0)
                self._observe("requests", "ok", 1)
                self.served += 1
                served += 1
                r.resolve(flow)
            else:
                self._observe("request_latency",
                              time.monotonic() - r.enqueued_at)
                if r.trace is not None:
                    exec_span(r.trace, tlm_spans.POISONED)
                self._observe("nonfinite")
                self._observe("requests", "poisoned", 1)
                log = tlm_events.current()
                if log is not None:
                    # joinable to the request trace (chaos drills): the
                    # sentinel's run-log record carries the trace id
                    log.event("nonfinite_output", request=r.id,
                              trace_id=(r.trace.trace_id
                                        if r.trace is not None else None))
                r.fail(NonFiniteOutput(
                    f"non-finite flow output for request {r.id} "
                    f"(poisoned row in an otherwise-healthy batch)"))
        return served

    # -- the loop + its crash surface --------------------------------------

    def _loop(self) -> None:
        while True:
            running = self._running
            busy = None
            if running is not None:
                # a part batch waits for its mates while the device is busy
                busy = lambda: not running.engine.ready(running.call)  # noqa: E731
            set_batch(self.device_batches + 1)    # the batch being waited for
            with host_stage("raft.batch.take", self._stage_done):
                batch, expired = self.queue.take_batch(
                    self.max_batch, self.max_wait, busy)
            if not batch:
                # nothing to stage: a finished batch is never held for a
                # batch that is not there.  (None: the queue is closed and
                # empty; []: deadlines passed, or the running batch is ready)
                self._fail_expired(expired)
                if batch is None or (running is not None and not busy()):
                    self._finish_running()
                if batch is None:
                    return
                continue
            with host_stage("raft.batch.form", self._stage_done):
                self._fail_expired(expired)
                self.batches += 1
                # cleared only on the success path: an exception
                # escaping here must leave the batch visible to
                # _thread_main's crash handler (it fails whatever is
                # not yet done, of this batch and of the running one)
                self._inflight_batch = batch
                # ambient trace ids for this batch: out-of-band
                # diagnostics fired from under here (fault_injected,
                # lock_violation, the non-finite sentinel) become
                # joinable to the request traces they hit
                tlm_spans.set_current_trace_ids(tuple(
                    r.trace.trace_id for r in batch
                    if r.trace is not None))
            try:
                if self.faults is not None:
                    self.faults.maybe_kill()   # chaos: thread-death arm
                self._execute(batch)
            finally:
                tlm_spans.set_current_trace_ids(())
            self._inflight_batch = None

    def _thread_main(self) -> None:
        try:
            self._loop()
        except BaseException as e:
            # the crash surface: fail whatever was popped but unresolved
            # (handler threads must never hang on a dead batcher), then
            # hand an Exception to the supervisor for restart; shutdown
            # signals propagate — threading's excepthook reports them
            running = self._running.group if self._running else []
            for r in running + (self._inflight_batch or []):
                if not r.done:
                    self._observe("requests", "error", 1)
                    if r.trace is not None:
                        # finish (idempotent) BEFORE failing: the
                        # supervisor dumps the flight recorder on this
                        # thread right after, and the crashed trace must
                        # already be in the ring — the woken handler's
                        # own finish becomes a no-op
                        r.trace.finish(tlm_spans.ERROR)
                    r.fail(BatcherCrashed(
                        f"batcher thread died mid-batch ({e!r}); "
                        f"the supervisor restarts it — retry"))
            self._inflight_batch = self._running = None
            if self.on_crash is not None and isinstance(e, Exception):
                self.on_crash(e)
            else:
                raise
