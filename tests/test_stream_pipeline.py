"""Batched stream advances in the batcher's two-deep pipeline (tier-1, CPU).

A coalesced group of advances is a job of ``MicroBatcher._pipeline`` as a
pairwise batch is: group n+1 is padded, placed and dispatched before group
n's flow is fetched, checked, projected and committed.  The stub cases drive
a real ``FlowServer`` / ``StreamCoordinator`` / ``SessionStore`` over a
recording fake of the engine's stream calls (no device, no compile: a call
"runs" until the test finishes it), so the order of the phases, the failure
ladder with a batch in flight and the drains are deterministic; the live
cases run the real engine at the tiny size."""

import threading
import time
import types
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from raft_tpu.serving import BatcherCrashed, FlowServer, ServeConfig

from test_serving import BUCKET, PhasedEngine

H, W = BUCKET


def _frame(v):
    return np.full((H, W, 3), v, np.float32)


class SlotEngine(PhasedEngine):
    """PhasedEngine's pair phases plus the engine's stream calls over the
    store's real ``SlotPool``.  The pool's "buffers" are ``(version, {slot:
    value})``: a commit installs a new pair, a dispatch gathers from the
    pair it finds.  A frame's value is its first pixel; a row's flow is
    ``(value of its frame, value its slot held at the gather)`` everywhere,
    so an answer says which scatter its gather saw.  Calls count in the
    order of their places, pair and stream together; ``hold`` calls run
    until ``finish``ed."""

    def __init__(self, hold=(), run_s=0.0, fail_dispatch=(), fail_wait=(),
                 nan_rows=(), fail_commits=(), fail_encode=()):
        super().__init__(run_s, hold)
        self.pool = None                      # the store's (make_server)
        self.fail_encode = set(fail_encode)   # frame values, once each
        self.fail_commit_rows = 0             # the next that many fail
        self.fail_dispatch = set(fail_dispatch)
        self.fail_wait = set(fail_wait)
        self.nan_rows = set(nan_rows)         # (call, row)
        self.fail_commits = set(fail_commits)  # ordinals of commit_stream
        self.commits = []                     # (call, slots written) each
        self.version = 0

    def _bufs(self, bucket):
        if self.pool.buffers(bucket) is None:
            self.pool.install(bucket, (0, {}))
        return self.pool.buffers(bucket)

    def _install(self, bucket, rows):
        self.version += 1
        self.pool.install(bucket, (self.version,
                                   {**self._bufs(bucket)[1], **rows}))

    # -- the batched advance, one phase at a time --------------------------

    def place_stream_batch(self, bucket, images, sizes=None):
        call = types.SimpleNamespace(
            i=len(self.calls), bucket=bucket, shape=images.shape,
            vals=images[:, 0, 0, 0].copy(), done=threading.Event(),
            poisoned=len(self.calls) in self.fail_wait)
        self.calls.append((bucket, images.shape[0]))
        self.issued.append(call)
        self._note("h2d", call.i)
        return call

    def dispatch_stream_batch(self, call, slots, active):
        if call.i in self.fail_dispatch:
            raise RuntimeError("the device refused the dispatch")
        call.slots, call.active = np.array(slots), np.array(active)
        call.version, state = self._bufs(call.bucket)
        # (a slot nobody committed reads 0: a rebuilt pool's finite garbage)
        call.prev = np.array([state.get(int(s), 0.0) for s in slots])
        self.dispatch(call)

    def fetch_stream_batch(self, call):
        self._note("fetch", call.i)
        b, h, w, _ = call.shape
        flow = np.zeros((b, h, w, 2), np.float32)
        flow[..., 0] = call.vals[:, None, None]
        flow[..., 1] = call.prev[:, None, None]
        for c, row in self.nan_rows:
            if c == call.i:
                flow[row, 0, 0, 0] = np.nan
        flow_lr = np.zeros((b, h // 8, w // 8, 2), np.float32)
        return flow, flow_lr, ("rows", call.i, call.vals), None, None

    def run_stream_batch(self, bucket, images, slots, active, sizes=None):
        call = self.place_stream_batch(bucket, images, sizes)
        self.dispatch_stream_batch(call, slots, active)
        self.wait(call)
        return self.fetch_stream_batch(call)

    run_stream_batch.composes_phases = True

    def commit_stream(self, bucket, slots, fmap_rows, cnet_rows, seeds,
                      mask):
        _, i, vals = fmap_rows
        if len(self.commits) in self.fail_commits:
            self.commits.append((i, None))
            self.version += 1
            self.pool.install(bucket, (self.version, {}))   # rebuilt zeroed
            self._note("commit_failed", i)
            raise RuntimeError("the commit scatter failed")
        rows = {int(s): float(v) for s, v, m in zip(slots, vals, mask) if m}
        self._install(bucket, rows)
        self.commits.append((i, sorted(rows)))
        self._note("commit", i)

    # -- opens and cold restarts -------------------------------------------

    def run_encode(self, bucket, image):
        v = float(image[0, 0, 0, 0])
        if v in self.fail_encode:
            self.fail_encode.discard(v)
            self._note("encode_failed", v)
            raise RuntimeError("the device refused the encoder pass")
        self._note("encode", v)
        return ("fmap", v), ("cnet", v)

    def run_stream(self, bucket, image, fmap_prev, cnet_prev, init,
                   sizes=None):
        v = float(image[0, 0, 0, 0])
        self._note("cold", v)
        _, h, w, _ = image.shape
        flow = np.zeros((1, h, w, 2), np.float32)
        flow[..., 0], flow[..., 1] = v, fmap_prev[1]
        return (flow, np.zeros((1, h // 8, w // 8, 2), np.float32),
                ("fmap", v), ("cnet", v), None)

    def commit_row(self, bucket, slot, fmap, cnet, seed):
        if self.fail_commit_rows:
            self.fail_commit_rows -= 1
            self.version += 1
            self.pool.install(bucket, (self.version, {}))   # rebuilt zeroed
            self._note("commit_row_failed", int(slot))
            raise RuntimeError("the width-1 commit failed")
        self._install(bucket, {int(slot): fmap[1]})
        self._note("commit_row", int(slot))

    # -- a pair call, as the server's _pair_engine wants it ----------------

    def run(self, bucket, im1, im2):
        call = self.place(bucket, im1, im2)
        self.dispatch(call)
        self.wait(call)
        return self.fetch(call)

    run.composes_phases = True

    def order(self, *phases):
        """The (phase, call) record, of ``phases`` alone."""
        with self.cv:
            return [e[:2] for e in self.log if e[0] in phases]

    def saw_some(self, phase, timeout=10.0):
        """Block until ``phase`` of any call is in the record."""
        with self.cv:
            assert self.cv.wait_for(
                lambda: any(e[0] == phase for e in self.log), timeout), phase

    def at(self, phase, i):
        """Position of ``phase`` of call ``i`` in the record."""
        with self.cv:
            return next(k for k, e in enumerate(self.log)
                        if e[:2] == (phase, i))


class Sessions:
    """A stub-engine server with ``n`` open sessions; session k's frames
    have the value ``k + t / 100`` (t = 0 the open's)."""

    def __init__(self, eng, n=6, face=None, **cfg):
        defaults = dict(buckets=(BUCKET,), max_batch=2, batch_steps=(1, 2),
                        max_wait_ms=60.0, queue_depth=32, port=0,
                        max_sessions=8, default_deadline_ms=20_000.0)
        defaults.update(cfg)
        self.eng = eng
        # (``face``: what the server is given of the engine, if not all)
        self.server = FlowServer(None, None, ServeConfig(**defaults),
                                 engine=face or eng)
        eng.pool = self.server.streams.pool
        self.server.start()
        self.pool = ThreadPoolExecutor(16)
        self.t = [0] * n
        self.sids = [self.server.streams.open(_frame(k), None)["session"]
                     for k in range(n)]

    def advance(self, *ks):
        """Send the next frame of sessions ``ks``, all at once; the futures
        of their answers."""
        futs = []
        for k in ks:
            self.t[k] += 1
            futs.append(self.pool.submit(
                self.server.streams.advance, self.sids[k],
                _frame(k + self.t[k] / 100), None))
        return futs

    def served(self, k, fut, warm=True):
        """``fut`` answered session k's newest frame from its frame
        before."""
        res = fut.result(timeout=20)
        flow = res["flow"]
        assert res["meta"]["warm"] is warm, (k, res["meta"])
        assert flow.shape == (H, W, 2)
        assert flow[0, 0, 0] == np.float32(k + self.t[k] / 100)
        assert flow[0, 0, 1] == np.float32(k + (self.t[k] - 1) / 100), \
            (k, flow[0, 0])
        return res

    def staged(self, when):
        return self.server.registry.get(
            "raft_serving_batches_staged_total").labels(when).value

    def session(self, k):
        return self.server.streams.store.get(self.sids[k])

    def demote(self, *ks):
        """Sessions ``ks`` lose their slots, as to LRU while parked."""
        for k in ks:
            self.server.streams.store.demote(self.session(k), "lru")
            assert not self.session(k).has_features

    def counts(self):
        """The counters of a restart, and (count, sum) of the step
        histograms."""
        m, reg = self.server.streams.metrics, self.server.registry
        out = {k: m[k].value for k in ("fnet_hits", "fnet_misses",
                                       "restarts_batched", "degraded")}
        for cause in ("demoted", "displaced", "degraded"):
            out["cold_" + cause] = m["cold_restarts"].labels(cause).value
        out["no_slot"] = m["promotions"].labels("none").value
        for name in ("raft_stream_step_batch", "raft_serving_batch_size"):
            h = reg.get(name)
            out[name] = (h.count, h.sum)
        return out

    def moved(self, before):
        """What :meth:`counts` has moved by since ``before``."""
        now = self.counts()
        return {k: (tuple(a - b for a, b in zip(now[k], v))
                    if isinstance(v, tuple) else now[k] - v)
                for k, v in before.items()
                if now[k] != v}

    def close(self):
        for i in range(len(self.eng.issued)):
            self.eng.finish(i)
        self.pool.shutdown(wait=True)
        self.server.stop()
        assert self.server.batcher._running is None


_STREAM = ("h2d", "dispatch", "wait", "fetch", "commit")


def _stream_phase_order():
    """Three groups of disjoint sessions: place(n+1) ends before wait(n)
    returns, dispatch(n+1) precedes fetch(n), commit(n) follows
    dispatch(n+1); a session's next gather sees its commit; the staged
    counter counts stream batches."""
    eng = SlotEngine(hold=(0, 1, 2))
    ss = Sessions(eng)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)                       # placed while call 0 "runs"
    assert not eng.has("wait", 0) and not any(f.done() for f in f0)
    eng.finish(0)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    f2 = ss.advance(4, 5)
    eng.saw("h2d", 2)
    assert not eng.has("wait", 1) and not any(f.done() for f in f1)
    eng.finish(1)
    for k, f in zip((2, 3), f1):
        ss.served(k, f)
    eng.finish(2)
    for k, f in zip((4, 5), f2):
        ss.served(k, f)
    assert eng.order(*_STREAM) == [
        ("h2d", 0), ("dispatch", 0), ("h2d", 1), ("wait", 0),
        ("dispatch", 1), ("fetch", 0), ("commit", 0), ("h2d", 2),
        ("wait", 1), ("dispatch", 2), ("fetch", 1), ("commit", 1),
        ("wait", 2), ("fetch", 2), ("commit", 2)]
    # a row was resolved only after its commit was dispatched
    assert all(len(slots) == 2 for _, slots in eng.commits)
    # the next frames gather what those commits scattered
    for k, f in zip(range(6), ss.advance(*range(6))):
        ss.served(k, f)
    # calls 1 and 2 were on the device before the call in front was ready;
    # the unheld ones after them are ready the moment they are dispatched
    assert (ss.staged("ahead"), ss.staged("late")) == (
        2, len(eng.order("dispatch")) - 2)
    return ss


def _stream_dispatch_reads_the_rows_as_they_stand():
    """Form and dispatch are a run apart: a row abandoned in between goes
    inactive and is failed, its batch-mate is served, and its session is
    where it was."""
    from raft_tpu.serving import DeadlineExceeded
    eng = SlotEngine(hold=(0, 1))
    ss = Sessions(eng)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    group = ss.server.batcher._inflight_batch
    gone = next(r for r in group if r.session.id == ss.sids[2])
    gone.abandoned = True                   # its handler gave up waiting
    eng.finish(0)
    eng.saw("dispatch", 1)
    assert list(eng.issued[1].active) == [r is not gone for r in group]
    eng.finish(1)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    with pytest.raises(DeadlineExceeded, match="abandoned"):
        f1[0].result(timeout=20)
    ss.served(3, f1[1])
    assert eng.commits[1] == (1, [ss.session(3).slot])
    assert ss.session(2).frames == 0 and ss.session(2).has_features
    ss.t[2] -= 1                            # the client sends it again
    [f] = ss.advance(2)
    ss.served(2, f)
    return ss


def _stream_ladder_dispatch_raises():
    """The batched call raising at dispatch degrades ITS rows to cold
    restarts; the batch in flight is served warm."""
    eng = SlotEngine(hold=(0,), fail_dispatch=(1,))
    ss = Sessions(eng)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    eng.finish(0)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    for k, f in zip((2, 3), f1):
        ss.served(k, f, warm=False)
    assert not eng.has("dispatch", 1)
    assert ss.server.streams.metrics["degraded"].value == 2
    assert eng.at("commit", 0) < min(
        k for k, e in enumerate(eng.log) if e[0] == "cold")
    for k, f in zip(range(4), ss.advance(*range(4))):
        ss.served(k, f)                     # healed: warm again
    return ss


def _stream_ladder_wait_raises():
    """The batched call raising at wait: its rows heal cold with the device
    to themselves — after the group dispatched behind them has run."""
    eng = SlotEngine(hold=(0, 1, 2), fail_wait=(1,))
    ss = Sessions(eng)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    eng.finish(0)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    f2 = ss.advance(4, 5)
    eng.saw("h2d", 2)
    eng.finish(1)                           # ... and its wait raises
    eng.saw("dispatch", 2)
    time.sleep(0.1)
    assert not any(e[0] == "cold" for e in eng.log)
    assert not any(f.done() for f in f1 + f2)
    eng.finish(2)
    for k, f in zip((2, 3), f1):
        ss.served(k, f, warm=False)
    for k, f in zip((4, 5), f2):
        ss.served(k, f)
    colds = [k for k, e in enumerate(eng.log) if e[0] == "cold"]
    assert eng.at("wait", 2) < min(colds) and max(colds) < eng.at("fetch", 2)
    assert not eng.has("fetch", 1)
    assert ss.server.streams.metrics["degraded"].value == 2
    return ss


def _stream_ladder_nonfinite_row():
    """A non-finite row degrades that row alone, with a batch in flight
    before it; its neighbour commits."""
    eng = SlotEngine(hold=(0, 1), nan_rows=((1, 0),))
    ss = Sessions(eng)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    group = list(ss.server.batcher._inflight_batch)
    eng.finish(0)
    eng.finish(1)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    bad = ss.sids.index(group[0].session.id)
    good = ss.sids.index(group[1].session.id)
    by_k = dict(zip((2, 3), f1))
    ss.served(bad, by_k[bad], warm=False)
    ss.served(good, by_k[good])
    assert eng.commits[1] == (1, [ss.session(good).slot])
    assert ss.server.registry.get("raft_nonfinite_outputs_total").value == 1
    assert ss.server.streams.metrics["degraded"].value == 1
    return ss


def _stream_ladder_failed_commit():
    """A failed commit of group n rebuilds the pool and demotes the bucket
    with group n+1 in flight: n+1's flows (gathered before the rebuild) are
    served, none of its rows commits, every session that was not healed is
    cold on its next frame, and no answer was gathered from the zeros."""
    eng = SlotEngine(hold=(0, 1), fail_commits=(0,))
    ss = Sessions(eng)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    eng.finish(0)
    eng.saw("commit_failed", 0)
    time.sleep(0.1)                         # the heals wait for call 1
    assert not any(e[0] == "cold" for e in eng.log)
    eng.finish(1)
    for k, f in zip((0, 1), f0):
        ss.served(k, f, warm=False)
    for k, f in zip((2, 3), f1):
        ss.served(k, f)                     # sound: served as warm rows
    assert eng.commits == [(0, None)]       # none of call 1's rows
    assert [ss.session(k).has_features for k in range(6)] == [
        True, True, False, False, False, False]
    futs = ss.advance(*range(6))
    for k, f in zip(range(6), futs):
        ss.served(k, f, warm=k < 2)
    for k, f in zip(range(6), ss.advance(*range(6))):
        ss.served(k, f)
    return ss


def _stream_open_goes_beside_a_running_group():
    """A session's open is taken while a group runs (it coalesces with
    nothing) and goes beside it: its encoder pass and its row's commit are
    dispatched and never fetched, the next group is still staged ahead, and
    the running group's commit, landing after the open's, leaves every slot
    right.  Behind a pairwise batch an open waits for the delivery."""
    eng = SlotEngine(hold=(0, 1))
    ss = Sessions(eng, n=4)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    opened = ss.pool.submit(ss.server.streams.open, _frame(4),
                            None).result(timeout=20)
    assert opened["frame"] == 0
    assert not eng.has("wait", 0) and not any(f.done() for f in f0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)                       # staged ahead all the same
    eng.finish(0)
    eng.finish(1)
    for k, f in zip((0, 1, 2, 3), f0 + f1):
        ss.served(k, f)
    assert eng.at("encode", 4.0) < eng.at("wait", 0)
    assert ss.staged("ahead") == 1
    ss.sids.append(opened["session"])
    ss.t.append(0)
    for k, f in zip(range(5), ss.advance(*range(5))):
        ss.served(k, f)
    eng.saw("fetch", 4)                     # (a group of two, two, one)
    pairs = [ss.pool.submit(ss.server.infer, _frame(0.5), _frame(0.25))
             for _ in range(2)]
    held = len(eng.calls)                   # the pair batch's call
    eng.hold.add(held)
    eng.saw("dispatch", held)
    opening = ss.pool.submit(ss.server.streams.open, _frame(5), None)
    time.sleep(0.15)
    assert not eng.has("encode", 5.0) and not opening.done()
    eng.finish(held)
    assert opening.result(timeout=20)["frame"] == 0
    for p in pairs:
        p.result(timeout=20)
    assert eng.at("fetch", held) < eng.at("encode", 5.0)
    return ss


def _restarts_at_the_place(eng, at=1):
    """Positions in the record of the encoder passes and width-1 commits
    made between call ``at - 1``'s dispatch and call ``at``'s ``h2d``."""
    lo, hi = eng.at("dispatch", at - 1), eng.at("h2d", at)
    with eng.cv:
        return [e[:2] for e in eng.log[lo:hi]
                if e[0] in ("encode", "commit_row")]


def _stream_cold_restart_rides_its_group():
    """A row whose session lost its slot is re-seated when its group is
    placed: the kept frame's encoder pass and a width-1 commit, dispatched
    before the group's own dispatch with NO wait for the group in flight in
    between, and the row is in the placed batch at the group's full width.
    No solo step, and nothing waits for the group staged behind."""
    eng = SlotEngine(hold=(0, 1, 2))
    ss = Sessions(eng)
    ss.demote(2)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    assert eng.calls[1] == (BUCKET, 2)      # at the group's full width
    slot = ss.session(2).slot
    assert _restarts_at_the_place(eng) == [("encode", 2.0),
                                           ("commit_row", slot)]
    assert not eng.has("wait", 0) and not any(f.done() for f in f0 + f1)
    row = [r.session.id for r in
           ss.server.batcher._inflight_batch].index(ss.sids[2])
    eng.finish(0)
    eng.saw("dispatch", 1)
    assert int(eng.issued[1].slots[row]) == slot    # gathers what it wrote
    f2 = ss.advance(4, 5)
    eng.saw("h2d", 2)                       # staged ahead all the same
    eng.finish(1)
    ss.served(2, f1[0], warm=False)         # answered before call 2 has run
    ss.served(3, f1[1])
    assert not eng.has("wait", 2)
    eng.finish(2)
    for k, f in zip((0, 1, 4, 5), f0 + f2):
        ss.served(k, f)
    assert not any(e[0] == "cold" for e in eng.log)
    assert ss.staged("ahead") == 2
    # its commit went with its neighbour's: the next frame is a warm row
    assert eng.commits[1] == (1, sorted([slot, ss.session(3).slot]))
    for k, f in zip((2, 3), ss.advance(2, 3)):
        ss.served(k, f)
    return ss


def _stream_two_restarts_in_one_group():
    """Two demoted rows of one group are both re-seated at its place, each
    into a slot of its own, and both ride."""
    eng = SlotEngine(hold=(0, 1))
    ss = Sessions(eng)
    ss.demote(2, 3)
    before = ss.counts()
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    assert eng.calls[1] == (BUCKET, 2)
    slots = [ss.session(k).slot for k in (2, 3)]
    assert None not in slots and slots[0] != slots[1]
    placed = _restarts_at_the_place(eng)
    assert sorted(placed) == sorted(
        [("encode", 2.0), ("encode", 3.0)]
        + [("commit_row", s) for s in slots])
    assert not eng.has("wait", 0)
    eng.finish(0)
    eng.finish(1)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    for k, f in zip((2, 3), f1):
        ss.served(k, f, warm=False)
    assert not any(e[0] == "cold" for e in eng.log)
    moved = ss.moved(before)
    assert (moved["fnet_misses"], moved["cold_demoted"],
            moved["restarts_batched"], moved["fnet_hits"]) == (2, 2, 2, 2)
    for k, f in zip(range(4), ss.advance(*range(4))):
        ss.served(k, f)
    return ss


def _stream_restart_without_a_slot_heals_solo():
    """``promote`` gives no slot (every one pinned by a session in flight):
    no device call at the place, and the row heals through the solo restart
    in its group's finish, cause ``demoted``."""
    eng = SlotEngine(hold=(0,))
    ss = Sessions(eng, n=4, max_sessions=2)     # 2 and 3 hold the slots
    assert [ss.session(k).has_features for k in range(4)] == [
        False, False, True, True]
    before = ss.counts()
    f0 = ss.advance(2, 3)
    eng.saw("dispatch", 0)
    f1 = ss.advance(0, 1)
    deadline = time.monotonic() + 5
    while ss.counts()["no_slot"] - before["no_slot"] < 2:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert len(eng.calls) == 1              # nothing placed: no row rides
    assert len(eng.order("encode")) == 4    # the opens' alone
    eng.finish(0)
    for k, f in zip((2, 3), f0):
        ss.served(k, f)
    for k, f in zip((0, 1), f1):
        ss.served(k, f, warm=False)
    colds = [k for k, e in enumerate(eng.log) if e[0] == "cold"]
    assert len(colds) == 2 and eng.at("commit", 0) < min(colds)
    moved = ss.moved(before)
    assert (moved["cold_demoted"], moved["fnet_misses"]) == (2, 2)
    assert "restarts_batched" not in moved and "degraded" not in moved
    # a solo heal is a step of width 1, the group in front one of width 2
    assert moved["raft_stream_step_batch"] == (3, 4.0)
    assert moved["raft_serving_batch_size"] == (1, 2.0)
    return ss


def _stream_restart_whose_encode_raises_heals_solo():
    """The place-time encoder pass raises: the slot is given back, the
    neighbour rides alone and stays warm, and the row heals solo, cause
    ``demoted``."""
    eng = SlotEngine(hold=(0, 1))
    ss = Sessions(eng)
    ss.demote(2)
    eng.fail_encode.add(2.0)                # the kept frame's pass, once
    in_use = ss.server.streams.pool.in_use(BUCKET)
    before = ss.counts()
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    assert eng.has("encode_failed", 2.0)
    assert eng.calls[1] == (BUCKET, 1)      # the neighbour alone is placed
    assert not ss.session(2).has_features
    assert ss.server.streams.pool.in_use(BUCKET) == in_use
    assert _restarts_at_the_place(eng) == []
    eng.finish(0)
    eng.finish(1)
    ss.served(2, f1[0], warm=False)
    ss.served(3, f1[1])
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    [cold] = [k for k, e in enumerate(eng.log) if e[0] == "cold"]
    assert eng.at("commit", 1) < cold
    moved = ss.moved(before)
    assert (moved["cold_demoted"], moved["fnet_misses"], moved["fnet_hits"]) == (
        1, 1, 3)
    assert "restarts_batched" not in moved and "degraded" not in moved
    for k, f in zip((2, 3), ss.advance(2, 3)):
        ss.served(k, f)                     # healed: warm again
    return ss


def _stream_restart_whose_commit_fails_demotes_the_bucket():
    """The place-time commit raises with a group in flight: the pool is
    rebuilt zeroed and every session of the bucket demoted, as after any
    failed commit.  The group in flight (gathered before the rebuild) is
    served and commits nothing, this group's rows heal solo, and on their
    next frames every session restarts from its kept frame: no answer was
    gathered from the zeros."""
    eng = SlotEngine(hold=(0,))
    ss = Sessions(eng)
    ss.demote(2)
    eng.fail_commit_rows = 1
    before = ss.counts()
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw_some("commit_row_failed")
    deadline = time.monotonic() + 5         # (the demotion follows it)
    while ss.session(3).has_features and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not any(ss.session(k).has_features for k in range(6))
    eng.finish(0)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)                     # sound: served as warm rows
    for k, f in zip((2, 3), f1):
        ss.served(k, f, warm=False)
    assert eng.commits == [] and len(eng.calls) == 1
    moved = ss.moved(before)
    assert (moved["cold_demoted"], moved["fnet_misses"]) == (2, 2)
    assert "restarts_batched" not in moved and "degraded" not in moved
    # 2 and 3 were healed into the rebuilt pool; the others restart at the
    # place from the frames the service kept
    futs = ss.advance(*range(6))
    for k, f in zip(range(6), futs):
        ss.served(k, f, warm=k in (2, 3))
    assert ss.moved(before)["restarts_batched"] == 4
    for k, f in zip(range(6), ss.advance(*range(6))):
        ss.served(k, f)
    return ss


def _stream_restarted_row_fails_the_sentinel():
    """A restarted row whose output is not finite is degraded as any row of
    the batched call is: its slot dropped, healed solo with cause
    ``degraded``; its neighbour, restarted too, is served by the call."""
    eng = SlotEngine(hold=(0, 1), nan_rows=((1, 0),))
    ss = Sessions(eng)
    ss.demote(2, 3)
    before = ss.counts()
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    group = list(ss.server.batcher._inflight_batch)
    eng.finish(0)
    eng.finish(1)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    bad = ss.sids.index(group[0].session.id)
    good = ss.sids.index(group[1].session.id)
    by_k = dict(zip((2, 3), f1))
    ss.served(bad, by_k[bad], warm=False)
    ss.served(good, by_k[good], warm=False)
    assert eng.commits[1] == (1, [ss.session(good).slot])
    assert len([e for e in eng.log if e[0] == "cold"]) == 1
    moved = ss.moved(before)
    assert (moved["cold_demoted"], moved["cold_degraded"], moved["degraded"],
            moved["restarts_batched"], moved["fnet_misses"],
            moved["fnet_hits"]) == (2, 1, 1, 1, 3, 2)
    assert "cold_displaced" not in moved
    assert ss.server.registry.get("raft_nonfinite_outputs_total").value == 1
    for k, f in zip((2, 3), ss.advance(2, 3)):
        ss.served(k, f)
    return ss


def _stream_abandoned_demoted_row_makes_no_device_call():
    """A demoted row whose handler gave up before the place is not
    re-seated: no encoder pass, no commit, no solo step; it fails, its
    neighbour is served and its session is where it was."""
    from raft_tpu.serving import DeadlineExceeded
    from raft_tpu.serving.stream import StreamRequest
    eng = SlotEngine()
    ss = Sessions(eng, n=4)
    ss.demote(2)
    before = ss.counts()
    reqs = []
    for k in (2, 3):
        ss.t[k] += 1
        reqs.append(StreamRequest(
            ss.session(k), "advance", _frame(k + ss.t[k] / 100)[None],
            (0, 0, 0, 0), time.monotonic() + 30.0))
    reqs[0].abandoned = True
    logged = len(eng.log)
    gone, kept = ss.server.streams.execute_group(reqs, eng)
    assert isinstance(gone[2], DeadlineExceeded) and gone[0] is None
    assert kept[2] is None and kept[0][0, 0, 0, 0] == np.float32(3.01)
    assert eng.calls[-1] == (BUCKET, 1)
    assert [e[0] for e in eng.log[logged:]] == [
        "h2d", "dispatch", "wait", "fetch", "commit"]
    assert not ss.session(2).has_features and ss.session(2).frames == 0
    assert ss.moved(before) == {"fnet_hits": 1}     # the neighbour's
    ss.t[2] -= 1
    for k, f in zip((2, 3), ss.advance(2, 3)):
        ss.served(k, f, warm=k != 2)
    return ss


def _stream_restart_counts_as_a_miss_at_the_groups_width():
    """The counters of one restart: a cache miss and no hit, one cold
    restart of cause ``demoted``, one restart served by the batched call,
    the answer ``warm: false`` and the next ``warm: true``; and the step
    histograms report the group's width, not a solo step's."""
    eng = SlotEngine(hold=(0,))
    ss = Sessions(eng)
    ss.demote(2)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    before = ss.counts()
    f1 = ss.advance(2, 3)
    eng.saw("h2d", 1)
    eng.finish(0)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    res = ss.served(2, f1[0], warm=False)
    assert (res["meta"]["batch_real"], res["meta"]["batch_padded"]) == (2, 2)
    ss.served(3, f1[1])
    moved = ss.moved(before)
    assert {k: v for k, v in moved.items() if not k.startswith("raft_")} == {
        "fnet_misses": 1, "cold_demoted": 1, "restarts_batched": 1,
        "fnet_hits": 3}                     # (0, 1 and 3: the warm rows)
    # two steps of width 2 (call 0 was observed after ``before`` was taken)
    assert moved["raft_stream_step_batch"] == (2, 4.0)
    assert moved["raft_serving_batch_size"] == (2, 4.0)
    before = ss.counts()
    [f] = ss.advance(2)
    ss.served(2, f)
    assert {k: v for k, v in ss.moved(before).items()
            if not k.startswith("raft_")} == {"fnet_hits": 1}
    return ss


def _stream_and_pair_batches_drain_each_other():
    """A pair batch behind a running group, and a group behind a running
    pair batch, begin when the running batch has been delivered."""
    eng = SlotEngine(hold=(0, 1))
    ss = Sessions(eng, n=2)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    pairs = [ss.pool.submit(ss.server.infer, _frame(0.5), _frame(0.25))
             for _ in range(2)]
    time.sleep(0.15)
    assert not eng.has("h2d", 1)            # not placed beside the group
    eng.finish(0)
    for k, f in zip((0, 1), f0):
        ss.served(k, f)
    eng.saw("dispatch", 1)
    assert eng.at("commit", 0) < eng.at("h2d", 1)
    f1 = ss.advance(0, 1)
    time.sleep(0.15)
    assert not eng.has("h2d", 2)            # nor a group beside the pairs
    eng.finish(1)
    for p in pairs:
        assert p.result(timeout=20).result.shape == (H, W, 2)
    for k, f in zip((0, 1), f1):
        ss.served(k, f)
    assert eng.at("fetch", 1) < eng.at("h2d", 2)
    return ss


def _stream_stub_without_phases_walks_blocking():
    """An engine with ``run_stream_batch`` alone keeps the blocking walk: a
    group begins after the group before it has committed, and no stream
    batch counts as staged ahead."""
    eng = SlotEngine(run_s=0.03)

    class Whole:
        run_encode, run_stream = eng.run_encode, eng.run_stream
        commit_stream, commit_row = eng.commit_stream, eng.commit_row

        def run_stream_batch(self, *args, **kw):
            return eng.run_stream_batch(*args, **kw)

    ss = Sessions(eng, face=Whole())
    for _ in range(3):
        for k, f in zip(range(6), ss.advance(*range(6))):
            ss.served(k, f)
    calls = [i for _, i in eng.order("h2d")]
    assert len(calls) >= 9
    for a, b in zip(calls, calls[1:]):
        assert eng.at("commit", a) < eng.at("h2d", b)
    assert (ss.staged("ahead"), ss.staged("late")) == (0, len(calls))
    return ss


def _stream_crash_fails_running_and_staged_groups():
    """The thread dying with one group running and one staged fails every
    waiter of both; the supervisor restarts the loop and the sessions, whose
    slots nothing touched, carry on."""
    class Dying(SlotEngine):
        dead = False

        def ready(self, call):              # outside every phase's guard
            if not self.dead and self.has("h2d", 1):
                self.dead = True
                raise RuntimeError("the loop itself dies")
            return super().ready(call)

    eng = Dying(hold=(0,))
    ss = Sessions(eng, n=4)
    f0 = ss.advance(0, 1)
    eng.saw("dispatch", 0)
    f1 = ss.advance(2, 3)
    for f in f0 + f1:
        with pytest.raises(BatcherCrashed):
            f.result(timeout=20)
    deadline = time.monotonic() + 5
    while not ss.server.batcher.alive and time.monotonic() < deadline:
        time.sleep(0.01)
    assert ss.server.batcher.alive and ss.server.supervisor.restarts == 1
    assert ss.server.batcher._running is None
    ss.t = [0] * 4                          # the clients send them again
    for k, f in zip(range(4), ss.advance(*range(4))):
        ss.served(k, f)
    return ss


@pytest.mark.parametrize("case", [
    _stream_phase_order, _stream_dispatch_reads_the_rows_as_they_stand,
    _stream_ladder_dispatch_raises, _stream_ladder_wait_raises,
    _stream_ladder_nonfinite_row, _stream_ladder_failed_commit,
    _stream_open_goes_beside_a_running_group,
    _stream_cold_restart_rides_its_group,
    _stream_two_restarts_in_one_group,
    _stream_restart_without_a_slot_heals_solo,
    _stream_restart_whose_encode_raises_heals_solo,
    _stream_restart_whose_commit_fails_demotes_the_bucket,
    _stream_restarted_row_fails_the_sentinel,
    _stream_abandoned_demoted_row_makes_no_device_call,
    _stream_restart_counts_as_a_miss_at_the_groups_width,
    _stream_and_pair_batches_drain_each_other,
    _stream_stub_without_phases_walks_blocking,
    _stream_crash_fails_running_and_staged_groups],
    ids=lambda f: f.__name__[8:])
def test_stream_pipeline(case):
    case().close()


def test_an_open_is_taken_while_the_device_is_busy():
    """``take_batch`` holds a part bucket back while a batch runs, for its
    mates; an open has none (its key is its session's) and is handed over
    at once, an advance alone is not."""
    from raft_tpu.serving import RequestQueue
    from raft_tpu.serving.stream import StreamRequest

    def step(op):
        return StreamRequest(types.SimpleNamespace(id="s", bucket=BUCKET), op,
                             np.zeros((1, H, W, 3), np.float32),
                             (0, 0, 0, 0), time.monotonic() + 30.0)

    q = RequestQueue(8)
    lone = step("advance")
    q.submit(lone)
    until = time.monotonic() + 0.05
    assert q.take_batch(2, 10.0, lambda: time.monotonic() < until) == ([], [])
    opening = step("open")
    assert (lone.coalesces, opening.coalesces) == (True, False)
    q.submit(opening)
    t0 = time.monotonic()
    # the advance is the older head: the open waits its turn behind it ...
    assert q.take_batch(2, 0.0, None)[0] == [lone]
    # ... and then goes though the device is busy and max_wait far away
    assert q.take_batch(2, 10.0, lambda: True)[0] == [opening]
    assert time.monotonic() - t0 < 1.0


# ------------------------------------------------ the real engine, tiny --

@pytest.fixture(scope="module")
def live():
    """A live streaming server at the tiny size whose every group is two
    rows wide (one batch step), so that a row's program is the same
    whichever walk it takes."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=2)
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=(BUCKET,), max_batch=2, batch_steps=(2,),
                          max_wait_ms=150.0, queue_depth=32, port=0,
                          default_deadline_ms=60_000.0, max_sessions=12,
                          session_ttl_s=600.0)
    server = FlowServer(config, params, sconfig)
    server.start()
    yield server
    server.stop()


def _clips(n, frames, seed=7):
    rng = np.random.RandomState(seed)
    return [[rng.rand(H, W, 3).astype(np.float32) for _ in range(frames)]
            for _ in range(n)]


def test_pipelined_walk_answers_as_the_blocking_walk(live):
    """3 x max_batch sessions walked five frames all at once (groups staged
    behind running groups) answer bit for bit what the same clips answer
    walked one group at a time with nothing beside it; the warm-start chain
    is kept in both."""
    streams = live.streams
    clips = _clips(6, 6)
    staged0 = sum(live.registry.get(
        "raft_serving_batches_staged_total").labels(w).value
        for w in ("ahead", "late"))

    def walk(clip):
        sid = streams.open(clip[0], None)["session"]
        out = [streams.advance(sid, f, None) for f in clip[1:]]
        streams.close(sid)
        return out

    with ThreadPoolExecutor(6) as pool:
        piped = list(pool.map(walk, clips))
    groups = sum(live.registry.get(
        "raft_serving_batches_staged_total").labels(w).value
        for w in ("ahead", "late")) - staged0
    assert 15 <= groups <= 30               # 30 advances, two a group

    sids = [streams.open(c[0], None)["session"] for c in clips]
    alone = [[] for _ in clips]
    with ThreadPoolExecutor(2) as pool:
        for t in range(1, 6):
            for a in (0, 2, 4):
                for k, res in zip((a, a + 1), pool.map(
                        lambda k: streams.advance(sids[k], clips[k][t],
                                                  None), (a, a + 1))):
                    alone[k].append(res)
    for sid in sids:
        streams.close(sid)
    for k in range(6):
        for t in range(5):
            p, a = piped[k][t], alone[k][t]
            assert p["meta"]["warm"] is True and a["meta"]["warm"] is True
            assert p["frame"] == a["frame"] == t + 1
            assert np.array_equal(p["flow"], a["flow"]), (k, t)
    # the chain is the warm start's: a frame's answer depends on the flow
    # before it, so the same two frames answer differently cold
    cold = live.infer(clips[0][4], clips[0][5]).result
    assert not np.array_equal(cold, piped[0][4]["flow"])
    assert live.engine.compile_misses == 0


def test_restarted_row_answers_the_zero_seeded_pair(live):
    """On the real engine: a session demoted while parked comes back as a
    row of its group's batched call, answered ``warm: false`` with the flow
    of the pair (kept frame, frame) from a zero seed, which is what
    ``/v1/flow`` answers; the advance after it is warm; and the pipelined walk (groups beside each other) answers bit
    for bit what the blocking walk (a group at a time) does."""
    streams, reg = live.streams, live.registry
    clips = _clips(4, 4, seed=13)
    batched0 = streams.metrics["restarts_batched"].value
    solo0 = live.engine.stream_calls, live.engine.encode_calls
    steps = reg.get("raft_stream_step_batch")
    steps0 = steps.count, steps.sum

    def walk(groups):
        sids = [streams.open(c[0], None)["session"] for c in clips]
        out = [[] for _ in clips]
        for t in (1, 2, 3):
            if t == 2:                      # session 0 was parked, and LRU
                streams.store.demote(streams.store.get(sids[0]), "lru")
            for g in groups:
                with ThreadPoolExecutor(len(g)) as pool:
                    for k, res in zip(g, pool.map(
                            lambda k: streams.advance(sids[k], clips[k][t],
                                                      None), g)):
                        out[k].append(res)
        for sid in sids:
            streams.close(sid)
        return out

    piped = walk([(0, 1, 2, 3)])
    # 2 walks' worth below; so far: 4 opens, 12 advances, 1 restart's encode
    assert (live.engine.stream_calls - solo0[0],
            live.engine.encode_calls - solo0[1]) == (12, 5)
    alone = walk([(0, 1), (2, 3)])
    for k in range(4):
        for t in range(3):
            p, a = piped[k][t], alone[k][t]
            cold = (k, t) == (0, 1)
            assert p["meta"]["warm"] is a["meta"]["warm"] is (not cold)
            assert p["meta"]["batch_real"] == 2     # never a solo step
            assert np.array_equal(p["flow"], a["flow"]), (k, t)
    pair = live.infer(clips[0][1], clips[0][2]).result
    # (float32, another program: tests/test_ragged.py's stream-against-pair
    # tolerance; the flows here run to a hundred pixels)
    np.testing.assert_allclose(piped[0][1]["flow"], pair, rtol=1e-4,
                               atol=1e-2)
    assert streams.metrics["restarts_batched"].value - batched0 == 2
    # twelve groups of two and the eight opens: the restarts made no step
    # of width 1
    assert (steps.count - steps0[0], steps.sum - steps0[1]) == (20, 32.0)
    assert live.engine.compile_misses == 0


def test_stream_batch_reads_the_pool_at_its_dispatch(live):
    """The engine's phases: a batch placed BEFORE a commit and dispatched
    after it gathers what the commit scattered (the buffers the place saw
    were donated into the commit)."""
    eng, pool = live.engine, live.streams.pool
    a, b, c = _clips(1, 3, seed=11)[0]
    sid = live.streams.open(a, None)["session"]
    slot = live.streams.store.get(sid).slot
    pad = np.zeros((1, H, W, 3), np.float32)
    images = np.concatenate([c[None], pad])
    slots = np.asarray([slot, pool.scratch], np.int32)
    active = np.asarray([True, False])
    want = eng.run_stream_batch(BUCKET, images, slots, active)[0][0]
    call = eng.place_stream_batch(BUCKET, images)
    fmap, cnet = eng.run_encode(BUCKET, b[None])
    eng.commit_row(BUCKET, slot, fmap, cnet,
                   np.zeros((1, H // 8, W // 8, 2), np.float32))
    eng.dispatch_stream_batch(call, slots, active)
    assert call.args is None
    eng.wait(call)
    got = eng.fetch_stream_batch(call)[0][0]
    assert np.isfinite(got).all() and not np.array_equal(got, want)
    again = eng.run_stream_batch(BUCKET, images, slots, active)[0][0]
    assert np.array_equal(got, again)       # flow(b -> c), not flow(a -> c)
    live.streams.close(sid)
