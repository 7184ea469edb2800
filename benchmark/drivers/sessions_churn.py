"""The load driver ``sessions_churn``: more live video sessions than device
slots, through ``POST /v1/stream``.

``drivers/sessions.py``'s clients play one session at a time to its end, so
the sessions alive are the sessions playing and ``--max-sessions`` covers
them all.  A video service's clients are not like that: an editor scrubs a
few frames and pauses, a camera sends while something moves.  Here a session
PAUSES: it stays open on the server, keeps its record there, and comes back
later to find its slot kept or taken (``raft_tpu/serving/session.py``:
``promote`` demotes the least recently used holder).  The clips, the bodies
and the session ids written into them are ``drivers/sessions.py``'s, imported
as they are.

* ``live_sessions`` (the cell's file) sessions are open at once and
  ``clients`` PLAYERS (threads, a keep-alive connection each) play them: a
  player holds one session, posts frame k only once frame k-1 was answered
  (closed loop, lockstep inside a session), and after a BURST of advances
  parks the session and takes a parked one.  Burst lengths are a bounded
  Pareto (``burst_shape`` on ``burst_frames``, rounded), cut at the session's
  end; session lengths one too (``session_shape`` on ``session_frames``,
  frames, the open's included).  At a session's end the player closes it,
  opens a new one (the j-th session opened plays clip ``(seed + j) % clips``)
  and plays a burst of that one;
* which parked session a player takes: with probability
  ``resume_recent_probability`` the one parked MOST recently (a short pause:
  its slot should still be there), else the one parked LONGEST (its slot is
  gone wherever the parked outnumber the spare slots).  It chooses among the
  sessions parked before its own joins the queue, so a short pause is another
  session's and never no pause at all.  Every draw is from ``--seed``: each
  quantity (a session's length, a burst, the share its first burst starts
  at, a choice) has a START drawn from the seed by ``random.Random`` and
  its n-th number is that start and n steps of an irrational, modulo one
  (:class:`Spread`).  A number of it is uniform on [0, 1) as an independent
  draw is, so a length or a burst has the table's bounded Pareto law, 48
  included, and a choice its probability; what the numbers of one quantity
  lack is independence of each other: any run of them covers [0, 1) evenly,
  so the bursts and the resumes of a 40 s window, which independent draws
  move by a quarter from seed to seed, move by a few in sixty (PERF.md §4
  has both spreads on the chip).  The n-th session opened has the n-th
  length, a player's first burst in the window the burst and the share of
  its number, and a burst or a choice after that the next of the run, by
  whichever player asks;
* the population is built in the warm-up, part of ``setup_s``: the parked
  sessions are opened first (the player's number and round give the ordinal),
  then each player opens the session it holds and advances it once, so the
  window starts on a live service whose slots are held by the players'
  sessions and the most recently opened of the parked, as they would be
  hours in.  For the same reason a player's first burst in the window is the
  REMAINDER of a drawn burst (a uniform share of it, from the seed): parks
  and resumes begin at once and not a burst later.  Nothing is closed
  between warm-up and window but what the check sessions replace;
* ``pairs_per_s`` is the advances answered 200, warm or cold, by
  ``loadgen.summarize``'s share rule; opens and closes count in
  ``attempted`` and ``failed`` and never in the rate.  An advance's answer
  says whether it was served from the session's slot (``warm``, one byte of
  the npz): the player reads that member, and no other, as the answer
  arrives.  ``summarize`` reports beside the metrics ``cold_advances``,
  ``resumes``, ``resumes_warm``, ``resumes_longest``;
* the kept answers are of CHECK sessions: ``check.sample`` players (from
  the seed) close what they hold at the window's start and open one each;
  its first burst is ``check_first_burst`` advances, then it is parked
  ``check_park_place`` places from the head of the queue and taken by the
  longest-parked rule alone, and only once that many advances sent since
  its park have been ANSWERED cold, and one more for each player whose
  session was waiting for a slot when it parked (each a promotion that
  demoted the least recently used holder, unless a close had freed a slot:
  closes are taken off; :meth:`Population._ripe` has the argument.  Places
  in the queue alone are resumes SENT, of which the last few have promoted
  nobody yet).  Kept, each of the session the issue gives it
  to: (A) the last advance of the FIRST check session's first burst
  (warm); (B) the first advance after the resume of the SECOND, which MUST
  come back ``warm: false`` (else the traffic did not do what the cell is
  for: the answer counts as missing and the run is not correct); (C) the
  second advance after the resume of the THIRD, which must come back warm
  after a cold one (seeded from the restart; else it counts as missing
  too).  The frame indices are the mix's ``kept_frames``.  An answer's key
  holds the frame indices at which its session was answered cold, and the
  reference walks the session from its open, restarting exactly there
  (``references/warm_restart.py``).
"""

from __future__ import annotations

import collections
import dataclasses
import io
import math
import random
import threading
import time
from typing import Optional

import numpy as np

import inputs
import loadgen
from drivers import sessions
from references.warm_restart import walk

KINDS = ("warm", "restart", "after_restart")      # kept answers A, B, C


@dataclasses.dataclass
class Live:
    """One live session as its players know it."""
    j: int                       # ordinal of its open
    clip: int
    sid: str
    frames: int                  # its length, the open's frame included
    at: int = 0                  # newest frame answered (0: the open's)
    check: int = -1              # a check session's number, or -1
    cold: list = dataclasses.field(default_factory=list)   # frames not warm
    pending: bool = False        # taken from the queue and not answered yet
    parked_t: float = 0.0        # (a check session) when it was parked, and
    need: int = 0                # the demotions that take its slot for sure


@dataclasses.dataclass
class Made(sessions.Made):
    """``drivers/sessions.py``'s clips and bodies, and the population the
    warm-up leaves for the window."""
    population: Optional["Population"] = None


@dataclasses.dataclass
class Window:
    records: list                # loadgen.Record of every request, with .op,
    t0: float                    # .session (j), .clip, .frame, .warm, .pick
    t1: float
    keep: dict                   # {(session j, frame index): clip} of the
    checks: dict                 # check sessions; {session j: its Live}
    kinds: list                  # the kinds of answer to keep


def bounded_pareto(u: float, shape: float, lo: float, hi: float) -> int:
    """The Pareto distribution of ``shape`` held to [lo, hi]: the inverse of
    its distribution function at ``u`` in [0, 1), rounded."""
    span = 1.0 - (lo / hi) ** shape
    return int(min(hi, max(lo, round(
        lo / (1.0 - u * span) ** (1.0 / shape)))))


class Spread:
    """One quantity's numbers in [0, 1): a start drawn from the seed, and
    the n-th is the start and n steps of ``STEPS[what]``, modulo one (an
    additive recurrence: its numbers are uniform one by one and cover [0, 1)
    evenly in any run; two quantities drawn side by side have steps that
    stay out of step with each other)."""

    # the golden ratio's fractional part, and the two of the plastic number
    # (M. Roberts' pair: even over the square of a burst and its share)
    STEPS = {"session": 0.6180339887498949, "pick": 0.6180339887498949,
             "burst": 0.7548776662466927, "share": 0.5698402909980532}

    def __init__(self, seed: int, what: str, first: int = 0):
        self.start = random.Random(f"{seed}:{what}").random()
        self.step, self.n = self.STEPS[what], first
        self.lock = threading.Lock()

    def nth(self, n: int) -> float:
        return (self.start + n * self.step) % 1.0

    def next(self) -> float:
        with self.lock:
            self.n += 1
            return self.nth(self.n - 1)


def remainder(share: float, drawn: int) -> int:
    """What is left of ``drawn`` at the point ``share`` of it."""
    return max(1, math.ceil(share * drawn))


def warm_of(payload: bytes) -> Optional[bool]:
    """An advance's ``warm``, the one member of the answer read here."""
    with np.load(io.BytesIO(payload)) as z:
        return bool(z["warm"]) if "warm" in z.files else None


class Population:
    """The live sessions: who holds which, which are parked and in what
    order, and every draw (a :class:`Spread` for each quantity)."""

    def __init__(self, seed: int, traffic: dict, clients: int, n_clips: int):
        self.seed, self.traffic, self.n_clips = int(seed), traffic, n_clips
        self.lock = threading.Lock()
        self.parked = collections.deque()     # longest parked first
        self.held = [None] * clients
        self.opened = 0                       # ordinals given out
        # (a player's first burst is the burst of its number: the run of
        # bursts goes on after the players')
        self.draws = {what: Spread(self.seed, what, first)
                      for what, first in (("session", 0), ("share", 0),
                                          ("burst", clients), ("pick", 0))}
        self.cold_sent = []      # send times of the advances answered cold
        self.close_done = []     # when each close was answered
        self.closing = 0         # closes sent and not answered yet

    def ordinal(self) -> int:
        with self.lock:
            self.opened += 1
            return self.opened - 1

    def saw_cold(self, sent: float) -> None:
        with self.lock:
            self.cold_sent.append(sent)

    def close_began(self, c: int) -> None:
        with self.lock:
            self.closing += 1
            self.held[c] = None

    def close_ended(self) -> None:
        with self.lock:
            self.closing -= 1
            self.close_done.append(time.monotonic())

    def _ripe(self, s: Live) -> bool:
        """May ``s`` be taken from the queue's head?  A check session only
        once its slot is gone for sure.  The store demotes the least
        recently used holder that is not in flight, so its slot goes after
        those of the parked sessions that held one when it was parked: the
        slots the players' sessions leave spare, and one more for each
        player whose session was waiting for a slot then (``need``, set at
        the park: ``check_park_place`` and those).  Every advance answered
        cold is a promotion, and demotes a holder unless it found a slot
        freed by a close: counted are the cold answers of advances sent
        since the park, less the closes answered since or still out."""
        if s.check < 0:
            return True
        colds = sum(1 for t in self.cold_sent if t > s.parked_t)
        closes = self.closing + sum(1 for t in self.close_done
                                    if t > s.parked_t)
        return colds - closes >= s.need

    def length(self, j: int) -> int:
        """Frames of the j-th session opened, the open's included."""
        t = self.traffic
        return bounded_pareto(self.draws["session"].nth(j),
                              float(t["session_shape"]), *t["session_frames"])

    def burst(self, c: int, whole: bool = True) -> int:
        """Player ``c``'s next burst; ``whole`` False: its first in the
        window, the remainder of a drawn one."""
        t, bursts = self.traffic, self.draws["burst"]
        drawn = bounded_pareto(bursts.next() if whole else bursts.nth(c),
                               float(t["burst_shape"]), *t["burst_frames"])
        return drawn if whole else remainder(self.draws["share"].nth(c),
                                             drawn)

    def swap(self, c: int, own: Live, place: int = None):
        """Player ``c`` parks ``own`` and takes a parked session: -> (the
        session, "recent" | "longest").  ``place``: where ``own`` joins the
        queue, counted from its head (None: its end)."""
        recent = self.draws["pick"].next() < float(
            self.traffic["resume_recent_probability"])
        with self.lock:
            if not self.parked:
                return own, "recent"          # (no session is parked)
            taken, pick = None, "longest"
            if recent:
                # (a check session is taken by the longest-parked rule alone)
                for i in range(len(self.parked) - 1, -1, -1):
                    if self.parked[i].check < 0:
                        taken, pick = self.parked[i], "recent"
                        del self.parked[i]
                        break
            if taken is None:
                i = next((i for i, s in enumerate(self.parked)
                          if self._ripe(s)), None)
                if i is None:
                    return own, "recent"      # (nothing parked is ripe)
                taken = self.parked[i]
                del self.parked[i]
            if place is None:
                self.parked.append(own)
            else:
                own.parked_t = time.monotonic()
                own.need = int(self.traffic["check_park_place"]) + sum(
                    1 for i, h in enumerate(self.held)
                    if i != c and (h is None or h.pending))
                self.parked.insert(min(place, len(self.parked)), own)
            taken.pending = True
            self.held[c] = taken
            return taken, pick


# ------------------------------------------------------------------ inputs

def make_inputs(seed: int, traffic: dict) -> Made:
    """``drivers/sessions.py``'s clips and bodies, from the seed and the
    mix."""
    made = sessions.make_inputs(seed, traffic)
    return Made(made.frames, made.opens, made.advances)


# ---------------------------------------------------------------- the loop

class _Players:
    """The requests of one phase (the warm-up, the window): its records and
    the three kinds of post."""

    def __init__(self, sut, made: Made, pop: Population, traffic: dict,
                 clients: int, keep: dict):
        self.made, self.pop, self.keep = made, pop, keep
        self.records, self.lock = [], threading.Lock()
        self.conns = loadgen.Client.connected(
            clients, sut.host, sut.port, traffic["endpoint"], 60.0)

    def post(self, c: int, op: str, body, s: Live, k: int):
        with self.lock:
            rec = loadgen.Record(len(self.records), k, time.monotonic())
            self.records.append(rec)
        rec.op, rec.session, rec.clip, rec.frame = op, s.j, s.clip, k
        rec.warm = rec.pick = None
        self.conns[c].one(rec, body, op != "close")
        return rec

    def open(self, c: int, j: int, frames: int,
             check: int = -1) -> Optional[Live]:
        pop = self.pop
        s = Live(j, (pop.seed + j) % pop.n_clips, "", frames, check=check)
        rec = self.post(c, "open", self.made.opens[s.clip], s, 0)
        if rec.status != 200:
            return None
        s.sid = str(inputs.npz_load(rec.payload)["session"])
        rec.payload = None
        return s

    def advance(self, c: int, s: Live):
        k = s.at + 1
        rec = self.post(c, "advance", sessions.advance_body(
            self.made.advances[s.clip][k - 1], s.sid), s, k)
        s.pending = False
        if rec.status == 200:
            rec.warm = warm_of(rec.payload)
            if rec.warm is False:
                s.cold.append(k)
                self.pop.saw_cold(rec.sent)
            s.at = k
        if (s.j, k) not in self.keep:
            rec.payload = None
        return rec

    def close(self, c: int, s: Live) -> None:
        self.pop.close_began(c)
        self.post(c, "close", inputs.npz_body(op=np.asarray("close"),
                                              session=np.asarray(s.sid)),
                  s, -1)
        self.pop.close_ended()

    def run(self, target, clients: int):
        """``target(c)`` on ``clients`` threads; an exception of any ends
        the phase with it."""
        failed = []

        def guarded(c):
            try:
                target(c)
            except BaseException as e:      # noqa: BLE001 (re-raised below)
                failed.append(e)
                raise

        threads = [threading.Thread(target=guarded, args=(c,),
                                    name=f"load-{c}", daemon=True)
                   for c in range(clients)]
        for t in threads:
            t.start()
        return threads, failed


def warm_up(sut, made: Made, seed: int, traffic: dict, cell: dict,
            seconds: float) -> None:
    """Build the population, part of set-up: the parked sessions opened
    first, in rounds, then every player's own, advanced once (which warms
    the request path: every kind of request but a close is sent).
    ``seconds`` is not used: the phase is as long as the population takes."""
    clients, live = int(cell["clients"]), int(cell["live_sessions"])
    if live < clients:
        raise SystemExit(f"benchmark: {live} live sessions cannot keep "
                         f"{clients} players busy")
    pop = Population(seed, traffic, clients, len(made.opens))
    pop.opened = live
    play = _Players(sut, made, pop, traffic, clients, {})
    n_parked = live - clients
    rounds = -(-n_parked // clients)
    barrier = threading.Barrier(clients, timeout=300.0)
    parked = [None] * n_parked

    def opened(c, j):
        s = play.open(c, j, pop.length(j))
        if s is None:
            barrier.abort()
            raise SystemExit(f"benchmark: the warm-up could not open "
                             f"session {j}")
        return s

    def player(c):
        for r in range(rounds):
            j = r * clients + c
            if j < n_parked:
                parked[j] = opened(c, j)
            barrier.wait()
        s = pop.held[c] = opened(c, n_parked + c)
        if play.advance(c, s).status != 200:
            barrier.abort()
            raise SystemExit(f"benchmark: the warm-up's advance of session "
                             f"{s.j} failed")
        play.conns[c].close()

    threads, failed = play.run(player, clients)
    for t in threads:
        t.join()
    if failed:
        raise failed[0]
    pop.parked.extend(parked)
    made.population = pop


def run_window(sut, made: Made, seed: int, traffic: dict, cell: dict,
               seconds: float, n_keep: int) -> Window:
    """Offer the mix's load for ``seconds`` on the population the warm-up
    left; keep ``n_keep`` advances of check sessions opened at the window's
    start, of the kinds ``KINDS`` in turn."""
    pop = made.population
    if pop is None:
        raise SystemExit("benchmark: the driver 'sessions_churn' runs its "
                         "window on the population its warm-up builds")
    clients = len(pop.held)
    at = [int(k) for k in traffic["kept_frames"]]
    first, place = int(traffic["check_first_burst"]), \
        int(traffic["check_park_place"])
    checkers = random.Random(seed ^ 0xC0FFEE).sample(
        range(clients), min(n_keep, clients))
    check_of = {c: i for i, c in enumerate(checkers)}
    # (the check sessions' ordinals, fixed before any thread runs)
    check_j = {c: pop.ordinal() for c in checkers}
    keep = {(check_j[c], k): (pop.seed + check_j[c]) % pop.n_clips
            for c in checkers for k in at}
    kinds = [KINDS[i % len(KINDS)] for i in range(len(checkers))]
    checks: dict = {}
    play = _Players(sut, made, pop, traffic, clients, keep)
    t_end = [math.inf]
    barrier = threading.Barrier(clients + 1)

    def renewed(c, s, j=None, check=-1):
        """``s`` closed and the next session opened in its place (tried
        again, not hammered, where the server refuses)."""
        play.close(c, s)
        while time.monotonic() < t_end[0]:
            j = pop.ordinal() if j is None else j
            new = play.open(c, j, pop.length(j), check)
            if new is not None:
                pop.held[c] = new
                return new
            j = None
            time.sleep(0.05)
        return None

    def player(c):
        s, resumed = pop.held[c], None
        barrier.wait()
        if c in check_of:
            s = renewed(c, s, check_j[c], check_of[c])
            if s is None:
                return
            checks[s.j] = s
            burst = first
        else:
            burst = pop.burst(c, whole=False)
        while time.monotonic() < t_end[0]:
            lost = False
            for _ in range(burst):
                if s.at + 1 >= s.frames or time.monotonic() >= t_end[0]:
                    break
                rec = play.advance(c, s)
                rec.pick, resumed = resumed, None
                if rec.status != 200:
                    lost = True    # a session does not go on past a lost frame
                    break
            if time.monotonic() >= t_end[0]:
                break
            if lost or s.at + 1 >= s.frames:
                s = renewed(c, s)
                if s is None:
                    break
                burst = pop.burst(c)
                continue
            first_park = s.check >= 0 and s.at == first
            s, resumed = pop.swap(c, s, place if first_park else None)
            burst = pop.burst(c)
        play.conns[c].close()

    threads, failed = play.run(player, clients)
    barrier.wait()
    t0 = time.monotonic()
    t_end[0] = t0 + seconds
    for t in threads:
        t.join()
    if failed:
        raise failed[0]
    return Window(play.records, t0, t0 + seconds, keep, checks, kinds)


def summarize(win: Window) -> dict:
    """``drivers/sessions.py``'s summary (the rate and the latencies over
    the advances, every request in ``attempted`` and ``failed``) and, beside
    the metrics, what the churn did: advances answered cold, resumes (the
    first advance of a session after it was taken from the queue), those of
    them answered warm and those that took the longest parked."""
    out = sessions.summarize(win)
    ok = [r for r in win.records if r.op == "advance" and r.status == 200]
    resumes = [r for r in ok if r.pick is not None]
    out.update(
        cold_advances=sum(1 for r in ok if r.warm is False),
        resumes=len(resumes),
        resumes_warm=sum(1 for r in resumes if r.warm),
        resumes_longest=sum(1 for r in resumes if r.pick == "longest"))
    return out


def kept_answers(win: Window) -> list:
    """[(ordinal, (clip, frame index, frames of the session answered cold up
    to it), flow array or None)], one for each of ``win.kinds``: the i-th
    check session gives the i-th kind.  ``warm`` is the last advance of its
    first burst; ``restart`` the first advance after its resume, which has
    to have come back ``warm: false``; ``after_restart`` the advance after
    such a one, which has to have come back warm.  An answer is missing
    (None) where it was never sent, says nothing of ``warm``, or is not of
    its kind: the session kept its slot through its pause, and the traffic
    did not do what the cell is for."""
    found = {(r.session, r.frame): r for r in win.records
             if r.op == "advance" and (r.session, r.frame) in win.keep}
    frame = dict(zip(KINDS, sorted({k for _, k in win.keep})))
    chosen = dict(zip(win.kinds, sorted({j for j, _ in win.keep})))

    def restarted(j):
        r = found.get((j, frame["restart"]))
        return r is not None and r.status == 200 and r.warm is False \
            and r.pick is not None

    out = []
    for kind in win.kinds:
        j, k = chosen.get(kind), frame[kind]
        r, s = found.get((j, k)), win.checks.get(j)
        flow = (inputs.npz_load(r.payload)["flow"]
                if r is not None and r.payload and r.warm is not None
                else None)
        if flow is not None and kind != "warm" and not (
                restarted(j) and (kind == "restart" or r.warm is True)):
            b = found.get((j, frame["restart"]))
            print(f"check: the kept answer {kind!r} (request {r.ordinal}, "
                  f"session {j}, frame {k}: warm={r.warm}) is not of its "
                  f"kind: the first advance after the session's resume came "
                  f"back warm={getattr(b, 'warm', None)} "
                  f"resumed={getattr(b, 'pick', None)}; it kept its slot "
                  f"through its pause, the traffic did not do what the cell "
                  f"is for", flush=True)
            flow = None
        cold = tuple(f for f in (s.cold if s is not None else ()) if f <= k)
        out.append((r.ordinal if r is not None else -1 - len(out),
                    (win.keep.get((j, k), 0), k, cold), flow))
    for r in found.values():            # (what was kept and not chosen)
        if (r.session, r.frame) not in {(chosen.get(kind), frame[kind])
                                        for kind in win.kinds}:
            r.payload = None
    return out


def reference_answers(forward, made: Made, which) -> dict:
    """{(clip, frame index, cold frames): the reference's answer}: the clip
    walked from frame 0 as far as the kept index, restarting at the frames
    the service reported cold (``references/warm_restart.py::walk``)."""
    return {(clip, k, cold): walk(forward, made.frames[clip], k, cold)[k]
            for clip, k, cold in sorted(set(which))}
