"""The load generator and the arithmetic on what it records.

One general generator, driven by a traffic file: a ``closed`` loop (each of
``clients`` threads sends its next request when the last one is answered, for
the whole window) or an ``open`` loop (requests are due on a schedule fixed
before the window, whether or not earlier ones have been answered).  Both
start from the loops of ``tools/serve_bench.py`` and mend their two faults: a
request of the open loop is timed from when it was DUE, not from when a
worker got round to it, and how late the generator ran is reported; and a run
ends after a WINDOW of seconds, not after a count.

Client threads speak keep-alive HTTP/1.1 with ``http.client``; sending and
receiving release the GIL, and a client never decodes an answer inside the
window (the few payloads kept for the output check stay bytes until it has
closed).
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import math
import queue
import random
import threading
import time
from typing import List, Optional

HEADERS = {"Content-Type": "application/octet-stream",
           "Accept": "application/octet-stream"}


@dataclasses.dataclass
class Record:
    ordinal: int                 # order in which the generator issued it
    body: int                    # which pre-encoded body was sent
    due: float                   # when it was due (monotonic seconds)
    sent: float = math.nan       # when the send began
    done: float = math.nan       # when the last byte of the answer arrived
    status: int = 0              # HTTP status; -1: transport error; 0: unsent
    timings: Optional[dict] = None   # the server's X-Raft-Timings, ms
    payload: Optional[bytes] = None  # kept only for sampled ordinals


# ---------------------------------------------------------------- schedules

def exponential_gaps(n: int, rate: float) -> List[float]:
    """The ``n`` quantile mid-points of an exponential distribution with mean
    ``1/rate``: a fixed multiset of gaps, Poisson-like once shuffled."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def open_schedule(seed: int, rate: float, seconds: float,
                  traffic: dict) -> List[float]:
    """Due times (seconds from the window's start) of an open loop.

    The window is cut into blocks of about ``block_s`` seconds.  Block k's
    arrivals are a fixed Poisson-like pattern: the exponential quantile gaps
    for its share of the requests, shuffled by ``pattern_seed + k`` from the
    traffic file, NOT by the run's seed.  The run's seed only permutes the
    order of the blocks.  So every seed offers the same bursts, the same lulls
    and the same number of requests, in another order, and a tail that
    differs between two seeds was not made by the schedule."""
    arrivals = traffic.get("arrivals", "poisson")
    blocks = max(1, int(seconds // float(traffic.get("block_s", 2.0))))
    block_len = seconds / blocks
    n = int(math.floor(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds}s schedules no request")
    counts = [n // blocks + (1 if k < n % blocks else 0) for k in range(blocks)]
    patterns = []
    for k, cnt in enumerate(counts):
        if cnt == 0:
            patterns.append([])
            continue
        if arrivals == "uniform":
            gaps = [1.0] * cnt
        elif arrivals == "poisson":
            gaps = exponential_gaps(cnt, 1.0)
            random.Random(int(traffic.get("pattern_seed", 0)) + k).shuffle(gaps)
        else:
            raise ValueError(f"arrivals {arrivals!r}")
        # the mid-point gaps sum to a little under cnt: stretch them so the
        # block's last request is due half a mean gap before the block ends
        scale = block_len * (cnt - 0.5) / cnt / sum(gaps)
        t, offs = 0.0, []
        for g in gaps:
            t += g * scale
            offs.append(t)
        patterns.append(offs)
    order = list(range(blocks))
    random.Random(seed).shuffle(order)
    due = []
    for slot, k in enumerate(order):
        due.extend(slot * block_len + t for t in patterns[k])
    return due


def sample_ordinals(seed: int, k: int, lo: int, hi: int) -> List[int]:
    """``k`` distinct request ordinals in [lo, hi) drawn from the seed."""
    hi = max(hi, lo + 1)
    return sorted(random.Random(seed ^ 0xC0FFEE).sample(
        range(lo, hi), min(k, hi - lo)))


# ------------------------------------------------------------------ clients

class Client:
    """One keep-alive connection."""

    def __init__(self, host: str, port: int, path: str, timeout: float):
        self.host, self.port, self.path, self.timeout = host, port, path, timeout
        self.conn = http.client.HTTPConnection(host, port, timeout=timeout)

    @classmethod
    def connected(cls, n: int, host, port, path, timeout) -> list:
        """``n`` clients, connected ONE AFTER ANOTHER, before any load.  The
        program's ``ThreadingHTTPServer`` listens with a backlog of 5: 64
        threads that open their connections at the same instant overflow it,
        and the kernel's retries (1, 3, 7, 15, 31 s) showed as requests that
        took 45 s and as clients that timed out (my chip run, PR 23)."""
        out = []
        for _ in range(n):
            c = cls(host, port, path, timeout)
            c.conn.connect()
            out.append(c)
        return out

    def one(self, rec: Record, body: bytes, keep: bool) -> None:
        rec.sent = time.monotonic()
        try:
            self.conn.request("POST", self.path, body=body, headers=HEADERS)
            resp = self.conn.getresponse()
            payload = resp.read()
            rec.done = time.monotonic()
            rec.status = resp.status
            hdr = resp.getheader("X-Raft-Timings")
            if hdr:
                try:
                    rec.timings = json.loads(hdr)
                except ValueError:
                    pass
            if keep and resp.status == 200:
                rec.payload = payload
        except Exception:
            rec.done = time.monotonic()
            rec.status = -1
            self.conn.close()
            self.conn = http.client.HTTPConnection(self.host, self.port,
                                                   timeout=self.timeout)

    def close(self) -> None:
        self.conn.close()


def run_closed(host, port, path, bodies, seed, clients, seconds, keep,
               timeout=60.0):
    """``clients`` threads, back to back, until ``seconds`` have passed.
    -> (records, t_start, t_end).  Ordinal i sends body (seed + i) % n."""
    records: List[Record] = []
    lock = threading.Lock()
    counter = [0]
    keep = set(keep)
    barrier = threading.Barrier(clients + 1)
    t_end_box = [math.inf]

    def worker(c):
        barrier.wait()
        while True:
            with lock:
                if time.monotonic() >= t_end_box[0]:
                    break
                i = counter[0]
                counter[0] += 1
                rec = Record(i, (seed + i) % len(bodies), time.monotonic())
                records.append(rec)
            c.one(rec, bodies[rec.body], i in keep)
        c.close()

    threads = [threading.Thread(target=worker, args=(c,), name=f"load-{k}",
                                daemon=True)
               for k, c in enumerate(Client.connected(clients, host, port,
                                                      path, timeout))]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.monotonic()
    t_end_box[0] = t0 + seconds
    for t in threads:
        t.join()
    return records, t0, t0 + seconds


def run_open(host, port, path, bodies, seed, due, seconds, workers, keep,
             timeout=60.0, drain_s=30.0):
    """Requests are due at ``t0 + due[i]``; a pool of ``workers`` connections
    sends each as soon as it is due and a connection is free.  Latency counts
    from the due time, so a starved pool shows as latency and as lateness,
    never as a lighter load.  -> (records, t_start, t_end)."""
    records = [Record(i, (seed + i) % len(bodies), 0.0)
               for i in range(len(due))]
    keep = set(keep)
    jobs: "queue.Queue" = queue.Queue()
    barrier = threading.Barrier(workers + 1)

    def worker(c):
        barrier.wait()
        while True:
            rec = jobs.get()
            if rec is None:
                break
            c.one(rec, bodies[rec.body], rec.ordinal in keep)
        c.close()

    threads = [threading.Thread(target=worker, args=(c,), name=f"load-{k}",
                                daemon=True)
               for k, c in enumerate(Client.connected(workers, host, port,
                                                      path, timeout))]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.monotonic()
    for rec, d in zip(records, due):
        rec.due = t0 + d
        delay = rec.due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        jobs.put(rec)
    for _ in threads:
        jobs.put(None)
    deadline = t0 + seconds + drain_s
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    return records, t0, t0 + seconds


# ---------------------------------------------------------------- arithmetic

def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100]) of a non-empty list."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def summarize(records, t0: float, t_end: float, loop: str) -> dict:
    """Everything the end-to-end metrics and the ``loadgen`` reader take from
    a window.  A request is ``ok`` when it was answered 200; in a closed loop
    it counts towards the rate only if the answer arrived inside the window.
    A pair still in flight when the window closes, and answered 200 after
    it, counts by the share of its time in the system that lay inside the
    window: answers come a device batch at a time (32 pairs every 2.7 s in
    the first cell), so whole answers alone cut the rate into steps of one
    batch in fifteen, 7 %, and a clock stopped at the last answer reads 5 %
    low whenever the window closes part-way through a batch's answers (my
    chip runs, PR 23).  Work and time are the whole window's, ramp-up
    included; ``pairs_per_nominal_s`` is the whole answers alone.
    Latency is answer time minus DUE time, over every request answered 200,
    however late; anything else is a failure and has no latency."""
    seconds = t_end - t0
    attempted = [r for r in records if r.status != 0 or not math.isnan(r.sent)]
    ok = [r for r in attempted if r.status == 200]
    in_window = [r for r in ok if r.done <= t_end]
    credit = sum((t_end - r.sent) / (r.done - r.sent) for r in ok
                 if r.sent < t_end < r.done)
    out = {
        "loop": loop,
        "window_s": seconds,
        "attempted": len(attempted) if loop == "closed" else len(records),
        "ok": len(ok),
        "ok_in_window": len(in_window),
        "failed": (len(attempted) - len(ok) if loop == "closed"
                   else len(records) - len(ok)),
        "in_flight_credit": credit,
        "pairs_per_s": (len(in_window) + credit) / seconds,
        "pairs_per_nominal_s": len(in_window) / seconds,
        "completed_per_s": len(ok) / max(
            max((r.done for r in ok), default=t_end) - t0, seconds),
    }
    if loop == "closed":
        # requests cut off by the window's end were still in flight: not
        # failures, and not answers inside the window either
        cut = [r for r in attempted if r.status == 200 and r.done > t_end]
        out["in_flight_at_end"] = len(cut)
    if ok:
        lat = [(r.done - r.due) * 1e3 for r in ok]
        late = [(r.sent - r.due) * 1e3 for r in ok]
        out.update(latency_p50_ms=percentile(lat, 50),
                   latency_p95_ms=percentile(lat, 95),
                   latency_max_ms=max(lat),
                   gen_late_p95_ms=percentile(late, 95),
                   gen_late_max_ms=max(late))
    if loop == "open":
        out["offered_per_s"] = len(records) / seconds
    # answers per tenth of the window, to see by eye whether the rate held
    tenths = [0] * 10
    for r in in_window:
        tenths[min(9, int(10 * (r.done - t0) / seconds))] += 1
    out["answers_per_tenth"] = tenths
    # when each answer came and when each pair in flight at the close was
    # sent and answered, ms from the window's start: the rate can be worked
    # out again from a run's log under any other rule
    out["answer_ms"] = [round((r.done - t0) * 1e3) for r in in_window]
    out["late_ms"] = [[round((r.sent - t0) * 1e3), round((r.done - t0) * 1e3)]
                      for r in ok if r.done > t_end]
    return out
