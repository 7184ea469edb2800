"""The load driver ``sessions``: video sessions through ``POST /v1/stream``.

RAFT's video protocol as a client runs it (Teed & Deng, ECCV 2020, the
warm-start rows of the Sintel evaluation): open a session with a clip's first
frame, then post frame k only once frame k-1 has been answered; each answer
is flow(frame k-1 -> frame k), and the server starts its recurrence from the
previous answer projected forward.  Every frame is uploaded once.

* ``clips`` seeded clips of the mix's one size: a canvas of ``inputs.py``'s
  noise moving at a constant velocity of at most ``max_shift`` px a frame,
  with a little sensor noise; every body is encoded once, before the window
  (an advance's body holds its session's id, 32 hex digits: they are written
  into a copy of the frame's encoded body, :func:`advance_body`);
* ``clients`` (the cell's file) sessions live at once, closed loop, lockstep
  inside a session.  The j-th session opened plays clip ``(seed + j) %
  clips``; a session lasts ``session_frames`` frames, uniform from the seed,
  a client's first ``first_session_frames`` so that sessions do not end
  together; at its end the client closes it and opens the next;
* the window's first requests are the opens: the warm-up's sessions are
  closed before it.  ``pairs_per_s`` is the advances answered 200, by
  ``loadgen.summarize``'s share rule for those in flight at the close; opens
  and closes count in ``attempted`` and ``failed`` and never in the rate;
* the kept answers are advances of different sessions opened at the window's
  start, one at each of the mix's ``kept_frames`` (index 1 is seeded with
  zeros and equals a pair; from 2 on the seed is the projected flow); the
  reference walks the answer's clip from frame 0, projecting its own 1/8
  flow into the next call's ``flow_init`` (``references/warm.py``).

The mix is for a program that serves ``/v1/stream`` on its measured path: a
stream request timed stage by stage as a pair is (``decode``, ``pad``,
``h2d``, ``deliver``, ``encode``, and ``raft.stream.*`` between a batch's two
device calls), which is what the cell's per-layer metrics read.  On a program
without those stages the accepted metrics that list the cell find nothing to
read, and its host chain, under the interpreter lock and outside every stage,
spreads the rate over the bound (4.2 % over six runs: the driver's check of
PR 39, PERF.md §6), so it is no yardstick for the cell.
:func:`require_measured_stream` says so and ends the run when the module is
loaded, before the server is built: one line, ``SystemExit``, no result line,
as ``run.load_named`` does for a name with no file.  It is the one place
besides ``system.py`` where the benchmark imports the program.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import io
import math
import random
import struct
import threading
import time
import zipfile
import zlib

import numpy as np

import inputs
import loadgen
from references.warm import forward_interpolate

ID_CHARS = 32                     # a session id: uuid4().hex
PLACEHOLDER = "0" * ID_CHARS
STREAM_STAGES = ("raft.stream.sentinel", "raft.stream.seed",
                 "raft.stream.commit")


def require_measured_stream(host_stages=None) -> None:
    """End the run unless the program times a batched advance's host chain
    (``telemetry/trace.py::HOST_STAGES`` names ``raft.stream.*``)."""
    if host_stages is None:
        from raft_tpu.telemetry.trace import HOST_STAGES as host_stages
    missing = [s for s in STREAM_STAGES if s not in host_stages]
    if missing:
        raise SystemExit(
            "benchmark: the load driver 'sessions' needs a program whose "
            "/v1/stream is on the measured path, and this one has no host "
            f"stage {', '.join(missing)}: the cell cannot be read on it")


require_measured_stream()


@dataclasses.dataclass
class Made:
    frames: list                 # [clip][k] uint8 [H, W, 3], the first few
    opens: list                  # [clip] the open's body: frame 0
    advances: list               # [clip][k - 1] AdvanceBody of frame k


@dataclasses.dataclass
class AdvanceBody:
    """An advance's npz body with a placeholder for the session's id, and
    where the id and its member's checksum lie in it."""
    template: bytes
    id_at: int                   # first byte of the id's UCS-4 characters
    member: tuple                # (start, end) of session.npy's data
    crc_at: tuple                # its CRC-32 in the local and central records


@dataclasses.dataclass
class Window:
    records: list                # loadgen.Record of every request, with
    t0: float                    # .op, .session (j), .clip and .frame
    t1: float
    keep: dict                   # {(session j, frame index): its clip}


# ------------------------------------------------------------------ inputs

def _npy(arr) -> bytes:
    buf = io.BytesIO()
    np.lib.format.write_array(buf, np.asarray(arr), allow_pickle=False)
    return buf.getvalue()


def encode_advance(frame: np.ndarray) -> AdvanceBody:
    """The body of an advance that posts ``frame``: ``session`` (a 0-d
    ``<U32`` array) then ``image``, stored."""
    sink = io.BytesIO()
    with zipfile.ZipFile(sink, "w", zipfile.ZIP_STORED) as zf:
        zf.writestr(zipfile.ZipInfo("session.npy"),
                    _npy(np.asarray(PLACEHOLDER)))
        zf.writestr(zipfile.ZipInfo("image.npy"), _npy(frame))
        start_dir = zf.start_dir
    template = sink.getvalue()
    with zipfile.ZipFile(io.BytesIO(template)) as zf:
        info = zf.infolist()[0]
        assert info.filename == "session.npy" and info.header_offset == 0
    n_name, n_extra = struct.unpack_from("<HH", template, 26)
    start = 30 + n_name + n_extra
    end = start + info.file_size
    return AdvanceBody(template, end - 4 * ID_CHARS, (start, end),
                       (14, start_dir + 16))


def advance_body(enc: AdvanceBody, sid: str) -> bytearray:
    """A copy of the encoded body with ``sid`` in the placeholder's place."""
    if len(sid) != ID_CHARS:
        raise ValueError(f"a session id of {ID_CHARS} characters, got {sid!r}")
    body = bytearray(enc.template)
    body[enc.id_at:enc.id_at + 4 * ID_CHARS] = sid.encode("utf-32-le")
    crc = zlib.crc32(body[enc.member[0]:enc.member[1]])
    for at in enc.crc_at:
        struct.pack_into("<I", body, at, crc)
    return body


def make_clip(seed: int, clip: int, n_frames: int, height: int, width: int,
              max_shift: int) -> list:
    """``n_frames`` ``uint8`` frames: a window gliding over a seeded canvas
    at a constant velocity (never zero), each frame with sensor noise of its
    own (a crop of one noise field, at an offset that differs by frame)."""
    rng = np.random.default_rng([int(seed), 0x5E55, clip])
    vx = vy = 0
    while vx == 0 and vy == 0:
        vx = int(rng.integers(-max_shift, max_shift + 1))
        vy = int(rng.integers(-max_shift // 2, max_shift // 2 + 1))
    span_x, span_y = abs(vx) * (n_frames - 1), abs(vy) * (n_frames - 1)
    canvas = inputs.make_canvas(rng, height + span_y, width + span_x)
    noise = rng.standard_normal((height + 64, width + 64, 3),
                                dtype=np.float32) * np.float32(0.01)
    frames = []
    for k in range(n_frames):
        x = k * vx if vx > 0 else span_x + k * vx
        y = k * vy if vy > 0 else span_y + k * vy
        ny, nx = (k * 37) % 64, (k * 23) % 64
        f = (canvas[y:y + height, x:x + width]
             + noise[ny:ny + height, nx:nx + width])
        frames.append(np.clip(f * 255.0 + 0.5, 0, 255).astype(np.uint8))
    return frames


def make_inputs(seed: int, traffic: dict, frames: int = None) -> Made:
    """Every clip and every body, from the seed and the mix: a clip a
    thread, beside the server's warm-up.  ``frames``: a clip's length, where
    only its first frames are wanted (``control_sessions.py``)."""
    n_clips = int(traffic["clips"])
    n_frames = frames or int(traffic["session_frames"][1])
    h, w = int(traffic["height"]), int(traffic["width"])
    kept = max(traffic["kept_frames"]) + 1

    def one(clip):
        fs = make_clip(seed, clip, n_frames, h, w, int(traffic["max_shift"]))
        return (fs[:kept], inputs.npz_body(image=fs[0]),
                [encode_advance(f) for f in fs[1:]])

    with concurrent.futures.ThreadPoolExecutor(n_clips) as pool:
        made = list(pool.map(one, range(n_clips)))
    return Made(*(list(col) for col in zip(*made)))


# ---------------------------------------------------------------- the loop

def session_lengths(seed: int, client: int, traffic: dict):
    """Frames of a client's first session, its second, ...: from the seed."""
    rng = random.Random((int(seed) << 8) ^ 0x5E55 ^ client)
    yield rng.randint(*traffic["first_session_frames"])
    while True:
        yield rng.randint(*traffic["session_frames"])


def _walk(sut, made: Made, seed: int, traffic: dict, cell: dict,
          seconds: float, keep: dict):
    """``clients`` threads, each a session at a time, until ``seconds`` have
    passed; a client closes the session it holds before it leaves.
    -> (records, t_start, t_end)."""
    clients = int(cell["clients"])
    n_clips = len(made.opens)
    records, lock = [], threading.Lock()
    opened = [0]
    t_end = [math.inf]
    barrier = threading.Barrier(clients + 1)

    def post(conn, op, body, j, clip, k):
        with lock:
            rec = loadgen.Record(len(records), k, time.monotonic())
            records.append(rec)
        rec.op, rec.session, rec.clip, rec.frame = op, j, clip, k
        conn.one(rec, body, op == "open" or (j, k) in keep)
        return rec

    def client(c, conn):
        lengths = session_lengths(seed, c, traffic)
        barrier.wait()
        while time.monotonic() < t_end[0]:
            with lock:
                j = opened[0]
                opened[0] += 1
            clip = (seed + j) % n_clips
            rec = post(conn, "open", made.opens[clip], j, clip, 0)
            if rec.status != 200:
                time.sleep(0.05)    # (a server that refuses is not hammered)
                continue
            sid = str(inputs.npz_load(rec.payload)["session"])
            rec.payload = None
            for k in range(1, next(lengths)):
                if time.monotonic() >= t_end[0]:
                    break
                rec = post(conn, "advance",
                           advance_body(made.advances[clip][k - 1], sid),
                           j, clip, k)
                if rec.status != 200:
                    break       # a session does not go on past a lost frame
            post(conn, "close", inputs.npz_body(op=np.asarray("close"),
                                                session=np.asarray(sid)),
                 j, clip, -1)
        conn.close()

    threads = [threading.Thread(target=client, args=(c, conn),
                                name=f"load-{c}", daemon=True)
               for c, conn in enumerate(loadgen.Client.connected(
                   clients, sut.host, sut.port, traffic["endpoint"], 60.0))]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.monotonic()
    t_end[0] = t0 + seconds
    for t in threads:
        t.join()
    return records, t0, t0 + seconds


def warm_up(sut, made: Made, seed: int, traffic: dict, cell: dict,
            seconds: float) -> None:
    """The request path's warm-up, part of set-up: the window's own clients
    for ``seconds``, nothing kept, every session closed at its end."""
    _walk(sut, made, seed, traffic, cell, seconds, {})


def run_window(sut, made: Made, seed: int, traffic: dict, cell: dict,
               seconds: float, n_keep: int) -> Window:
    """Offer the mix's load for ``seconds``; keep ``n_keep`` advances, of
    different sessions among those opened at the window's start, at the
    mix's ``kept_frames`` in turn."""
    clients = int(cell["clients"])
    at = [int(k) for k in traffic["kept_frames"]]
    sessions = random.Random(seed ^ 0xC0FFEE).sample(
        range(clients), min(n_keep, clients))
    keep = {(j, at[i % len(at)]): (seed + j) % len(made.opens)
            for i, j in enumerate(sessions)}
    records, t0, t1 = _walk(sut, made, seed, traffic, cell, seconds, keep)
    return Window(records, t0, t1, keep)


def summarize(win: Window) -> dict:
    """``loadgen.summarize`` over the advances (the rate, the latencies),
    with every request of the window in ``attempted`` and ``failed``."""
    out = loadgen.summarize([r for r in win.records if r.op == "advance"],
                            win.t0, win.t1, "closed")
    everything = loadgen.summarize(win.records, win.t0, win.t1, "closed")
    out["advances_attempted"] = out["attempted"]
    out.update(attempted=everything["attempted"], failed=everything["failed"],
               opens=sum(1 for r in win.records if r.op == "open"
                         and r.status == 200),
               closes=sum(1 for r in win.records if r.op == "close"
                          and r.status == 200))
    return out


def kept_answers(win: Window) -> list:
    """[(ordinal, (clip, frame index), flow array or None)] of the kept
    advances; a kept advance that was never sent (its session lost a frame
    before it) is an answer that is missing."""
    found = {(r.session, r.frame): r for r in win.records
             if r.op == "advance" and (r.session, r.frame) in win.keep}
    out = []
    for (j, k), clip in win.keep.items():
        r = found.get((j, k))
        flow = (inputs.npz_load(r.payload)["flow"]
                if r is not None and r.payload else None)
        out.append((r.ordinal if r is not None else -1 - j, (clip, k), flow))
    return out


def reference_answers(forward, made: Made, which) -> dict:
    """{(clip, frame index): the reference's answer}: each clip walked from
    frame 0 as far as its last kept index, the reference's own 1/8 flow
    projected into the next call's ``flow_init``.  ``forward(image1, image2,
    flow_init=...)`` is ``references/warm.py``'s ``flow`` with the weights,
    the sizes and the precision bound (``check.forward``)."""
    out = {}
    for clip in sorted({c for c, _ in which}):
        frames, flow_init = made.frames[clip], None
        for k in range(1, max(k for c, k in which if c == clip) + 1):
            flow, flow_lr = forward(frames[k - 1], frames[k],
                                    flow_init=flow_init)
            flow_init = forward_interpolate(np.asarray(flow_lr))
            if (clip, k) in which:
                out[clip, k] = np.asarray(flow)
    return out
