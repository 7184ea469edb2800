"""Named-stage tracing: ``stage()`` scopes + the step-window profiler.

RAFT's forward pass is ~10 structurally identical GRU iterations — without
names, an xprof trace is a wall of indistinguishable fusions and nobody can
say *which* stage regressed or recompiled.  ``stage(name)`` wraps
``jax.named_scope`` so the op names XLA emits (and the engine's stage map
records) carry ``raft/fnet``, ``raft/corr_lookup``, ``update/gru`` …
prefixes; it also maintains a thread-local stage stack that
:mod:`watchdogs` reads to attribute recompiles and NaN events to the stage
that produced them.

``TraceWindow`` generalizes the train loop's steps-5-to-8 profiler capture
to any per-step loop (val batches, bench reps, serve device batches):
construct with a trace dir + window, call ``on_step(i)`` once per step, and
the jax.profiler trace starts/stops itself; ``stop()`` in a finally block
covers early exits.

Stages name *device code*; ``host_stage(name)`` names what the *host* does
between device calls (decode, pad, h2d, fetch, deliver …): one call site
yields the request span, a ``jax.profiler.TraceAnnotation`` on the device
trace's clock and the stage's wall and CPU seconds (``StageCounters``: a
stage that runs and a stage that waits for the interpreter lock differ in
the second, and one that stood still for seconds says so).
``instruction_stages`` turns the ``op_name`` metadata of a compiled
executable's text into an instruction -> ``stage()`` path map, because the
chip's trace events carry the instruction's name and no scope.
:mod:`spans` names *requests* — ID-carrying spans with parent links and
status threaded through the serving plane (queue wait vs device execute vs
respond, per request).
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
from typing import Callable, Dict, Optional

from . import events as _events

_stack = threading.local()


def _stages() -> list:
    if not hasattr(_stack, "names"):
        _stack.names = []
    return _stack.names


def current_stage() -> Optional[str]:
    """Innermost active ``stage()`` name on this thread (provenance for the
    watchdogs), or None outside any stage."""
    names = _stages()
    return names[-1] if names else None


@contextlib.contextmanager
def stage(name: str):
    """``jax.named_scope(name)`` + provenance bookkeeping.

    Usable both as a context manager around trace-time code and (because
    named_scope supports it) as a decorator.  Zero-dependency fallback:
    when jax is unimportable the scope is a no-op but the provenance stack
    still works, so host-side tooling can reuse it.
    """
    names = _stages()
    names.append(name)
    try:
        try:
            import jax
            scope = jax.named_scope(name)
        except ImportError:
            scope = contextlib.nullcontext()
        with scope:
            yield
    finally:
        names.pop()


# -- host stages ------------------------------------------------------------
#
# annotation name -> request span name (OBSERVABILITY.md "Host stages").
# The counter label is the annotation name less its ``raft.`` prefix.  A
# stage with no span (``take``: the batcher waits for requests, which belongs
# to no request) still writes its annotation and counter.  ``raft.stream.*``
# are a batched advance's host chain between its two device calls
# (serving/stream.py ``_finish_warm``), children of ``execute`` as the
# engine's stages are.  ``raft.stream.cold.*`` are a cold restart's
# (``finish``, ``_cold_advance``): the wait for the batch staged behind the
# group, the previous frame's encoder pass, the solo step with its sentinel,
# the projection and the row's commit; a group without a cold row opens none.
HOST_STAGES: Dict[str, Optional[str]] = {
    "raft.http.decode": "decode",
    "raft.http.admit": "admit",
    "raft.batch.take": None,
    "raft.batch.form": "batch_form",
    "raft.batch.pad": "pad",
    "raft.engine.h2d": "execute_h2d",
    "raft.engine.dispatch": "execute_dispatch",
    "raft.engine.wait": "execute_block",
    "raft.engine.fetch": "execute_fetch",
    "raft.stream.sentinel": "execute_sentinel",
    "raft.stream.seed": "execute_seed",
    "raft.stream.commit": "execute_commit",
    "raft.stream.cold.wait": "execute_cold_wait",
    "raft.stream.cold.encode": "execute_cold_encode",
    "raft.stream.cold.step": "execute_cold_step",
    "raft.stream.cold.attach": "execute_cold_attach",
    "raft.batch.deliver": "deliver",
    "raft.http.encode": "encode",
    "raft.http.respond": "respond",
}


def set_batch(ordinal: Optional[int]) -> None:
    """The device batch this thread is working on (set by the batcher): every
    ``host_stage`` opened on the thread carries it as ``batch=<n>``."""
    _stack.batch = ordinal


class HostStage:
    """One timed host stage; ``t0``/``t1`` are ``time.monotonic()`` and
    ``c0``/``c1`` ``time.thread_time()`` at entry and exit, ``span`` the
    request-span name of the stage (or None).  The thread's CPU clock counts
    its user and system time whether or not it holds the interpreter lock
    (numpy's ``copyto`` and ``isfinite`` release it and still run here) and
    stands still while the thread sleeps on that lock, on a registry lock or
    in the run queue: wall less CPU of a stage that waits for no device and
    no socket is its lock and scheduler wait.  ``holds``: the stage is a
    container, and the stages its thread opens inside it are its parts (the
    batcher nests their spans under its span by their times)."""

    __slots__ = ("name", "span", "holds", "t0", "t1", "c0", "c1")

    def __init__(self, name: str, holds: bool = False):
        self.name = name
        self.span = HOST_STAGES[name]
        self.holds = holds
        self.t0 = self.t1 = self.c0 = self.c1 = 0.0

    @property
    def label(self) -> str:
        return self.name[5:]              # less 'raft.'

    @property
    def wall(self) -> float:
        return self.t1 - self.t0

    @property
    def cpu(self) -> float:
        return self.c1 - self.c0


@contextlib.contextmanager
def host_stage(name: str, sink: Optional[Callable] = None,
               holds: bool = False, **attrs):
    """Time one host stage of ``HOST_STAGES`` under a profiler annotation.

    Opens ``jax.profiler.TraceAnnotation(name, batch=<n>, **attrs)`` — with
    no profiler session that is one atomic check — and on exit, also when the
    body raised, stamps ``t1`` and ``c1`` and hands the :class:`HostStage` to
    ``sink`` (the caller's span-and-counter recorder), so one site yields all
    four records.  Yields the stage for callers that place the span
    themselves.  ``holds=True`` declares the stage a container of the stages
    opened inside it (:class:`HostStage`)."""
    st = HostStage(name, holds)
    batch = getattr(_stack, "batch", None)
    if batch is not None:
        attrs.setdefault("batch", batch)
    try:
        import jax
        ann = jax.profiler.TraceAnnotation(name, **attrs)
    except ImportError:
        ann = contextlib.nullcontext()
    with ann:
        st.t0, st.c0 = time.monotonic(), time.thread_time()
        try:
            yield st
        finally:
            st.t1, st.c1 = time.monotonic(), time.thread_time()
            if sink is not None:
                sink(st)


# a stage longer than this stood still: the longest device batch of any
# benchmark cell is 0.87 s, and the longest engine.wait lies under it
STALL_SECONDS = 2.0
# _deliver's non-finite pass, counted inside batch.deliver under a label of
# its own.  No annotation: benchmark/stages.py adds up every raft.batch.*
# annotation's overlap with an idle gap, and a nested one would count twice
SENTINEL = "batch.deliver.sentinel"


class StageCounters:
    """What every sink of a host stage counts, by ``stage=`` label:
    ``wall`` (``raft_serving_stage_seconds_total``), ``cpu``
    (``raft_serving_stage_cpu_seconds_total``) and, for a stage that lasted
    over ``STALL_SECONDS``, ``stalled``
    (``raft_serving_stalled_seconds_total``) with one ``host_stall`` run-log
    event and one ``log_fn`` line (a ``batch.take`` for as long as requests
    were open without a break, by the server's ``tracer``).  The labelled
    children are made up front: every label shows in /metrics from the
    start, and no increment calls ``labels()``, which takes the family's
    lock — the handler threads' stages take it too, and a batcher that
    blocks on a lock gives up the interpreter lock to all of them."""

    def __init__(self, wall, cpu, stalled, log_fn: Optional[Callable] = None,
                 tracer=None):
        self._children = {
            label: (wall.labels(label), cpu.labels(label),
                    stalled.labels(label))
            for label in [name[5:] for name in HOST_STAGES] + [SENTINEL]}
        self.log_fn = log_fn
        self.tracer = tracer              # the server's spans.Tracer

    def record(self, label: str, wall: float, cpu: float) -> None:
        """One finished stage."""
        wall_c, cpu_c, stalled_c = self._children[label]
        wall_c.inc(wall)
        cpu_c.inc(cpu)
        if wall > STALL_SECONDS:
            self._stalled(label, stalled_c, wall, cpu)

    def _stalled(self, label, child, wall, cpu) -> None:
        held, counted = {}, wall
        if label == "batch.take":
            # a take with no request open is an idle server, not a stall:
            # what counts of it is its end, through which the handlers held
            # a request that did not reach the queue
            held_s = self.tracer.held_s() if self.tracer is not None else 0.0
            if held_s <= STALL_SECONDS:
                return
            counted = min(held_s, wall)
            held = {"open_traces": self.tracer.open_traces,
                    "held_s": round(counted, 3)}
        child.inc(counted)
        rec = dict(stage=label, wall_s=round(wall, 3), cpu_s=round(cpu, 3),
                   batch=getattr(_stack, "batch", None),
                   thread=threading.current_thread().name, **held)
        log = _events.current()
        if log is not None:
            log.event("host_stall", **rec)
        if self.log_fn is not None:
            self.log_fn("host stage stood still: " + " ".join(
                f"{k}={v}" for k, v in rec.items()))


# -- instruction -> stage map -----------------------------------------------

# op_name segments that say how the code was traced, not which stage() it is in
_STRUCTURAL = re.compile(
    r"^(jit|pjit|vmap|jvp|transpose|remat|checkpoint|custom_jvp_call|"
    r"custom_vjp_call|custom_vjp_call_jaxpr|closed_call|core_call|while|"
    r"body|cond|body_fun|cond_fun|scan|branch_\d+(_fun)?|shard_map)(\(.*\))?$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%?([\w.\-]+)$")
STAGE_MAP_VERSION = 1
TEXT_HEAD = 120          # characters of an instruction's text kept in the map


def stage_path(op_name: str) -> str:
    """``jit(fn)/while/body/closed_call/raft/corr_lookup/l0/dot_general`` ->
    ``raft/corr_lookup/l0``: the ``stage()`` scopes around the primitive."""
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts if not _STRUCTURAL.match(p))


def _operands(rest: str) -> list:
    """Names of an instruction's operands from ``shape opcode(a, b), ...``."""
    m = re.search(r" [a-z][\w\-]*\(", rest)
    if not m:
        return []
    depth, out, cur = 1, [], []
    for ch in rest[m.end():]:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                break
        if ch == "," and depth == 1:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    names = []
    for tok in out:
        m = _OPERAND.search(tok.strip())
        if m:
            names.append(m.group(1))
    return names


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_TARGETS = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def instruction_stages(hlo_text: str) -> Dict[str, dict]:
    """``{instruction name: {"stage": path, "loop": depth, "text": head of
    its line}}`` for every instruction of a compiled module's text that can
    run as an operation of its own: those of the entry computation, of loop
    bodies and conditions, of branches and called computations — not those
    inside fused computations and reducers (a fusion's ``calls=``, a
    reduce's ``to_apply=``), which the device runs as part of their caller.

    The stage is the ``stage_path`` of the instruction's ``op_name``.  An
    instruction the compiler made and gave no ``op_name`` takes a stage from
    what it belongs to: a fusion its root's, anything else (a layout
    ``convert``, a ``copy``, a ``bitcast``) the stage of its first operand
    that has one.  ``loop`` is how many ``while`` bodies enclose the
    instruction: it runs once per program run at 0, once per iteration at
    1."""
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            cur.append((bool(m.group(1)), m.group(2), m.group(3)))
    fused = {}                            # fusion -> its fused computation
    inner = set()                         # computations run inside a caller
    runs_in: Dict[str, tuple] = {}        # computation -> (caller's, +depth)
    for comp, insts in comps.items():
        for _root, name, rest in insts:
            op = _OPCODE.search(rest)
            op = op.group(1) if op else ""
            for kind, target, branches in _TARGETS.findall(rest):
                if branches:
                    for t in branches.split(","):
                        runs_in[t.strip().lstrip("%")] = (comp, 0)
                elif op == "while":
                    runs_in[target] = (comp, 1)
                elif op in ("call", "conditional"):
                    runs_in[target] = (comp, 0)
                else:
                    inner.add(target)
                    if kind == "calls":
                        fused[name] = target

    def depth(comp: str, seen=()) -> int:
        if comp not in runs_in or comp in seen:
            return 0
        parent, add = runs_in[comp]
        return add + depth(parent, seen + (comp,))

    stages: Dict[str, str] = {}

    def resolve(comp: str) -> None:
        for _root, name, rest in comps.get(comp, ()):
            m = _OP_NAME.search(rest)
            if m:
                st = stage_path(m.group(1))
            elif name in fused:
                target = fused[name]
                if target in comps and not any(
                        n in stages for _r, n, _t in comps[target]):
                    resolve(target)
                st = next((stages.get(n, "") for r, n, _t
                           in comps.get(target, ()) if r), "")
            else:
                st = next((stages[o] for o in _operands(rest)
                           if stages.get(o)), "")
            stages[name] = st

    for comp in comps:
        if comp not in inner:
            resolve(comp)
    return {name: {"stage": stages.get(name, ""), "loop": depth(comp),
                   "text": f"%{name} = {rest}"[:TEXT_HEAD]}
            for comp, insts in comps.items() if comp not in inner
            for _root, name, rest in insts}


# -- profiler captures ------------------------------------------------------

def profile_options():
    """The options of every profiler capture this program starts
    (``TraceWindow``, ``POST /debug/profile``).  Python tracer OFF: it
    records every call of every thread, and the program's own stages are in
    the trace as ``raft.*`` annotations.  Host tracer level 2; level 1 costs
    the same on the chip (8 s of the served raft-things: 214 MB and 130-139 s
    of ``stop_trace`` under either, PERF.md PR 24), because what fills a
    capture is the runtime's own events — four million ``Transpose`` chunk
    events of the float32 batch's relayout in 5 s — which both levels
    hold."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


class TraceWindow:
    """Start/stop a jax.profiler trace over a step window.

    ``TraceWindow(dir, first, steps)`` traces steps ``[first, first+steps)``
    — call ``on_step(i)`` before executing step ``i``; returns True while
    tracing.  A ``trace_dir`` of None makes every call a no-op, so call
    sites need no conditionals.  ``stop()`` is idempotent and must run on
    every exit path (the profiler otherwise holds its buffer forever).
    """

    def __init__(self, trace_dir: Optional[str], first: int = 2,
                 steps: int = 4, log_fn=None):
        self.trace_dir = trace_dir
        self.first = first
        self.last = first + steps          # exclusive
        self._tracing = False
        self._done = trace_dir is None
        self._log = log_fn or (lambda msg: None)

    def on_step(self, step: int) -> bool:
        if self._done:
            return False
        if not self._tracing and self.first <= step < self.last:
            import jax
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=profile_options())
            self._tracing = True
        elif self._tracing and step >= self.last:
            self.stop()
        return self._tracing

    def stop(self) -> None:
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False
            self._done = True
            self._log(f"wrote profiler trace to {self.trace_dir}")


class CaptureBusy(RuntimeError):
    """A profiler capture is already in flight (the profiler is a
    process-wide singleton — two concurrent start_trace calls corrupt
    each other's XPlane output).  HTTP maps this to 409."""


# jax.profiler.start_trace/stop_trace share one process-global profiler:
# the on-demand capture endpoint must single-flight across ALL servers in
# the process (tests run several), not per FlowServer.
_capture_lock = threading.Lock()

MAX_CAPTURE_MS = 60_000.0


def capture_profile(trace_dir: Optional[str], duration_ms: float,
                    log_fn=None) -> dict:
    """Time-boxed on-demand ``jax.profiler`` capture: start a trace, sleep
    ``duration_ms`` while the serving threads keep working, stop, return
    ``{"trace_dir", "duration_ms", "started"}`` — the TraceWindow
    semantics keyed by wall time instead of step count, for profiling a
    LIVE replica (POST /debug/profile) without a restart.

    Single-flight via a process-wide non-blocking lock (:class:`CaptureBusy`
    when one is already running).  ``trace_dir=None`` allocates a fresh
    temp dir per capture; each capture lands in a timestamped subdirectory
    so repeated captures never collide."""
    if not 0 < duration_ms <= MAX_CAPTURE_MS:
        raise ValueError(f"duration_ms must be in (0, {MAX_CAPTURE_MS:g}], "
                         f"got {duration_ms}")
    if not _capture_lock.acquire(blocking=False):
        raise CaptureBusy("a profiler capture is already running")
    try:
        import os
        import tempfile
        started = time.time()
        if trace_dir is None:
            dest = tempfile.mkdtemp(prefix="raft-profile-")
        else:
            dest = os.path.join(trace_dir, time.strftime(
                "%Y%m%dT%H%M%S", time.gmtime(started)))
            os.makedirs(dest, exist_ok=True)
        import jax
        jax.profiler.start_trace(dest, profiler_options=profile_options())
        try:
            time.sleep(duration_ms / 1000.0)
        finally:
            jax.profiler.stop_trace()
        if log_fn is not None:
            log_fn(f"on-demand profiler capture: {duration_ms:g}ms "
                   f"-> {dest}")
        return {"trace_dir": dest, "duration_ms": duration_ms,
                "started": round(started, 3)}
    finally:
        _capture_lock.release()
