"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a TPU v5e's
trace holds (looked at by hand on the chip, PR 23):

* plane ``/device:TPU:<n>``, line ``XLA Modules``: one event per executed
  program, named ``jit_fn(<fingerprint>)``;
* same plane, line ``XLA Ops``: one event per executed HLO instruction, NAMED
  BY THE INSTRUCTION'S WHOLE TEXT (``%corr_lookup.35 = f32[4,7040,9,9]{...}
  custom-call(...)``), start and duration in nanoseconds, and no statistic
  that carries the ``jax.named_scope`` path.  A ``while`` is one event that
  CONTAINS the events of its body, so events nest;
* plane ``/host:CPU``: one line per host thread, with the profiler's own
  host events (``TraceMe``/``TraceAnnotation``) and, when the Python tracer
  is on, every Python call;
* plane ``Task Environment``: wall-clock start and stop of the session, which
  includes the seconds ``stop_trace`` itself takes.

So a stage cannot be found by scope today; what can be found is an
instruction by its name (a Pallas kernel is ``%<kernel function>.<n>``, the
update loop is ``%while.<n>``) and a program run.  ``reduce_trace`` clips
everything to the host annotation that brackets the captured window
(``WINDOW_ANNOTATION``, written by run.py around its sleep), so busy seconds
and the window are on one clock.  Nothing here knows a metric's name.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_ANNOTATION = "benchmark_traced_window"
# instructions that only contain others: counted as busy time, but not as
# operations of their own
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")
# host events that say what a thread waits for, not what the host is doing
HOST_NOISE = re.compile(
    r"^(ThreadpoolListener::|\$?<unknown>|\$selectors|\$socket|\$threading"
    r"|\$time sleep|\$queue|" + WINDOW_ANNOTATION + ")")
MIN_HOST_NS = 20_000.0            # shorter host events explain no gap


def op_name(text: str) -> str:
    """'%corr_lookup.35 = f32[4,7040,9,9]{...} custom-call(...)' ->
    'corr_lookup.35'."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def op_label(text: str) -> str:
    """A short label that still says what the instruction is:
    'corr_lookup.35 f32[4,7040,9,9] custom-call'."""
    name = op_name(text)
    m = re.match(r"^%?\S+ = (\(?[a-z0-9]+\[[^\]]*\])[^ ]* ([a-z\-]+)\(", text)
    return f"{name} {m.group(1)} {m.group(2)}" if m else name[:80]


@dataclasses.dataclass
class Op:
    name: str                    # 'corr_lookup.35'
    label: str
    total_ns: float = 0.0
    count: int = 0
    whole_ns: float = 0.0        # the part inside WHOLE program runs


@dataclasses.dataclass
class Trace:
    window_s: float
    devices: Dict[int, dict]     # ordinal -> busy_ns, gaps, ops, modules
    host_events: List[Tuple[float, float, str]]
    clipped: bool = False        # was the window annotation found?

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d["busy_ns"] for d in self.devices.values()) / (
            1e9 * len(self.devices))

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def ops(self) -> List[Op]:
        merged: Dict[str, Op] = {}
        for d in self.devices.values():
            for op in d["ops"].values():
                m = merged.setdefault(op.label, Op(op.name, op.label))
                m.total_ns += op.total_ns
                m.count += op.count
                m.whole_ns += op.whole_ns
        return list(merged.values())

    def select(self, pattern: str) -> List[Op]:
        """The instructions whose NAME matches (containers included)."""
        rx = re.compile(pattern)
        return [op for op in self.ops() if rx.search(op.name)]

    def op_seconds(self, pattern: str, whole_runs: bool = False) -> float:
        """Device seconds of the matching instructions, averaged over the
        devices; ``whole_runs``: only what lies inside program runs that the
        window holds whole (a run cut by the window's start has lost its
        ``while`` event, which began before the capture)."""
        n = max(len(self.devices), 1)
        return sum(op.whole_ns if whole_runs else op.total_ns
                   for op in self.select(pattern)) / 1e9 / n

    def _modules(self, pattern: str):
        rx = re.compile(pattern)
        return [m for d in self.devices.values() for m in d["modules"]
                if rx.search(m[0])]

    def module_runs(self, pattern: str = "") -> int:
        """Program runs that lie wholly inside the window."""
        return sum(1 for m in self._modules(pattern) if m[2])

    def module_seconds(self, pattern: str = "") -> float:
        """Device seconds inside the window spent in matching programs,
        averaged over the devices."""
        n = max(len(self.devices), 1)
        return sum(m[1] for m in self._modules(pattern)) / 1e9 / n

    def mean_run_seconds(self, pattern: str = "") -> Optional[float]:
        """Mean device seconds of one whole program run."""
        whole = [m[1] for m in self._modules(pattern) if m[2]]
        return sum(whole) / len(whole) / 1e9 if whole else None

    def top_ops(self, k: int = 10) -> list:
        ops = [op for op in self.ops() if not CONTAINERS.match(op.name)]
        top = sorted(ops, key=lambda op: -op.total_ns)[:k]
        return [[op.label, op.total_ns / 1e9] for op in top]

    def top_gaps(self, k: int = 10, longest: int = 50) -> list:
        """The ``longest`` idle gaps of the busiest device, each named by the
        host event that covered most of it (of two that cover it alike, the
        shorter, which is the more specific); gaps of one name are summed."""
        if not self.devices:
            return []
        dev = max(self.devices.values(), key=lambda d: d["busy_ns"])
        gaps = sorted(dev["gaps"], key=lambda g: g[0] - g[1])[:longest]
        by_name: Dict[str, float] = {}
        for a, b in gaps:
            name = self._host_during(a, b)
            by_name[name] = by_name.get(name, 0.0) + (b - a)
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def _host_during(self, a: float, b: float) -> str:
        best = None
        for s, e, name in self.host_events:
            ov = min(e, b) - max(s, a)
            if ov <= 0:
                continue
            cand = (round(ov / (b - a), 2), s - e, name)
            if best is None or cand > best:
                best = cand
        return best[2] if best else "no host event recorded"


def _merge(intervals: List[Tuple[float, float]]):
    """-> (union length, gaps between merged intervals)."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce_trace(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    # the window: the host annotation run.py wrote around its sleep; without
    # it (a trace taken some other way), first to last device event
    lo, hi, clipped = 0.0, float("inf"), False
    host_events: List[Tuple[float, float, str]] = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            thread = line.name.split("/")[0]
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if ev.name == WINDOW_ANNOTATION:
                    lo, hi, clipped = s, e, True
                if e - s < MIN_HOST_NS or HOST_NOISE.match(ev.name):
                    continue
                host_events.append((s, e, f"{ev.name[:80]} @{thread}"
                                    if thread else ev.name[:80]))
    raw: Dict[int, dict] = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops_ev, mod_ev = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops_ev = [(float(ev.start_ns),
                           float(ev.start_ns) + float(ev.duration_ns),
                           ev.name) for ev in line.events]
            elif line.name == MODULES_LINE:
                mod_ev = [(float(ev.start_ns),
                           float(ev.start_ns) + float(ev.duration_ns),
                           ev.name) for ev in line.events]
        raw[int(m.group(1))] = {"ops": ops_ev, "modules": mod_ev}
    if not clipped:
        ends = [t for d in raw.values() for s, e, _ in d["ops"] for t in (s, e)]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    devices: Dict[int, dict] = {}
    for ordinal, d in raw.items():
        modules, whole = [], []
        for s, e, name in d["modules"]:
            c = _clip(s, e, lo, hi)
            if c is not None:       # (name, ns inside the window, whole run?)
                modules.append((name, c[1] - c[0], c == (s, e)))
                if c == (s, e):
                    whole.append(c)
        whole.sort()
        starts = [w[0] for w in whole]
        intervals, ops = [], {}
        for s, e, text in d["ops"]:
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            intervals.append(c)
            label = op_label(text)
            op = ops.setdefault(label, Op(op_name(text), label))
            op.total_ns += c[1] - c[0]
            op.count += 1
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= whole[i][1]:
                op.whole_ns += e - s
        busy, gaps = _merge(intervals)
        if intervals:       # the idle edges of the window are gaps too
            first, last = min(s for s, _ in intervals), max(e for _, e in intervals)
            gaps = ([(lo, first)] if first > lo else []) + gaps + (
                [(last, hi)] if hi > last else [])
        devices[ordinal] = {"busy_ns": busy, "gaps": gaps, "ops": ops,
                            "modules": modules}
    host_events = [(max(s, lo), min(e, hi), n) for s, e, n in host_events
                   if min(e, hi) > max(s, lo)]
    return Trace(window_s=(hi - lo) / 1e9, devices=devices,
                 host_events=host_events, clipped=clipped)


def describe(path: str, events_per_line: int = 4) -> str:
    """A by-hand look at a trace: planes, lines, a few events with every
    statistic.  ``python3 benchmark/tracered.py <file-or-dir>``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r} stats="
                   f"{[(k, str(v)[:40]) for k, v in plane.stats][:8]}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            seen = set()
            for ev in evs:
                if ev.name in seen:
                    continue
                seen.add(ev.name)
                if len(seen) > events_per_line:
                    break
                out.append(f"    {ev.name[:100]!r} start={ev.start_ns:.0f} "
                           f"dur={ev.duration_ns:.0f} stats="
                           f"{[(k, str(v)[:120]) for k, v in ev.stats]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    print(describe(target, int(sys.argv[2]) if len(sys.argv) > 2 else 4))
