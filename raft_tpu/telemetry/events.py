"""Run manifests + the structured JSONL run-event log.

Every CLI mode (and the bench tools) emits a **manifest** — git sha, jax /
jaxlib versions, device kind + count, dtype/kernel config hash, argv — so
any artifact a run leaves behind (bench JSON lines, ``BENCH_serving.json``,
train ``metrics.jsonl``) can be attributed to an exact commit + config +
hardware.

The **RunLog** is an append-only ``events.jsonl``: one JSON object per
event, ``{"t": <unix seconds>, "event": <kind>, ...fields}``, with the
manifest always the first record.  ``tools/tlm.py`` tails, summarizes and
diffs these logs.

No jax import at module scope — manifests must be writable from tooling
(``tlm``, the linter CI job) running without a jax install; device fields
are ``None`` only when jax cannot be imported at all.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from typing import IO, Optional

SCHEMA_VERSION = 1


def _git_sha(cwd: Optional[str] = None) -> Optional[str]:
    """HEAD sha of the repo containing this file (or ``cwd``); None outside
    a checkout or without a git binary — never raises."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=cwd or os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def config_hash(config) -> Optional[str]:
    """Short stable hash of a config dataclass (RAFTConfig, TrainConfig,
    ServeConfig...): the dtype/kernel identity of a run.  Two runs with the
    same hash executed the same numeric program modulo weights/data."""
    if config is None:
        return None
    if dataclasses.is_dataclass(config) and not isinstance(config, type):
        payload = dataclasses.asdict(config)
    elif isinstance(config, dict):
        payload = config
    else:
        payload = {"repr": repr(config)}
    blob = json.dumps(payload, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _device_info() -> dict:
    """Backend/device identity; Nones only when jax is unimportable.

    Touching ``jax.devices()`` initializes the backend — acceptable here
    because every caller emits the manifest from a process that is about to
    run device work anyway (bench/train/val/serve all init the backend
    moments later).  A backend that fails to come up raises: a manifest
    that hides the device it ran on attributes nothing.
    """
    try:
        import jax
    except ImportError:     # tooling without jax still manifests
        return {"backend": None, "device_kind": None, "device_count": None,
                "jax_version": None, "jaxlib_version": None}
    import jaxlib
    devs = jax.devices()
    return {"jax_version": jax.__version__,
            "jaxlib_version": jaxlib.__version__,
            "backend": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def run_manifest(config=None, mode: Optional[str] = None,
                 extra: Optional[dict] = None) -> dict:
    """The provenance record stamped into every artifact this stack emits.

    Keys are stable (tlm compare diffs them field-by-field); ``extra``
    merges caller-specific fields (e.g. bench's winning candidate name).
    """
    m = {
        "schema": SCHEMA_VERSION,
        "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": _git_sha(),
        "python": sys.version.split()[0],
        "argv": list(sys.argv),
        "mode": mode,
        "config_hash": config_hash(config),
    }
    m.update(_device_info())
    if extra:
        m.update(extra)
    return m


class RunLog:
    """Append-only JSONL event stream for one run.

    ``RunLog(dir_or_file)`` opens ``<dir>/events.jsonl`` (creating the
    directory) or the given ``*.jsonl`` path directly; ``event(kind, ...)``
    appends one timestamped record and flushes (the log must survive a
    crash mid-run — that is half its point).  Thread-safe enough for the
    serving stack: a line-buffered append per event, no shared state.
    """

    def __init__(self, path, manifest: Optional[dict] = None):
        p = Path(path)
        if p.suffix != ".jsonl":
            p.mkdir(parents=True, exist_ok=True)
            p = p / "events.jsonl"
        else:
            p.parent.mkdir(parents=True, exist_ok=True)
        self.path = p
        self._f: Optional[IO[str]] = open(p, "a")
        if manifest is not None:
            self.event("manifest", **manifest)

    # positional-only first parameter: event payloads may legitimately
    # carry a "kind" field of their own (e.g. the trace records' request
    # class) and must not collide with the event name
    def event(self, kind: str, /, **fields) -> dict:
        rec = {"t": round(time.time(), 3), "event": kind}
        rec.update(fields)
        if self._f is not None:
            self._f.write(json.dumps(rec, default=str) + "\n")
            self._f.flush()
        return rec

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self) -> "RunLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def start_run(out_dir, mode: str, config=None,
              extra: Optional[dict] = None) -> RunLog:
    """Open ``<out_dir>/events.jsonl`` with the manifest as first record —
    the one-liner every CLI mode calls."""
    return RunLog(out_dir, manifest=run_manifest(config=config, mode=mode,
                                                 extra=extra))


# The process's active run log, set by the CLI entry point so library
# subsystems (watchdogs, the training loop) can attach events without
# threading a RunLog through every signature.  None outside a CLI run —
# callers must treat it as optional.
_current: Optional[RunLog] = None


def set_current(log: Optional[RunLog]) -> None:
    global _current
    _current = log


def current() -> Optional[RunLog]:
    return _current


def read_events(path) -> list:
    """Parse a run log (dir or .jsonl file) tolerantly: partial trailing
    lines from a crash mid-append are dropped, not fatal."""
    p = Path(path)
    if p.is_dir():
        p = p / "events.jsonl"
    records = []
    if not p.exists():
        return records
    for ln in p.read_text().splitlines():
        if not ln.strip():
            continue
        try:
            records.append(json.loads(ln))
        except json.JSONDecodeError:
            pass
    return records
