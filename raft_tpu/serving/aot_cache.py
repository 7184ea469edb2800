"""AOT executable cache: serialize warmed executables to disk, load on boot.

PR 14's fleet made cold start the dominant cost of elasticity: every
autoscale-up, chaos respawn and rolling hot-swap pays the full
(kind x bucket x batch-step x policy) compile storm (~tens of seconds per
replica).  The compiled executables are pure functions of the config and
the device — so a replica that already paid the storm can export them, and
every later replica on the same (config, device kind, jax version) loads
instead of compiling.  With a warm cache directory a fresh replica serves
its first 200 with ZERO XLA compiles (RecompileWatch-verified — loading a
serialized executable fires no backend_compile_duration event).

Mechanism: ``jax.experimental.serialize_executable`` —
``serialize(compiled) -> (payload, in_tree, out_tree)`` round-trips a
``jax.stages.Compiled`` bit-identically through
``deserialize_and_load``.  (``jax.export`` is NOT suitable here: it
serializes StableHLO, which still compiles on load.)

Layout (SERVING.md "Cold start & cache")::

    <root>/<config_hash>-<device_kind>-<jax_version>/
        manifest.json          identity + warmup-grid signature
        pair-432x1024-b4-<policyhash>.bin    one pickle per engine key:
                               the serialized executable, its pytrees, and
                               the ids of the devices it was compiled for
        pair-432x1024-b4-<policyhash>.stages.json   instruction name ->
                               stage() path of that executable
                               (telemetry/trace.instruction_stages): the
                               chip's trace names an operation by its
                               instruction, this names its stage

Invalidation is whole-directory: the manifest's identity fields
(config_hash / device_kind / jax_version / jaxlib_version) must ALL match
the running process or the directory is treated cold and warmup falls back
to compiling — a stale cache can cost time, never correctness.  A corrupt
or unreadable entry is skipped with a warning (load counted, miss
counted), again falling back to compile.

An executable is loaded onto the devices it was compiled for
(``execution_devices``), never onto "every local device": on a host with
several chips (or eight virtual CPU devices) a one-device executable loaded
without them spans them all and fails at its first call.  Device ids are
process-local — each one-chip fleet replica sees its chip as id 0 — so a
shared directory stays valid across replicas.
"""

from __future__ import annotations

import dataclasses
import json
import hashlib
import logging
import os
import pickle
import re
import time
from pathlib import Path
from typing import Optional

_log = logging.getLogger("raft_tpu.serving.aot_cache")

MANIFEST_VERSION = 2      # 2: entries carry their execution device ids
MANIFEST_NAME = "manifest.json"

# The engine's executable-cache key, in order.  raftlint B5 checks this
# literal stays arity-synced with the tuples
# serving/config.enumerate_warmup_grid emits — a key-schema drift between the compiler and the cache would
# silently mis-key every entry.
KEY_FIELDS = ("kind", "h", "w", "b", "policy")


def _slug(text: str) -> str:
    """Filesystem-safe token (device kinds like 'TPU v4' have spaces)."""
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", str(text)).strip("_") or "unknown"


def cache_identity(config) -> dict:
    """The (config, toolchain, device) identity a cache directory is valid
    for.  Every field must match exactly at load time."""
    import jax
    from ..telemetry.events import config_hash
    return {
        "config_hash": config_hash(config),
        "device_kind": jax.devices()[0].device_kind,
        "jax_version": jax.__version__,
        "jaxlib_version": getattr(__import__("jaxlib"), "__version__",
                                  jax.__version__),
    }


def execution_devices(compiled) -> list:
    """The devices ``compiled`` (a ``jax.stages.Compiled``) runs on, in
    assignment order.  Every sharding of one executable shares the device
    assignment, so the first one — input, or output for the no-argument
    ``szero`` kind — names it."""
    import jax
    s = jax.tree.leaves((compiled.input_shardings,
                         compiled.output_shardings))[0]
    mesh = getattr(s, "mesh", None)
    if mesh is not None:
        return list(mesh.devices.flat)
    return sorted(s.device_set, key=lambda d: d.id)


def key_filename(key) -> str:
    """Deterministic per-key entry name: ``pair-432x1024-b4-<8hex>.bin``.

    The iters policy is free-form text ('converge:0.05:3'); hash it so the
    name stays filesystem-safe while distinct policies never collide.
    """
    kind, h, w, b, policy = key
    phash = hashlib.sha256(repr(policy).encode()).hexdigest()[:8]
    return f"{_slug(kind)}-{int(h)}x{int(w)}-b{int(b)}-{phash}.bin"


@dataclasses.dataclass
class CacheStats:
    """Counters mirrored to /metrics and /healthz.

    ``loads``  = deserialize attempts (file existed, we tried);
    ``hits``   = keys served from the cache;
    ``misses`` = keys that fell back to compile (absent, corrupt, or the
                 whole directory failed identity validation);
    ``saves``  = executables exported this process.
    """
    hits: int = 0
    misses: int = 0
    loads: int = 0
    saves: int = 0

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "loads": self.loads, "saves": self.saves}


class EngineCache:
    """Disk cache of serialized engine executables for one config+device.

    Not thread-safe by design: the engine serializes warmup and export
    under its own lock.  Safe across processes for the fleet's shared-dir
    usage: entries are written via atomic rename, and two replicas racing
    to write the same key produce identical payloads.
    """

    def __init__(self, root, config):
        self.root = Path(root)
        self.identity = cache_identity(config)
        sub = (f"{self.identity['config_hash']}-"
               f"{_slug(self.identity['device_kind'])}-"
               f"{_slug(self.identity['jax_version'])}")
        self.dir = self.root / sub
        self.stats = CacheStats()
        self._valid: Optional[bool] = None   # manifest validation memo

    # -- identity / manifest ------------------------------------------------

    def validate(self) -> bool:
        """True when the directory's manifest matches this process's
        identity exactly.  Memoized; a missing manifest (fresh dir) is
        INVALID for loading but fine for saving — save() populates it."""
        if self._valid is None:
            self._valid = self._validate_once()
        return self._valid

    def _validate_once(self) -> bool:
        path = self.dir / MANIFEST_NAME
        try:
            manifest = json.loads(path.read_text())
        except FileNotFoundError:
            return False
        except Exception as e:                      # corrupt manifest
            _log.warning(f"engine cache: unreadable manifest {path}: {e}; "
                         f"treating directory as cold")
            return False
        if manifest.get("version") != MANIFEST_VERSION:
            _log.warning(f"engine cache: manifest version "
                         f"{manifest.get('version')!r} != {MANIFEST_VERSION}; "
                         f"treating directory as cold")
            return False
        for field, want in self.identity.items():
            got = manifest.get(field)
            if got != want:
                _log.warning(f"engine cache: stale {field} "
                             f"(cache {got!r} != process {want!r}); "
                             f"treating directory as cold")
                return False
        return True

    def write_manifest(self, grid) -> None:
        """Stamp the directory with identity + the warmup-grid signature
        (serving/config.enumerate_warmup_grid output) — the authoritative
        list of keys a warm directory is expected to hold."""
        self.dir.mkdir(parents=True, exist_ok=True)
        manifest = {
            "version": MANIFEST_VERSION,
            **self.identity,
            "key_fields": list(KEY_FIELDS),
            "keys": [list(k) for k in grid],
            "entries": [key_filename(k) for k in grid],
            "created_unix": time.time(),
        }
        tmp = self.dir / (MANIFEST_NAME + ".tmp")
        tmp.write_text(json.dumps(manifest, indent=2, default=str))
        os.replace(tmp, self.dir / MANIFEST_NAME)
        self._valid = True

    def manifest(self) -> Optional[dict]:
        try:
            return json.loads((self.dir / MANIFEST_NAME).read_text())
        except Exception:
            return None

    # -- load / save --------------------------------------------------------

    def load(self, key):
        """Deserialize the executable for ``key``, or None (caller
        compiles).  Every None is counted as a miss; a file we attempted
        counts as a load; a success counts as a hit."""
        if not self.validate():
            self.stats.misses += 1
            return None
        path = self.dir / key_filename(key)
        if not path.exists():
            self.stats.misses += 1
            return None
        self.stats.loads += 1
        try:
            import jax
            from jax.experimental import serialize_executable as _se
            with open(path, "rb") as f:
                payload, in_tree, out_tree, device_ids = pickle.load(f)
            by_id = {d.id: d for d in jax.devices()}
            ex = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:
            _log.warning(f"engine cache: corrupt entry {path.name} "
                         f"({type(e).__name__}: {e}); recompiling")
            self.stats.misses += 1
            return None
        self.stats.hits += 1
        return ex

    def save_stages(self, key, compiled) -> bool:
        """Write the instruction -> ``stage()`` map of ``compiled`` beside its
        entry, unless it is there (so a warm boot pays nothing): taken from
        the ``op_name`` metadata of the executable's own text, which a
        loaded executable carries as a compiled one does.  Best effort — a
        map is telemetry, never worth failing a boot for."""
        path = self.dir / (key_filename(key)[:-len(".bin")] + ".stages.json")
        if path.exists():
            return True
        try:
            from ..telemetry.trace import (STAGE_MAP_VERSION,
                                           instruction_stages)
            doc = {"version": STAGE_MAP_VERSION,
                   "key": dict(zip(KEY_FIELDS, key)),
                   "instructions": instruction_stages(compiled.as_text())}
            self.dir.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(f".tmp.{os.getpid()}")
            tmp.write_text(json.dumps(doc, default=str))
            os.replace(tmp, path)
        except Exception as e:
            _log.warning(f"engine cache: no stage map for {key}: "
                         f"{type(e).__name__}: {e}")
            return False
        return True

    def save(self, key, compiled) -> bool:
        """Export a ``jax.stages.Compiled`` under ``key`` (atomic rename;
        idempotent — an existing entry is left alone).  Returns True when
        an entry exists on disk afterwards."""
        path = self.dir / key_filename(key)
        if path.exists():
            return True
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            device_ids = [d.id for d in execution_devices(compiled)]
            self.dir.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(f".tmp.{os.getpid()}")
            with open(tmp, "wb") as f:
                pickle.dump((payload, in_tree, out_tree, device_ids), f)
            os.replace(tmp, path)
        except Exception as e:
            _log.warning(f"engine cache: could not export {key}: "
                         f"{type(e).__name__}: {e}")
            return False
        self.stats.saves += 1
        return True
