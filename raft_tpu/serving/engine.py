"""Warm inference engine: one AOT-compiled executable per declared shape.

The compile cache is keyed by (bucket H, bucket W, padded batch) over a
fixed (config, params-dtype) pair.  ``warmup()`` lowers and compiles the
whole (bucket x batch-step) grid up front — XLA's jit cache never decides
anything at serve time, so a steady-state device call can only ever be a
dictionary lookup plus execution (raftlint R2 discipline made structural).
``compile_misses`` stays at its post-warmup value forever on a healthy
server; the tests and the load bench assert exactly that.

Sharded execution: ``dp_devices > 1`` wraps the same inference fn in
``parallel.make_dp_eval_fn`` (shard_map over the 'data' axis), so a padded
batch splits across local chips — batch steps are multiples of the device
count by ServeConfig construction.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional, Tuple

import numpy as np

from ..config import RAFTConfig, adaptive_iters
from ..lint.concurrency import guarded_by
from ..telemetry import spans as tlm_spans
from ..telemetry.log import get_logger
from ..telemetry.trace import host_stage
from ..telemetry.watchdogs import watched_lock
from .config import ServeConfig, enumerate_warmup_grid

_log = get_logger("serve")

# executable kinds that run the recurrent core, and with it the correlation
# lookup (encode / scommit / szero / spoison do not)
_LOOKUP_KINDS = ("pair", "stream", "sbatch")


@dataclasses.dataclass(frozen=True)
class Program:
    """One executable as the engine compiles and calls it."""

    fn: object                       # the jitted function
    specs: tuple                     # its arguments in order, as their specs
    outputs: Tuple[str, ...]         # what it returns, in order
    resident: Tuple[int, ...] = ()   # arguments that are the pool's buffers
    donated: Tuple[int, ...] = ()    # arguments the outputs take the place of


class Programs:
    """The table of the engine's kinds: for a key, WHICH function over WHICH
    arguments returning WHAT (:meth:`program`).  Its readers are the compile
    (``InferenceEngine._compile_traced``), the engine's call sites and
    fetches (:meth:`takes_sizes`, :meth:`named`), the reload probe, and the
    static budget (``lint/budget.kind_footprint``, which builds one from
    abstract params and no device), so a program's signature is written
    down here and nowhere else.

    ``params`` are the weights or their specs; ``capacity`` the pool's
    slots.  A ragged table's lookup kinds take the rows' ``sizes`` last
    and hand no key-block counts out (the ragged launch has no band
    schedule to count); ``mesh`` makes ``pair`` the data-parallel program.
    ``donate``: the pool's buffers are DONATED into the scatter programs so
    a commit updates rows in place (the CPU backend has no donation)."""

    def __init__(self, config: RAFTConfig, params, capacity: int, *,
                 iters: Optional[int] = None, ragged: bool = False,
                 stream: bool = True, mesh=None, donate: bool = False):
        import jax

        from ..models.raft import (make_encode_fn, make_inference_fn,
                                   make_stream_batch_step_fn,
                                   make_stream_step_fn)
        from .session import make_slot_commit_fn, make_slot_poison_fn

        self.config, self.capacity, self.ragged = config, capacity, ragged
        self.params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        adaptive = adaptive_iters(config.iters_policy)
        # the dense Pallas lookup's key-block counts ride out of the lookup
        # kinds beside their outputs, in the one fetch
        self.keyblocks = kb = (config.corr_impl == "pallas" and not ragged
                               and mesh is None)
        tail = ("iters_used",) * adaptive + ("corr_keyblocks",) * kb
        if mesh is not None:
            from ..parallel import make_dp_eval_fn
            pair = make_dp_eval_fn(config, mesh, iters=iters,
                                   with_iters=adaptive)
        else:
            pair = jax.jit(make_inference_fn(config, iters=iters,
                                             counted=adaptive, keyblocks=kb))
        # kind -> (function, outputs, donated arguments); the function of
        # ``szero`` is its shapes, so :meth:`program` makes it with its key
        self._kinds = {"pair": (pair, ("flow",) + tail, ())}
        if stream:
            # plain single-device jits even under --serve-dp (batch-1
            # session steps and slot scatters cannot shard over the data
            # axis)
            step = ("flow", "flow_lr", "fmap", "cnet") + tail
            bufs = ("fmap_buf", "cnet_buf", "flow_buf")
            pool = (0, 1, 2) if donate else ()
            quant = config.quant_slots
            self._kinds.update(
                encode=(jax.jit(make_encode_fn(config)), ("fmap", "cnet"),
                        ()),
                stream=(jax.jit(make_stream_step_fn(
                    config, iters=iters, keyblocks=kb)), step, ()),
                sbatch=(jax.jit(make_stream_batch_step_fn(
                    config, iters=iters, keyblocks=kb)), step, ()),
                scommit=(jax.jit(make_slot_commit_fn(quant=quant),
                                 donate_argnums=pool), bufs, pool),
                spoison=(jax.jit(make_slot_poison_fn(quant=quant),
                                 donate_argnums=pool[:1]), bufs[:1],
                         pool[:1]),
                szero=(None, bufs, ()))
        # eval_shape is pure, so threads that race a miss only recompute
        self.feature_specs = functools.lru_cache(maxsize=None)(
            self._eval_features)

    def _eval_features(self, h: int, w: int, b: int):
        """(fmap, cnet) specs of ``b`` frames — derived from the model
        itself (jax.eval_shape over the encode function), never hardcoded,
        so bf16 compute or a variant change flows through automatically."""
        import jax
        import jax.numpy as jnp
        img = jax.ShapeDtypeStruct((b, h, w, 3), jnp.float32)
        return jax.eval_shape(self._kinds["encode"][0], self.params, img)

    def slot_specs(self, h: int, w: int) -> tuple:
        """ShapeDtypeStructs of a bucket's pool buffers ([capacity+1, …] —
        the extra row is the scratch slot padding rows aim at).  Under
        ``quant='int8'`` the fmap/cnet entries are 2-leaf pytrees
        ``((cap+1, …) int8 vals, (cap+1, C) f32 per-channel scales)`` —
        positional signatures everywhere stay at three buffer args (jit
        handles pytree args), only the leaves change."""
        import jax
        import jax.numpy as jnp
        fs, cs = self.feature_specs(h, w, 1)
        cap1 = self.capacity + 1
        flow = jax.ShapeDtypeStruct((cap1, h // 8, w // 8, 2), jnp.float32)
        if self.config.quant_slots:
            def q(s):
                return (jax.ShapeDtypeStruct((cap1,) + s.shape[1:],
                                             jnp.int8),
                        jax.ShapeDtypeStruct((cap1, s.shape[-1]),
                                             jnp.float32))
            return (q(fs), q(cs), flow)
        return (jax.ShapeDtypeStruct((cap1,) + fs.shape[1:], fs.dtype),
                jax.ShapeDtypeStruct((cap1,) + cs.shape[1:], cs.dtype),
                flow)

    def takes_sizes(self, kind: str) -> bool:
        """Does a ``kind`` call end with the rows' [b, 2] int32 live sizes
        (the only shape-bearing metadata of a ragged program: a runtime
        argument, so one executable serves every declared resolution)?"""
        return self.ragged and kind in _LOOKUP_KINDS

    def named(self, kind: str, out) -> Dict[str, object]:
        """A ``kind`` call's outputs by name, in the program's order (a
        program with one output returns it bare)."""
        names = self._kinds[kind][1]
        return dict(zip(names, out if len(names) > 1 else (out,)))

    def program(self, key: Tuple) -> Program:
        """The program of ``key`` = (kind, h, w, b[, policy])."""
        import jax
        import jax.numpy as jnp

        kind, h, w, b = key[:4]
        if kind not in self._kinds:
            raise ValueError(f"unknown executable kind {kind!r}")
        fn, outputs, donated = self._kinds[kind]
        img = jax.ShapeDtypeStruct((b, h, w, 3), jnp.float32)
        flow = jax.ShapeDtypeStruct((b, h // 8, w // 8, 2), jnp.float32)
        idx = jax.ShapeDtypeStruct((b,), jnp.int32)
        mask = jax.ShapeDtypeStruct((b,), jnp.bool_)
        resident = ()
        if kind == "pair":
            specs = (self.params, img, img)
        elif kind == "encode":
            specs = (self.params, img)
        elif kind == "stream":
            specs = (self.params, img, *self.feature_specs(h, w, b), flow)
        elif kind == "sbatch":
            specs = (self.params, img, *self.slot_specs(h, w), idx, mask)
            resident = (2, 3, 4)
        elif kind == "scommit":
            specs = (*self.slot_specs(h, w), idx,
                     *self.feature_specs(h, w, b), flow, mask)
            resident = (0, 1, 2)
        elif kind == "spoison":
            specs = (self.slot_specs(h, w)[0], idx)
            resident = (0,)
        else:
            shapes = self.slot_specs(h, w)
            # tree.map (not a flat tuple comprehension): under quant the
            # fmap/cnet entries are nested (vals, scales) pytrees and the
            # zeroed buffers must mirror that structure
            fn = jax.jit(lambda: jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes))
            specs = ()
        if self.takes_sizes(kind):
            specs += (jax.ShapeDtypeStruct((b, 2), jnp.int32),)
        return Program(fn, specs, outputs, resident, donated)


class ReloadMismatch(ValueError):
    """New params don't match the serving template (tree structure or a
    leaf's shape/dtype differs, or the probe produced non-finite flow) —
    the swap is rejected and the engine keeps serving the old weights."""


class PairCall:
    """One call of a ``pair`` executable between its phases: the executable
    and its placed arguments until the dispatch, the outputs after it."""

    __slots__ = ("ex", "args", "out")
    kind = "pair"

    def __init__(self, ex, args: tuple):
        self.ex, self.args, self.out = ex, args, None


class StreamBatchCall(PairCall):
    """One call of an ``sbatch`` executable between its phases.  ``args`` are
    the placed frames (and, ragged, the rows' sizes): the pool's buffers,
    the slots and ``active`` join them at the dispatch."""

    __slots__ = ("bucket", "rows")
    kind = "stream"

    def __init__(self, ex, args: tuple, bucket: Tuple[int, int]):
        super().__init__(ex, args)
        self.bucket, self.rows = bucket, 0


class InferenceEngine:
    """(kind, bucket, batch, iters-policy) -> compiled executable, with
    hit/miss accounting.  ``kind`` is ``"pair"`` (the /v1/flow two-frame
    executable), ``"encode"`` (single-frame fnet+cnet — session open /
    cold restart), ``"stream"`` (one-encoder sessionful step, the cold
    batch-1 form), or one of the slot-pool family — ``"sbatch"`` (the
    CONTINUOUS-BATCHED stream step: b different sessions advanced in one
    call, gathering cached maps from their pool slots), ``"scommit"``
    (masked scatter of updated rows back into the pool buffers),
    ``"szero"`` (fresh zeroed buffers, built at warmup so a pool reset
    never compiles) and ``"spoison"`` (chaos session arm: NaN one slot's
    fmap row — warmed only when the injector is armed).  Every kind
    shares the cache, the warmup pass, and the no-recompile discipline
    with the pairwise grid.  With ``iters_policy='converge:...'``
    (ServeConfig override or model-config default) flow-producing
    executables return (…, iters_used): per-sample early exit runs
    INSIDE the compiled while_loop, so shapes — and therefore the warm
    compile grid — never change with the data.

    Thread model (SERVING.md "Threading model"): device calls arrive on
    the single batcher thread, but warmup runs on the server's start
    thread and tests/tools call the engine directly, so every mutable
    member is annotated and guarded — ``_lock`` for the executable cache
    and the call counters (the 1-fnet-per-frame acceptance observables:
    a dropped increment is a wrong benchmark).  What each kind IS — its
    function, arguments and outputs — is :class:`Programs`' to say; its
    feature-spec cache needs no lock (pure, and the serve-time miss path
    compiles while holding ``_lock``).  The slot pool is
    only ever touched OUTSIDE the engine locks (pool._lock is a leaf of
    the hierarchy)."""

    _exec = guarded_by("_lock")
    compile_hits = guarded_by("_lock")
    compile_misses = guarded_by("_lock")
    pair_calls = guarded_by("_lock")
    corr_keyblocks = guarded_by("_lock")
    encode_calls = guarded_by("_lock")
    stream_calls = guarded_by("_lock")
    rows_quantized = guarded_by("_lock")
    weight_version = guarded_by("_lock")
    weight_tag = guarded_by("_lock")

    def __init__(self, config: RAFTConfig, params, sconfig: ServeConfig,
                 iters: Optional[int] = None, stream: bool = False,
                 faults=None, pool=None, cache=None):
        import jax

        # chaos harness (serving/faults.py): injected engine exceptions,
        # latency spikes, and NaN output rows enter HERE — the boundary
        # the rest of the stack must contain.  None (the default) costs
        # one attribute check per device call.
        self.faults = faults

        if sconfig.iters_policy is not None:
            # the serving tier declares its compute policy up front, like
            # its buckets and batch steps; it overrides the model config so
            # warmup compiles exactly what serve time executes
            config = dataclasses.replace(config,
                                         iters_policy=sconfig.iters_policy)
        self.config = config
        self.sconfig = sconfig
        self.iters = iters
        self.iters_policy = config.iters_policy
        # ragged mixed-resolution serving: every flow-producing executable
        # takes a per-row [b, 2] int32 sizes argument and runs at the max
        # box, so ONE (kind, b, policy) executable serves every declared
        # bucket — the cache key keeps its 5-tuple schema, but only max-box
        # (h, w) values ever appear in it (the warmup grid collapses to
        # O(batch-steps), serving/config.enumerate_warmup_grid)
        self.ragged = bool(sconfig.ragged)
        self.max_box = sconfig.max_box
        # aot_cache.EngineCache or None: warmup load-or-compiles through
        # it, export_cache() populates it for the fleet's shared dir
        self.cache = cache
        # MXU passes of each pyramid level's correlation matmul in this
        # engine's executables (None off the Pallas kernel).  The kernel
        # decides them while a program is traced, from the dtype of the
        # encoder's maps: 1/3/3/3 for bfloat16 maps, 6 for float32 ones
        self.corr_mxu_terms = None
        if config.corr_impl == "pallas":
            from ..ops.corr import level_mxu_passes
            self.corr_mxu_terms = level_mxu_passes(
                config.compute_dtype, config.corr_levels,
                config.corr_precision)
        if config.quant_weights:
            # quant='bf16w': the encoder weights live on device in bf16
            # (half the encoder param HBM); reload() applies the same cast
            # so the swap template stays consistent
            from ..models.raft import cast_encoder_weights
            params = cast_encoder_weights(params, config)
        self.params = jax.tree.map(jax.numpy.asarray, params)
        mesh = None
        if sconfig.dp_devices > 1:
            from ..parallel.mesh import make_mesh
            if len(jax.devices()) < sconfig.dp_devices:
                raise ValueError(
                    f"dp_devices={sconfig.dp_devices} but only "
                    f"{len(jax.devices())} device(s) visible")
            mesh = make_mesh(sconfig.dp_devices)
        self.stream = stream
        self.pool = pool                  # session.SlotPool (stream servers)
        if stream and self.pool is None:
            from .session import SlotPool
            self.pool = SlotPool(max(1, sconfig.max_sessions),
                                 arena=(self.max_box if self.ragged
                                        else None))
        # what every kind of executable is (the streaming kinds live in the
        # same cache and warm grid as the pair ones)
        self.programs = Programs(
            config, self.params, self.pool.capacity if stream else 0,
            iters=iters, ragged=self.ragged, stream=stream, mesh=mesh,
            donate=jax.default_backend() != "cpu")
        if stream:
            # does the pool fit this chip beside the stream programs?  Asked
            # of shapes, before anything is compiled or allocated
            from .admission import admit_stream
            admit_stream(self.programs, sconfig,
                         jax.devices()[0].device_kind)
        # budget None: a cold cache miss compiles while holding the lock
        # (deliberate — see _get_executable), which busts any hold budget
        self._lock = watched_lock("InferenceEngine._lock", budget_s=None)
        self._exec: Dict[Tuple[str, int, int, int, str], object] = {}
        self.compile_hits = 0
        self.compile_misses = 0
        self.encode_calls = 0     # fnet-pass accounting: 1 per encode call,
        self.stream_calls = 0     # 1 per stream step (the acceptance
        self.pair_calls = 0       # criterion's counters), 2 per pair row
        # rows a commit wrote through the int8 quantiser (quant='int8')
        self.rows_quantized = 0
        # [visited, possible, tiles, steps, stored, live] of the lookup's
        # band schedule over the pair batches and stream steps run so far
        # (RAFTOutput.corr_keyblocks; the
        # server turns their growth into raft_serving_corr_keyblocks_*_total,
        # raft_serving_corr_tiles_total,
        # raft_serving_corr_grid_steps_total and
        # raft_serving_corr_key_positions_total)
        self.corr_keyblocks = [0, 0, 0, 0, 0, 0]
        self.weight_version = 1   # bumped by reload(); healthz reports it
        self.weight_tag = None
        self.warmup_seconds = 0.0
        self.warmup_loaded = 0    # executables served from the AOT cache

    # -- compile-cache bookkeeping ---------------------------------------

    def _key(self, h: int, w: int, b: int,
             kind: str = "pair") -> Tuple[str, int, int, int, str]:
        """Engine-cache key: the executable kind and the iteration policy
        ride along with the shape, so an executable can never be reused
        under a different compute policy than it was warmed with (and
        stays warm across every difficulty mix — early exit is inside the
        executable)."""
        return (kind, h, w, b, self.iters_policy)

    def _compile(self, key: Tuple[str, int, int, int, str]):
        if self.cache is not None:
            # serialized executables cannot carry host callbacks — the
            # NaN sentinel's jax.debug.callback trampoline is a
            # PyCapsule, which does not pickle — so a cache-attached
            # engine traces its whole grid sentinel-free.  Uniform by
            # construction: every entry this engine saves is one a
            # fresh replica can load.
            from ..telemetry.watchdogs import suppress_nan_sentinel
            with suppress_nan_sentinel():
                return self._compile_traced(key)
        return self._compile_traced(key)

    def _compile_traced(self, key: Tuple[str, int, int, int, str]):
        prog = self.programs.program(key)
        return prog.fn.lower(*prog.specs).compile()

    def _get_executable(self, key: Tuple[int, int, int, str]):
        with self._lock:
            ex = self._exec.get(key)
            if ex is not None:
                self.compile_hits += 1
                return ex
            self.compile_misses += 1
        # compile outside the lock would race duplicate compiles; the
        # grid is tiny and warmup covers it, so hold the lock instead
        with self._lock:
            ex = self._exec.get(key)
            if ex is None:
                ex = self._compile(key)
                self._exec[key] = ex
            return ex

    def warmup(self, verbose: bool = True) -> int:
        """AOT-compile every declared (bucket, batch-step); returns the
        number of executables built.  Warmup compiles are not counted as
        cache misses — `compile_misses` measures serve-time surprises.

        With an attached AOT cache (serving/aot_cache.EngineCache) every
        key LOAD-OR-COMPILES: a valid serialized entry deserializes in
        milliseconds and fires no XLA compile event (RecompileWatch sees
        nothing), a miss compiles and is exported for the next replica.
        ``warmup_loaded`` counts the loads; the manifest is (re)stamped
        with the grid afterwards so the directory advertises exactly the
        keys it holds."""
        t0 = time.monotonic()
        n = 0
        loaded = 0
        # the grid is enumerated beside ServeConfig (serving/config.py) and
        # the static budget analyzer (lint/budget.py) reads the same list,
        # so `raftlint --budget` capacity reports and the live compile
        # surface are one list by construction — the parity test pins it
        # anyway
        grid = enumerate_warmup_grid(self.config, self.sconfig,
                                     stream=self.stream,
                                     chaos=self.faults is not None)
        corr_terms = ""
        if self.corr_mxu_terms:
            corr_terms = ("corr terms "
                          + "/".join(map(str, self.corr_mxu_terms)) + " ")
        for (kind, h, w, b, _policy) in grid:
            key = self._key(h, w, b, kind)
            with self._lock:
                if key in self._exec:
                    continue
            ex = self.cache.load(key) if self.cache is not None else None
            from_cache = ex is not None
            if ex is None:
                ex = self._compile(key)
                if self.cache is not None:
                    self.cache.save(key, ex)
            if self.cache is not None:
                # instruction -> stage() map beside the entry (a no-op once
                # it is there): what lets a trace reader name device time
                self.cache.save_stages(key, ex)
            with self._lock:
                self._exec.setdefault(key, ex)
            n += 1
            loaded += int(from_cache)
            if verbose:
                verb = "loaded" if from_cache else "warmed"
                terms = corr_terms if kind in _LOOKUP_KINDS else ""
                _log.info(f"{verb} {kind} bucket {h}x{w} batch {b} {terms}"
                          f"({time.monotonic() - t0:.1f}s elapsed)")
        if self.cache is not None:
            self.cache.write_manifest(grid)
        self.warmup_seconds = time.monotonic() - t0
        self.warmup_loaded = loaded
        return n

    def export_cache(self) -> dict:
        """Export every in-memory executable plus the manifest into the
        attached AOT cache — the /admin/cache/prestage hook the fleet's
        RollingUpdater calls before flipping weights, so a post-swap
        respawn finds a fully-populated shared directory.  Idempotent
        (existing entries are kept); a no-op without a cache."""
        if self.cache is None:
            return {"exported": 0, "entries": 0, "dir": None}
        with self._lock:
            items = list(self._exec.items())
        exported = sum(1 for key, ex in items if self.cache.save(key, ex))
        for key, ex in items:
            self.cache.save_stages(key, ex)
        grid = enumerate_warmup_grid(self.config, self.sconfig,
                                     stream=self.stream,
                                     chaos=self.faults is not None)
        self.cache.write_manifest(grid)
        return {"exported": exported, "entries": len(items),
                "dir": str(self.cache.dir)}

    def _ensure_slot_buffers(self, bucket: Tuple[int, int]) -> None:
        """Build this bucket's pool buffers via the warmed ``szero``
        executable, LAZILY on the bucket's first stream call: buffers
        are (capacity+1) rows of fmap+cnet+seed PER BUCKET, so eager
        allocation at warmup would cost num_buckets x that in device
        memory before a single session opens.  szero is compiled at
        warmup, so the lazy fill executes a warm executable — no
        serve-time compile (a --no-warmup server pays one counted
        compile here instead)."""
        if self.pool.buffers(bucket) is None:
            self.reset_slots(bucket)

    def reset_slots(self, bucket: Tuple[int, int]) -> None:
        """(Re)install zeroed pool buffers for a bucket — warmup fill,
        and the recovery path after a failed commit scatter (whose
        donated inputs are dead): the coordinator demotes every session
        of the bucket right after, so no one ever gathers the zeros."""
        h, w = bucket
        ex = self._get_executable(self._key(h, w, 1, "szero"))
        self.pool.install(bucket, ex())

    @property
    def executables(self) -> int:
        with self._lock:
            return len(self._exec)

    def keys(self):
        with self._lock:
            return sorted(self._exec)

    # -- zero-downtime weight hot-swap -------------------------------------

    def weight_info(self) -> dict:
        with self._lock:
            return {"version": self.weight_version, "tag": self.weight_tag}

    def reload(self, params, tag: Optional[str] = None,
               probe: bool = True) -> dict:
        """Atomically swap the serving weights for ``params`` without
        touching the executable cache.  Every executable was AOT-compiled
        with the params as a RUNTIME argument (``ex(self.params, ...)``)
        specialized only on avals, so a new tree with identical structure
        and leaf shape/dtype flows through every warm executable with
        zero recompiles — the cache keys ``(kind, h, w, b, policy)`` stay
        valid by construction.  Anything else is a template mismatch and
        is rejected up front (:class:`ReloadMismatch`; the /admin/reload
        endpoint maps it to 409), leaving the old weights serving.

        The swap itself happens in three phases, all off the serving
        path: stage (device upload, no lock held), probe (execute one
        already-warm pair executable against the staged tree and check
        the flow is finite — catches sharding/layout surprises, e.g.
        under --serve-dp, before any request can see them), then a
        single reference flip under ``_lock``.  In-flight device calls
        read ``self.params`` once per call, so they finish on whichever
        tree they started with — no request is ever dropped or torn."""
        import jax
        from jax.tree_util import tree_flatten_with_path

        if self.config.quant_weights:
            # same cast the constructor applied: the swap template (leaf
            # dtypes included) must match the serving tree
            from ..models.raft import cast_encoder_weights
            params = cast_encoder_weights(params, self.config)
        staged = jax.tree.map(jax.numpy.asarray, params)
        old_paths, old_td = tree_flatten_with_path(self.params)
        new_paths, new_td = tree_flatten_with_path(staged)
        if old_td != new_td:
            raise ReloadMismatch(
                f"param tree structure differs: serving has "
                f"{old_td.num_leaves} leaves, pushed tree has "
                f"{new_td.num_leaves} (layout/naming mismatch)")
        for (path, old), (_, new) in zip(old_paths, new_paths):
            if (old.shape, old.dtype) != (new.shape, new.dtype):
                name = jax.tree_util.keystr(path)
                raise ReloadMismatch(
                    f"leaf {name} differs: serving "
                    f"{old.dtype}{list(old.shape)} vs pushed "
                    f"{new.dtype}{list(new.shape)}")
        probed = False
        if probe:
            # cheapest warm pair executable; _get_executable is a cache
            # hit by construction (the key came out of the cache), so the
            # probe can never be the compile the no-recompile gate hunts
            pair_keys = [k for k in self.keys() if k[0] == "pair"]
            if pair_keys:
                kind, h, w, b, _pol = min(
                    pair_keys, key=lambda k: k[1] * k[2] * k[3])
                ex = self._get_executable(self._key(h, w, b, kind))
                img = np.zeros((b, h, w, 3), np.float32)
                out = ex(*self._with_sizes("pair", b, None, staged, img, img))
                flow = np.asarray(self.programs.named("pair", out)["flow"])
                if not np.all(np.isfinite(flow)):
                    raise ReloadMismatch(
                        "probe produced non-finite flow; rejecting swap")
                probed = True
        with self._lock:
            self.params = staged
            self.weight_version += 1
            self.weight_tag = tag
            info = {"version": self.weight_version, "tag": tag,
                    "probed": probed}
        _log.info(f"hot-swapped weights -> version {info['version']}"
                  f" tag={tag} probed={probed}")
        return info

    # -- the device call --------------------------------------------------

    def _call(self, kind: str, ex, args: tuple, wait: bool = True):
        """One call of a warm executable under its host stages, timed at the
        only place that can tell them apart (the executable call returns as
        soon as the work is enqueued — wall clock at the call site lies):
        ``dispatch`` is the enqueue alone; ``wait`` blocks until the outputs
        are ready — the device's run as the host sees it.  Each stage is a
        ``raft.engine.*`` profiler annotation and, inside a batch, a child
        span of ``execute`` plus stage seconds."""
        sink = functools.partial(tlm_spans.record_device_stage, kind)
        with host_stage("raft.engine.dispatch", sink, call=kind):
            out = ex(*args)
        if wait:
            self._block(kind, out)
        return out

    def _block(self, kind: str, out) -> None:
        """Until ``out`` is ready (``raft.engine.wait``)."""
        import jax
        sink = functools.partial(tlm_spans.record_device_stage, kind)
        with host_stage("raft.engine.wait", sink, call=kind):
            jax.block_until_ready(out)

    def _fetch(self, kind: str, *arrays) -> tuple:
        """Ready device arrays -> host numpy (``raft.engine.fetch``)."""
        sink = functools.partial(tlm_spans.record_device_stage, kind)
        with host_stage("raft.engine.fetch", sink, call=kind):
            return tuple(np.asarray(a) for a in arrays)

    def _h2d(self, kind: str, ex, first: int, *arrays) -> list:
        """``raft.engine.h2d``: put ``arrays`` on the device as ``ex``'s
        arguments ``first``, ``first + 1`` ..., the runtime's host relayout
        included, and wait for them (what the call would transfer implicitly
        before the device can start).  Once this returns the caller may
        rewrite the arrays.  The runtime reads them by their strides: the
        batcher hands over views of channel-planar buffers, which the
        relayout has only to tile."""
        import jax
        sink = functools.partial(tlm_spans.record_device_stage, kind)
        with host_stage("raft.engine.h2d", sink, call=kind):
            if jax.default_backend() == "cpu":
                # the CPU client aliases a host array it finds aligned
                # instead of copying it: give it one nobody rewrites
                arrays = [np.array(a) for a in arrays]
            shardings = ex.input_shardings[0][first:first + len(arrays)]
            return jax.block_until_ready(
                jax.device_put(list(arrays), list(shardings)))

    def _count_keyblocks(self, counts) -> None:
        """Add one call's [visited, possible, tiles, steps, stored, live]
        (fetched beside its outputs) to ``corr_keyblocks``."""
        with self._lock:
            for i, v in enumerate(counts):
                self.corr_keyblocks[i] += int(v)

    def _sizes_arg(self, n: int, sizes) -> np.ndarray:
        """Per-row [n, 2] int32 live-size metadata for a ragged device
        call.  None = every row live on the full max box (direct engine
        callers and padding rows)."""
        if sizes is None:
            h, w = self.max_box
            return np.tile(np.asarray([[h, w]], np.int32), (n, 1))
        return np.asarray(sizes, np.int32)

    def _with_sizes(self, kind: str, n: int, sizes, *args) -> tuple:
        """``args`` of a ``kind`` call over ``n`` rows, their ``sizes`` last
        where the kind takes them."""
        if self.programs.takes_sizes(kind):
            args += (self._sizes_arg(n, sizes),)
        return args

    def _fetch_outputs(self, kind: str, out) -> Dict[str, object]:
        """A finished ``kind`` call's outputs by name
        (:meth:`Programs.named`): on the host all but the maps, which stay
        on the device; the key-block counts that ride beside them go to
        ``corr_keyblocks``."""
        res = self.programs.named(kind, out)
        host = [n for n in res if n not in ("fmap", "cnet")]
        res.update(zip(host, self._fetch(
            "pair" if kind == "pair" else "stream",
            *(res[n] for n in host))))
        if "corr_keyblocks" in res:
            self._count_keyblocks(res.pop("corr_keyblocks"))
        return res

    # -- a pair call, one phase at a time ----------------------------------
    #
    # ``run`` is place -> dispatch -> wait -> fetch.  The batcher calls the
    # phases apart (serving/batcher.py): it places batch n+1 while batch n
    # runs, and dispatches it before it fetches n.

    def place(self, bucket: Tuple[int, int], im1: np.ndarray,
              im2: np.ndarray, sizes=None) -> "PairCall":
        """Put the padded pair on the device (:meth:`_h2d`).  Once this
        returns the caller may rewrite ``im1`` / ``im2``."""
        h, w = bucket
        n = im1.shape[0]
        ex = self._get_executable(self._key(h, w, n))
        with self._lock:
            self.pair_calls += 1
        if self.faults is not None:
            self.faults.pre_engine_call()
        im1, im2 = self._h2d("pair", ex, 1, im1, im2)
        return PairCall(ex, self._with_sizes("pair", n, sizes, self.params,
                                             im1, im2))

    def dispatch(self, call: "PairCall") -> None:
        """Enqueue a placed call; returns before the device has run it."""
        call.out = self._call("pair", call.ex, call.args, wait=False)
        call.args = None              # the device holds what it needs

    def ready(self, call: "PairCall") -> bool:
        """Has a dispatched call finished?  Never blocks."""
        import jax
        return all(a.is_ready() for a in jax.tree.leaves(call.out))

    def wait(self, call: "PairCall") -> None:
        """Block until a dispatched call's outputs are ready."""
        self._block(call.kind, call.out)

    def fetch(self, call: "PairCall"):
        """A finished call's outputs on the host: the flow, or (flow,
        iters_used [n] int32) under a converge policy; the key-block counts
        that ride beside them go to ``corr_keyblocks``."""
        res = self._fetch_outputs("pair", call.out)
        flow, iters_used = res["flow"], res.get("iters_used")
        if self.faults is not None:
            flow = self.faults.corrupt_rows(flow)
        return flow if iters_used is None else (flow, iters_used)

    def run(self, bucket: Tuple[int, int], im1: np.ndarray,
            im2: np.ndarray, sizes=None):
        """[n, BH, BW, 3] float32 pair -> [n, BH, BW, 2] float32 flow.
        ``n`` must be a declared batch step (the batcher pads to one).
        Under a converge policy returns (flow, iters_used [n] int32) —
        the batcher passes per-row counts through to each request.
        ``sizes`` ([n, 2] int32) is required-by-convention in ragged mode:
        per-row live extents inside the max-box ``bucket`` (None = all rows
        full box); ignored in dense mode."""
        call = self.place(bucket, im1, im2, sizes)
        self.dispatch(call)
        self.wait(call)
        return self.fetch(call)

    # the server pipelines an engine through its phases only where ``run``
    # is this composition of them (server.FlowServer._pair_engine)
    run.composes_phases = True

    def run_encode(self, bucket: Tuple[int, int], image: np.ndarray):
        """[1, BH, BW, 3] float32 frame -> DEVICE-resident (fmap, cnet)
        maps — one fnet pass (session open / cold-restart half of the
        streaming path).  The outputs are deliberately not pulled to
        host: they are the session cache."""
        h, w = bucket
        ex = self._get_executable(self._key(h, w, image.shape[0], "encode"))
        with self._lock:
            self.encode_calls += 1
        if self.faults is not None:
            self.faults.pre_engine_call()
        [image] = self._h2d("encode", ex, 1, image)
        # outputs stay device-resident (they are the session cache), so
        # there is no block-until-ready here — dispatch only
        return self._call("encode", ex, (self.params, image), wait=False)

    def run_stream(self, bucket: Tuple[int, int], image: np.ndarray,
                   fmap_prev, cnet_prev, flow_init: np.ndarray,
                   sizes=None):
        """One sessionful step: current frame + cached previous maps +
        warm-start seed -> (flow [1,BH,BW,2] np, flow_lr [1,bh,bw,2] np,
        fmap_cur dev, cnet_cur dev, iters_used np or None).  Exactly one
        fnet pass per call — the streaming saving the tests assert via
        ``encode_calls``/``stream_calls``.  ``sizes`` as in :meth:`run`."""
        h, w = bucket
        n = image.shape[0]
        ex = self._get_executable(self._key(h, w, n, "stream"))
        with self._lock:
            self.stream_calls += 1
        if self.faults is not None:
            self.faults.pre_engine_call()
        [image] = self._h2d("stream", ex, 1, image)
        out = self._call("stream", ex, self._with_sizes(
            "stream", n, sizes, self.params, image, fmap_prev, cnet_prev,
            flow_init))
        res = self._fetch_outputs("stream", out)
        flow = res["flow"]
        if self.faults is not None:
            flow = self.faults.corrupt_rows(flow)
        return (flow, res["flow_lr"], res["fmap"], res["cnet"],
                res.get("iters_used"))

    # -- the continuous-batched stream path (slot pool) --------------------
    #
    # ``run_stream_batch`` is place -> dispatch -> wait -> fetch, as ``run``
    # is, and the batcher walks a group of advances through the same
    # pipeline (serving/stream.py): ``ready`` and ``wait`` are the pair
    # call's.

    def place_stream_batch(self, bucket: Tuple[int, int],
                           images: np.ndarray,
                           sizes=None) -> "StreamBatchCall":
        """Put a stream batch's padded frames on the device
        (:meth:`_h2d`).  Once this returns the caller may rewrite
        ``images``."""
        h, w = bucket
        b = images.shape[0]
        self._ensure_slot_buffers(bucket)
        ex = self._get_executable(self._key(h, w, b, "sbatch"))
        if self.faults is not None:
            self.faults.pre_engine_call()
        [images] = self._h2d("stream", ex, 1, images)
        return StreamBatchCall(
            ex, self._with_sizes("sbatch", b, sizes, images), bucket)

    def dispatch_stream_batch(self, call: "StreamBatchCall",
                              slots: np.ndarray, active: np.ndarray) -> None:
        """Enqueue a placed stream batch over the pool's buffers AS THEY
        STAND NOW (a commit since the place has donated and swapped them),
        gathering ``slots`` [b] int32 (padding and dropped rows aim at the
        scratch slot) for the rows ``active`` [b] bool; returns before the
        device has run it.  ``stream_calls`` counts REAL rows (per-frame
        fnet accounting, the acceptance counters)."""
        active = np.asarray(active, bool)
        call.rows = int(active.sum())
        with self._lock:
            self.stream_calls += call.rows
        images, *sizes = call.args
        fbuf, cbuf, flbuf = self.pool.buffers(call.bucket)
        call.out = self._call(
            "stream", call.ex,
            (self.params, images, fbuf, cbuf, flbuf,
             np.asarray(slots, np.int32), active, *sizes), wait=False)
        call.args = None              # the device holds what it needs

    def fetch_stream_batch(self, call: "StreamBatchCall") -> tuple:
        """A finished stream batch's ``(flow [b] np, flow_lr [b] np,
        fmap_rows dev, cnet_rows dev, iters_used [b] np or None)`` — the
        updated map ROWS stay device-resident until :meth:`commit_stream`
        scatters the finite ones into the pool."""
        res = self._fetch_outputs("sbatch", call.out)
        flow = res["flow"]
        if self.faults is not None:
            # chaos must poison a REAL row: padding rows (the suffix, by
            # the coordinator's construction) are discarded before the
            # sentinel, so a roll landing there would silently test
            # nothing
            flow = np.concatenate(
                [self.faults.corrupt_rows(flow[:call.rows]),
                 flow[call.rows:]])
        return (flow, res["flow_lr"], res["fmap"], res["cnet"],
                res.get("iters_used"))

    def run_stream_batch(self, bucket: Tuple[int, int], images: np.ndarray,
                         slots: np.ndarray, active: np.ndarray,
                         sizes=None):
        """ONE device call advancing ``active.sum()`` different sessions:
        ``images`` [b, BH, BW, 3] (padded to a declared batch step),
        ``slots`` [b] int32 pool rows, ``active`` [b] bool; what
        :meth:`fetch_stream_batch` returns."""
        call = self.place_stream_batch(bucket, images, sizes)
        self.dispatch_stream_batch(call, slots, active)
        self.wait(call)
        return self.fetch_stream_batch(call)

    # the server pipelines stream batches only where ``run_stream_batch`` is
    # this composition (server.FlowServer._stream_engine)
    run_stream_batch.composes_phases = True

    def commit_stream(self, bucket: Tuple[int, int], slots: np.ndarray,
                      fmap_rows, cnet_rows, seeds: np.ndarray,
                      mask: np.ndarray) -> None:
        """Scatter updated rows into the pool buffers (masked: padding
        rows and sentinel-rejected rows write their old value back) and
        swap the pool refs.  The buffers were donated into the
        executable (off-CPU), so the swap is mandatory — the old refs
        are dead.  A commit that RAISES leaves the donated inputs in an
        undefined state, so the buffers are rebuilt zeroed here before
        the exception propagates; the caller must then demote the
        bucket's sessions (``store.demote_bucket``) so nothing gathers
        the zeros."""
        h, w = bucket
        b = int(np.asarray(slots).shape[0])
        self._ensure_slot_buffers(bucket)
        ex = self._get_executable(self._key(h, w, b, "scommit"))
        fbuf, cbuf, flbuf = self.pool.buffers(bucket)
        try:
            # commit is dispatch-only: the rows stay device-resident
            out = self._call(
                "commit", ex,
                (fbuf, cbuf, flbuf, np.asarray(slots, np.int32), fmap_rows,
                 cnet_rows, np.asarray(seeds, np.float32),
                 np.asarray(mask, bool)), wait=False)
        except Exception:
            self.reset_slots(bucket)
            raise
        self.pool.install(bucket, out)
        if self.config.quant_slots:
            with self._lock:
                self.rows_quantized += int(np.count_nonzero(mask))

    def commit_row(self, bucket: Tuple[int, int], slot: int, fmap, cnet,
                   seed: np.ndarray) -> None:
        """Width-1 commit: install one session's fresh maps + warm-start
        seed into its slot (session open / cold-restart attach)."""
        self.commit_stream(bucket, np.asarray([slot], np.int32),
                           fmap, cnet, seed, np.asarray([True]))

    def poison_slot(self, bucket: Tuple[int, int], slot: int) -> None:
        """Chaos ``session`` arm: NaN one slot's cached fmap row in place
        (drills only — the executable is warmed only when the injector is
        armed)."""
        h, w = bucket
        self._ensure_slot_buffers(bucket)
        ex = self._get_executable(self._key(h, w, 1, "spoison"))
        fbuf, cbuf, flbuf = self.pool.buffers(bucket)
        self.pool.install(bucket,
                          (ex(fbuf, np.asarray([slot], np.int32)),
                           cbuf, flbuf))
