"""Serving metric set + compat shim over the shared telemetry registry.

The Counter / Gauge / Histogram / Registry primitives were born here and
now live in :mod:`raft_tpu.telemetry.registry`, where the training loop
and the data loaders count with the same classes (one
observability spine — OBSERVABILITY.md).  This module re-exports them
unchanged (``from raft_tpu.serving.metrics import Counter`` keeps working
and *is* the telemetry class) and keeps the serving-specific part: the
metric set whose names SERVING.md, the tests and the Prometheus scrape
contract pin.  The ``/metrics`` text output is byte-identical to the
pre-refactor renderer.
"""

from __future__ import annotations

import functools
from typing import Dict

from ..telemetry.registry import (_Metric,  # noqa: F401 — compat re-export
                                  Counter, Gauge, Histogram, Registry,
                                  DEFAULT_LATENCY_BUCKETS,
                                  ITERS_USED_BUCKETS, _fmt,
                                  register_process_start_time)
from ..telemetry.trace import StageCounters


def make_serving_metrics(registry: Registry, config,
                         queue_depth_fn=None) -> Dict[str, _Metric]:
    """The serving stack's metric set, in one place so the names in
    SERVING.md, the tests, and the code can't drift.  ``queue_depth_fn``
    makes the depth gauge a live callback (sampled at scrape time) so it
    can never go stale between submissions."""
    occ = tuple(i / 10 for i in range(1, 11))
    batch = tuple(float(s) for s in config.batch_steps)
    register_process_start_time(registry)
    metrics = {
        "requests": registry.counter(
            "raft_serving_requests_total",
            "Requests by terminal status",
            labelnames=("status",)),
        "pairs": registry.counter(
            "raft_serving_pairs_total",
            "Image pairs successfully served"),
        "queue_depth": registry.gauge(
            "raft_serving_queue_depth",
            "Requests currently waiting in the admission queue",
            fn=queue_depth_fn),
        "batch_size": registry.histogram(
            "raft_serving_batch_size",
            "Real (unpadded) requests per device batch",
            buckets=batch),
        "batch_occupancy": registry.histogram(
            "raft_serving_batch_occupancy",
            "Real requests / padded batch size per device call",
            buckets=occ),
        "padding_waste": registry.histogram(
            "raft_batch_padding_waste_ratio",
            "Padding pixels / total pixels per device batch: batch-fill "
            "rows plus, under --ragged, each row's dead embedding beyond "
            "its routed resolution (observed on pairwise and coalesced "
            "stream batches alike)",
            buckets=occ),
        "request_latency": registry.histogram(
            "raft_serving_request_latency_seconds",
            "End-to-end request latency (enqueue to result)"),
        # host stages (telemetry/trace.host_stage): every site that times a
        # stage for a request span also counts its seconds here, sampled or
        # not, so a window's host time per device batch needs no profiler
        "stage_seconds": registry.counter(
            "raft_serving_stage_seconds_total",
            "Host seconds by stage of the serving path (the raft.* "
            "profiler annotation less its prefix: http.decode, http.admit, "
            "batch.take, batch.form, batch.pad, engine.h2d, "
            "engine.dispatch, engine.wait, engine.fetch, batch.deliver, "
            "http.encode, http.respond; of a batched /v1/stream advance "
            "also stream.sentinel, stream.seed, stream.commit, and of a "
            "cold restart stream.cold.wait, stream.cold.encode, "
            "stream.cold.step, stream.cold.attach, which hold the engine.* "
            "seconds of the solo calls inside them) and, inside "
            "batch.deliver, batch.deliver.sentinel",
            labelnames=("stage",)),
        # the same stages on the thread's own CPU clock (time.thread_time):
        # wall less CPU of a stage that waits for no device and no socket is
        # what its thread waited for the interpreter lock and the run queue
        "stage_cpu_seconds": registry.counter(
            "raft_serving_stage_cpu_seconds_total",
            "CPU seconds of the stage's own thread by stage of the serving "
            "path, same labels as raft_serving_stage_seconds_total plus "
            "batch.deliver.sentinel (the non-finite pass inside "
            "batch.deliver, in both families)",
            labelnames=("stage",)),
        "stalled_seconds": registry.counter(
            "raft_serving_stalled_seconds_total",
            "Wall seconds of host stages that each lasted over "
            "trace.STALL_SECONDS (of a batch.take, the end through which "
            "requests were open without a break); each also writes a "
            "host_stall run-log event",
            labelnames=("stage",)),
        "device_calls": registry.counter(
            "raft_serving_device_calls_total",
            "Device calls issued by the batcher (pairwise batches, retries "
            "and bisection halves, stream steps)"),
        "device_rows": registry.counter(
            "raft_serving_device_rows_total",
            "Rows carried by those device calls: kind=real are requests, "
            "kind=padded the batch step they were padded up to",
            labelnames=("kind",)),
        "batches_staged": registry.counter(
            "raft_serving_batches_staged_total",
            "Device batches (pairwise batches and batched stream advances) "
            "by when their inputs reached the device: when=ahead before "
            "the batch in front of them was ready (the device did not "
            "wait for the host), when=late otherwise (the first of a run "
            "of batches, and every one the host was too slow for)",
            labelnames=("when",)),
        "compile_misses": registry.counter(
            "raft_serving_compile_cache_misses_total",
            "Device calls that had to compile (0 after warmup = the "
            "no-recompile-storm guarantee)"),
        # the fused correlation lookup's band schedule (ops/corr_pallas
        # .schedule_keyblocks), reduced on the device from the schedules the
        # kernels were given and fetched with the flow: visited / possible
        # is the share of the bands a batch did work in, visited / tiles
        # the bands a query tile visited a level (1.0: one band each),
        # grid_steps / tiles the steps its launch took for it (1.0: none
        # that did nothing)
        "keyblocks_visited": registry.counter(
            "raft_serving_corr_keyblocks_visited_total",
            "(query tile, band of key rows) grid steps of the correlation "
            "lookup that did work, over levels, iterations and device batches "
            "(pair batches and stream steps)"),
        "keyblocks_possible": registry.counter(
            "raft_serving_corr_keyblocks_possible_total",
            "What the lookup would visit had every tile taken every band of "
            "every level"),
        "corr_tiles": registry.counter(
            "raft_serving_corr_tiles_total",
            "(query tile, level) pairs of the correlation lookup: visited / "
            "tiles is the bands a tile visited a level, 1.0 where every "
            "tile's windows lay in one band"),
        "corr_grid_steps": registry.counter(
            "raft_serving_corr_grid_steps_total",
            "Grid steps the correlation lookup's launches took, visited or "
            "skipped: a banded launch takes as many a tile as the tile of "
            "the batch that needs most bands; grid_steps / tiles is 1.0 "
            "where no launch took a step that did nothing"),
        "corr_key_positions": registry.counter(
            "raft_serving_corr_key_positions_total",
            "Key positions the correlation lookup's grid steps multiplied "
            "and selected over, counted beside them by the levels' block "
            "plans (ops/corr_pallas.schedule_keyblocks): stored (a step's "
            "map rows x the lanes a row is stored in) and live (x the map's "
            "own columns); live / stored is the share of the lanes that held "
            "a key",
            labelnames=("kind",)),
        "iters_used": (iters_used := registry.histogram(
            "raft_iters_used",
            "GRU iterations spent per request — fills only under "
            "--iters-policy converge:* (per-sample early exit); stays "
            "empty under 'fixed', where every request costs the declared "
            "count",
            buckets=ITERS_USED_BUCKETS)),
        # live mean over everything observed so far: sum/count of the
        # histogram, sampled at scrape time — never goes stale
        "iters_mean": registry.gauge(
            "raft_iters_mean",
            "Mean GRU iterations per request (adaptive-compute saving)",
            fn=iters_used.mean),
    }
    # not a family: the recorder of the three stage families above, shared
    # by the handlers' sink and the batcher's (the server gives it its
    # log_fn and its tracer)
    metrics["stages"] = StageCounters(metrics["stage_seconds"],
                                      metrics["stage_cpu_seconds"],
                                      metrics["stalled_seconds"])
    return metrics


def make_stream_metrics(registry: Registry, store,
                        buckets=None) -> Dict[str, _Metric]:
    """The streaming (/v1/stream) metric families — one definition site,
    same contract as :func:`make_serving_metrics`.  The session gauges are
    live callbacks on the store; the eviction counter is handed back to
    the store so it can label the reason at the decision site.
    ``buckets`` (the declared resolution buckets) wires the per-bucket
    slot-pool gauges — slots in use vs capacity, the device-memory
    utilization of the continuous-batching stream path."""
    m = {
        "sessions_active": registry.gauge(
            "raft_stream_sessions_active",
            "Sessions holding device-resident feature maps "
            "(bounded by --max-sessions)",
            fn=store.active_count),
        "sessions_resident": registry.gauge(
            "raft_stream_sessions_resident",
            "Session records resident, demoted (features evicted) included",
            fn=store.resident_count),
        "opens": registry.counter(
            "raft_stream_opens_total",
            "Sessions opened"),
        "frames": registry.counter(
            "raft_stream_frames_total",
            "Stream advances served (one flow pair each)"),
        "fnet_hits": registry.counter(
            "raft_stream_fnet_cache_hits_total",
            "Advances served from cached previous-frame features "
            "(ONE encoder pass instead of two)"),
        "fnet_misses": registry.counter(
            "raft_stream_fnet_cache_misses_total",
            "Advances that cold-restarted (features evicted: two encoder "
            "passes, the zero-seeded pair, correct flow)"),
        "encoder_passes": registry.counter(
            "raft_stream_encoder_passes_total",
            "Encoder passes (fnet + cnet of one frame) on the stream path, "
            "by the engine's own call counters: call=encode a session open "
            "or the previous frame of a cold restart, call=stream the "
            "current frame of an advance (one a row of a batched step)",
            labelnames=("call",)),
        "evictions": registry.counter(
            "raft_stream_evictions_total",
            "Session evictions by reason: lru (features demoted past "
            "--max-sessions), ttl (idle record reaped), capacity "
            "(record evicted outright), degraded (breaker open / faulted "
            "step: features dropped, next advance cold-restarts)",
            labelnames=("reason",)),
        "cold_restarts": registry.counter(
            "raft_stream_cold_restarts_total",
            "Cold restarts of an advance (the kept frame's encoder pass and "
            "a zero seed; every one is also an fnet cache miss) by cause: "
            "demoted (the session held no slot when its group was placed: "
            "LRU took it while the session was parked; re-seated at the "
            "place, the row rides the group's batched call: "
            "raft_stream_restarts_batched_total), displaced (it lost the "
            "slot between its group's place and dispatch), degraded (its "
            "row of the batched call faulted); the last two, and a demoted "
            "row that could not be re-seated, heal through a solo step",
            labelnames=("cause",)),
        "restarts_batched": registry.counter(
            "raft_stream_restarts_batched_total",
            "Cold restarts whose row was served by its group's batched call "
            "(re-seated at the place: no wait, no solo step); over "
            "raft_stream_cold_restarts_total the share of restarts that "
            "took that form"),
        "promotions": registry.counter(
            "raft_stream_promotions_total",
            "Slots given to a session that held none (an open, a cold "
            "restart at its group's place, a solo heal's attach) by result: "
            "free (a slot was free), "
            "demoted_other (an LRU holder was demoted for it: "
            "raft_stream_evictions_total{reason=\"lru\"}), none (every "
            "slot pinned by a session in flight: the session stays cold)",
            labelnames=("result",)),
        "rows_quantized": registry.counter(
            "raft_stream_rows_quantized_total",
            "Slot rows committed through the int8 quantiser (--quant int8; "
            "0 without it), by the engine's own count of the commits' "
            "masked-in rows: one an advance served from the batched call, "
            "one an open, one more a cold restart's re-seat (the kept "
            "frame's maps) — frames + opens + restarts in a sound window"),
        "degraded": registry.counter(
            "raft_stream_degraded_total",
            "Stream advances whose warm step faulted (engine error or "
            "non-finite output) and were transparently retried through "
            "the cold-restart path"),
        # the continuous-batching observables (ROADMAP item 1): stream
        # device steps now coalesce across sessions, so these report the
        # REAL per-step width (batched advances also fold into the
        # shared raft_serving_batch_size/occupancy histograms)
        "steps": registry.counter(
            "raft_stream_steps_total",
            "Stream device steps executed (one per device call: a "
            "coalesced multi-session advance counts once)"),
        "step_seconds": registry.histogram(
            "raft_stream_step_seconds",
            "Device time per stream step (one batched step advances "
            "every coalesced session)"),
        "step_batch": registry.histogram(
            "raft_stream_step_batch",
            "Sessions coalesced per stream device step (continuous "
            "batching width; 1 = a solo step / session open)",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0)),
        "step_occupancy": registry.histogram(
            "raft_stream_step_occupancy",
            "Real sessions / padded slots per stream device step (the "
            "stream twin of raft_serving_batch_occupancy)",
            buckets=tuple(i / 10 for i in range(1, 11))),
    }
    store.evictions = m["evictions"]
    store.promotions = m["promotions"]
    # every child from the start
    for family, values in (("encoder_passes", ("encode", "stream")),
                           ("cold_restarts", ("demoted", "displaced",
                                              "degraded")),
                           ("promotions", ("free", "demoted_other", "none"))):
        for value in values:
            m[family].labels(value)
    if buckets:
        pool = store.pool
        in_use = registry.gauge(
            "raft_stream_slots_in_use",
            "Device-resident slot-pool rows allocated per bucket "
            "(sessions whose maps sit in batch slots, ready to coalesce)",
            labelnames=("bucket",))
        cap = registry.gauge(
            "raft_stream_slot_capacity",
            "Slot-pool rows declared per bucket (--max-sessions)",
            labelnames=("bucket",))
        for (h, w) in buckets:
            in_use.labels(f"{h}x{w}").set_fn(
                functools.partial(pool.in_use, (h, w)))
            cap.labels(f"{h}x{w}").set(pool.capacity)
        m["slots_in_use"], m["slot_capacity"] = in_use, cap
        # what the pool's buffers hold on the device, leaf by leaf, from
        # the arrays themselves (0 until a bucket's first stream call
        # installs them)
        m["pool_bytes"] = pool_bytes = registry.gauge(
            "raft_stream_pool_bytes",
            "Device bytes of the slot pool's buffers by leaf, every bucket "
            "together: vals (the fmap/cnet maps: int8 codes under --quant "
            "int8), scales (their float32 scales a channel and row; 0 "
            "without quant), seed (the float32 warm-start seeds)",
            labelnames=("leaf",))
        for leaf in ("vals", "scales", "seed"):
            pool_bytes.labels(leaf).set_fn(
                lambda leaf=leaf: pool.leaf_bytes()[leaf])
        if getattr(pool, "arena", None) is not None:
            # ragged arena (SERVING.md "Ragged serving"): the buckets all
            # map onto one max-box arena, so per-bucket in_use gauges
            # report the shared count; this gauge prices how much of the
            # allocated arena rows is LIVE page pixels (vs dead embedding)
            m["arena_live_pixels"] = registry.gauge(
                "raft_stream_arena_live_pixels",
                "Live page pixels resident in the shared ragged slot "
                "arena (sum of slot extents; the box-pixel denominator "
                "is slots_in_use x arena h x w)",
                fn=functools.partial(pool.used_pixels, pool.arena))
    return m


def make_slo_metrics(registry: Registry, slo) -> Dict[str, _Metric]:
    """SLO burn-rate families over the span data (telemetry/spans.py
    SLOTracker).  Registered only while tracing is on (trace_sample > 0)
    so `--trace-sample 0` keeps the /metrics exposition free of tracing
    families.  The violation counter is handed back to the tracker (the
    decision-site labeling pattern the session store uses)."""
    burn = registry.gauge(
        "raft_slo_burn_rate",
        "Error-budget burn rate per request class: violating fraction of "
        "the SLO window / slo_budget (1 = burning exactly the budget, "
        ">> 1 = this replica cannot meet its latency objective)",
        labelnames=("class",))
    for cls in sorted(slo.objectives):
        burn.labels(cls).set_fn(functools.partial(slo.burn_rate, cls))
    violations = registry.counter(
        "raft_slo_violations_total",
        "Requests that burned error budget, by class (slower than the "
        "class objective, or terminated shed/timeout/poisoned/error)",
        labelnames=("class",))
    for cls in sorted(slo.objectives):
        violations.labels(cls)        # pre-create: exposition shows 0
    slo.violations = violations
    return {"burn_rate": burn, "violations": violations}


def make_robustness_metrics(registry: Registry,
                            breaker=None) -> Dict[str, _Metric]:
    """The self-healing metric families (failure containment, ISSUE 11):
    always registered — they are production health signals, not debug
    toggles.  The breaker's transition counter is handed back to it (the
    decision-site labeling pattern the session store uses)."""
    m = {
        "nonfinite": registry.counter(
            "raft_nonfinite_outputs_total",
            "Flow output rows rejected by the non-finite sentinel "
            "(each fails only its own request with a 500)"),
        "batcher_restarts": registry.counter(
            "raft_batcher_restarts_total",
            "Batcher-thread crashes recovered by the supervisor "
            "(healthz reports degraded while recent)"),
    }
    if breaker is not None:
        registry.gauge(
            "raft_breaker_state",
            "Circuit breaker state: 0 closed, 1 half-open, 2 open "
            "(open sheds with 503 + Retry-After)",
            fn=breaker.state_code)
        m["breaker_transitions"] = registry.counter(
            "raft_breaker_transitions_total",
            "Breaker state transitions by destination",
            labelnames=("to",))
        breaker.transitions = m["breaker_transitions"]
    return m


def make_engine_cache_metrics(registry: Registry) -> Dict[str, _Metric]:
    """AOT executable-cache families (serving/aot_cache.py) — registered
    only when --engine-cache-dir attaches a cache, so a cacheless server's
    /metrics exposition is untouched.  The counters are bulk-filled from
    the cache's warmup stats after start()."""
    return {
        "hits": registry.counter(
            "raft_engine_cache_hits_total",
            "Warmup keys served from the serialized AOT cache "
            "(deserialized executable — no XLA compile)"),
        "misses": registry.counter(
            "raft_engine_cache_misses_total",
            "Warmup keys that fell back to compiling (absent, corrupt, "
            "or stale cache directory)"),
    }


def make_fault_metrics(registry: Registry) -> Dict[str, _Metric]:
    """Registered only when --chaos/RAFT_TPU_CHAOS arms the injector, so
    an un-drilled server's /metrics exposition carries no chaos families."""
    return {
        "faults": registry.counter(
            "raft_fault_injected_total",
            "Faults injected by the chaos harness, by arm "
            "(serving/faults.py; absent unless chaos is armed)",
            labelnames=("arm",)),
    }
