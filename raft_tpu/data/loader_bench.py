"""Input-pipeline throughput benchmark: ``python -m raft_tpu.data.loader_bench``.

Measures the host decode/augment/transport path at training shapes — the
number that decides whether the input pipeline can feed a TPU (VERDICT
round 1, weak #7 analog): a v5e chip stepping RAFT at training shapes
consumes ~50-300 pairs/sec depending on iters, and PERF.md round 7 rebuilt
the host->device path around that gap.  The report is STAGED so each layer
of the rebuild is attributable:

* ``sequential`` — in-process decode+augment vs decode-only (the device-aug
  host path) rates: what one core's GIL-bound budget buys each way;
* ``mp`` — worker-process sweep crossing transport (pickle queues vs the
  shared-memory slot ring) with host path (decode+augment vs decode-only);
* ``device_aug_e2e`` — the full new pipeline: decode-only shm workers ->
  pre-allocated batch collation -> PrefetchLoader staging + jitted on-device
  augmentation, measured in delivered batches on this host's default
  backend.

Uses the procedural synthetic dataset as the decode stand-in (no real
dataset is downloadable in this environment); its per-sample cost — pyramid
multi-octave texture synthesis + remap — is the same order as PNG decode of
a Sintel frame, and the FlowAugmentor on top is identical to real training.

Provenance: the JSON report (``--out BENCH_input.json``) embeds a telemetry
run manifest (schema: metric/value/unit/error + ``manifest``)
and the run appends stage events to ``events.jsonl`` (``--run-log``).
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from ..telemetry import default_registry, run_manifest, start_run
from .augment import FlowAugmentor
from .augment_device import DecodeOnlyDataset, make_device_augmentor
from .mp_loader import MPSampleLoader, measure_rate
from .synthetic import SyntheticFlowDataset


def make_dataset(crop=(368, 496), length=4096, device_aug: bool = False):
    # source frames comfortably larger than the crop so FlowAugmentor's
    # random scale/crop runs its real code path
    src = (crop[0] + 72, crop[1] + 84)
    base = SyntheticFlowDataset(size=src, length=length, max_flow=16.0,
                                augmentor=None if device_aug
                                else FlowAugmentor(crop))
    return DecodeOnlyDataset(base) if device_aug else base


def _host_path_rates(ds_aug, ds_dec, samples: int) -> dict:
    """Per-worker host-path service rate, measured in-process so the number
    is one core's deterministic budget rather than 2-core scheduling noise:
    what ONE worker spends per sample on each side of the rebuild —
    decode+augment+pickle (the status-quo transport serializes every
    sample) vs decode-only+slot-write (the device-aug/shm path)."""
    import pickle
    import time

    from .mp_loader import SampleSpec, ShmRing

    def pickle_cost(s):
        pickle.dumps(s, protocol=pickle.HIGHEST_PROTOCOL)

    spec = SampleSpec.from_sample(ds_dec[0])
    ring = ShmRing(2, spec.nbytes)
    t_aug = t_dec = 0.0
    try:
        k = [0]

        def write_cost(s):
            k[0] ^= 1
            spec.write(ring.shms[k[0]].buf, s)

        for i in range(2):   # warmup both paths (cv2 caches, shm pages)
            pickle_cost(ds_aug[i])
            write_cost(ds_dec[i])
        # SAMPLE-LEVEL interleave: the two paths alternate within the same
        # measurement window, so a shared sandbox's transient load bursts
        # hit both nearly equally and the cost RATIO stays trustworthy even
        # when the absolute rates wobble
        for i in range(samples):
            t0 = time.perf_counter()
            pickle_cost(ds_aug[i])
            t1 = time.perf_counter()
            write_cost(ds_dec[i])
            t_aug += t1 - t0
            t_dec += time.perf_counter() - t1
    finally:
        ring.close()
    return {
        "decode_augment_pickle_pairs_per_s": round(samples / t_aug, 2),
        "decode_only_shm_pairs_per_s": round(samples / t_dec, 2),
        "ratio_decode_only_vs_host_aug": round(t_aug / t_dec, 2),
    }


def _mp_rate(ds, workers: int, samples: int, transport: str) -> float:
    loader = MPSampleLoader(ds, num_workers=workers, seed=0,
                            transport=transport)
    try:
        # warmup must drain the pre-filled result buffer (queue depth
        # 2*w) or the buffered samples arrive instantly and inflate the
        # measured steady-state rate
        return measure_rate(iter(loader), samples, warmup=2 * workers + 2)
    finally:
        loader.close()


def _device_aug_e2e(crop, workers: int, batch: int, batches: int,
                    log=None) -> dict:
    """The rebuilt pipeline end to end on this host's default backend:
    decode-only shm workers -> BatchBuffers collation -> PrefetchLoader
    staging with the jitted device augmentor."""
    import jax

    from .augment_device import make_batch_augment_fn
    from .pipeline import BatchBuffers, PrefetchLoader, batched

    ds = make_dataset(crop, device_aug=True)
    batch_aug = make_batch_augment_fn(make_device_augmentor("synthetic", crop),
                                      hw=ds.canonical_hw)

    def augment_fn(b, key):
        return tuple(batch_aug(key, *b[:3]))

    loader = MPSampleLoader(ds, num_workers=workers, seed=0, transport="shm")
    pf = PrefetchLoader(
        batched(iter(loader), batch,
                collator=BatchBuffers.for_loader(batch, 2)),
        augment_fn=augment_fn, augment_seed=0)

    def materialized(it):
        # block on every batch INSIDE the timed window: consuming
        # async-dispatched jax arrays at host dispatch rate would overstate
        # the rate the augment compute can actually sustain
        for b in it:
            yield jax.block_until_ready(b)

    try:
        rate = measure_rate(materialized(pf), batches, warmup=3)
    finally:
        pf.close()
        loader.close()
    out = {"backend": jax.default_backend(),
           "batch": batch, "workers": workers,
           "pairs_per_s": round(rate * batch, 2)}
    if log is not None:
        log.event("stage", name="device_aug_e2e", **out)
    return out


def run(samples: int = 32, workers=(1, 2), crop=(368, 496),
        batch: int = 4, e2e_batches: int = 8, log=None) -> dict:
    results = {"crop": list(crop), "samples_per_point": samples,
               "stages": {}}

    ds_aug = make_dataset(crop)
    ds_dec = make_dataset(crop, device_aug=True)
    seq = {
        "decode_plus_augment_pairs_per_s": round(
            measure_rate(ds_aug.sample_iter(seed=0), samples), 2),
        "decode_only_pairs_per_s": round(
            measure_rate(ds_dec.sample_iter(seed=0), samples), 2),
    }
    seq["ratio_decode_only_vs_augment"] = round(
        seq["decode_only_pairs_per_s"]
        / seq["decode_plus_augment_pairs_per_s"], 2)
    results["stages"]["sequential"] = seq
    if log is not None:
        log.event("stage", name="sequential", **seq)

    host = _host_path_rates(ds_aug, ds_dec, samples)
    results["stages"]["host_path_per_worker"] = host
    if log is not None:
        log.event("stage", name="host_path_per_worker", **host)

    mp = {}
    for w in workers:
        point = {
            "pickle_augment_pairs_per_s": round(
                _mp_rate(ds_aug, w, samples, "pickle"), 2),
            "shm_augment_pairs_per_s": round(
                _mp_rate(ds_aug, w, samples, "shm"), 2),
            "shm_decode_only_pairs_per_s": round(
                _mp_rate(ds_dec, w, samples, "shm"), 2),
        }
        # distinct name from the host_path_per_worker ratio: this one is
        # end-to-end across processes and bounded by core contention
        point["ratio_shm_decode_only_vs_pickle_aug_e2e"] = round(
            point["shm_decode_only_pairs_per_s"]
            / point["pickle_augment_pairs_per_s"], 2)
        mp[f"workers_{w}"] = point
        if log is not None:
            log.event("stage", name="mp", workers=w, **point)
    results["stages"]["mp"] = mp

    results["stages"]["device_aug_e2e"] = _device_aug_e2e(
        crop, max(workers), batch, e2e_batches, log=log)

    wmax = f"workers_{max(workers)}"
    results["ratio_decode_only_vs_host_aug_per_worker"] = \
        host["ratio_decode_only_vs_host_aug"]
    results["metric"] = "input_shm_decode_only_pairs_per_s"
    results["value"] = mp[wmax]["shm_decode_only_pairs_per_s"]
    results["unit"] = "pairs/sec"
    results["error"] = None
    results["data_metrics"] = {
        k: v for k, v in default_registry().snapshot().items()
        if k.startswith("raft_data_")}
    return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--samples", type=int, default=32)
    p.add_argument("--crop", type=int, nargs=2, default=(368, 496))
    p.add_argument("--workers", type=int, nargs="+", default=(1, 2),
                   help="worker-process counts to measure")
    p.add_argument("--batch", type=int, default=4,
                   help="batch size for the device-aug end-to-end stage")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="also write the report JSON (e.g. BENCH_input.json)")
    p.add_argument("--run-log", default=".", metavar="DIR",
                   help="append stage events to DIR/events.jsonl "
                        "('none' disables)")
    args = p.parse_args(argv)

    log = None
    if args.run_log != "none":
        log = start_run(Path(args.run_log), mode="loader_bench")
    try:
        results = run(samples=args.samples, workers=tuple(args.workers),
                      crop=tuple(args.crop), batch=args.batch, log=log)
        results["manifest"] = run_manifest(mode="loader_bench")
        if log is not None:
            log.event("result", metric=results["metric"],
                      value=results["value"], unit=results["unit"])
    except BaseException as e:  # noqa: BLE001 — the driver parses stdout JSON
        results = {"metric": "input_shm_decode_only_pairs_per_s",
                   "value": None, "unit": "pairs/sec",
                   "error": f"{type(e).__name__}: {e}",
                   "manifest": run_manifest(mode="loader_bench")}
        print(json.dumps(results), flush=True)
        raise
    finally:
        if log is not None:
            log.close()
    print(json.dumps(results), flush=True)
    if args.out:
        Path(args.out).write_text(json.dumps(results, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
