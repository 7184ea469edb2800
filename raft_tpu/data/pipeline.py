"""Host -> device input pipeline.

TPU-native replacement for the reference's tensorpack chain
``QueueInput -> StagingInput(device='/gpu:0')`` (reference infer_raft.py:37,
SURVEY.md §2.3): a background-thread prefetcher that batches numpy samples
and stages them onto device (optionally sharded over a mesh) ahead of
compute, double-buffered so host decode/augment overlaps device steps.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional, Sequence, Tuple

import jax
import numpy as np

from ..telemetry.registry import default_registry


def _pipeline_metrics(registry=None):
    """Shared input-pipeline metrics on the process-default registry
    (atomic get-or-create: loaders may be built from several threads)."""
    reg = registry or default_registry()
    return {
        "wait": reg.get_or_histogram(
            "raft_data_wait_seconds",
            "Seconds the consumer (train step) blocked waiting for a "
            "staged batch — the starvation signal"),
        "depth": reg.get_or_gauge(
            "raft_data_queue_depth",
            "Staged device batches currently buffered ahead of the consumer"),
        "partial": reg.get_or_counter(
            "raft_data_partial_batches_total",
            "Epoch-final batches smaller than batch_size (dropped unless "
            "drop_remainder=False)"),
        "batches": reg.get_or_counter(
            "raft_data_batches_total",
            "Batches staged onto device by PrefetchLoader"),
    }


def _apply_pads(image: np.ndarray, ph: int, pw: int,
                mode: str) -> Tuple[np.ndarray, Tuple[int, int, int, int]]:
    if mode == "sintel":
        pads = (ph // 2, ph - ph // 2, pw // 2, pw - pw // 2)
    else:
        pads = (ph, 0, 0, pw)
    t, b, l, r = pads
    if not (ph or pw):
        return image, pads      # (np.pad copies even where it adds nothing)
    width = [(0, 0)] * (image.ndim - 3) + [(t, b), (l, r), (0, 0)]
    return np.pad(image, width, mode="edge"), pads


def pad_to_multiple(image: np.ndarray, multiple: int = 8,
                    mode: str = "sintel") -> Tuple[np.ndarray, Tuple[int, int, int, int]]:
    """Replicate-pad [..., H, W, C] so H, W divide ``multiple``.

    mode 'sintel': split padding between both sides; 'kitti': pad top/right
    only.  Returns (padded, (top, bottom, left, right)) for unpad_flow."""
    h, w = image.shape[-3], image.shape[-2]
    return _apply_pads(image, (-h) % multiple, (-w) % multiple, mode)


def pad_to_shape(image: np.ndarray, target_hw: Tuple[int, int],
                 mode: str = "sintel") -> Tuple[np.ndarray, Tuple[int, int, int, int]]:
    """Replicate-pad [..., H, W, C] up to an exact (H, W) — the serving
    resolution-bucket variant of :func:`pad_to_multiple` (same replicate
    semantics and pads tuple, so :func:`unpad` inverts both).  Raises when
    the image exceeds the target."""
    h, w = image.shape[-3], image.shape[-2]
    th, tw = target_hw
    if h > th or w > tw:
        raise ValueError(f"image ({h}, {w}) exceeds pad target ({th}, {tw})")
    return _apply_pads(image, th - h, tw - w, mode)


def embed_to_shape(arr: np.ndarray,
                   target_hw: Tuple[int, int]) -> np.ndarray:
    """Corner-anchor [..., H, W, C] into an exact (H, W) max box by
    ZERO-padding bottom/right only — the ragged-serving embed.  Unlike
    :func:`pad_to_shape` the content is not centered and not replicated:
    the ragged model path needs the live crop at (0, 0) with deterministic
    zeros outside (models/raft.py re-masks in-graph, so the zeros are a
    contract, not a numerics requirement).  Invert by slicing
    ``out[..., :h, :w, :]``."""
    h, w = arr.shape[-3], arr.shape[-2]
    th, tw = target_hw
    if h > th or w > tw:
        raise ValueError(f"image ({h}, {w}) exceeds embed target ({th}, {tw})")
    width = [(0, 0)] * (arr.ndim - 3) + [(0, th - h), (0, tw - w), (0, 0)]
    return np.pad(arr, width)


def unpad(arr: np.ndarray, pads: Tuple[int, int, int, int]) -> np.ndarray:
    t, b, l, r = pads
    h, w = arr.shape[-3], arr.shape[-2]
    return arr[..., t:h - b if b else h, l:w - r if r else w, :]


def batch_samples(samples: Sequence[Tuple[np.ndarray, ...]]) -> Tuple[np.ndarray, ...]:
    """Stack a list of per-sample tuples into batched arrays."""
    return tuple(np.stack([s[i] for s in samples]) for i in range(len(samples[0])))


class BatchBuffers:
    """Pre-allocated collation buffers: samples are copied row-by-row into a
    ring of reusable batch arrays instead of ``np.stack`` allocating fresh
    multi-MB arrays every batch.

    Copy-on-arrival is also the safety contract the shared-memory transport
    needs: an ``MPSampleLoader(transport='shm')`` sample is a VIEW into a
    ring slot that is recycled on the next iteration, so it must land in a
    stable buffer before the consumer advances — which ``add`` guarantees
    and a deferred ``np.stack`` would not.

    ``depth`` bounds how many emitted batches may be alive at once (the
    prefetch queue + one being consumed + one in-flight device copy); the
    ring reuses the oldest buffer after that.  Size it as
    ``prefetch_depth + 3`` (``for_loader`` does).
    """

    def __init__(self, batch_size: int, depth: int = 6):
        assert batch_size >= 1 and depth >= 2
        self.batch_size = batch_size
        self.depth = depth
        self._rings: Optional[Tuple[Tuple[np.ndarray, ...], ...]] = None
        self._k = 0

    @classmethod
    def for_loader(cls, batch_size: int, prefetch_depth: int) -> "BatchBuffers":
        return cls(batch_size, depth=prefetch_depth + 3)

    def _ensure(self, sample: Tuple[np.ndarray, ...]) -> None:
        if self._rings is None:
            self._rings = tuple(
                tuple(np.empty((self.batch_size,) + np.shape(f),
                               dtype=np.asarray(f).dtype) for f in sample)
                for _ in range(self.depth))

    def add(self, i: int, sample: Tuple[np.ndarray, ...]) -> None:
        """Copy ``sample`` into row ``i`` of the current batch buffer."""
        self._ensure(sample)
        for buf, field in zip(self._rings[self._k], sample):
            buf[i] = field

    def emit(self, count: int) -> Tuple[np.ndarray, ...]:
        """Return the filled batch (sliced to ``count`` rows if partial) and
        advance the ring."""
        bufs = self._rings[self._k]
        self._k = (self._k + 1) % self.depth
        if count == self.batch_size:
            return bufs
        return tuple(b[:count] for b in bufs)


def batched(sample_iter: Iterator, batch_size: int,
            drop_remainder: bool = True,
            collator: Optional[BatchBuffers] = None) -> Iterator:
    """Group samples into batches.

    ``drop_remainder=True`` (historical behavior) silently discards the
    epoch-final partial batch; either way a partial batch bumps the
    ``raft_data_partial_batches_total`` counter so the loss is visible.
    ``collator`` switches from per-batch ``np.stack`` to copy-on-arrival
    into pre-allocated :class:`BatchBuffers` (required for shm-transport
    samples, which are views only valid until the next iteration)."""
    metrics = _pipeline_metrics()
    n = 0
    buf = []
    for s in sample_iter:
        if collator is not None:
            collator.add(n, s)
        else:
            buf.append(s)
        n += 1
        if n == batch_size:
            yield collator.emit(n) if collator is not None else \
                batch_samples(buf)
            n = 0
            buf = []
    if n:
        metrics["partial"].inc()
        if not drop_remainder:
            yield collator.emit(n) if collator is not None else \
                batch_samples(buf)


class PrefetchLoader:
    """Background-thread prefetch + async device staging (the StagingInput
    analog): a pump thread dispatches ``device_put`` for up to ``depth``
    batches ahead of consumption, so host collation and H2D copies overlap
    device steps.

    ``sharding`` (a jax.sharding.Sharding) places each batch directly in its
    distributed layout — e.g. NamedSharding(mesh, P('data')) for DP — so the
    train step consumes pre-sharded arrays with no repacking.

    ``augment_fn(batch, key) -> batch`` runs on the staged (device) batch
    from the pump thread — the device-side augmentation hook
    (:mod:`raft_tpu.data.augment_device`): dispatch is async, so augment
    compute also overlaps the consumer's step.  ``key`` derives from
    ``augment_seed`` folded with the batch index (deterministic per run).

    Lifecycle: iterate to exhaustion, or ``close()`` (also a context
    manager) on early exit — e.g. a ``max_steps`` break — otherwise the
    daemon pump keeps decoding and ``device_put``-ing, pinning up to
    ``depth`` buffered device batches for the rest of the process.

    Telemetry (process-default registry): ``raft_data_wait_seconds``
    (consumer starvation histogram), ``raft_data_queue_depth``,
    ``raft_data_batches_total``.
    """

    def __init__(self, batch_iter: Iterable, buffer_size: int = 2,
                 sharding=None, device=None,
                 augment_fn: Optional[Callable] = None,
                 augment_seed: int = 0):
        self._iter = iter(batch_iter)
        self._q: queue.Queue = queue.Queue(maxsize=max(1, buffer_size))
        self._sharding = sharding
        self._device = device
        self._augment_fn = augment_fn
        self._augment_seed = augment_seed
        self._done = object()
        self._error = None
        self._stop = threading.Event()
        self._metrics = _pipeline_metrics()
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _stage(self, batch, index: int):
        if self._sharding is not None:
            batch = jax.tree.map(
                lambda x: jax.device_put(x, self._sharding), batch)
        elif self._device is not None:
            batch = jax.tree.map(
                lambda x: jax.device_put(x, self._device), batch)
        else:
            batch = jax.tree.map(jax.numpy.asarray, batch)
        if self._augment_fn is not None:
            key = jax.random.fold_in(
                jax.random.PRNGKey(self._augment_seed), index)
            batch = self._augment_fn(batch, key)
        return batch

    def _put(self, item) -> bool:
        """Bounded put that aborts when close() is racing — a plain
        blocking put would park the pump forever on a full queue."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _pump(self):
        try:
            for index, batch in enumerate(self._iter):
                if self._stop.is_set():
                    return
                staged = self._stage(batch, index)
                if not self._put(staged):
                    return
                self._metrics["batches"].inc()
                self._metrics["depth"].set(self._q.qsize())
        except BaseException as e:   # surfaced in the consumer, not swallowed
            self._error = e
        finally:
            self._put(self._done)

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        t0 = time.monotonic()
        item = self._q.get()
        self._metrics["wait"].observe(time.monotonic() - t0)
        self._metrics["depth"].set(self._q.qsize())
        if item is self._done:
            if self._error is not None:
                raise RuntimeError("input pipeline worker failed") from self._error
            raise StopIteration
        return item

    def close(self) -> None:
        """Stop the pump, drop buffered device batches, join the thread.
        Idempotent; safe mid-iteration (the early-exit path)."""
        self._stop.set()
        # drain so a pump parked in put() observes the stop promptly, and so
        # buffered device arrays are released rather than pinned in the queue
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)
        self._metrics["depth"].set(0)
        # release anything staged between the drain and the join
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def __enter__(self) -> "PrefetchLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def synthetic_batches(batch_size: int, size: Tuple[int, int], seed: int = 0,
                      max_flow: float = 10.0) -> Iterator:
    """Endless random (im1, im2, flow, valid) batches — smoke-test input for
    the training loop when no dataset directory is available."""
    rng = np.random.RandomState(seed)
    h, w = size
    while True:
        im1 = rng.rand(batch_size, h, w, 3).astype(np.float32)
        im2 = rng.rand(batch_size, h, w, 3).astype(np.float32)
        flow = (rng.rand(batch_size, h, w, 2).astype(np.float32) - 0.5) * max_flow
        valid = np.ones((batch_size, h, w), np.float32)
        yield im1, im2, flow, valid
