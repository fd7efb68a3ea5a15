"""Batching + background prefetch — the TPU-native replacement for
``torch.utils.data.DataLoader`` (reference `frcnn.py:19-23`, SURVEY.md §2.3
"host-side input pipeline ... feeding device").

Design: the dataset's __getitem__ is pure numpy on host; a background
thread pool assembles fixed-shape batches ahead of the training loop into a
bounded queue, so the host pipeline overlaps device step time (SURVEY.md §7
hard part #4 — input-bound chips waste the 6x target). Batches are plain
dicts of stacked numpy arrays; the trainer moves them to device (sharded
`jax.device_put`) itself, keeping this module framework-free.

Epoch semantics mirror the reference trainer: sequential or seeded-shuffle
order, drop_last (the fixed-shape train step wants full batches).

Threads (not processes) are enough to scale ingest across cores: the
sample hot path — JPEG decode + fused resize/normalize — is one ctypes
call into native/frcnn_native.cpp, and ctypes releases the GIL for the
call's duration, so ``num_workers`` decode threads genuinely run in
parallel (the torch DataLoader needs worker *processes* because its
Python-side transforms hold the GIL).
"""

from __future__ import annotations

import multiprocessing
import queue
import threading
import time
import traceback
from concurrent import futures
from typing import Dict, Iterator, Optional, Sequence

import numpy as np

from replication_faster_rcnn_tpu.faultlib import failpoints
from replication_faster_rcnn_tpu.telemetry import spans as tspans


def collate(samples: Sequence[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Stack per-sample dicts into one batch dict."""
    return {k: np.stack([s[k] for s in samples]) for k in samples[0]}


def fetch_sample(ds, idx: int, on_skip=None):
    """``ds[idx]`` with fault containment: one retry (truncated reads and
    NFS hiccups are transient), then deterministic substitution by the
    nearest following index that decodes — one rotten JPEG two hours into
    an epoch must cost one sample, not the run.

    ``on_skip(idx, exc)`` is called once per abandoned index (after the
    failed retry) and may raise to enforce a skip budget — substitution
    without a cap would silently train on a collapsing dataset. With no
    ``on_skip`` the substitution is unbudgeted. Raises the last error only
    if every index in the dataset fails.

    The ``loader.fetch`` failpoint wraps every dataset access (the
    original, the retry, and each substitution probe), so an injected
    IOError rides exactly this containment and an injected ``nan`` fault
    poisons the decoded sample the way a corrupt image would.
    """

    def _get(i: int):
        inj = failpoints.fire("loader.fetch", index=int(i))  # ioerror raises
        sample = ds[int(i)]
        if inj is not None and inj.kind == "nan":
            sample = failpoints.poison_batch(sample)
        return sample

    try:
        return _get(idx)
    except Exception:
        try:
            return _get(idx)  # the one retry
        except Exception as exc:
            if on_skip is not None:
                on_skip(int(idx), exc)
            n = len(ds)
            for delta in range(1, n):
                j = (int(idx) + delta) % n
                try:
                    return _get(j)
                except Exception:
                    continue
            raise


def _mp_worker(dataset, task_q, result_q, skip_budget: int = 0) -> None:
    """Worker-process loop: build collated batches for index lists.

    Runs only dataset/numpy code — no jax, no device ops: the chip
    belongs to the parent process, and a child that reached for it would
    fail or hang. Errors are shipped back as formatted tracebacks:
    exception objects aren't reliably picklable.

    Failing samples get the same retry-then-substitute treatment as the
    thread path (``fetch_sample``), with a per-worker skip budget —
    worker counters can't be shared cheaply across processes, and since
    workers are re-forked each epoch a per-worker cap is the per-epoch
    cap divided by the worker count, same order of protection.
    """
    skips = 0

    def on_skip(idx, exc):
        nonlocal skips
        skips += 1
        if skip_budget and skips > skip_budget:
            raise RuntimeError(
                f"loader worker sample-skip budget exhausted: {skips} "
                f"failed samples (> {skip_budget}); last at index {idx}: "
                f"{exc!r}"
            )

    while True:
        item = task_q.get()
        if item is None:
            return
        seq, idxs = item
        try:
            if skip_budget:
                batch = collate([fetch_sample(dataset, i, on_skip) for i in idxs])
            else:  # containment disabled
                batch = collate([dataset[int(i)] for i in idxs])
            result_q.put((seq, batch))
        except BaseException:  # noqa: BLE001 — report, don't kill the worker
            result_q.put((seq, ("__error__", traceback.format_exc())))


class DataLoader:
    """Iterable over fixed-shape batches with background prefetch.

    Args:
      dataset: map-style dataset (len + __getitem__ -> dict of numpy).
      batch_size: per-iteration global batch.
      shuffle: seeded reshuffle each epoch (seed + epoch), deterministic —
        required for checkpoint-resume reproducibility (SURVEY.md §5).
      drop_last: drop the trailing partial batch (default True: the jitted
        step is compiled for exactly batch_size).
      prefetch: max batches buffered ahead (0 disables threading).
      num_workers: workers assembling samples within a batch; negative
        means auto — min(4, schedulable cores): on a 1-core host a
        4-thread pool measured SLOWER than single-thread ingest
        (benchmarks/loader_throughput.json).
      cache_ram: memoize decoded samples in host RAM (`data/cache.py`):
        epoch 1 pays the decode, every later epoch is a memcpy. The
        single-core answer to an input-bound chip — decode throughput
        can't be scaled by workers when there is one core. Bounded by
        FRCNN_CACHE_MAX_BYTES (default 64 GiB).
      worker_mode: "thread" (default — the native decode path releases
        the GIL, so threads scale it across cores) or "process" —
        fork-based worker processes, one whole batch per task, results
        re-ordered to the deterministic epoch order. Use "process" when
        the per-sample work is GIL-bound Python (the numpy fallback
        decode path, heavy augmentation), where threads serialize
        (the thread loader was GIL-capped at 1x there). Fork (not
        spawn) on purpose: a forked child inherits the parent's modules
        and runs only numpy code, never re-importing jax.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        shuffle: bool = True,
        drop_last: bool = True,
        prefetch: int = 2,
        num_workers: int = 4,
        seed: int = 0,
        worker_mode: str = "thread",
        augment_hflip: bool = False,
        augment_scale=None,
        augment_scale_device: bool = False,
        augment_device: bool = False,
        augment_translate: float = 0.0,
        stall_timeout: float = 120.0,
        cache_ram: bool = False,
        sample_skip_budget: int = 8,
        process_index: int = 0,
        process_count: int = 1,
        train_resolutions=(),
        bucket_chunk: int = 1,
    ) -> None:
        if worker_mode not in ("thread", "process"):
            raise ValueError(f"worker_mode must be thread|process, got {worker_mode!r}")
        if process_count < 1 or not 0 <= process_index < process_count:
            raise ValueError(
                f"process_index={process_index} out of range for "
                f"process_count={process_count}"
            )
        if batch_size % process_count:
            raise ValueError(
                f"global batch_size={batch_size} must divide evenly over "
                f"{process_count} processes"
            )
        # multi-process data sharding: every process draws the SAME
        # deterministic global epoch order (seed + epoch), then each keeps
        # only its contiguous rows of every global batch — matching the
        # mesh's process-contiguous device order, so
        # `parallel.shard_batch` can assemble the global array from local
        # rows with zero cross-host traffic. Augment draws key on the
        # GLOBAL sample index, so the global batch content is independent
        # of the process count (topology-change-tolerant resume).
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.stall_timeout = float(stall_timeout)
        self.augment_hflip = augment_hflip
        self.augment_scale = augment_scale
        self.augment_scale_device = augment_scale_device
        self.augment_device = augment_device
        self.augment_translate = float(augment_translate)
        if cache_ram:
            from replication_faster_rcnn_tpu.data.cache import CachedView

            dataset = CachedView(dataset)
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.prefetch = prefetch
        if num_workers < 0:  # auto: scale with the host, never beyond 4
            import os

            try:  # cores this process may RUN on (cgroup/taskset-aware)
                avail = len(os.sched_getaffinity(0))
            except (AttributeError, OSError):
                avail = os.cpu_count() or 1
            num_workers = min(4, avail)
        self.num_workers = max(1, num_workers)
        self.seed = seed
        self.worker_mode = worker_mode
        self.epoch = 0
        self.start_batch = 0  # mid-epoch offset (set_epoch)
        self._q: Optional["queue.Queue"] = None  # live prefetch queue
        # sample fault containment (fetch_sample): failed samples are
        # retried once then substituted, up to this many per epoch — past
        # it the epoch errors out (a collapsing dataset must not be
        # silently papered over). 0 disables containment entirely.
        self.sample_skip_budget = int(sample_skip_budget)
        self._epoch_skips = 0
        self._skip_lock = threading.Lock()
        # multi-scale buckets (data.train_resolutions): the feed only
        # ASSIGNS each global batch to a bucket (bucket_of); the resample
        # to the bucket's shape runs on device inside that bucket's
        # compiled program. bucket_chunk = train.steps_per_dispatch so all
        # K batches of one fused dispatch share a bucket.
        self.train_resolutions = tuple(
            (int(r[0]), int(r[1])) for r in (train_resolutions or ())
        )
        self.bucket_chunk = max(1, int(bucket_chunk))

    def set_epoch(self, epoch: int, start_batch: int = 0) -> None:
        """Select the epoch — and optionally a mid-epoch offset.

        ``start_batch`` resumes iteration at that global batch index of
        the epoch's deterministic order: the consumed prefix is never
        decoded or collated (unlike draw-and-discard replay), and the
        remaining suffix is bitwise identical to an uninterrupted epoch —
        the global order is a pure function of (seed, epoch), so slicing
        it is exact. Elastic fleet shrink leans on the same property: a
        re-formed feed at a NEW process_count and the same ``start_batch``
        re-partitions the unconsumed suffix disjointly across the new
        world size."""
        if start_batch < 0:
            raise ValueError(f"start_batch must be >= 0, got {start_batch}")
        self.epoch = epoch
        self.start_batch = int(start_batch)
        with self._skip_lock:  # pool workers bump the counter concurrently
            self._epoch_skips = 0  # the skip budget is per-epoch

    def _on_sample_skip(self, idx: int, exc: Exception) -> None:
        """Budget + telemetry for one abandoned sample (thread path; pool
        workers land here concurrently, hence the lock)."""
        with self._skip_lock:
            self._epoch_skips += 1
            skips = self._epoch_skips
        if skips > self.sample_skip_budget:
            raise RuntimeError(
                f"loader sample-skip budget exhausted: {skips} failed "
                f"samples this epoch (> {self.sample_skip_budget}); last "
                f"at index {idx}: {exc!r}"
            )
        import sys

        print(
            f"warning: sample {idx} failed twice, substituting neighbor "
            f"({skips}/{self.sample_skip_budget} skips this epoch): {exc!r}",
            file=sys.stderr,
        )
        tspans.current_tracer().instant(
            "data/sample_skipped", cat="data", idx=int(idx),
            skips=skips, error=repr(exc)[:200],
        )

    def bucket_of(self, batch_pos: int) -> int:
        """Resolution-bucket index for the GLOBAL batch at ``batch_pos``
        of the current epoch — a pure function of (seed, epoch,
        batch_pos // bucket_chunk), so every process agrees, a
        ``set_epoch(epoch, start_batch=)`` resume replays the identical
        sequence, and the local row-block sharding keeps each bucket's
        shards disjoint exactly like the unbucketed feed. Returns 0 when
        bucketing is off."""
        if len(self.train_resolutions) <= 1:
            return 0
        from replication_faster_rcnn_tpu.data.augment import bucket_index

        return bucket_index(
            self.seed,
            self.epoch,
            int(batch_pos),
            len(self.train_resolutions),
            chunk=self.bucket_chunk,
        )

    def queue_depth(self) -> Optional[int]:
        """Batches currently buffered ahead of the consumer (thread-mode
        prefetch only; None before iteration or in process mode). A depth
        pinned at 0 under load means the feed can't keep up — the number
        the watchdog snapshots to tell feed-starvation from a wedged
        device."""
        q = self._q
        return q.qsize() if q is not None else None

    def _order(self) -> np.ndarray:
        n = len(self.dataset)
        if not self.shuffle:
            return np.arange(n)
        rng = np.random.RandomState(self.seed + self.epoch)
        return rng.permutation(n)

    def __len__(self) -> int:
        n = len(self.dataset)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _batches(self) -> Iterator[np.ndarray]:
        order = self._order()
        bs = self.batch_size
        end = len(order) - (len(order) % bs if self.drop_last else 0)
        local = bs // self.process_count
        lo = self.process_index * local
        for i in range(self.start_batch * bs, end, bs):
            # this process's contiguous block of the global batch (the
            # whole batch in single-process runs: lo=0, local=bs)
            yield order[i + lo : i + lo + local]

    def _epoch_dataset(self):
        """The dataset view for the current epoch: identity, or the
        deterministic hflip/scale-jitter augmentations keyed on
        (seed, epoch, idx) — computed per-iteration so set_epoch()
        re-rolls the draws while resume replays them exactly."""
        if self.augment_device and (
            self.augment_hflip or self.augment_scale or self.augment_translate
        ):
            # fully on-device mode: the host ships raw pixels plus the
            # int32 (idx, epoch) row the compiled step's splitmix draws
            # key on — no host flip, no host box affine, no host resample
            from replication_faster_rcnn_tpu.data.augment import AugmentTagView

            return AugmentTagView(self.dataset, self.epoch)
        if not (self.augment_hflip or self.augment_scale):
            return self.dataset
        from replication_faster_rcnn_tpu.data.augment import AugmentedView

        return AugmentedView(
            self.dataset,
            self.seed,
            self.epoch,
            hflip=self.augment_hflip,
            scale_range=self.augment_scale,
            scale_on_device=self.augment_scale_device,
        )

    def _build(
        self, idxs: np.ndarray, pool: Optional[futures.ThreadPoolExecutor], ds
    ) -> Dict[str, np.ndarray]:
        # decode+augment+collate for one batch; runs on the producer thread,
        # so under healthy prefetch these spans OVERLAP step spans in the
        # trace — visibly parallel lanes, not a serial pipeline
        with tspans.current_tracer().span(
            "data/build", cat="data", batch=len(idxs)
        ):
            if not self.sample_skip_budget:  # containment disabled
                if pool is None or len(idxs) == 1:
                    return collate([ds[int(i)] for i in idxs])
                return collate(list(pool.map(lambda i: ds[int(i)], idxs)))
            on_skip = self._on_sample_skip
            if pool is None or len(idxs) == 1:
                return collate([fetch_sample(ds, i, on_skip) for i in idxs])
            return collate(
                list(pool.map(lambda i: fetch_sample(ds, i, on_skip), idxs))
            )

    def _iter_processes(self) -> Iterator[Dict[str, np.ndarray]]:
        """Process-worker iteration: whole batches farmed to forked
        workers, yielded strictly in epoch order (a reorder buffer keyed
        on sequence number — checkpoint-resume reproducibility must not
        depend on worker scheduling). In-flight tasks are bounded so the
        result queue never holds more than workers+prefetch batches."""
        from replication_faster_rcnn_tpu.data.cache import CachedView

        if isinstance(self.dataset, CachedView):
            # forked workers fill copy-on-write caches that die with them
            # (workers are re-forked each epoch) — warming in the parent
            # FIRST makes the cache genuinely shared; without this,
            # cache_ram + process mode silently re-decodes every epoch
            self.dataset.warm()
        ctx = multiprocessing.get_context("fork")
        task_q = ctx.Queue()
        result_q = ctx.Queue()
        ds = self._epoch_dataset()
        procs = [
            ctx.Process(
                target=_mp_worker,
                args=(ds, task_q, result_q, self.sample_skip_budget),
                daemon=True,
            )
            for _ in range(self.num_workers)
        ]
        for p in procs:
            p.start()
        try:
            batches = list(self._batches())
            cap = self.num_workers + max(self.prefetch, 1)
            next_submit = next_yield = 0
            buf: Dict[int, object] = {}
            while next_yield < len(batches):
                while next_submit < len(batches) and next_submit - next_yield < cap:
                    task_q.put((next_submit, batches[next_submit]))
                    next_submit += 1
                # per-wait clock: time spent *waiting on this batch*, not
                # time since the last receipt — consumer time at yield
                # (train steps, compiles) must not count toward the
                # deadline; a truly deadlocked worker still never delivers
                last_progress = time.monotonic()
                while next_yield not in buf:
                    try:
                        seq, payload = result_q.get(
                            timeout=min(5.0, self.stall_timeout)
                        )
                    except queue.Empty:
                        # a forked worker can die without reporting (OOM
                        # kill, native-decode segfault) — fail loudly
                        # instead of blocking forever on a batch that
                        # will never arrive
                        dead = [p for p in procs if not p.is_alive()]
                        if dead:
                            codes = [p.exitcode for p in dead]
                            raise RuntimeError(
                                f"{len(dead)} loader worker(s) died "
                                f"(exitcodes {codes}) before batch "
                                f"{next_yield} arrived"
                            )
                        # liveness isn't progress: a fork-inherited lock
                        # deadlock (the primary risk of forking a
                        # multithreaded JAX parent) leaves workers alive
                        # but forever silent — an overall no-progress
                        # deadline turns that silent hang into an error
                        if time.monotonic() - last_progress > self.stall_timeout:
                            raise RuntimeError(
                                "loader made no progress for "
                                f"{self.stall_timeout:.0f}s waiting on batch "
                                f"{next_yield} with all {len(procs)} workers "
                                "alive — likely a fork-inherited lock "
                                "deadlock; use worker_mode='thread' or "
                                "raise stall_timeout"
                            )
                        continue
                    buf[seq] = payload
                    last_progress = time.monotonic()
                payload = buf.pop(next_yield)
                next_yield += 1
                if isinstance(payload, tuple) and payload and payload[0] == "__error__":
                    raise RuntimeError(f"loader worker failed:\n{payload[1]}")
                yield payload
        finally:
            for _ in procs:
                try:
                    task_q.put_nowait(None)
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            for p in procs:
                p.join(timeout=2)
                if p.is_alive():
                    p.terminate()

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        if self.worker_mode == "process" and self.num_workers > 1:
            yield from self._iter_processes()
            return
        # one pool per iteration, reused across every batch (pool
        # creation/teardown per batch is measurable on the hot input path)
        pool: Optional[futures.ThreadPoolExecutor] = None
        if self.num_workers > 1:
            pool = futures.ThreadPoolExecutor(self.num_workers)
        ds = self._epoch_dataset()

        if self.prefetch <= 0:
            try:
                for idxs in self._batches():
                    yield self._build(idxs, pool, ds)
            finally:
                if pool is not None:
                    pool.shutdown(wait=False)
            return

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        self._q = q
        stop = threading.Event()
        err: list = []

        def put_unless_stopped(item) -> bool:
            """Bounded put that gives up once the consumer is gone — a plain
            q.put could block forever on an abandoned iterator."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer() -> None:
            try:
                for idxs in self._batches():
                    if stop.is_set():
                        return
                    if not put_unless_stopped(self._build(idxs, pool, ds)):
                        return
            except BaseException as e:  # surface worker errors to the consumer
                err.append(e)
            finally:
                put_unless_stopped(None)
                if pool is not None:
                    pool.shutdown(wait=False)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        tracer = tspans.current_tracer()
        try:
            while True:
                batch = q.get()
                if batch is None:
                    if err:
                        raise err[0]
                    return
                tracer.counter("loader/queue_depth", q.qsize())
                yield batch
        finally:
            stop.set()
            self._q = None
            while not q.empty():
                q.get_nowait()


def make_dataset(cfg, split: str = "train", **kwargs):
    """Dataset factory keyed on DataConfig.dataset."""
    from replication_faster_rcnn_tpu.config import DataConfig  # noqa: F401

    kind = cfg.dataset
    if kind == "voc":
        from replication_faster_rcnn_tpu.data.voc import VOCDataset

        return VOCDataset(cfg, split, **kwargs)
    if kind == "coco":
        from replication_faster_rcnn_tpu.data.coco import COCODataset

        split_map = {"train": "train2017", "val": "val2017"}
        return COCODataset(cfg, split_map.get(split, split), **kwargs)
    if kind == "synthetic":
        from replication_faster_rcnn_tpu.data.synthetic import SyntheticDataset

        return SyntheticDataset(cfg, split, **kwargs)
    if kind == "tokens":
        from replication_faster_rcnn_tpu.data.tokens import TokenDataset

        return TokenDataset(cfg, split, **kwargs)
    raise ValueError(f"unknown dataset kind {kind!r}")
