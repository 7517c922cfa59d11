"""Overlapped host-feed pipeline: parallel decode + device prefetch.

The reference hides IO behind ONE producer thread per stream
(src/utils/thread_buffer.h:22, iter_thread_imbin-inl.hpp): enough when
a K40 consumed ~250 images/sec, hopeless against a TPU step that eats
16k/sec while a single core decodes ~1-2k (docs/performance.md, the
recorded 160x host/device gap). This module rebuilds the feed as three
overlapped stages, each measured by a metrics.StallClock so the
bottleneck is an observable, not a guess:

* ``ParallelDecodeIterator`` — a multi-worker decode pool between the
  packfile reader and the augmenter: raw JPEG objects are read in .lst
  order on the consumer's thread (cheap), decoded on ``prefetch_worker``
  workers, and consumed strictly in submission order through a bounded
  in-flight window (``prefetch_depth``) — ordered, backpressured, and
  bitwise-deterministic: the augmenter above still draws its RNG in
  consumption order, so ``prefetch_worker = 4`` and ``0`` produce the
  same batches.
* ``DevicePrefetchIterator`` — runs ``Trainer.stage`` /
  ``GroupStager.stage`` on a background thread ``depth`` batches ahead,
  so the host->device transfer overlaps the previous step's compute
  instead of sitting on the critical path inside ``Trainer.update``.
* the CLI's dispatch-ahead train loop (cli.py) consumes the staged
  stream without blocking on step results — JAX's async dispatch runs
  ahead and only synchronizes at metric/eval/checkpoint boundaries.

Worker pools are thread-based by default: both decoders release the
GIL (cv2.imdecode and the native libjpeg loader), so threads fan out
across cores without pickling overhead. ``prefetch_mode = process``
ships the encoded bytes to spawned worker processes instead — for
decoders that hold the GIL.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Optional

from . import DataIterator, ProducerFailure, drain_producer
from ..analysis import hot_path
from ..analysis import lockcheck as _lockcheck
from ..metrics import StallClock
from ..obs import trace as _trace


def _decode_task(idx, label, buf):
    """Decode one encoded image object into a DataInst — the unit of
    work shipped to pool workers. Top-level (picklable) so the process
    mode can reference it; imports stay inside so a spawned worker
    loads numpy, cv2 and this package's jax-free modules (config,
    graph, io, analysis, obs, metrics) and NEVER jax: a chip belongs to
    one process, and a worker that initialised a backend would take it
    from its parent or hang (tests/test_prefetch.py pins the import
    set). The span puts each decode on its worker
    thread's trace lane (a spawned process has no tracer installed, so
    there it is the disabled one-branch path)."""
    from .image import DataInst, _decode_image
    with _trace.span("decode", "feed"):
        return DataInst(idx, label, _decode_image(buf))


class ParallelDecodeIterator:
    """Instance iterator running the image decode on a worker pool.

    Sits between an ImageBinIterator (which exposes ``next_raw()``:
    encoded objects in .lst order) and the AugmentIterator. The
    consumer pumps raw objects into the pool up to ``prefetch_depth``
    in flight — the bounded window IS the backpressure: reads pause
    while the window is full and resume as results are consumed — and
    pops results in submission order, so downstream sees exactly the
    serial stream, just sooner.

    Keys (withheld from the chain, like every wrapper's own knobs):
      prefetch_worker = N   decode workers; 0 = serial passthrough,
                            -1 (default) = auto: min(4, cores), or 0
                            when the native C++ loader (its own decode
                            threads) is active
      prefetch_depth = D    max decoded-or-decoding items in flight
                            (default 16 x workers — sized to cover a
                            batch of downstream assembly)
      prefetch_mode = m     thread (default) | process | auto
    """

    AUTO_WORKERS = 4

    def __init__(self, base, prefetch_worker: int = -1,
                 prefetch_depth: int = 0,
                 prefetch_mode: str = "auto") -> None:
        self.base = base
        self.prefetch_worker = prefetch_worker
        self.prefetch_depth = prefetch_depth
        self.prefetch_mode = prefetch_mode
        self._pool = None
        self._pending = deque()
        self._eof = False
        self._workers = 0
        self._depth = 0
        self._value = None
        # consumer-side time blocked on a not-yet-finished decode:
        # > 0 means the pool (not the reader) bounds this stage
        self.decode_wait = StallClock()

    # ------------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        if name == "prefetch_worker":
            self.prefetch_worker = int(val)
        elif name == "prefetch_depth":
            if int(val) < 0:
                raise ValueError("prefetch_depth must be >= 0")
            self.prefetch_depth = int(val)
        elif name == "prefetch_mode":
            if val not in ("auto", "thread", "process"):
                raise ValueError(
                    "prefetch_mode must be auto|thread|process (got %s)"
                    % val)
            self.prefetch_mode = val
        else:
            self.base.set_param(name, val)

    def init(self) -> None:
        import os
        self.base.init()
        if self.prefetch_depth < 0:   # constructor arg bypasses set_param
            raise ValueError("prefetch_depth must be >= 0")
        cores = os.cpu_count() or 1
        w = self.prefetch_worker
        if w < 0:
            # auto: the native loader already decodes on C++ threads —
            # a Python pool on top would only add hand-off overhead
            if getattr(self.base, "native_active", False):
                w = 0
            else:
                w = min(self.AUTO_WORKERS, cores)
        elif w > cores:
            # oversubscription measurably LOSES throughput (GIL churn +
            # context switching; docs/performance.md): prefetch_worker
            # is a ceiling, the hardware sets the floor. Ordering /
            # backpressure semantics are worker-count independent.
            w = cores
        self._workers = w
        # default window: 16 items per worker — must comfortably cover
        # one BATCH of downstream assembly (during which the consumer
        # thread holds the GIL augmenting/packing and pops nothing), or
        # the workers idle at every batch boundary; measured best
        # around 16x on the 2-core rig, and ~0.5 MB per 256px item
        # keeps even a 64-deep window in tens of MB
        self._depth = self.prefetch_depth or 16 * max(w, 1)

    def before_first(self) -> None:
        # in-flight futures belong to the abandoned epoch: drop them
        # (workers finish their current decode and go idle)
        self._pending.clear()
        self._eof = False
        self.base.before_first()

    # ------------------------------------------------------------------
    def _ensure_pool(self):
        if self._pool is not None:
            return self._pool
        if self.prefetch_mode == "process":
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor
            # spawn, not fork: the parent may have jax + XLA threads up
            self._pool = ProcessPoolExecutor(
                self._workers,
                mp_context=multiprocessing.get_context("spawn"))
        else:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                self._workers, thread_name_prefix="decode")
        return self._pool

    def _pump(self) -> None:
        """Top the in-flight window up to prefetch_depth."""
        while not self._eof and len(self._pending) < self._depth:
            item = self.base.next_raw()
            if item is None:
                self._eof = True
                break
            idx, label, kind, val = item
            if kind == "img":   # native loader already decoded it
                self._pending.append(("v", (idx, label, val)))
            else:
                self._pending.append(
                    ("f", self._pool.submit(_decode_task, idx, label,
                                            val)))

    @hot_path
    def next(self) -> bool:
        if self._workers <= 0:
            # serial passthrough: same read + decode path, no pool —
            # the determinism tests diff this leg against the pooled one
            item = self.base.next_raw()
            if item is None:
                return False
            idx, label, kind, val = item
            if kind == "img":
                from .image import DataInst
                self._value = DataInst(idx, label, val)
            else:
                self._value = _decode_task(idx, label, val)
            return True
        self._ensure_pool()
        self._pump()
        if not self._pending:
            return False
        tag, payload = self._pending.popleft()
        if tag == "v":
            from .image import DataInst
            idx, label, data = payload
            self._value = DataInst(idx, label, data)
        else:
            t0 = time.perf_counter()
            # .result() re-raises a worker's decode error right here,
            # in the consumer — a corrupt image fails the epoch loudly
            self._value = payload.result()
            self.decode_wait.add_wait(time.perf_counter() - t0)
        self._pump()
        return True

    @property
    def value(self):
        return self._value

    @property
    def workers(self) -> int:
        """Effective worker count after auto/clamp resolution (0 =
        serial) — what actually ran, for benchmark records."""
        return self._workers

    @property
    def in_flight(self) -> int:
        """Decoded-or-decoding items currently buffered (bounded by
        prefetch_depth — the backpressure tests pin this)."""
        return len(self._pending)

    def bind_registry(self, registry=None,
                      prefix: str = "cxxnet_decode"):
        """Publish the decode-wait clock (consumer blocked on a not-
        yet-finished decode) into an obs registry. Returns the hooks
        (for ``Registry.remove_hook`` at end of use)."""
        return [self.decode_wait.bind_registry(prefix + "_wait",
                                               registry)]

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class DevicePrefetchIterator:
    """Stage batches onto the device ``depth`` ahead, off the step loop.

    Wraps the training DataIterator + a Trainer: a producer thread
    pulls host batches, issues their host->device transfer
    (``Trainer.stage``, or ``GroupStager`` stacked group transfers when
    ``fuse_steps > 1`` with group staging), and parks the resulting
    StagedBatch handles in a bounded queue. The consumer (the CLI's
    dispatch-ahead train loop) pops ready-on-device batches and
    dispatches — H2D rides behind the previous step's compute instead
    of inside ``Trainer.update``. Queue items are a StagedBatch (plain
    or fused group) or a list of per-batch StagedBatch (a full
    ``fuse_steps`` group staged per-batch under ``group_staging = 0``).

    Batch order, augmentation RNG, and update math are untouched: the
    producer is the only thread touching the base iterator, stages in
    stream order, and ``stage``/``GroupStager.add`` copy or ship the
    host buffers before the next ``next()`` — iterators that reuse
    buffers stay safe, and the staged stream is bitwise-identical with
    the prefetcher on or off (pinned by tests/test_prefetch.py; the
    resulting trajectories agree to float tolerance — XLA execution
    itself is not run-to-run bitwise deterministic on every backend).

    Producer errors surface in the consumer's ``next()``; every
    boundary carries a StallClock:
      source_wait — producer blocked on the base iterator (decode-bound)
      stage_busy  — producer issuing + fencing H2D transfers
      put_wait    — producer blocked on a full queue (device-bound:
                    the healthy state)
      get_wait    — consumer blocked on an empty queue (feed stall:
                    the device starving — what this module eliminates)
    """

    def __init__(self, base: DataIterator, trainer, depth: int = 2,
                 fuse: Optional[int] = None,
                 group_staging: Optional[int] = None) -> None:
        self.base = base
        self.trainer = trainer
        self.depth = max(1, int(depth))
        self.fuse = max(1, trainer.fuse_steps if fuse is None else fuse)
        self.group_staging = (trainer.group_staging
                              if group_staging is None else group_staging)
        self._queue = None
        self._thread = None
        self._value = None
        self._gen = 0       # epoch generation: bumped by before_first
                            # so an abandoned producer stops decoding +
                            # staging instead of finishing its epoch
        self._gs = None     # GroupStager, built once: its stacked host
                            # buffers (~K x batch bytes) stay warm
                            # across rounds like the legacy loop's
        self.source_wait = StallClock()
        self.stage_busy = StallClock()
        self.put_wait = StallClock()
        self.get_wait = StallClock()

    # ------------------------------------------------------------------
    def _put(self, q, item) -> None:
        t0 = time.perf_counter()
        q.put(item)
        dt = time.perf_counter() - t0
        self.put_wait.add_wait(dt)
        tr = _trace.sink()
        if tr is not None and dt > 1e-4:
            # only materialized waits become spans: an uncontended put
            # is sub-100us and would bury the lane in noise
            tr.complete("feed.backpressure", "feed", t0,
                        t0 + dt)

    @hot_path
    def _produce(self, q, gen) -> None:
        from ..trainer import GroupStager
        tr = self.trainer
        _trace.name_os_thread()     # its line in a profiler capture
        try:
            self.base.before_first()
            use_groups = self.fuse > 1 and self.group_staging != 0
            # one stager suffices (no rotation): stage() fences the
            # transfer before returning, so refilling its host buffers
            # afterwards is safe — and the NEXT group's fill already
            # overlaps the consumer's dispatches, which is the overlap
            # that matters here
            gs = None
            if use_groups:
                if self._gs is None:
                    self._gs = GroupStager(tr)
                gs = self._gs
                gs.n = 0    # an abandoned epoch may have left a
                            # partial fill; the buffers themselves are
                            # safe to overwrite (stage/flush fence)
            pend = []
            step = 0    # the batch's ordinal in the round: the staged
                        # batch carries it to feed.get and trainer.update
            while True:
                if gen != self._gen:
                    # before_first superseded this epoch: stop decoding
                    # and staging (the drain frees our queue slot, we
                    # notice here at the latest one item later) instead
                    # of burning the rest of the epoch into buffers
                    # nobody will pop
                    q.put(None)
                    return
                t0 = time.perf_counter()
                with _trace.span("feed.source_next", "feed"):
                    has = self.base.next()
                self.source_wait.add_wait(time.perf_counter() - t0)
                if not has:
                    break
                batch = self.base.value
                t0 = time.perf_counter()
                with _trace.span("feed.stage", "feed", {"step": step}):
                    if gs is not None:
                        gs.add(batch)   # copies now; base may reuse
                        staged = gs.stage() if gs.full else None
                    else:
                        staged = tr.stage(batch)
                    if staged is not None:
                        staged.step = step  # a group: its last batch's
                self.stage_busy.add_busy(time.perf_counter() - t0)
                step += 1
                if gs is not None:
                    if staged is not None:
                        self._put(q, staged)
                elif self.fuse > 1:
                    pend.append(staged)
                    if len(pend) == self.fuse:
                        self._put(q, pend)
                        pend = []
                else:
                    self._put(q, staged)
            # round tail: a partial group falls back to per-step items
            if gs is not None and gs.n:
                t0 = time.perf_counter()
                tail = gs.flush()
                self.stage_busy.add_busy(time.perf_counter() - t0)
                for s in tail:
                    self._put(q, s)
            elif pend:
                self._put(q, pend)
        except BaseException as e:
            q.put(ProducerFailure(e))
            return
        q.put(None)

    # ------------------------------------------------------------------
    def before_first(self) -> None:
        import threading
        # bump the generation FIRST so a mid-epoch producer cancels at
        # its next loop check rather than staging out the whole epoch
        self._gen += 1
        if self._thread is not None:
            # restart mid-epoch: drain the old producer out (its staged
            # device buffers are simply dropped)
            drain_producer(self._queue, self._thread)
        self._queue = _lockcheck.make_queue("io.prefetch.stage",
                                            maxsize=self.depth)
        self._thread = threading.Thread(
            target=self._produce, args=(self._queue, self._gen),
            name="dev-prefetch", daemon=True)
        self._thread.start()

    @hot_path
    def next(self) -> bool:
        if self._queue is None:
            self.before_first()
        t0 = time.perf_counter()
        with _trace.span("feed.get", "feed") as sp:
            item = self._queue.get()
            if sp is not _trace.NOOP_SPAN:
                sp.note(step=getattr(item, "step", None))
        self.get_wait.add_wait(time.perf_counter() - t0)
        if item is None or isinstance(item, ProducerFailure):
            self._thread.join()
            self._thread = None
            self._queue = None
            if item is not None:
                item.reraise()
            return False
        self._value = item
        return True

    @property
    def value(self):
        """A StagedBatch (plain or fused group) or list of StagedBatch."""
        return self._value

    def bind_registry(self, registry=None,
                      prefix: str = "cxxnet_feed"):
        """Publish the four boundary clocks plus the headline
        ``<prefix>_stall_frac`` gauge into an obs registry (pulled at
        scrape time; the producer/consumer hot paths are untouched).
        The training CLI binds the global registry here so the
        ``telemetry_port`` endpoint can answer 'is the device
        starving?' mid-round. Returns the hooks — pass them to
        ``Registry.remove_hook`` when this iterator is done (a
        registered hook pins the iterator, its trainer, and their
        device buffers)."""
        from ..obs.registry import get_registry
        reg = registry or get_registry()
        hooks = [
            self.source_wait.bind_registry(prefix + "_source", reg),
            self.stage_busy.bind_registry(prefix + "_stage", reg),
            self.put_wait.bind_registry(prefix + "_backpressure", reg),
            self.get_wait.bind_registry(prefix + "_get", reg),
        ]
        g = reg.gauge(prefix + "_stall_frac",
                      "consumer wait over total accounted feed time")
        hooks.append(reg.add_hook(
            lambda: g.set(self.stats()["feed_stall_frac"])))
        return hooks

    def stats(self) -> dict:
        """Per-boundary stall snapshot; ``feed_stall_frac`` is consumer
        wait over total producer-accounted + consumer-wait time — the
        headline 'device waited on data' fraction."""
        total = (self.source_wait.wait_s + self.stage_busy.busy_s
                 + self.put_wait.wait_s + self.get_wait.wait_s)
        return {
            "source_wait": self.source_wait.snapshot(),
            "stage_busy": self.stage_busy.snapshot(),
            "put_wait": self.put_wait.snapshot(),
            "get_wait": self.get_wait.snapshot(),
            "feed_stall_frac": (self.get_wait.wait_s / total
                                if total > 0 else 0.0),
        }
