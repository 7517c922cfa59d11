"""Iteration-level continuous batching over a split-phase decoder.

The fixed-shape serving path (serve/engine.py over an
``export_generate`` artifact) batches at REQUEST granularity: every
dispatch runs the whole monolithic prefill+decode program, so a
request arriving mid-generation waits for the entire previous batch to
finish, empty slots burn dummy decode work, and the first token only
exists when the last one does. This module schedules the
``export_decode_step`` artifact (serving.ExportedStepDecoder) at TOKEN
granularity instead — Orca-style iteration-level scheduling over a
paged KV pool:

* PAGED KV POOL — the decoder owns a device pool of ``kv_block``-slot
  pages (the 128-multiple ``cache_slots`` granule from
  ops/decode_attend.py); each decoding request holds a block table of
  ``blocks_per_seq`` pages (serve/kvpool.BlockPool allots them, page 0
  reserved as the trash page unbound slots write into). Pages rebind
  the moment a request leaves, with no device copies.
* PREFILL/DECODE SPLIT — prompts prefill in their OWN dispatch at the
  narrowest exported width bucket that holds them, then join the
  per-token decode loop; at most one prefill runs between decode
  steps, so a long prompt never stalls tokens already streaming
  (``prefill_split=False`` restores the coupled behavior — new
  requests only join once every slot is idle — as the measured
  contrast).
* CONTINUOUS DECODE — every :meth:`_decode_step` advances whichever
  requests currently occupy slots by one token; requests join and
  leave between steps, and a request that asked for fewer tokens
  (``max_new`` per request) frees its slot early.
* STREAMING — each emitted token is pushed to the request's event
  queue immediately (:class:`StreamRequest`), so time-to-first-token
  is one prefill away regardless of time-to-last-token;
  serve/server.py renders the events as SSE chunks.
* PREFIX CACHE — a cross-request token-prefix trie
  (serve/prefixcache.py) shares completed prompts' KV pages
  copy-on-write: a request whose prompt extends a cached prefix binds
  the shared pages into its block table at admission and dispatches
  the artifact's INCREMENTAL tail-prefill program over only the
  uncached tokens — at heavy template share that is the difference
  between recomputing every system prompt and paying it once.

Greedy outputs are bitwise-identical to the fixed-shape path from the
same weights (the step program's attend is shape-identical to the
monolithic slot layout); at temperature > 0 the sampled stream depends
on which slots/steps a request lands in, exactly as it already depends
on the batch it shares a dispatch with.

The engine mirrors ServingEngine's operational surface — admission
queue + shedding, per-request deadlines, drain, state machine,
registry metrics — and adds the streaming observability the ROADMAP
asks for: TTFT and TPOT histograms with request-id exemplars, a
slot-occupancy gauge, and dummy-slot-step counters (serve/stats.py).
"""

from __future__ import annotations

import queue as _qmod
import sys
import threading
import time
import traceback
from typing import List, Optional, Sequence

import numpy as np

from ..analysis import hot_path
from ..analysis import lockcheck as _lockcheck
from ..obs import attrib as _attrib
from ..obs import profile as _profile
from ..obs import trace as _trace
from ..obs.registry import Registry
from .engine import (DrainError, QueueFullError, Request, RequestExpired,
                     coerce_tokens)
from .kvpool import BlockPool
from .stats import ServeStats


class StreamRequest(Request):
    """A decode request whose tokens stream out as they are emitted.

    ``events()`` yields dicts in emission order: token chunks
    ``{"row": r, "i": i, "tokens": [t, ...]}`` — ``i`` the 0-based
    index of the chunk's first completion token; one chunk per decode
    call per row (only when the request was submitted with
    ``stream=True``) — and exactly one terminal ``{"done": True}`` /
    ``{"error": msg}``. ``result()`` keeps the fixed-path contract:
    the completed (rows, seq_len) token matrix."""

    __slots__ = ("stream", "n_new", "row_tokens", "_events",
                 "rows_left", "t_first", "t_prefill_start", "t_bound")

    def __init__(self, rows: int, payload, timeout_s, n_new: int,
                 stream: bool):
        super().__init__(rows, payload, timeout_s)
        self.stream = bool(stream)
        self.n_new = int(n_new)
        self.row_tokens: List[list] = [[] for _ in range(rows)]
        self.rows_left = rows
        self.t_first: Optional[float] = None
        self.t_prefill_start: Optional[float] = None
        self.t_bound: Optional[float] = None
        self._events: _qmod.Queue = _qmod.Queue()

    def push_event(self, ev: dict) -> None:
        self._events.put(ev)

    def events(self, timeout: Optional[float] = None):
        """Iterate events until the terminal one; raises TimeoutError
        if ``timeout`` seconds pass without a new event."""
        while True:
            try:
                ev = self._events.get(timeout=timeout)
            except _qmod.Empty:
                raise TimeoutError(
                    "no stream event within %.3fs"
                    % (timeout if timeout is not None else -1.0))
            yield ev
            if "done" in ev or "error" in ev:
                return

    def timing(self) -> dict:
        t = super().timing()
        t["ttft_ms"] = (None if self.t_first is None else
                        round(1000.0 * (self.t_first - self.t_submit),
                              3))
        # non-overlapping phase breakdown (queue -> prefill ->
        # ready-wait -> decode -> stream): the per-request view of the
        # attribution ledger's phases (docs/observability.md). Rows
        # that finish at prefill (n_new exhausted by the first token)
        # never bind a lane — their ready-wait and decode are a true
        # 0.0, not unknown. "stream" is tokens-ready to response
        # assembly: timing() is called while the answer/done event is
        # being built, so it measures the flush the caller still waits
        # through.
        def ms(a, b):
            return None if a is None or b is None \
                else round(1000.0 * (b - a), 3)
        done = self.t_done
        bound = self.t_bound if self.t_bound is not None \
            else (done if done is not None else None)
        # keys derive from the SHARED phase vocabulary
        # (obs/profile.py REQUEST_PHASES): trace_report --phases and
        # the profiler's request-phase joins need no mapping table
        vals = (ms(self.t_submit, self.t_prefill_start),
                ms(self.t_prefill_start, self.t_first),
                ms(self.t_first, bound),
                ms(bound, done),
                (None if done is None else
                 round(1000.0 * (time.monotonic() - done), 3)))
        t["phases"] = {"%s_ms" % p: v
                       for p, v in zip(_profile.REQUEST_PHASES, vals)}
        return t


class _Row:
    """One admitted prompt row waiting for (or bound to) a slot."""

    __slots__ = ("req", "ridx", "toks", "plen", "blocks",
                 "ntok", "last", "clen", "shared", "nodes", "shard")

    def __init__(self, req: StreamRequest, ridx: int,
                 toks: np.ndarray, plen: int):
        self.req = req
        self.ridx = ridx
        self.toks = toks            # (plen,) prompt ids
        self.plen = int(plen)
        self.blocks: Optional[list] = None
        self.ntok = 0               # tokens emitted so far
        self.last = 0               # last emitted token id
        self.clen = 0               # cached-prefix tokens (kv_block x)
        self.shared: list = []      # shared prefix pages (refs held)
        self.nodes: list = []       # pinned trie nodes
        self.shard = 0              # mesh slice owning its pages/lane


class ContinuousDecodeEngine:
    """Continuous-batching scheduler over an ExportedStepDecoder.

    Knobs:
      queue_limit     admitted-but-unslotted prompt ROWS before
                      admission sheds (429)
      timeout_ms      per-request deadline (0 disables); enforced at
                      admission sweep and prefill pick-up (a request
                      already decoding finishes its stream)
      prefill_split   True (default): prefills interleave with decode
                      steps, at most one per step. False: new requests
                      only join when every slot is idle — the coupled
                      legacy behavior, kept for paired benchmarking
      kv_blocks       runtime clamp on live pool pages (<= the
                      exported pool; 0 = whole pool) — admission
                      control without a re-export
      kv_dtype        which exported cache-dtype rung to serve
                      ("native" | "int8" | "auto" = native when
                      exported, else the artifact's first rung). The
                      int8 rung halves the pool bytes per sequence
                      (kv_bytes_per_seq in the artifact meta), so the
                      same byte budget holds ~2x the KV state —
                      docs/serving.md's rung table
      prefix_cache    cross-request prefix cache
                      (serve/prefixcache.py): "auto" (default) = on
                      when the artifact carries the rung's tail-
                      prefill programs, True = required (raises
                      otherwise), False = off. A request whose prompt
                      extends a cached prefix binds the shared pages
                      into its block table at admission and runs
                      incremental prefill on only the uncached tail
      prefix_capacity_pages
                      page budget for trie-held (published) pages;
                      0 = half the usable pool. Pinned pages are
                      never evicted
      step_hook       callable invoked before every decode step — the
                      fault-injection / test-throttle seam (raising
                      fails the step's requests through the real error
                      path, sleeping is a real stall)
      warmup          pre-run every prefill bucket + one decode step
                      inside start() so no user request eats a
                      first-call cost
      registry / obs_labels / slo_ms / stats / seed / start as in
      ServingEngine.
    """

    kind = "decode"
    supports_stream = True

    def __init__(self, decoder, queue_limit: int = 64,
                 timeout_ms: float = 30000.0,
                 prefill_split: bool = True, kv_blocks: int = 0,
                 kv_dtype: str = "auto",
                 prefix_cache="auto", prefix_capacity_pages: int = 0,
                 max_wait_ms: float = 0.0, max_batch=None,
                 dispatch_depth: int = 0,
                 stats: Optional[ServeStats] = None, seed: int = 0,
                 registry: Optional[Registry] = None,
                 obs_labels: Optional[dict] = None,
                 step_hook=None, slo_ms: Optional[float] = None,
                 warmup: bool = False, start: bool = True):
        from ..serving import ExportedStepDecoder
        if not isinstance(decoder, ExportedStepDecoder):
            raise TypeError(
                "ContinuousDecodeEngine needs an export_decode_step "
                "artifact (kind=generate_step); got %r — serve "
                "monolithic decoders through ServingEngine" % (decoder,))
        self.callee = decoder
        self.batch = decoder.batch
        self.buckets = list(decoder.buckets)
        self.max_batch = self.batch
        if kv_dtype == "auto":
            kvs = decoder.kv_dtypes
            kv_dtype = "native" if "native" in kvs else kvs[0]
        if kv_dtype not in decoder.kv_dtypes:
            raise ValueError(
                "artifact carries no %r KV rung (exported: %s) — "
                "re-export with kv_dtypes including it"
                % (kv_dtype, decoder.kv_dtypes))
        self.kv_dtype = kv_dtype
        # step rungs of this kv family: each decode call dispatches at
        # the smallest exported bucket holding the live rows, so
        # partial occupancy runs a load-proportional program
        self._step_buckets = decoder.step_buckets(kv_dtype)
        self.attend_kernel = decoder.rung(kv_dtype)["attend_kernel"]
        self.queue_limit = int(queue_limit)
        self.timeout_s = float(timeout_ms) / 1000.0
        self.prefill_split = bool(prefill_split)
        self.dispatch_depth = 0      # surface parity with ServingEngine
        self.stats = stats or ServeStats()
        self.step_hook = step_hook
        self.obs_labels = dict(obs_labels or {})
        self.registry = registry if registry is not None else Registry()
        # mesh-carrying artifact (docs/serving.md "sharded serving"):
        # slots and the pool's page space both split across the dp
        # shards — lane i belongs to shard i // (B/dp), and a row's
        # pages come from its shard's pool slice, so the step
        # program's page gather never leaves the shard
        self.mesh = getattr(decoder, "mesh", None)
        self.dp = int(getattr(decoder, "dp", 1) or 1)
        if self.batch % self.dp:
            raise ValueError(
                "artifact slot count %d does not divide its %d-way "
                "data axis" % (self.batch, self.dp))
        self.lanes_per_shard = self.batch // self.dp
        self.pool = BlockPool(decoder.pool_blocks, decoder.kv_block,
                              limit=int(kv_blocks), shards=self.dp)
        # cross-request prefix cache: needs the rung's exported tail-
        # prefill programs (a hit skips straight to incremental
        # prefill, so there is nothing to do without them)
        has_tail = decoder.has_tail_prefill(self.kv_dtype)
        if self.dp > 1:
            # shared trie pages live in ONE shard's slice and would
            # pin every later hit to the publishing shard — the
            # cross-shard prefix cache is future work, so a mesh
            # engine serves with the cache off (and says so loudly
            # when the operator demanded it on)
            if prefix_cache is True:
                raise ValueError(
                    "prefix_cache=True is not supported on a "
                    "mesh-carrying (dp=%d) artifact: shared prefix "
                    "pages would pin requests to the publishing "
                    "shard — serve with prefix_cache=auto/False "
                    "(docs/serving.md)" % self.dp)
            prefix_cache = False
        if prefix_cache is True and not has_tail:
            raise ValueError(
                "prefix_cache=True but the artifact carries no %s-"
                "rung tail-prefill programs — re-export with "
                "tail_prefill=True (and a prompt region wider than "
                "one kv_block page)" % self.kv_dtype)
        self.prefix = None
        self._tail_ws: list = []
        if prefix_cache is not False and has_tail:
            from .prefixcache import PrefixCache
            self.prefix = PrefixCache(
                self.pool, decoder.kv_block,
                capacity_pages=int(prefix_capacity_pages),
                # at least one sequence must stay allocatable with
                # the trie full — cache growth must never wedge
                # admission
                reserve_pages=decoder.blocks_per_seq)
            self._tail_ws = decoder.tail_widths(self.kv_dtype)
        self._ntail = 0
        # prefill-compute accounting: slot-tokens each prefill program
        # actually ran (rows bucket x width bucket) — the number the
        # prefix cache shrinks (a 32-token tail dispatches a 64-wide
        # program instead of the 192-wide full prefill), reported
        # beside the dispatch counts so the ledger can attribute
        # compute, not just events
        self._pf_slot_tokens = 0
        self._pools = decoder.new_pool(kv_dtype)
        self._trash_tpl: dict = {}   # bucket -> trash block table
        self._slots: List[Optional[_Row]] = [None] * self.batch
        self._nlive = 0
        self._bucket_steps = {b: 0 for b in self._step_buckets}
        self._seed = int(seed)
        self._greedy_key = None
        self._nstep = 0
        self._nprefill = 0
        self._warmup_on_start = bool(warmup)
        self._warmed = False
        self.warmup_runs = 0
        if self.pool.usable_per_shard < decoder.blocks_per_seq:
            raise ValueError(
                "kv_blocks=%d leaves %d usable pages per shard; one "
                "sequence needs %d"
                % (kv_blocks, self.pool.usable_per_shard,
                   decoder.blocks_per_seq))
        from collections import deque
        self._q = deque()        # rows waiting for PREFILL
        # rows already prefilled (pages + first token emitted) parked
        # until a decode lane frees: decoupling prefill from lane
        # availability is what lets prefills batch — lanes free one at
        # a time, so a lane-coupled prefill degenerates to singleton
        # dispatches and its fixed cost swamps the schedule
        self._ready = deque()
        self._cond = _lockcheck.make_condition("serve.continuous.cond")
        self._live_lock = _lockcheck.make_lock("serve.continuous.live")
        self._live: set = set()      # admitted, unanswered requests
        self._closed = False
        self._draining = False
        self._started = False
        g_q = self.registry.gauge("cxxnet_serve_queue_depth",
                                  "requests pending admission",
                                  tuple(self.obs_labels))
        g_slots = self.registry.gauge(
            "cxxnet_serve_slots_live",
            "decode slots currently bound to a request",
            tuple(self.obs_labels))
        g_blocks = self.registry.gauge(
            "cxxnet_serve_kv_blocks_in_use",
            "paged KV pool pages currently held by requests",
            tuple(self.obs_labels))
        buckets = [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0]
        if slo_ms:
            buckets.append(float(slo_ms) / 1000.0)
        self._h_latency = self.registry.histogram(
            "cxxnet_serve_request_latency_seconds",
            "per-request completion latency (submit to answer)",
            tuple(self.obs_labels), buckets=buckets)
        self._h_ttft = self.registry.histogram(
            "cxxnet_serve_ttft_seconds",
            "submit to first streamed token",
            tuple(self.obs_labels), buckets=buckets)
        self._h_tpot = self.registry.histogram(
            "cxxnet_serve_tpot_seconds",
            "mean per-output-token time after the first token",
            tuple(self.obs_labels),
            buckets=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                     0.05, 0.1, 0.25])
        self.slo_ms = float(slo_ms) if slo_ms else None
        self._registry_hooks = [
            self.stats.bind_registry(self.registry,
                                     labels=self.obs_labels),
            self.registry.add_hook(lambda: (
                g_q.set(self.queue_depth, **self.obs_labels),
                g_slots.set(self._nlive, **self.obs_labels),
                g_blocks.set(self.pool.in_use, **self.obs_labels))),
            # pool-sizing gauges (live + high-water peak): the peak is
            # what the docs' pool-sizing guidance is measured against
            self.pool.bind_registry(self.registry, self.obs_labels),
            # goodput attribution export: the hook reads the ACTIVE
            # ledger per scrape, so enabling attribution after the
            # engine started still publishes cxxnet_attrib_* here.
            # Unlabeled deliberately — the ledger is process-global,
            # and stamping per-engine labels would replicate the same
            # global numbers under every replica
            _attrib.bind_registry(self.registry),
            # program-profiler export (obs/profile.py): same contract
            _profile.bind_registry(self.registry),
        ]
        # join the artifact's exported program shapes against the
        # analytic cost model (per-shard step costs when dp > 1):
        # registered into the module-level table so a profiler
        # enabled after engine start still costs them
        try:
            _profile.register_costs(
                decoder.profile_costs(dp=self.dp))
        except Exception:
            pass
        if self.prefix is not None:
            self._registry_hooks.append(
                self.prefix.bind_registry(self.registry,
                                          self.obs_labels))
        self._thread = threading.Thread(
            target=self._loop, name="serve-continuous", daemon=True)
        if start:
            self.start()

    # ------------------------------------------------------------------
    def start(self) -> None:
        if not self._started:
            if self._warmup_on_start:
                self.warmup()
            self._started = True
            self._thread.start()

    def warmup(self) -> None:
        """Pre-run every prefill bucket (INCLUDING its pool-scatter —
        the jitted donated scatter compiles per (rows, width, rung)
        shape) and EVERY exported step bucket of the engine's KV rung,
        plus the key fold, so every first-call cost on the serving
        path lands before traffic. All warmup writes go through trash
        block tables, so the pool stays clean. Runs inside a
        ``jitcheck.allow`` window: with the recompile sentinel armed
        these compiles are sanctioned warmup (docs/analysis.md).

        Coverage is per RUNG dimension deliberately: the r10 sentinel
        caught intermediate prefill buckets' trim slices compiling
        mid-traffic, and the rung refactor multiplies the program
        space by kv_dtype x step bucket — a missed combo here is a
        guaranteed scheduler-thread compile under load (the gate in
        tools/analysis_gate.py --rungs replays exactly this
        contract).

        Also a sanctioned ``shardcheck.allow`` window: warmup's eager
        trim slices and dummy control arrays pay deliberate host
        uploads, and an engine may warm (hot-swap spare, fresh bench
        window) while the transfer guard is already armed — the
        thread-local allowance is exactly the lifecycle the sentinel
        defines for warmup (docs/analysis.md)."""
        from ..analysis import jitcheck as _jitcheck
        from ..analysis import shardcheck as _shardcheck
        from ..serving import scatter_prefill_kv
        c = self.callee
        with _jitcheck.allow("serve.continuous.warmup"), \
                _shardcheck.allow("serve.continuous.warmup"):
            key = self._fold_key(0)
            maxr = c.prefill_rows[-1]
            for w in c.prefill_widths:
                nb = -(-w // c.kv_block)
                outs = {}
                for r in c.prefill_rows:
                    toks = np.zeros((r, w), np.int32)
                    lens = np.ones((r,), np.int32)
                    # through the staged seam (pre_call): a mesh
                    # artifact's programs cannot consume host numpy
                    outs[r] = c.pre_call(r, w)(toks, lens, key)
                    np.asarray(outs[r][0])
                    self.warmup_runs += 1
                for n in range(1, maxr + 1):
                    # warm every (bucket, live-rows) combo a dispatch
                    # can arrive with, FROM the bucket pick_rows would
                    # really route it to: the prefill trim slices
                    # (first[:n], k[:, :n]) and the (rows, width)-
                    # keyed scatter jit each compile per combo — the
                    # r10 recompile sentinel caught the old maxr-only
                    # loop leaving the intermediate buckets' slices to
                    # compile MID-TRAFFIC on the scheduler thread
                    first, k, v = outs[c.pick_rows(n)]
                    fn, kn, vn = first[:n], k[:, :n], v[:, :n]
                    np.asarray(fn)
                    self._pools = scatter_prefill_kv(
                        self._pools, kn, vn,
                        [[0] * nb for _ in range(n)], c.kv_block)
            nblk = c.blocks_per_seq
            if self.prefix is not None:
                # prefix-cache tail prefills: one compile per (rows,
                # tail width, rung) — a cache hit mid-traffic must
                # dispatch an already-compiled program. The trim
                # slices and the offset scatter reuse the shapes the
                # full-prefill loop above just warmed (the scatter's
                # start offsets are host-side index arithmetic, not
                # part of the compile key)
                for w in c.tail_widths(self.kv_dtype):
                    for r in c.prefill_rows:
                        out = c.tail_call(self.kv_dtype, r, w)(
                            *self._pools,
                            np.zeros((r, w), np.int32),
                            np.zeros((r,), np.int32),
                            np.ones((r,), np.int32),
                            np.zeros((r, nblk), np.int32), key)
                        np.asarray(out[0])
                        self.warmup_runs += 1
            for b in self._step_buckets:
                out = c.step_call(self.kv_dtype, b)(
                    *self._pools,
                    self._trash_bt(b),
                    np.ones((b,), np.int32),
                    np.zeros((b,), np.int32),
                    np.zeros((b,), np.int32), key)
                self._pools, nxt = out[:-1], out[-1]
                np.asarray(nxt)
                self.warmup_runs += 1
        self._warmed = True

    # ------------------------------------------------------------------
    @property
    def state(self) -> str:
        if self._closed:
            return "closed"
        if self._draining:
            return "draining"
        if self._warmup_on_start and not self._warmed:
            return "warming"
        return "serving"

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._q)

    @property
    def live_requests(self) -> int:
        with self._live_lock:
            return len(self._live)

    @property
    def slots_live(self) -> int:
        return self._nlive

    def retry_after_s(self) -> float:
        if self._closed or self._draining \
                or (self._warmup_on_start and not self._warmed):
            return 2.0
        est = self.stats.estimate_clear_s(self.queue_depth)
        return min(max(est, 1.0), 30.0)

    def healthz(self) -> dict:
        c = self.callee
        return {"ok": self.state == "serving", "state": self.state,
                "kind": self.kind, "batch": self.batch,
                "buckets": list(self.buckets),
                "dispatch_depth": 0, "queue_depth": self.queue_depth,
                "seq_len": c.seq_len,
                "max_prompt_len": c.max_prompt_len,
                "max_new": c.max_new,
                "continuous": True, "stream": True,
                "prefill_split": self.prefill_split,
                "kv_dtype": self.kv_dtype,
                "attend_kernel": self.attend_kernel,
                "step_buckets": list(self._step_buckets),
                "slots_live": self._nlive,
                "ready_rows": len(self._ready),
                "prefix_cache": self.prefix is not None,
                "mesh": c.meta.get("mesh"),
                "kv_pool": self.pool.snapshot()}

    def metrics(self) -> dict:
        snap = self.stats.snapshot()
        snap["queue_depth"] = self.queue_depth
        snap["state"] = self.state
        snap["kind"] = self.kind
        snap["exported_batch"] = self.batch
        snap["buckets"] = list(self.buckets)
        snap["max_batch"] = self.max_batch
        snap["queue_limit"] = self.queue_limit
        snap["dispatch_depth"] = 0
        snap["warmup_runs"] = self.warmup_runs
        snap["continuous"] = True
        snap["prefill_split"] = self.prefill_split
        snap["kv_dtype"] = self.kv_dtype
        snap["attend_kernel"] = self.attend_kernel
        snap["mesh"] = self.callee.meta.get("mesh")
        snap["step_bucket_dispatches"] = dict(self._bucket_steps)
        snap["slots_live"] = self._nlive
        snap["ready_rows"] = len(self._ready)
        snap["kv_pool"] = self.pool.snapshot()
        snap["tail_prefills"] = self._ntail
        snap["prefill_slot_tokens"] = self._pf_slot_tokens
        snap["prefix_cache"] = None if self.prefix is None \
            else self.prefix.snapshot()
        return snap

    # ------------------------------------------------------------------
    def submit_tokens(self, tokens: np.ndarray, lens: Sequence[int],
                      seed: Optional[int] = None,
                      timeout_ms: Optional[float] = None,
                      priority=None, max_new: Optional[int] = None,
                      stream: bool = False) -> StreamRequest:
        """Enqueue a generate request (same contract as
        ServingEngine.submit_tokens) plus the continuous extras:
        ``max_new`` caps this request's emitted tokens at fewer than
        the artifact's (its slot frees early); ``stream=True`` pushes
        per-token events (StreamRequest.events). ``seed`` folds into
        the shared per-step sampling keys — irrelevant at the greedy
        temperature-0 export."""
        c = self.callee
        toks, lens = coerce_tokens(c, tokens, lens)
        n_new = c.max_new if max_new is None else int(max_new)
        if not 1 <= n_new <= c.max_new:
            raise ValueError("max_new must be in [1, %d], got %d"
                             % (c.max_new, n_new))
        t = self.timeout_s if timeout_ms is None \
            else float(timeout_ms) / 1000.0
        req = StreamRequest(toks.shape[0], (toks, lens, seed),
                            t if t and t > 0 else None, n_new, stream)
        self._admit(req)
        return req

    def submit(self, *a, **kw):
        raise RuntimeError("this engine serves a decoder; "
                           "use submit_tokens")

    def _finish_req(self, req: StreamRequest, value=None,
                    error: Optional[BaseException] = None) -> bool:
        if req._finish(value, error):
            with self._live_lock:
                self._live.discard(req)
            req.push_event({"done": True} if error is None
                           else {"error": str(error)})
            return True
        return False

    def _release_row(self, row: _Row) -> None:
        """Drop every pool reference a row holds — its full block
        table once allocated (shared prefix pages decref back to the
        trie, owned pages free), or just its admission-time shared
        pages before that — and unpin its trie nodes. The one place
        row-held pages are given back, so no exit path (done, expired,
        drained, failed, closed) can leak a reference."""
        if row.blocks is not None:
            self.pool.release(row.blocks, owner=row.req.id)
            row.blocks = None
        elif row.shared:
            self.pool.release(row.shared, owner=row.req.id)
        row.shared = []
        if row.nodes:
            if self.prefix is not None:
                self.prefix.unpin(row.nodes)
            row.nodes = []
        row.clen = 0

    def _sweep_expired_locked(self) -> int:
        now = time.monotonic()
        dead = []
        alive = []
        for r in self._q:
            (dead if r.req.deadline is not None
             and now > r.req.deadline else alive).append(r)
        if not dead:
            return 0
        self._q.clear()
        self._q.extend(alive)
        failed = set()
        for r in dead:
            self._release_row(r)
            if r.req not in failed:
                failed.add(r.req)
                self.stats.on_timeout()
                self._finish_req(r.req, error=RequestExpired(
                    "request expired after %.0f ms in queue (swept at "
                    "admission)"
                    % (1000.0 * (now - r.req.t_submit))))
        return len(dead)

    @hot_path
    def _admit(self, req: StreamRequest) -> None:
        toks, lens, _ = req.payload
        with self._cond:
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._draining:
                raise DrainError("engine is draining — not admitting")
            if len(self._q) + req.rows > self.queue_limit:
                self._sweep_expired_locked()
            if len(self._q) + req.rows > self.queue_limit:
                self.stats.on_reject()
                raise QueueFullError(
                    "admission queue full (%d pending rows)"
                    % len(self._q))
            with self._live_lock:
                self._live.add(req)
            for r, pl in enumerate(lens.tolist()):
                row = _Row(req, r, toks[r, :pl].copy(), pl)
                if self.prefix is not None:
                    # admission-time trie lookup: the deepest cached
                    # prefix path is pinned for the request lifetime,
                    # and the row's prefill shrinks to the tail
                    row.nodes, row.shared = \
                        self.prefix.match_and_pin(row.toks,
                                                  owner=req.id)
                    row.clen = len(row.shared) * self.callee.kv_block
                self._q.append(row)
            tr = _trace.sink()
            if tr is not None:
                with tr.span("serve.admit", "serve",
                             {"request_id": req.id, "rows": req.rows}):
                    tr.flow_start("request", req.seq, "serve")
            self._cond.notify()

    # ------------------------------------------------------------------
    def _free_slot_ids(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _trash_bt(self, b: int) -> np.ndarray:
        """A (b, blocks_per_seq) block table of trash pages — each
        dispatch row pointing at ITS shard slice's trash page, so a
        dead lane's writes stay inside the shard that owns the lane
        (page 0 everywhere on a single-device pool). One template per
        bucket, built once and copied per dispatch: this runs on the
        scheduler thread inside every decode step."""
        tpl = self._trash_tpl.get(b)
        if tpl is None:
            nblk = self.callee.blocks_per_seq
            if self.dp > 1:
                trash = np.repeat(
                    np.asarray([self.pool.trash_page(s)
                                for s in range(self.dp)], np.int32),
                    b // self.dp)
                tpl = np.broadcast_to(trash[:, None],
                                      (b, nblk)).copy()
            else:
                tpl = np.zeros((b, nblk), np.int32)
            self._trash_tpl[b] = tpl
        return tpl.copy()

    def _fold_key(self, tag: int):
        # greedy artifact: the key is dead weight — the cached return
        # skips the per-step fold_in dispatch AND the allow-window
        # entry on the hot loop
        if self._greedy_key is not None:
            return self._greedy_key
        import jax

        from ..analysis import shardcheck as _shardcheck
        # seed-material upload is a deliberate host->device step,
        # sanctioned under the armed transfer sentinel
        with _shardcheck.allow("prng-seed"):
            if float(self.callee.meta.get("temperature", 0.0)) == 0.0:
                self._greedy_key = np.asarray(
                    jax.random.PRNGKey(self._seed), np.uint32)
                return self._greedy_key
            base = jax.random.PRNGKey(self._seed)
            return np.asarray(jax.random.fold_in(base, tag), np.uint32)

    def _row_class(self, row: _Row):
        """Dispatch class of a waiting row — rows only batch within
        one class (one program per dispatch). Prefix-cache hits run
        the TAIL program at the tail's width bucket; with the cache
        on, a COLD row whose whole prompt fits a tail bucket ALSO
        rides the tail program at ``clen = 0`` (bitwise-equal to the
        classic prefill — the tail program is a general offset
        prefill), so cached tails and short cold prompts merge into
        ONE dispatch class instead of fragmenting the schedule into
        per-width singletons. Wide cold prompts keep the classic
        prefill program."""
        if row.clen:
            return ("tail", self._pick_tail(row.plen - row.clen))
        if self._tail_ws and row.plen <= self._tail_ws[-1]:
            return ("tail", self._pick_tail(row.plen))
        return ("full", self.callee.pick_width(row.plen))

    def _pick_tail(self, n: int) -> int:
        for w in self._tail_ws:
            if w >= n:
                return w
        # unreachable for artifacts this exporter wrote (tail widths
        # cover prompt_len - kv_block); raise attributably rather
        # than let a bare StopIteration kill the scheduler thread
        return self.callee.pick_tail_width(n, self.kv_dtype)

    @hot_path
    def _prefill_dispatch(self) -> bool:
        """Prefill waiting rows: one prefill program run at the head
        row's class (full prompts at their width bucket; prefix-cache
        hits through the narrower TAIL program, attending over their
        shared pages), prompt K/V scattered into the pool — tail rows
        from their start page, never touching shared pages — first
        token emitted (the TTFT moment — it streams NOW, even if every
        decode lane is busy), rows parked on the ready queue until a
        lane frees. Returns whether anything was prefilled."""
        c = self.callee
        nblk = c.blocks_per_seq
        maxr = c.prefill_rows[-1]
        take: List[_Row] = []
        with self._cond:
            # one pass: drop dead rows, fail expired ones, and collect
            # candidates of the OLDEST waiter's class from anywhere in
            # the queue — classes must not mix in one dispatch (a long
            # prompt prefills in its own dispatch, never dragging
            # short ones to the wide program; a cached row dispatches
            # a different program entirely), and head-run-only
            # gathering would cap batches at the interleave's run
            # length
            now = time.monotonic()
            kept: List[_Row] = []
            cand: List[_Row] = []
            head_cls = None
            for row in self._q:
                if row.req.done:           # failed by drain/sweep
                    self._release_row(row)
                    continue
                if row.req.deadline is not None \
                        and now > row.req.deadline:
                    self._release_row(row)
                    self.stats.on_timeout()
                    self._finish_req(row.req, error=RequestExpired(
                        "request expired after %.0f ms before prefill"
                        % (1000.0 * (now - row.req.t_submit))))
                    continue
                cls = self._row_class(row)
                if head_cls is None:
                    head_cls = cls
                if cls == head_cls and len(cand) < maxr:
                    cand.append(row)
                else:
                    kept.append(row)
            if not cand:
                self._q.clear()
                self._q.extend(kept)
                return False
            # a cache hit needs only the pages its shared prefix does
            # not cover — the capacity half of the prefix-cache win
            need = {id(r): nblk - len(r.shared) for r in cand}
            if self._nlive and self._ready:
                # batch formation, starvation-keyed: while the ready
                # queue holds prefilled rows the lanes CANNOT starve,
                # so the prefill holds until the full candidate bucket
                # fits in free pool pages. A saturated pool frees one
                # sequence per completion, and prefilling at that
                # granularity degenerates to singleton dispatches
                # whose fixed cost swamps the schedule — the 4x pool
                # (serving.export_decode_step default) keeps the ready
                # backlog deep enough that this hold is free. The
                # moment the ready queue drains, prefill runs with
                # whatever fits (an idle lane always gets fed)
                want = min(len(cand), maxr)
                if self.pool.free_blocks \
                        < sum(need[id(r)] for r in cand[:want]):
                    self._q.clear()
                    self._q.extend(sorted(
                        cand + kept, key=lambda r: r.req.t_submit))
                    return False
            for row in cand:
                # row -> shard placement: the slice with the most
                # free pages (pool.pick_shard) — pages are 4x lanes
                # per slice, so page balance tracks lane balance; a
                # single-device pool always picks shard 0
                shard = self.pool.pick_shard(need[id(row)])
                if shard is None:
                    # pool-pressure eviction: ask the trie to give
                    # back exclusively-held pages before turning a
                    # row away — a cache allowed to sit on pages
                    # while admission starves would invert its
                    # whole purpose
                    if self.prefix is not None:
                        self.prefix.reclaim(
                            need[id(row)] - self.pool.free_blocks)
                        shard = self.pool.pick_shard(need[id(row)])
                    if shard is None:
                        kept.append(row)
                        continue
                # shared prefix pages head the block table (logical
                # pages [0, clen/kv_block)), owned pages fill the rest
                row.shard = shard
                row.blocks = row.shared + self.pool.alloc(
                    need[id(row)], owner=row.req.id, shard=shard)
                row.shared = []
                take.append(row)
            self._q.clear()
            self._q.extend(sorted(kept,
                                  key=lambda r: r.req.t_submit))
        if not take:
            return False
        is_tail = head_cls[0] == "tail"
        w = head_cls[1]
        n = len(take)
        toks = np.zeros((n, w), np.int32)
        lens = np.zeros((n,), np.int32)
        clens = np.zeros((n,), np.int32)
        for i, row in enumerate(take):
            toks[i, :row.plen - row.clen] = row.toks[row.clen:]
            lens[i] = row.plen
            clens[i] = row.clen
        self._nprefill += 1
        self._pf_slot_tokens += c.pick_rows(n) * w
        t_pf0 = time.monotonic()
        for row in take:
            if row.req.t_prefill_start is None:
                row.req.t_prefill_start = t_pf0
        tr = _trace.sink()
        try:
            with _trace.span("serve.prefill", "serve",
                             {"rows": n, "width": w,
                              "tail": is_tail}):
                if tr is not None:
                    for row in take:
                        tr.flow_step("request", row.req.seq, "serve")
                from ..serving import scatter_prefill_kv
                if is_tail:
                    # incremental prefill: compute K/V for only the
                    # uncached tails, attending over the shared
                    # prefix pages (read-only), then scatter the tail
                    # K/V into each row's OWN pages from its start
                    # page — the copy-on-write write path
                    bt = np.array([row.blocks for row in take],
                                  np.int32)
                    first, k, v = c.tail_prefill(
                        self._pools, toks, clens, lens, bt,
                        self._fold_key(self._nprefill),
                        kv=self.kv_dtype)
                    first = np.asarray(first)
                    self._ntail += 1
                    self._pools = scatter_prefill_kv(
                        self._pools, k, v,
                        [row.blocks for row in take], c.kv_block,
                        starts=clens, valid=lens - clens)
                else:
                    first, k, v = c.prefill(
                        toks, lens, self._fold_key(self._nprefill))
                    # the sanctioned materialize: first tokens must
                    # reach the host to stream out — this wait IS
                    # the TTFT
                    first = np.asarray(first)
                    self._pools = scatter_prefill_kv(
                        self._pools, k, v,
                        [row.blocks for row in take], c.kv_block)
        except Exception as e:
            self.stats.on_error(len({r.req for r in take}))
            for row in take:
                self._release_row(row)
                self._finish_req(row.req, error=e)
            # the scatter donates the pool buffers; after a failure
            # partway through them nothing in the pool can be trusted
            self._fail_all_inflight(e)
            return True
        self.stats.on_prefill(n)
        a = _attrib.active()
        if a is not None:
            # one event per prefill program run: bucket_rows x width
            # slot-tokens split into real prompt tokens (goodput) and
            # bucket padding (empty rows + intra-row width padding).
            # Tail rows' goodput is only the uncached tail — the
            # shared-prefix tokens were someone else's goodput already.
            rows_b = c.pick_rows(n)
            live_tok = 0
            pages = 0
            shard = take[0].shard
            for row in take:
                live_tok += row.plen - row.clen
                pages += nblk - row.clen // c.kv_block
                if row.shard != shard:
                    shard = -1
            st = rows_b * w
            a.record("tail_prefill" if is_tail else "prefill",
                     self.kv_dtype, shard if self.dp > 1 else 0,
                     rows_b, n, w, st, live_tok, st - live_tok,
                     0, 0, 0, pages)
        pr = _profile.active()
        if pr is not None:
            # continuous-site profile event: prefill dispatch ->
            # scattered K/V wall of the (rows, width) program. The
            # shard column mirrors the attrib convention (-1 when not
            # sharded or when the batch spans shards)
            shard = take[0].shard
            for row in take:
                if row.shard != shard:
                    shard = -1
            pr.record("continuous",
                      "tail_prefill" if is_tail else "prefill",
                      self.kv_dtype, c.pick_rows(n), w,
                      shard if self.dp > 1 else -1,
                      (time.monotonic() - t_pf0) * 1000.0)
        if self.prefix is not None:
            # publish the completed prompts' full pages back: later
            # requests with the same prefix bind them instead of
            # recomputing (rows that were themselves hits only add
            # pages PAST their matched depth)
            for row in take:
                self.prefix.publish(row.toks, row.blocks,
                                    owner=row.req.id)
        now = time.monotonic()
        first = first.tolist()
        for i, row in enumerate(take):
            req = row.req
            if req.t_dispatch is None:
                req.t_dispatch = now
            req.t_infer = now
            self._emit(row, [first[i]], now)
            if row.ntok >= req.n_new:
                self._row_done(row, now)
            else:
                self._ready.append(row)
        self._bind_ready()
        return True

    def _bind_ready(self) -> None:
        """Move prefilled rows from the ready queue into free decode
        lanes (requests failed while parked just give their pages
        back). On a mesh, a lane only takes rows of ITS shard — the
        row's pages live in that shard's pool slice, and binding it
        anywhere else would make every step's page gather
        cross-shard."""
        for i, s in enumerate(self._slots):
            if s is not None:
                continue
            shard = i // self.lanes_per_shard
            row = None
            skipped: List[_Row] = []
            while self._ready:
                cand = self._ready.popleft()
                if cand.req.done:
                    self._release_row(cand)
                    continue
                if self.dp > 1 and cand.shard != shard:
                    skipped.append(cand)
                    continue
                row = cand
                break
            for cnd in reversed(skipped):
                self._ready.appendleft(cnd)
            if row is None:
                if self.dp == 1:
                    return
                continue
            if row.req.t_bound is None:
                row.req.t_bound = time.monotonic()
            self._slots[i] = row
            self._nlive += 1

    def _emit(self, row: _Row, toks: List[int], now: float) -> None:
        """Hand ``toks`` (this call's chunk) to the request: one event
        per decode call per row, not per token — per-token queue
        wake-ups against a few hundred blocked client threads are real
        scheduler load on the hot loop."""
        req = row.req
        i0 = len(req.row_tokens[row.ridx])
        req.row_tokens[row.ridx].extend(toks)
        row.ntok += len(toks)
        row.last = toks[-1]
        if req.t_first is None:
            req.t_first = now
            self._h_ttft.observe(now - req.t_submit, exemplar=req.id,
                                 **self.obs_labels)
        if req.stream:
            req.push_event({"row": row.ridx, "i": i0,
                            "tokens": list(toks)})

    def _row_done(self, row: _Row, now: float) -> None:
        """Row finished: release its pages (shared prefix pages decref
        back to the trie), complete the request when it was the last
        row out."""
        self._release_row(row)
        req = row.req
        req.rows_left -= 1
        if req.rows_left > 0:
            return
        toks, lens, _ = req.payload
        out = np.array(toks, copy=True)
        for r in range(req.rows):
            got = req.row_tokens[r]
            out[r, int(lens[r]):int(lens[r]) + len(got)] = got
        req.t_done = now
        if self._finish_req(req, value=out):
            self.stats.on_complete(now - req.t_submit, req.rows)
            self._h_latency.observe(now - req.t_submit,
                                    exemplar=req.id, **self.obs_labels)
            ntok = max(len(t) for t in req.row_tokens)
            if ntok > 1 and req.t_first is not None:
                self._h_tpot.observe(
                    (now - req.t_first) / (ntok - 1),
                    exemplar=req.id, **self.obs_labels)
            tr = _trace.sink()
            if tr is not None:
                with tr.span("serve.complete", "serve",
                             {"request_id": req.id}):
                    tr.flow_end("request", req.seq, "serve")

    def _fail_all_inflight(self, error: BaseException) -> None:
        """Pool-integrity reset after a failed donated call: every row
        with K/V in the (now untrustworthy or consumed) pool fails,
        pages return, and the pool is rebuilt from scratch. Queued
        rows (no pool state yet) stay queued — but their prefix-cache
        matches are VOID (the matched pages' content dies with the
        pool), so their pins and shared references release and they
        fall back to cold prefill. The trie itself resets the same
        way: its held references release instead of leaking pages
        whose K/V no longer exists."""
        for i, row in enumerate(self._slots):
            if row is None:
                continue
            self._release_row(row)
            self._slots[i] = None
            self._nlive -= 1
            self._finish_req(row.req, error=error)
        while self._ready:
            row = self._ready.popleft()
            self._release_row(row)
            self._finish_req(row.req, error=error)
        if self.prefix is not None:
            # one _cond hold across the queued-row release AND the
            # trie reset: an _admit interleaving between them could
            # pin a node the reset is about to release (admissions
            # match under _cond, so holding it closes the race; lock
            # order stays cond -> prefixcache -> kvpool)
            with self._cond:
                for row in self._q:
                    self._release_row(row)
                self.prefix.reset()
        self._pools = self.callee.new_pool(self.kv_dtype)

    def _reap_dead_slots(self) -> None:
        """Release slots whose request was already failed externally
        (drain straggler window, close) — their pages go back and the
        slot rebinds next prefill."""
        for i, row in enumerate(self._slots):
            if row is not None and row.req.done:
                self._release_row(row)
                self._slots[i] = None
                self._nlive -= 1

    @hot_path
    def _decode_step(self) -> None:
        """One decode call for every live slot, dispatched at the
        smallest exported step bucket holding them: build the step
        inputs from the slot table (live rows PACKED into the bucket's
        leading rows — lane identity is host bookkeeping; every
        per-call array and the block table are rebuilt here anyway),
        run the rung's step program, fan the sampled tokens out to
        their requests. Bucket choice is pure host arithmetic on the
        host-known live count — no device sync."""
        self._reap_dead_slots()
        self._bind_ready()
        live = [(i, s) for i, s in enumerate(self._slots)
                if s is not None]
        if not live:
            return   # all slots idle: no dispatch at all
        c = self.callee
        nblk = c.blocks_per_seq
        if self.dp == 1:
            b = c.pick_step_bucket(len(live), self.kv_dtype)
            placed = [(j, i, row)
                      for j, (i, row) in enumerate(live)]
        else:
            # per-shard packing: dispatch rows [s*(b/dp), ...) belong
            # to mesh shard s, so each live row must land in its own
            # shard's chunk (its pages live in that slice) — the
            # bucket is the smallest whose PER-SHARD capacity holds
            # the busiest shard; dummies point at their shard's trash
            by_shard: List[list] = [[] for _ in range(self.dp)]
            for i, row in live:
                by_shard[i // self.lanes_per_shard].append((i, row))
            per_need = max(len(g) for g in by_shard)
            b = next((bb for bb in self._step_buckets
                      if bb // self.dp >= per_need),
                     self._step_buckets[-1])
            placed = []
            for s, g in enumerate(by_shard):
                for jloc, (i, row) in enumerate(g):
                    placed.append((s * (b // self.dp) + jloc, i, row))
        bt = self._trash_bt(b)
        lens = np.ones((b,), np.int32)
        stepv = np.zeros((b,), np.int32)
        last = np.zeros((b,), np.int32)
        for j, i, row in placed:
            bt[j] = row.blocks
            lens[j] = row.plen
            stepv[j] = row.ntok - 1
            last[j] = row.last
        self._nstep += 1
        self._bucket_steps[b] = self._bucket_steps.get(b, 0) + 1
        T = c.step_tokens
        t_dec0 = time.monotonic()
        try:
            if self.step_hook is not None:
                self.step_hook()
            with _trace.span("serve.decode_step", "serve",
                             {"live": len(live),
                              "bucket": b,
                              "dummy": b - len(live),
                              "step_tokens": T}):
                out = c.step_call(self.kv_dtype, b)(
                    *self._pools, bt, lens, stepv, last,
                    self._fold_key(1 << 20 | self._nstep))
                pools, nxt = out[:-1], out[-1]
                # the sanctioned materialize: the sampled tokens must
                # reach the host every step — they are the stream
                toks = np.asarray(nxt)     # (b, step_tokens)
        except Exception as e:
            reqs = {row.req for _, row in live}
            self.stats.on_error(len(reqs))
            for i, row in live:
                self._release_row(row)
                self._slots[i] = None
                self._nlive -= 1
            for req in reqs:
                self._finish_req(req, error=e)
            # the step call donates the pool buffers — a failure may
            # have consumed them, and the ready rows' prefilled K/V
            # lived there: fail everything in flight, rebuild fresh
            self._fail_all_inflight(e)
            return
        self._pools = pools
        now = time.monotonic()
        emitted = 0
        a = _attrib.active()
        over_s = [0] * self.dp if a is not None else None
        live_s = [0] * self.dp if a is not None else None
        pages_s = [0] * self.dp if a is not None else None
        lps = b // self.dp
        toks = toks.tolist()
        for j, i, row in placed:
            # a row completing mid-call discards its overshoot tokens
            # (their pool writes die with the row's pages)
            take = min(T, row.req.n_new - row.ntok)
            if a is not None:
                s = j // lps
                over_s[s] += T - take
                live_s[s] += 1
                pages_s[s] += nblk
            self._emit(row, toks[j][:take], now)
            emitted += take
            if row.ntok >= row.req.n_new:
                self._slots[i] = None
                self._nlive -= 1
                self._row_done(row, now)
        self.stats.on_step(emitted, b * T - emitted)
        if a is not None:
            # one event per mesh shard (per rung x bucket x shard):
            # each shard's lanes_per_shard x step_tokens slot-tokens
            # split into emitted tokens (goodput), dummy lanes, and
            # mid-step overshoot discarded past n_new
            for s in range(self.dp):
                st = lps * T
                dummy = (lps - live_s[s]) * T
                good = st - dummy - over_s[s]
                a.record("decode", self.kv_dtype, s, lps, live_s[s],
                         T, st, good, 0, dummy, over_s[s], 0,
                         pages_s[s])
        pr = _profile.active()
        if pr is not None:
            # continuous-site profile event: step submit -> sampled
            # tokens materialized. One event per mesh shard (the cost
            # table registers per-shard step costs, flops/dp), same
            # wall for each — the shards run one SPMD program
            wall = (now - t_dec0) * 1000.0
            for s in range(self.dp):
                pr.record("continuous", "decode", self.kv_dtype,
                          lps, T, s if self.dp > 1 else -1, wall)

    def _loop(self) -> None:
        """Scheduler thread body. Device calls fail their own requests
        (``_prefill_dispatch`` / ``_decode_step``); anything ELSE that
        raises here is a scheduler bug, and the thread is the boundary:
        it records the traceback, closes admission and fails every
        request in flight with the error — a dead scheduler must reach
        its clients as errors, not as requests that wait out their
        timeouts."""
        try:
            while True:
                with self._cond:
                    while not self._closed and not self._q \
                            and not self._ready and self._nlive == 0:
                        self._cond.wait(0.05)
                    if self._closed:
                        return
                if self._q and (self.prefill_split
                                or self._nlive == 0):
                    self._prefill_dispatch()
                if self._nlive or self._ready:
                    self._decode_step()
        except Exception as e:
            sys.stderr.write("serve-continuous scheduler died:\n%s"
                             % traceback.format_exc())
            with self._cond:
                self._closed = True
                self._cond.notify_all()
            self._fail_everything(e)

    # ------------------------------------------------------------------
    def drain(self, timeout: float = 10.0) -> int:
        """Stop admitting, keep decoding what's in flight, fail the
        stragglers after ``timeout`` seconds (their slots and pool
        pages are reaped on the next scheduler pass)."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = time.monotonic() + max(float(timeout), 0.0)
        while time.monotonic() < deadline:
            if self.live_requests == 0:
                return 0
            time.sleep(0.005)
        with self._live_lock:
            stragglers = list(self._live)
        n = 0
        for r in stragglers:
            if self._finish_req(r, error=DrainError(
                    "request %s unanswered after %.1fs drain window"
                    % (r.id, timeout))):
                self.stats.on_drained()
                n += 1
        with self._cond:
            while self._q:
                # queued stragglers hold prefix-cache pins/references
                # from admission — give them back before dropping
                self._release_row(self._q.popleft())
        if n:
            _trace.instant("serve.drain_stragglers", "serve",
                           {"failed": n})
        return n

    def _fail_everything(self, error: Exception) -> None:
        """Fail every queued, parked, bound and otherwise live request
        with ``error`` and give their pages back. Runs with the
        scheduler thread gone (``close`` after the join, or the thread
        itself on its way out)."""
        with self._cond:
            while self._q:
                row = self._q.popleft()
                self._release_row(row)
                self._finish_req(row.req, error=error)
        while self._ready:
            row = self._ready.popleft()
            self._release_row(row)
            self._finish_req(row.req, error=error)
        for i, row in enumerate(self._slots):
            # rows a drain failed while they sat in a lane: the
            # scheduler thread is gone, so their pages reap here
            if row is not None:
                self._release_row(row)
                self._slots[i] = None
                self._nlive -= 1
                self._finish_req(row.req, error=error)
        with self._live_lock:
            leftovers = list(self._live)
        for req in leftovers:
            self._finish_req(req, error=error)

    def close(self, timeout: float = 10.0) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._started:
            self._thread.join(timeout)
        self._fail_everything(RuntimeError("engine closed"))
        if self.prefix is not None:
            # every row reference is gone; the trie's own page
            # references go back too, so a drained engine leaves the
            # pool provably empty (the leak check the tests pin)
            self.prefix.reset()
        self.registry.collect()
        for h in self._registry_hooks:
            self.registry.remove_hook(h)
        self._registry_hooks = []

    def __enter__(self) -> "ContinuousDecodeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
