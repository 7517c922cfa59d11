"""CLI task driver: ``python -m cxxnet_tpu config.conf [k=v ...]``.

Mirrors the reference's CXXNetLearnTask (reference: src/cxxnet_main.cpp:16-471):
the same argv contract (config file + k=v overrides), the same tasks
(train / finetune / pred / extract), continue-training via model-dir scan,
save_model cadence, ``test_io`` pipeline dry-run, per-round eval lines on
stderr and progress lines on stdout.
"""

from __future__ import annotations

import os
import sys
import time
from typing import List, Optional, Tuple

import numpy as np

from . import checkpoint, config
from .analysis import hot_path
from .io import DataIterator, create_iterator
from .profiler import StepTimer, TraceSession, device_memory_summary
from .trainer import GroupStager, StagedBatch, Trainer

ConfigEntry = Tuple[str, str]


def parse_mesh_spec(val: str) -> Tuple[int, int]:
    """``export_mesh`` / ``serve_mesh`` syntax: ``D`` (data-parallel
    ways) or ``DxM`` / ``D,M`` (data x model) -> (data, model)."""
    s = val.strip().lower().replace("x", ",")
    parts = [int(p) for p in s.split(",") if p.strip()]
    if not parts or len(parts) > 2 or any(p < 1 for p in parts):
        raise ValueError(
            "mesh spec must be D or DxM (data[,model] ways, each "
            ">= 1), got %r" % val)
    return parts[0], parts[1] if len(parts) > 1 else 1


def check_serve_mesh(mesh_s: str, mesh_meta, src: str) -> None:
    """``serve_mesh``: the operator's topology intent, checked against
    what the artifact actually carries (``mesh_meta`` = the meta's
    mesh stanza or None) — deploying a single-device artifact where a
    4-way mesh was expected (or vice versa) fails HERE with both
    named, not as mysterious capacity/latency at traffic time. Both
    serve topologies (single engine AND the replica router) run
    through this."""
    if not mesh_s or mesh_s == "0":
        return
    want_dp, want_mp = parse_mesh_spec(mesh_s)
    have = dict(zip(mesh_meta["axes"], mesh_meta["shape"])) \
        if mesh_meta else {}
    have_dp = int(have.get("data", 1))
    have_mp = int(have.get("model", 1))
    if (want_dp, want_mp) != (have_dp, have_mp):
        raise RuntimeError(
            "serve_mesh=%s expects a %dx%d (data x model) mesh "
            "artifact, but %s carries %s — re-export with "
            "export_mesh=%s or fix serve_mesh"
            % (mesh_s, want_dp, want_mp, src,
               "mesh %s" % (mesh_meta,) if mesh_meta
               else "no mesh (single-device)", mesh_s))


class LearnTask:
    def __init__(self) -> None:
        self.cfg: List[ConfigEntry] = []
        self.task = "train"
        self.net_type = 0
        self.trainer: Optional[Trainer] = None
        self.itr_train: Optional[DataIterator] = None
        self.itr_pred: Optional[DataIterator] = None
        self.itr_evals: List[DataIterator] = []
        self.eval_names: List[str] = []
        self.model_dir = "models"
        self.num_round = 10
        self.max_round = 1 << 31
        self.test_io = 0
        self.silent = 0
        self.start_counter = 0
        self.continue_training = 0
        self.save_period = 1
        self.model_in = "NULL"
        self.name_pred = "pred.txt"
        self.print_step = 100
        # overlapped feed (io/prefetch.py): a background thread stages
        # batches device-side device_prefetch_depth ahead of the
        # dispatch loop; device_prefetch = 0 restores the legacy
        # one-ahead helper loop. (prefetch_depth without the prefix is
        # the DECODE-POOL window, an iterator-section key — distinct
        # knob, distinct name, so a global setting of one cannot
        # silently reconfigure the other.)
        self.device_prefetch = 1
        self.device_prefetch_depth = 2
        self.extract_node_name = ""
        self.output_format = 1
        # unified observability (docs/observability.md): trace_out=<f>
        # writes a Chrome trace-event JSON of every host thread lane
        # (decode workers, dev-prefetch producer, dispatch loop, serve
        # pipeline); telemetry_port=N serves the global metrics
        # registry over HTTP beside the run (0 binds a free port)
        self.trace_out = ""
        self.telemetry_port: Optional[int] = None
        self._telemetry = None
        self._flight = None          # task=serve's flight recorder
        self._attrib = None          # task=serve's attribution ledger
        self._slo = None             # task=serve's SLO engine
        self._obs_hooks: List = []   # global-registry hooks this run
                                     # registered; removed at run end
                                     # so repeated in-process runs do
                                     # not pin dead trainers/feeds
        self.trace = TraceSession()
        self.timer = StepTimer()
        from concurrent.futures import ThreadPoolExecutor
        self._stager = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="h2d-stage")

    # ------------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        """Reference: cxxnet_main.cpp:83-105."""
        if val == "default":
            return
        if name == "net_type":
            self.net_type = int(val)
        elif name == "print_step":
            self.print_step = int(val)
        elif name == "continue":
            self.continue_training = int(val)
        elif name == "save_model":
            self.save_period = int(val)
        elif name == "start_counter":
            self.start_counter = int(val)
        elif name == "model_in":
            self.model_in = val
        elif name == "model_dir":
            self.model_dir = val
        elif name == "num_round":
            self.num_round = int(val)
        elif name == "max_round":
            self.max_round = int(val)
        elif name == "silent":
            self.silent = int(val)
        elif name == "task":
            self.task = val
        elif name == "test_io":
            self.test_io = int(val)
        elif name == "extract_node_name":
            self.extract_node_name = val
        elif name == "output_format":
            self.output_format = 1 if val == "txt" else 0
        elif name == "device_prefetch":
            self.device_prefetch = int(val)
        elif name == "device_prefetch_depth":
            self.device_prefetch_depth = int(val)
            if self.device_prefetch_depth < 1:
                raise ValueError("device_prefetch_depth must be >= 1")
        elif name == "trace_out":
            self.trace_out = val
        elif name == "telemetry_port":
            self.telemetry_port = int(val)
            if self.telemetry_port < 0:
                raise ValueError("telemetry_port must be >= 0 "
                                 "(0 binds a free port)")
        self.trace.set_param(name, val)
        self.cfg.append((name, val))

    # ------------------------------------------------------------------
    def run(self, argv: List[str]) -> int:
        if len(argv) < 1:
            print("Usage: <config>")
            return 0
        for name, val in config.parse_file(argv[0]):
            self.set_param(name, val)
        for name, val in config.parse_cli_overrides(argv[1:]):
            self.set_param(name, val)
        # multi-host runtime (replaces the dist parameter server deployment)
        d = dict(self.cfg)
        if "dist_coordinator" in d:
            from . import parallel
            parallel.init_distributed(
                d["dist_coordinator"],
                int(d.get("dist_num_worker",
                          os.environ.get("PS_NUM_WORKER", "1"))),
                int(d.get("dist_worker_rank",
                          os.environ.get("PS_RANK", "0"))))
        from .obs import trace as obs_trace
        from .obs.registry import get_registry
        try:
            # observability setup lives INSIDE the try: if e.g. the
            # telemetry port is taken, the already-installed tracer
            # still gets uninstalled below instead of accumulating
            # events for the rest of the process
            if self.trace_out:
                obs_trace.start(self.trace_out)
            if self.telemetry_port is not None:
                from .obs.telemetry import start_telemetry
                self._telemetry = start_telemetry(self.telemetry_port)
                if not self.silent:
                    print("telemetry on http://127.0.0.1:%d/metrics"
                          % self._telemetry.port)
                    sys.stdout.flush()
            self.init()
            if not self.silent:
                print("initializing end, start working")
            if self.task in ("train", "finetune"):
                self.task_train()
            elif self.task == "pred":
                self.task_predict()
            elif self.task == "extract":
                self.task_extract()
            elif self.task == "export_model":
                self.task_export()
            elif self.task == "generate":
                self.task_generate()
            elif self.task == "export_reference":
                self.task_export_reference()
            elif self.task == "serve":
                self.task_serve()
        finally:
            # each cleanup is independent: a failing trace write must
            # not skip the server shutdown (or vice versa) nor mask
            # the task's own exception
            for h in self._obs_hooks:
                get_registry().remove_hook(h)
            self._obs_hooks = []
            # serve-task observability: torn down HERE, not inside
            # task_serve — a setup failure between installing the
            # recorder and entering serve_forever must not leak a
            # process-global sink or a ticking daemon thread
            if self._slo is not None:
                try:
                    self._slo.stop()
                except Exception as e:
                    sys.stderr.write("slo shutdown failed: %s\n" % e)
                self._slo = None
            if self._flight is not None:
                obs_trace.set_flight(None)
                self._flight = None
            if self._attrib is not None:
                from .obs import attrib as _attrib
                _attrib.disable()
                self._attrib = None
            if self._telemetry is not None:
                try:
                    self._telemetry.shutdown()
                    self._telemetry.server_close()
                except Exception as e:
                    sys.stderr.write("telemetry shutdown failed: %s\n"
                                     % e)
                self._telemetry = None
            if self.trace_out:
                try:
                    path = obs_trace.stop()
                    if path and not self.silent:
                        print("wrote host trace to %s (chrome://"
                              "tracing / tools/trace_report.py)"
                              % path)
                except Exception as e:
                    sys.stderr.write("trace write failed: %s\n" % e)
        return 0

    # ------------------------------------------------------------------
    def _create_trainer(self) -> Trainer:
        tr = Trainer()
        for k, v in self.cfg:
            tr.set_param(k, v)
        if self.task in ("train", "finetune") and self.device_prefetch \
                and not self.test_io \
                and all(k != "donate_inputs" for k, _ in self.cfg):
            # the device-prefetch feed stages every batch fresh and
            # dispatches it exactly once, so the step programs may
            # donate their input buffers; an explicit donate_inputs in
            # the config always wins
            tr.set_param("donate_inputs", "1")
        return tr

    def init(self) -> None:
        """Reference: cxxnet_main.cpp:108-133."""
        if self.task == "serve" and dict(self.cfg).get("export_in"):
            # serving an exported artifact: self-contained (weights
            # baked in) — no trainer, no params, no iterators to build
            return
        if self.task == "train" and self.continue_training:
            found = checkpoint.find_latest_model(
                self.model_dir, self.start_counter)
            if found is None:
                raise RuntimeError(
                    "Init: cannot find models for continue training; "
                    "specify model_in instead")
            path, counter = found
            print("Init: Continue training from round %d" % counter)
            self.trainer = self._create_trainer()
            self.trainer.load_model(path)
            self.start_counter = counter + 1
            self.create_iterators()
            self._warn_unconsumed()
            return
        self.continue_training = 0
        if self.model_in == "NULL":
            if self.task != "train":
                raise RuntimeError("must specify model_in if not training")
            self.trainer = self._create_trainer()
            self.trainer.init_model()
        else:
            self.trainer = self._create_trainer()
            if self.task == "finetune":
                self.trainer.copy_model_from(self.model_in)
            else:
                self.trainer.load_model(self.model_in)
                base = os.path.basename(self.model_in).split(".")[0]
                if base.isdigit():
                    self.start_counter = int(base)
                self.start_counter += 1
        self.create_iterators()
        self._warn_unconsumed()
        if self.task in ("generate", "export_model", "serve"):
            from . import generate
            why = generate.decode_blocker(self.trainer.net)
            if why:
                raise RuntimeError("task = %s is not implemented for "
                                   "this net: %s" % (self.task, why))

    # keys the CLI layer itself consumes (set_param above + run())
    CLI_KEYS = frozenset([
        "net_type", "print_step", "continue", "save_model",
        "start_counter", "model_in", "model_dir", "num_round",
        "max_round", "silent", "task", "test_io", "extract_node_name",
        "output_format", "data", "eval", "pred", "iter",
        # overlapped-feed knobs (io/prefetch.py + task_train)
        "device_prefetch", "device_prefetch_depth",
        # TraceSession (obs/trace.py ProfilerSession)
        "profile", "profile_dir", "profile_start_batch",
        "profile_stop_batch",
        # unified observability (obs/, docs/observability.md)
        "trace_out", "telemetry_port",
    ])
    # keys consumed only by a specific task's run() — claimed for the
    # audit ONLY when that task is active, so a stray 'temperature='
    # in a training config still trips strict=1
    TASK_KEYS = {
        "generate": frozenset(["prompts", "gen_out", "max_new",
                               "temperature", "gen_seed"]),
        "export_reference": frozenset(["ref_out"]),
        "export_model": frozenset(["export_decode", "max_new",
                                   "temperature", "export_prompt_len",
                                   "export_out", "export_batch",
                                   "export_batch_ladder",
                                   "export_platform",
                                   # split-phase (paged) decoder
                                   # (export_decode = step)
                                   "export_kv_block",
                                   "export_pool_blocks",
                                   "export_prefill_rows",
                                   "export_prefill_widths",
                                   # typed rungs (docs/serving.md)
                                   "export_kv_dtype",
                                   "export_paged_attend",
                                   "export_step_buckets",
                                   # mesh-carrying artifacts
                                   # (sharded serving)
                                   "export_mesh"]),
        "serve": frozenset(["export_in", "serve_host", "serve_port",
                            "serve_mesh",
                            "serve_max_wait_ms", "serve_max_batch",
                            "serve_queue_limit", "serve_timeout_ms",
                            "serve_dispatch_depth", "serve_warmup",
                            "serve_access_log",
                            # continuous batching (serve/continuous.py)
                            "serve_stream", "serve_prefill_split",
                            "serve_kv_blocks", "serve_kv_dtype",
                            # cross-request prefix cache
                            # (serve/prefixcache.py)
                            "serve_prefix_cache",
                            "serve_prefix_capacity_pages",
                            # multi-replica front end (serve/router.py)
                            "serve_replicas", "serve_max_retries",
                            "serve_priority_default", "serve_swap",
                            # SLO engine + flight recorder (obs/slo.py,
                            # obs/flight.py, docs/observability.md)
                            "slo_p99_ms", "slo_target", "slo_windows",
                            "flight_events", "flight_dump_dir",
                            # goodput attribution ledger (obs/attrib.py)
                            "attrib_events"]),
    }

    def _iter_section_keys(self) -> set:
        """Keys appearing inside data/eval/pred iterator sections —
        claimed by the iterator factory, excluded from the global
        unconsumed-key audit (same flag walk as create_iterators)."""
        flag, keys = 0, set()
        for name, val in self.cfg:
            if name in ("data", "eval", "pred"):
                flag = 1
            elif name == "iter" and val == "end":
                flag = 0
            elif flag:
                keys.add(name)
        return keys

    def _warn_unconsumed(self) -> None:
        """Report config keys nothing consumed (VERDICT r3 #5 — the
        silently no-op'd warmup_epochs class of bug; the reference
        broadcast-and-ignores). ``strict = 1`` makes it fatal."""
        if self.trainer is None:
            return
        bad = self.trainer.unconsumed_keys(
            extra_known=self.CLI_KEYS | self._iter_section_keys()
            | self.TASK_KEYS.get(self.task, frozenset()))
        if not bad:
            return
        msg = ("unconsumed config keys (no component recognized them "
               "- typo?): %s" % ", ".join(bad))
        if self.trainer.strict:
            raise ValueError(msg + " (strict = 1 makes this fatal; "
                             "fix or remove the keys)")
        print("Warning: " + msg, file=sys.stderr)

    def create_iterators(self) -> None:
        """Order-sensitive iterator sections (reference:
        cxxnet_main.cpp:214-264): data/eval/pred ... iter=end. Global
        (outside-section) keys are broadcast to every iterator before
        init, like the reference's defcfg + InitIter — that is how a
        global ``batch_size``/``input_shape`` reaches the pipeline."""
        flag = 0
        evname = ""
        itcfg: List[ConfigEntry] = []
        defcfg: List[ConfigEntry] = []
        pending: List[Tuple[int, str, List[ConfigEntry]]] = []
        for name, val in self.cfg:
            if name == "data":
                flag = 1
                continue
            if name == "eval":
                evname = val
                flag = 2
                continue
            if name == "pred":
                flag = 3
                self.name_pred = val
                continue
            if name == "iter" and val == "end":
                pending.append((flag, evname, itcfg))
                flag = 0
                itcfg = []
                continue
            if flag != 0:
                itcfg.append((name, val))
            else:
                defcfg.append((name, val))
        # pred uses only its own iterator; export_model, generate, and
        # serve use none at all (a serving box has the checkpoint +
        # prompts, not the training packfiles)
        no_train_io = self.task in ("pred", "export_model", "generate",
                                    "export_reference", "serve")
        for flag, evname, itcfg in pending:
            if flag == 1 and not no_train_io:
                assert self.itr_train is None, "can only have one data"
                self.itr_train = create_iterator(itcfg, defcfg)
            elif flag == 2 and not no_train_io:
                self.itr_evals.append(create_iterator(itcfg, defcfg))
                self.eval_names.append(evname)
            elif flag == 3 and self.task in ("pred", "extract"):
                assert self.itr_pred is None, "can only have one pred"
                self.itr_pred = create_iterator(itcfg, defcfg)

    # ------------------------------------------------------------------
    def _print_progress(self, sample_counter: int, start: float) -> None:
        """Reference progress line every print_step batches
        (cxxnet_main.cpp:378-387). ``print_step = 0`` disables it."""
        if self.print_step <= 0 or self.silent \
                or sample_counter % self.print_step != 0:
            return
        elapsed = int(time.time() - start)
        print("\r%80s\r" % "", end="")
        print("round %8d:[%8d] %d sec elapsed"
              % (self.start_counter - 1, sample_counter, elapsed), end="")
        sys.stdout.flush()

    def _recover_from_nan(self, msg: str) -> None:
        """nan_guard=2 recovery: restore the newest checkpoint, halve the
        learning rate(s), rewind the round counter to the restore point."""
        # join any in-flight async checkpoint write first: the newest
        # checkpoint may still be landing on the ckpt-save thread
        self.trainer.wait_for_save()
        found = checkpoint.find_latest_model(self.model_dir)
        import jax
        if jax.process_count() > 1:
            # ranks must agree on the restore point: an independent scan
            # can resolve differently per rank (rank 0's meta.json still
            # in flight, NFS attribute-cache lag), silently diverging
            # the replicas — rank 0's verdict wins
            import numpy as _np
            from jax.experimental import multihost_utils
            counter = int(multihost_utils.broadcast_one_to_all(
                _np.int64(found[1] if found is not None else -1)))
            found = (checkpoint.model_path(self.model_dir, counter),
                     counter) if counter >= 0 else None
        if found is None:
            raise RuntimeError(
                "nan_guard=2: no checkpoint in %s to recover from "
                "(raise save_model cadence); original error: %s"
                % (self.model_dir, msg))
        path, counter = found
        # Halve every EFFECTIVE learning rate by compounding the
        # recovery_lr_scale multiplier, an internal updater key that
        # multiplies each updater's final rate (incl. Adam's constant-
        # rate fast path). Appending halved eta/lr values cannot do
        # this: layer-bucket and tag-scoped rates override appended
        # globals, and a config with no global eta at all would yield
        # nothing to halve. Only non-netconfig entries are scanned —
        # a bucket entry is layer-scoped and would be the wrong
        # compounding base for every other layer.
        scale = 1.0
        in_net = False
        for k, v in self.trainer.cfg:
            if k == "netconfig":
                in_net = v == "start"
            elif not in_net and k == "recovery_lr_scale":
                scale = float(v)
        self.trainer.set_param("recovery_lr_scale", repr(scale * 0.5))
        self.trainer.load_model(path)
        self.start_counter = counter + 1
        sys.stderr.write(
            "nan_guard: %s\nnan_guard=2: restored %s, lr_scale %g -> %g "
            "(halves every learning rate, incl. tag- and layer-scoped), "
            "resuming at round %d\n"
            % (msg, path, scale, scale * 0.5, self.start_counter))
        sys.stderr.flush()

    def save_model_file(self) -> None:
        """Reference: cxxnet_main.cpp:173-182 (cadence check + %04d name)."""
        counter = self.start_counter
        self.start_counter += 1
        # the reference checks the *incremented* counter against the period
        if self.save_period == 0 or self.start_counter % self.save_period != 0:
            return
        os.makedirs(self.model_dir, exist_ok=True)
        self.trainer.save_model(checkpoint.model_path(self.model_dir, counter))

    def _serial_round(self, dispatch, gstagers, use_groups, fuse,
                      sample_counter, start):
        """Legacy (``device_prefetch = 0``) round body, plus the
        ``test_io`` dry-run walk: one-ahead device staging on the
        helper thread — batch k+1's host->device transfer is issued
        while batch k computes; group_staging rotates two GroupStagers
        so one fills while the other's transfer flies."""
        self.itr_train.before_first()
        pending = []
        cur, infl = 0, None
        while True:
            has_next = self.itr_train.next()
            if self.test_io != 0:
                if not has_next:
                    break
                sample_counter += 1
                self._print_progress(sample_counter, start)
                continue
            if use_groups:
                if has_next:
                    # add() copies the batch NOW, so the iterator
                    # may reuse its buffers on the next next()
                    gs = gstagers[cur]
                    gs.add(self.itr_train.value)
                    if gs.full:
                        fut = self._stager.submit(gs.stage)
                        # dispatch the PREVIOUS group while this
                        # one's transfer flies on the helper thread
                        if infl is not None:
                            sample_counter = dispatch(
                                infl.result(), sample_counter)
                        infl = fut
                        cur ^= 1
                    continue
                if infl is not None:
                    sample_counter = dispatch(infl.result(),
                                              sample_counter)
                    infl = None
                # round tail: partial group falls back per-step
                for s in gstagers[cur].flush():
                    sample_counter = dispatch([s], sample_counter)
                break
            nxt = None
            if has_next:
                nxt = self._stager.submit(self.trainer.stage,
                                          self.itr_train.value)
            if len(pending) >= fuse:
                sample_counter = dispatch(pending, sample_counter)
                pending = []
            # resolve before touching the iterator again: next() may
            # reuse the buffers the stager is still reading
            if nxt is not None:
                pending.append(nxt.result())
            if not has_next:
                break
        if self.test_io == 0 and pending:
            # round tail: a partial group falls back to per-step
            sample_counter = dispatch(pending, sample_counter)
        return sample_counter

    def task_train(self) -> None:
        """Reference: cxxnet_main.cpp:344-412."""
        start = time.time()
        if self.continue_training == 0 and self.model_in == "NULL":
            self.save_model_file()
        else:
            for itr, name in zip(self.itr_evals, self.eval_names):
                sys.stderr.write(self.trainer.evaluate(itr, name))
            sys.stderr.write("\n")
            sys.stderr.flush()
        if self.itr_train is None:
            # still surface a failed async write of the round-0 checkpoint
            self.trainer.wait_for_save()
            return
        if self.test_io:
            print("start I/O test")
        # overlapped feed, two generations:
        #  * device_prefetch = 1 (default): DevicePrefetchIterator
        #    (io/prefetch.py) stages batches/groups prefetch_depth
        #    ahead on its own thread; this loop just pops ready-on-
        #    device work and dispatches without blocking on step
        #    results — JAX's async dispatch runs ahead and only
        #    synchronizes at metric/eval/checkpoint boundaries. Time
        #    blocked waiting for the feed is recorded as feed stall
        #    (StepTimer.note_feed_wait) so starvation is measurable.
        #  * device_prefetch = 0 (and test_io): the legacy one-ahead
        #    helper-thread staging below. With fuse_steps = K both
        #    modes group K batches per dispatch (Trainer.update_fused);
        #    group_staging = 1 ships each group as ONE stacked
        #    transfer (GroupStager), rotating two stagers here so one
        #    fills while the other's transfer flies.
        # Either feed preserves batch order, bytes, and RNG
        # consumption (tests/test_prefetch.py pins the staged stream
        # bitwise); fixed-seed trajectories agree across modes to
        # float tolerance.
        fuse = max(1, self.trainer.fuse_steps)
        use_feed = self.device_prefetch != 0 and self.test_io == 0
        use_groups = fuse > 1 and self.trainer.group_staging != 0 \
            and not use_feed
        feed = None
        # publish the train-loop telemetry into the global registry
        # (the telemetry_port endpoint and any in-process scraper read
        # the same numbers the round summary prints)
        from .obs.registry import get_registry, watch_steptimer
        self._obs_hooks.append(
            watch_steptimer(self.timer, registry=get_registry()))
        if use_feed:
            from .io.prefetch import DevicePrefetchIterator
            feed = DevicePrefetchIterator(
                self.itr_train, self.trainer,
                depth=self.device_prefetch_depth)
            self._obs_hooks += feed.bind_registry(get_registry())
        gstagers = [GroupStager(self.trainer),
                    GroupStager(self.trainer)] if use_groups else None

        @hot_path
        def dispatch(group, sample_counter):
            # group: a list of per-batch StagedBatch, or one fused
            # StagedBatch group. dispatch is async: the call returns
            # while the device computes, so the next batches'
            # transfers (helper thread) overlap this group's step(s)
            # (@hot_path: the SYNC lint gate keeps host syncs out —
            # a float()/np.asarray() here would serialize the loop)
            if isinstance(group, StagedBatch):
                n = group.fused or 1
                with self.trace.step(n):
                    self.trainer.update_fused(group)
            else:
                n = len(group)
                with self.trace.step(n):
                    if n == 1:
                        self.trainer.update(group[0])
                    else:
                        self.trainer.update_fused(group)
            self.timer.tick(n)
            for _ in range(n):
                sample_counter += 1
                self._print_progress(sample_counter, start)
            return sample_counter

        cc = self.max_round
        while self.start_counter <= self.num_round and cc > 0:
            cc -= 1
            if not self.silent:
                print("update round %d" % (self.start_counter - 1), end="")
                sys.stdout.flush()
            sample_counter = 0
            self.trainer.start_round(self.start_counter)
            self.timer.reset_clock()
            if feed is not None:
                # dispatch-ahead loop: the producer thread owns the
                # base iterator (before_first runs there); this loop
                # only pops staged work and dispatches it
                feed.before_first()
                while True:
                    t0 = time.perf_counter()
                    has = feed.next()
                    self.timer.note_feed_wait(time.perf_counter() - t0)
                    if not has:
                        break
                    item = feed.value
                    if isinstance(item, StagedBatch) and not item.fused:
                        item = [item]   # tail / unfused: per-step path
                    sample_counter = dispatch(item, sample_counter)
            else:
                sample_counter = self._serial_round(
                    dispatch, gstagers, use_groups, fuse,
                    sample_counter, start)
            if self.test_io == 0:
                try:
                    sys.stderr.write("[%d]" % self.start_counter)
                    if not self.itr_evals:
                        sys.stderr.write(self.trainer.evaluate(None, "train"))
                    for itr, name in zip(self.itr_evals, self.eval_names):
                        sys.stderr.write(self.trainer.evaluate(itr, name))
                    sys.stderr.write("\n")
                    sys.stderr.flush()
                except RuntimeError as e:
                    # nan_guard = 2: elastic recovery — reload the latest
                    # checkpoint, halve eta, re-run the round (beyond the
                    # reference, whose only recovery is a manual restart
                    # with continue=1; cxxnet_main.cpp:135-157). Each
                    # attempt still burns max_round budget, so a
                    # hopelessly diverging run terminates.
                    if self.trainer.nan_guard < 2 \
                            or "nan_guard" not in str(e):
                        raise
                    self._recover_from_nan(str(e))
                    continue
            if not self.silent:
                print("\nround %d speed: %s" % (
                    self.start_counter,
                    self.timer.summary(self.trainer.batch_size)))
                if self.trace.enabled:
                    mem = device_memory_summary()
                    if mem:
                        print("device memory: %s" % mem)
                    if feed is not None:
                        st = feed.stats()
                        print("feed: source %.2fs, stage %.2fs, "
                              "backpressure %.2fs, stall %.2fs "
                              "(stall frac %.3f, run total)"
                              % (st["source_wait"]["wait_s"],
                                 st["stage_busy"]["busy_s"],
                                 st["put_wait"]["wait_s"],
                                 st["get_wait"]["wait_s"],
                                 st["feed_stall_frac"]))
            self.save_model_file()
        self.trace.close()
        self.trainer.wait_for_save()
        if not self.silent:
            print("\nupdating end, %d sec in all" % int(time.time() - start))

    # ------------------------------------------------------------------
    def task_predict(self) -> None:
        """Reference: cxxnet_main.cpp:266-283. With fuse_steps the
        pred stream groups K batches per forward dispatch + fetch
        (Trainer.predict_fused); per-batch padding is trimmed from the
        flattened group exactly as the per-batch path trims it."""
        assert self.itr_pred is not None, \
            "must specify a pred iterator to generate predictions"
        print("start predicting...")
        fuse = max(1, self.trainer.fuse_steps)
        # same staging modes as the train/eval streams: GroupStager
        # (one stacked put per group) by default, per-batch staging
        # with the fused dispatch under group_staging = 0
        gs = GroupStager(self.trainer) \
            if fuse > 1 and self.trainer.group_staging != 0 else None
        with open(self.name_pred, "w") as fo:
            self.itr_pred.before_first()
            pend, sizes = [], []   # per-slot (rows, valid)

            def write_group(preds):
                base = 0
                for rows, sz in sizes:
                    for j in range(sz):
                        fo.write("%g\n" % preds[base + j])
                    base += rows
                sizes.clear()

            while self.itr_pred.next():
                batch = self.itr_pred.value
                if fuse > 1:
                    sizes.append((batch.batch_size,
                                  batch.batch_size - batch.num_batch_padd))
                    if gs is not None:
                        gs.add(batch)   # copies; iterator may reuse
                        if gs.full:
                            write_group(
                                self.trainer.predict_fused(gs.stage()))
                    else:
                        # stage() blocks until the transfer lands, so
                        # the iterator may reuse its buffers at next()
                        pend.append(self.trainer.stage(batch))
                        if len(pend) == fuse:
                            write_group(
                                self.trainer.predict_fused(pend))
                            pend = []
                else:
                    preds = self.trainer.predict(batch)
                    sz = batch.batch_size - batch.num_batch_padd
                    for j in range(sz):
                        fo.write("%g\n" % preds[j])
            if gs is not None and gs.n:
                write_group(self.trainer.predict_fused(gs.flush()))
            elif pend:
                write_group(self.trainer.predict_fused(pend))
        print("finished prediction, write into %s" % self.name_pred)

    def task_export_reference(self) -> None:
        """task=export_reference: write the loaded model as an original-
        framework binary .model (refmodel.write_model) so a migration
        can also go BACK to the C++ framework. Keys: ref_out (output
        path, default ref.model)."""
        import jax

        from . import refmodel
        d = dict(self.cfg)
        out = d.get("ref_out", "ref.model")
        tr = self.trainer
        # cross-process-sharded weights must be gathered, and only
        # process 0 may write — the same contract as save_model
        params_host = [None if p is None else
                       {t: tr._fetch_global(a) for t, a in p.items()}
                       for p in tr.params]
        if jax.process_index() == 0:
            refmodel.write_model(out, tr.net_cfg, tr.epoch_counter,
                                 params_host)
        if not self.silent:
            print("wrote reference binary model to %s" % out)

    def task_generate(self) -> None:
        """task=generate: autoregressive sampling from a causal token
        net (no reference analogue — cxxnet has no sequence models).
        Keys: prompts (text file, one prompt of space-separated token
        ids per line), gen_out (output path, default gen.txt), max_new
        (tokens to append, default 32), temperature (0 = greedy),
        gen_seed. Each output line is the prompt plus its completion."""
        d = dict(self.cfg)
        if "prompts" not in d:
            raise RuntimeError("task=generate needs prompts=<file>")
        out_path = d.get("gen_out", "gen.txt")
        max_new = int(d.get("max_new", "32"))
        temperature = float(d.get("temperature", "0"))
        seed = int(d.get("gen_seed", "0"))
        S = self.trainer.net.node_shapes[0][2]
        rows = []
        with open(d["prompts"]) as f:
            for line in f:
                ids = [int(t) for t in line.split()]
                if not ids:
                    continue
                if len(ids) + max_new > S:
                    raise RuntimeError(
                        "prompt of %d + max_new %d exceeds seq_len %d"
                        % (len(ids), max_new, S))
                rows.append(ids)
        bs = self.trainer.global_batch
        with open(out_path, "w") as fo:
            for lo in range(0, len(rows), bs):
                chunk = rows[lo:lo + bs]
                toks = np.zeros((len(chunk), S), np.int32)
                lens = np.zeros(len(chunk), np.int32)
                for i, ids in enumerate(chunk):
                    toks[i, :len(ids)] = ids
                    lens[i] = len(ids)
                # distinct seed per chunk: a repeated seed would give
                # correlated (or identical) sampling streams across
                # batches of the prompts file
                out = self.trainer.generate(toks, lens, max_new,
                                            temperature, seed + lo)
                for i, ids in enumerate(chunk):
                    fo.write(" ".join(
                        str(int(t))
                        for t in out[i, :len(ids) + max_new]) + "\n")
        if not self.silent:
            print("generated %d completions into %s"
                  % (len(rows), out_path))

    def task_export(self) -> None:
        """task=export_model: AOT-serialize the forward pass (weights
        baked in, versioned StableHLO) for serving without the framework
        — no reference analogue (its only deployment was task=pred in
        the training binary). Keys: export_out (path), export_batch
        (serving batch size, default batch_size),
        export_batch_ladder (comma list of shape buckets, or "auto"
        for powers of two up to the export batch — one artifact whose
        smallest fitting bucket serves each request,
        docs/serving.md), export_platform (comma list, default the
        training platform). With export_decode=1 the KV-cache DECODER
        is exported instead (serving.export_generate): max_new /
        temperature / export_prompt_len shape the artifact; the
        decode_layout and decode_kv knobs resolve exactly as
        task=generate would. export_decode=step exports the
        SPLIT-PHASE decoder for continuous batching instead
        (serving.export_decode_step — paged KV pool + width-bucketed
        prefills): export_kv_block / export_pool_blocks size the pool
        pages, export_prefill_rows / export_prefill_widths (comma
        lists) override the prefill bucket ladders,
        export_kv_dtype (comma list of native|int8, default the
        trainer's decode_kv) picks the cache-dtype rungs,
        export_step_buckets (comma list) adds sub-batch decode-step
        rungs, export_paged_attend (fused|gather, default fused)
        picks the attend kernel (docs/serving.md rung table).
        export_mesh = D | DxM emits a MESH-CARRYING artifact for any
        of the three export kinds: programs compiled under pjit with
        explicit shardings over a data(xmodel) mesh on the local
        devices, the mesh + per-arg PartitionSpecs recorded in the
        meta, batch ladders rounded up to data-axis multiples
        (docs/serving.md "sharded serving")."""
        from . import serving
        d = dict(self.cfg)
        out = d.get("export_out", "model.export")
        plats = d.get("export_platform", "")
        platforms = [p.strip() for p in plats.split(",") if p.strip()] \
            or None
        # export_mesh = D | DxM: emit a MESH-CARRYING artifact — every
        # program compiled under pjit with explicit shardings over a
        # data(xmodel) mesh on the local devices, mesh + PartitionSpecs
        # recorded in the meta (docs/serving.md "sharded serving")
        mesh = None
        mesh_s = d.get("export_mesh", "").strip()
        if mesh_s and mesh_s != "0":
            dpw, mpw = parse_mesh_spec(mesh_s)
            if dpw * mpw > 1:
                mesh = serving.make_serving_mesh(
                    dpw, mpw,
                    platform=platforms[0] if platforms else None)
        bs = int(d.get("export_batch", "0")) or None
        ladder_s = d.get("export_batch_ladder", "").strip()
        if ladder_s == "auto":
            ladder = serving.auto_ladder(bs or self.trainer.batch_size)
        elif ladder_s:
            ladder = [int(x) for x in ladder_s.split(",") if x.strip()]
        else:
            ladder = None
        dec = d.get("export_decode", "0").strip()
        if dec == "step":
            rows_s = d.get("export_prefill_rows", "").strip()
            widths_s = d.get("export_prefill_widths", "").strip()
            kv_s = d.get("export_kv_dtype", "").strip()
            sb_s = d.get("export_step_buckets", "").strip()
            serving.export_decode_step(
                self.trainer, out,
                max_new=int(d.get("max_new", "32")),
                temperature=float(d.get("temperature", "0")),
                prompt_len=int(d.get("export_prompt_len", "0")) or None,
                batch_size=bs,
                prefill_rows=[int(x) for x in rows_s.split(",")
                              if x.strip()] or None,
                prefill_widths=[int(x) for x in widths_s.split(",")
                                if x.strip()] or None,
                kv_block=int(d.get("export_kv_block", "128")),
                pool_blocks=int(d.get("export_pool_blocks", "0"))
                or None,
                kv_dtypes=[x.strip() for x in kv_s.split(",")
                           if x.strip()] or None,
                step_buckets=[int(x) for x in sb_s.split(",")
                              if x.strip()] or None,
                paged_attend=d.get("export_paged_attend",
                                   "fused").strip() or "fused",
                platforms=platforms, mesh=mesh)
            print("exported split-phase decoder to %s (+.meta)%s"
                  % (out, " [mesh %s]" % mesh_s if mesh else ""))
            return
        if int(dec or "0"):
            serving.export_generate(
                self.trainer, out,
                max_new=int(d.get("max_new", "32")),
                temperature=float(d.get("temperature", "0")),
                prompt_len=int(d.get("export_prompt_len", "0")) or None,
                batch_size=bs, batch_ladder=ladder,
                platforms=platforms, mesh=mesh)
            print("exported decoder to %s (+.meta)%s"
                  % (out, " [mesh %s]" % mesh_s if mesh else ""))
            return
        serving.export_model(self.trainer, out, batch_size=bs,
                             batch_ladder=ladder, platforms=platforms,
                             mesh=mesh)
        print("exported model to %s (+.meta)%s"
              % (out, " [mesh %s]" % mesh_s if mesh else ""))

    def task_serve(self) -> None:
        """task=serve: dynamic-batching HTTP inference server
        (docs/serving.md). Serves either an exported artifact
        (``export_in = served.bin`` — forward or decoder, no trainer
        is built) or the live loaded model (``model_in = ...``). Keys:
        serve_host (default 127.0.0.1), serve_port (default 8080; 0
        binds a free port), serve_max_wait_ms (batching window,
        default 5), serve_max_batch (rows per dispatch, default the
        largest exported bucket), serve_queue_limit (pending requests
        before 429, default 64), serve_timeout_ms (per-request
        deadline, default 30000), serve_dispatch_depth (batches in
        flight between the dispatch and completion threads, default
        2; 0 = serial dispatch), serve_warmup (default 1: pre-run
        every exported bucket at start so no user request eats a
        first-call compile), serve_access_log (default 0: one
        structured JSON line per request on stderr — method, path,
        status, request_id, wall ms; docs/observability.md).

        MESH-CARRYING artifacts (export_mesh=D[xM] at export time;
        docs/serving.md "sharded serving") serve through the same
        engines: the artifact's recorded mesh is realized on the
        local devices at load (a topology that cannot carry it fails
        with the expected vs available counts named), every dispatch
        stages its batch directly into the declared shards, and on a
        split-phase decoder the paged KV pool allocates per mesh
        slice. serve_mesh = D | DxM asserts the operator's intended
        topology against what the artifact carries (default 0 =
        accept the artifact as-is); serve_replicas > 1 rejects mesh
        artifacts (the mesh IS the scale-out — N replicas would
        contend for the same devices).

        A generate_step artifact (export_decode=step) serves through
        the CONTINUOUS-BATCHING engine instead (serve/continuous.py):
        paged KV pool, prefill/decode phase split, per-token SSE
        streaming on /generate ({"stream": true}). Its knobs:
        serve_stream (default 1; 0 returns 403 on stream requests),
        serve_prefill_split (default 1; 0 = coupled legacy scheduling
        for A/B measurement), serve_kv_dtype (auto|native|int8 —
        which exported cache-dtype rung to serve; int8 holds ~2x the
        KV state per pool byte, docs/serving.md rung table),
        serve_kv_blocks (default 0 = the whole
        exported pool; fewer pages = admission control without a
        re-export), serve_prefix_cache (default 1 = on when the
        artifact carries tail-prefill programs: cross-request
        copy-on-write KV page sharing keyed by a token-prefix trie,
        serve/prefixcache.py — a prompt extending a cached prefix
        skips straight to incremental tail prefill; 0 = off),
        serve_prefix_capacity_pages (trie page budget; default 0 =
        half the usable pool).

        serve_replicas = N (default 1) runs the resilient multi-
        replica topology instead: N supervised ServingEngine replicas
        (each its own artifact load + warmup) behind the SLO-aware
        router — failover with serve_max_retries (default 1) bounded
        retries, priority classes (serve_priority_default, default
        "normal"), deadline-aware shedding, graceful drain, and the
        POST /swap hot-artifact-swap endpoint (serve_swap = 0
        disables). Needs export_in (a live trainer cannot be
        replicated). Blocks until interrupted.

        Observability knobs (docs/observability.md): flight_events
        (default 65536; 0 disables) keeps an always-on bounded ring of
        trace events (obs/flight.py) that SLO incidents dump
        retroactively; attrib_events (default 8192; 0 disables) arms
        the goodput attribution ledger (obs/attrib.py) — GET
        /debug/attrib and the cxxnet_attrib_* series report the
        waste taxonomy; slo_p99_ms = T (0 = off) runs the burn-rate SLO
        engine (obs/slo.py) over the request-latency histogram —
        slo_target (default 0.99) the good fraction, slo_windows
        (default "60,5" seconds) the multi-window rule, incident dumps
        land in flight_dump_dir (default "flight"). With the engine on,
        GET /slo reports objectives/burn/incidents and /healthz carries
        the incident count."""
        from . import serving
        from .serve import ServingEngine
        from .serve.server import build_server
        d = dict(self.cfg)
        from .obs.registry import get_registry
        timeout_ms = float(d.get("serve_timeout_ms", "30000"))
        n_rep = int(d.get("serve_replicas", "1"))
        slo_ms = float(d.get("slo_p99_ms", "0"))
        engine_kw = dict(
            max_wait_ms=float(d.get("serve_max_wait_ms", "5")),
            max_batch=int(d.get("serve_max_batch", "0")) or None,
            queue_limit=int(d.get("serve_queue_limit", "64")),
            timeout_ms=timeout_ms,
            dispatch_depth=int(d.get("serve_dispatch_depth", "2")),
            slo_ms=slo_ms or None)
        # always-on flight recorder: negligible append cost, and any
        # SLO incident (or operator request) can dump the last N
        # seconds as a Chrome trace after the fact
        flight_events = int(d.get("flight_events", "65536"))
        flight = None
        if flight_events > 0:
            from .obs import trace as obs_trace
            from .obs.flight import FlightRecorder
            flight = self._flight = obs_trace.set_flight(
                FlightRecorder(flight_events))
        # always-on goodput attribution ledger: same contract as the
        # flight recorder (bench's armed serve p50 band is the cost
        # proof); GET /debug/attrib and cxxnet_attrib_* report it
        attrib_events = int(d.get("attrib_events", "8192"))
        if attrib_events > 0:
            from .obs import attrib as _attrib
            self._attrib = _attrib.enable(capacity=attrib_events)
        if n_rep > 1:
            if "export_in" not in d:
                raise RuntimeError(
                    "serve_replicas > 1 needs export_in=<artifact> "
                    "(each replica loads its own copy; a live trainer "
                    "cannot be replicated)")
            from .serve.replica import ReplicaSet
            from .serve.router import Router
            path = d["export_in"]
            meta_path = path + ".meta"
            _meta = {}
            if os.path.exists(meta_path):
                import json as _json
                with open(meta_path) as f:
                    _meta = _json.load(f)
                if _meta.get("kind") == "generate_step":
                    raise RuntimeError(
                        "serve_replicas > 1 does not support "
                        "generate_step artifacts: the continuous-"
                        "batching engine is single-replica (set "
                        "serve_replicas=1, or export a monolithic "
                        "decoder for the router topology)")
                if _meta.get("mesh"):
                    raise RuntimeError(
                        "serve_replicas > 1 does not support "
                        "mesh-carrying artifacts: every replica "
                        "would contend for the same %s mesh devices "
                        "— the mesh itself is the scale-out (one "
                        "engine serves every shard); set "
                        "serve_replicas=1, or export without "
                        "export_mesh for the router topology"
                        % (_meta["mesh"].get("shape"),))
            # the operator's serve_mesh assertion applies to the
            # router topology too (a mesh artifact was rejected just
            # above, so this catches the other direction: expecting a
            # mesh from an artifact that carries none)
            check_serve_mesh(d.get("serve_mesh", "").strip(),
                             _meta.get("mesh"), path)
            rs = ReplicaSet(
                lambda: serving.load_exported(path), n=n_rep,
                engine_kw=engine_kw, registry=get_registry(),
                version=os.path.basename(path))
            rs.start()
            backend = Router(
                rs,
                max_retries=int(d.get("serve_max_retries", "1")),
                timeout_ms=timeout_ms,
                default_priority=d.get("serve_priority_default",
                                       "normal"))
        else:
            if "export_in" in d:
                callee = serving.load_exported(d["export_in"])
            elif self.trainer is not None:
                callee = self.trainer
            else:
                raise RuntimeError(
                    "task=serve needs export_in=<artifact> or "
                    "model_in=<ckpt>")
            check_serve_mesh(
                d.get("serve_mesh", "").strip(),
                (getattr(callee, "meta", None) or {}).get("mesh"),
                d.get("export_in", "the live model"))
            if isinstance(callee, serving.ExportedStepDecoder):
                # a split-phase artifact serves through the
                # continuous-batching engine: paged KV pool, prefill/
                # decode split, per-token streaming (docs/serving.md)
                from .serve.continuous import ContinuousDecodeEngine
                backend = ContinuousDecodeEngine(
                    callee,
                    queue_limit=int(d.get("serve_queue_limit", "64")),
                    timeout_ms=timeout_ms,
                    prefill_split=bool(
                        int(d.get("serve_prefill_split", "1"))),
                    kv_blocks=int(d.get("serve_kv_blocks", "0")),
                    kv_dtype=d.get("serve_kv_dtype",
                                   "auto").strip() or "auto",
                    prefix_cache="auto" if int(
                        d.get("serve_prefix_cache", "1")) else False,
                    prefix_capacity_pages=int(
                        d.get("serve_prefix_capacity_pages", "0")),
                    slo_ms=slo_ms or None,
                    warmup=bool(int(d.get("serve_warmup", "1"))),
                    registry=get_registry())
            else:
                backend = ServingEngine(
                    callee,
                    warmup=bool(int(d.get("serve_warmup", "1"))),
                    # the process-global registry: /metrics?format=prom
                    # and a telemetry_port endpoint in the same process
                    # render one shared view
                    registry=get_registry(), **engine_kw)
        slo_eng = None
        if slo_ms > 0:
            from .obs.slo import (SLOEngine, availability_slo,
                                  latency_slo)
            windows = [float(x)
                       for x in d.get("slo_windows", "60,5").split(",")
                       if x.strip()]
            slo_eng = SLOEngine(
                get_registry(),
                [latency_slo(slo_ms,
                             float(d.get("slo_target", "0.99"))),
                 availability_slo()],
                windows_s=windows or (60.0, 5.0), flight=flight,
                dump_dir=d.get("flight_dump_dir", "flight"))
            self._slo = slo_eng
            slo_eng.start(period_s=max(min(windows or [5.0]) / 4.0,
                                       0.25))
            if self._telemetry is not None:
                # the telemetry endpoint (started before the task ran)
                # gains /slo + the healthz incident count too
                self._telemetry.slo = slo_eng
        srv = build_server(
            backend, d.get("serve_host", "127.0.0.1"),
            int(d.get("serve_port", "8080")),
            # 0 disables the deadline engine-side; the handler's result
            # wait must then be unbounded too, not an instant 504
            request_timeout=(timeout_ms / 1000.0 if timeout_ms > 0
                             else None),
            verbose=not self.silent,
            access_log=bool(int(d.get("serve_access_log", "0"))),
            allow_swap=bool(int(d.get("serve_swap", "1"))),
            allow_stream=bool(int(d.get("serve_stream", "1"))),
            slo=slo_eng)
        host, port = srv.server_address[:2]
        if not self.silent:
            print("serving %s on http://%s:%d (buckets %s, "
                  "max_wait %gms, queue %d, dispatch_depth %s%s)"
                  % (backend.kind, host, port,
                     ",".join(map(str, backend.buckets)),
                     engine_kw["max_wait_ms"],
                     engine_kw["queue_limit"],
                     backend.dispatch_depth,
                     ", replicas %d" % n_rep if n_rep > 1 else ""))
            sys.stdout.flush()
        try:
            srv.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            # slo/flight teardown lives in run()'s finally (it must
            # also cover setup failures before this point)
            srv.server_close()
            backend.close()

    def task_extract(self) -> None:
        """Reference: cxxnet_main.cpp:284-343."""
        assert self.itr_pred is not None, \
            "must specify a pred iterator for feature extraction"
        if not self.extract_node_name:
            raise RuntimeError(
                "extract node name must be specified in task extract")
        print("start predicting...")
        nrow = 0
        dshape = None
        mode = "w" if self.output_format else "wb"
        with open(self.name_pred, mode) as fo:
            self.itr_pred.before_first()
            while self.itr_pred.next():
                batch = self.itr_pred.value
                feat = self.trainer.extract_feature(
                    batch, self.extract_node_name)
                sz = batch.batch_size - batch.num_batch_padd
                nrow += sz
                for j in range(sz):
                    row = feat[j].reshape(-1)
                    if self.output_format:
                        fo.write(" ".join("%g" % v for v in row) + " \n")
                    else:
                        row.astype(np.float32).tofile(fo)
                if sz:
                    dshape = feat[0].shape
        with open(self.name_pred + ".meta", "w") as fm:
            fm.write("%d,%d,%d,%d\n" % ((nrow,) + tuple(dshape)))
        print("finished prediction, write into %s" % self.name_pred)


def main(argv: Optional[List[str]] = None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    from .parallel import place_compile_cache
    place_compile_cache()
    return LearnTask().run(argv)
