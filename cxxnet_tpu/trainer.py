"""Trainer: the INetTrainer surface over one jit-compiled sharded step.

The reference CXXNetThreadTrainer (reference: src/nnet/nnet_impl-inl.hpp:16-455)
splits each batch over per-device worker threads and syncs grads through a
parameter server. Here there is exactly one program: a jitted
fwd+bwd+update step over a device mesh; the batch is sharded on the data
axis, parameters are replicated, and XLA emits the ICI all-reduce.
``update_period`` gradient accumulation is preserved
(nnet_impl-inl.hpp:149-150,181-184): the step accumulates into a grad
buffer and applies the updaters every k-th call.
"""

from __future__ import annotations

import collections
import os
import sys
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import parallel
from .graph import NetConfig
from .io import DataBatch, DataIterator
from .metrics import MetricSet
from .model import Network
from .obs import trace as _trace
from .updater import NetUpdater, UpdaterHyperParams

ConfigEntry = Tuple[str, str]


class StagedBatch:
    """A batch whose host->device transfer has been issued (Trainer.stage).

    ``fused`` > 0 marks a STACKED group of that many batches staged as
    one transfer (Trainer.stage_fused); its device fields carry a
    leading group axis. ``step`` is the batch's ordinal in its round
    where a feed staged it (io/prefetch.py): the one identifier the
    ``feed.stage``, ``feed.get`` and ``trainer.update`` spans of a
    batch share across the two threads."""

    __slots__ = ("device", "host", "fused", "step")

    def __init__(self, device, host: DataBatch, fused: int = 0) -> None:
        self.device = device
        self.host = host
        self.fused = fused
        self.step = None


class GroupStager:
    """Incrementally assemble a fuse_steps group in preallocated
    stacked host buffers, then ship it as ONE transfer.

    ``add(batch)`` copies the batch's fields into the next slot AT CALL
    TIME, so iterators that reuse their buffers across next() are safe
    (the reason the CLI cannot call stage_fused directly). ``stage()``
    issues the single put for a full group; ``flush()`` stages a
    partial tail per-slot for the per-step path. The caller must not
    refill a stager while its staged transfer may still be reading the
    buffers — rotate two stagers and consume one's StagedBatch (e.g.
    dispatch it) before adding to it again, as the CLI loop does."""

    def __init__(self, trainer: "Trainer") -> None:
        self.tr = trainer
        self.k = trainer.fuse_steps
        self.n = 0
        self._bufs = None

    def add(self, batch: DataBatch) -> None:
        if self.n >= self.k:
            raise RuntimeError("GroupStager is full; stage() it first")
        tr = self.tr
        tr._maybe_set_norm(batch)
        data, extras, labels = tr._host_fields(batch)
        if self._bufs is None:
            def alloc(a):
                return np.empty((self.k,) + a.shape, a.dtype)
            self._bufs = (alloc(data), tuple(alloc(e) for e in extras),
                          [alloc(l) for l in labels])
        d, es, ls = self._bufs
        d[self.n] = data
        for buf, e in zip(es, extras):
            buf[self.n] = e
        for buf, l in zip(ls, labels):
            buf[self.n] = l
        self.n += 1

    @property
    def full(self) -> bool:
        return self.n >= self.k

    def stage(self) -> "StagedBatch":
        """One put for the full group; resets the fill counter."""
        if not self.full:
            raise RuntimeError(
                "GroupStager.stage needs %d batches, has %d (use "
                "flush() for a partial tail)" % (self.k, self.n))
        with _trace.span("trainer.stage_group", "h2d"):
            d, es, ls = self._bufs
            out = self.tr._put_group(d, es, ls)
            # device_put is async: wait for the transfer so the caller
            # may refill these host buffers the moment this returns
            # (stage runs on the CLI's helper thread, so blocking here
            # IS the overlap)
            jax.block_until_ready(out.device)
            self.n = 0
            return out

    def flush(self) -> List["StagedBatch"]:
        """Stage a partial tail: one per-batch StagedBatch per slot."""
        d, es, ls = self._bufs if self._bufs else (None, (), [])
        out = []
        for j in range(self.n):
            dev = self.tr._put_fields(
                d[j], tuple(e[j] for e in es), [l[j] for l in ls])
            out.append(StagedBatch(dev, None))
        if out:
            jax.block_until_ready([s.device for s in out])  # reusable
        self.n = 0
        return out


def _placed(attr: str, shardings: str) -> property:
    """A tree of the train state, committed on assignment to the
    shardings the steps were jitted with (a device array is re-wrapped,
    not copied). A jit keys its build on whether a donated input is
    committed, and a step's outputs are: any other first input costs a
    build at step 2. The steps store their placed outputs to ``attr``."""
    def assign(self, tree):
        if tree is not None:
            tree = jax.device_put(tree, getattr(self, shardings))
        setattr(self, attr, tree)
    return property(lambda self: getattr(self, attr), assign)


class Trainer:
    """Config-driven trainer; mirrors the INetTrainer contract
    (reference: src/nnet/nnet.h:18-92)."""

    params = _placed("_params", "_psh")
    opt_state = _placed("_opt_state", "_osh")

    def __init__(self) -> None:
        self.cfg: List[ConfigEntry] = []
        self.batch_size = 100
        self.update_period = 1
        self.fuse_steps = 1
        # unroll 2 measured as fast as single-dispatch in quiet windows
        # (unroll 1 pays ~2.5% scan-loop overhead on AlexNet; 8 buys
        # nothing more and compiles 4x longer) — see docs/performance.md
        self.fuse_unroll = 2
        # 1: fused groups (train via CLI, eval here) also ship as ONE
        # stacked transfer per group; 0: per-batch staging everywhere
        self.group_staging = 1
        # 1: the jitted train steps DONATE their input-data buffers
        # (data/extras/labels), letting XLA reuse that HBM for
        # activations — right for a feed that stages every batch fresh
        # (the CLI's device-prefetch loop turns it on). 0 (default):
        # inputs stay live after dispatch, so a staged batch may be
        # dispatched repeatedly (tools/perf_lab.py and the tests cycle
        # a fixed staged set)
        self.donate_inputs = 0
        self.eval_train = 1
        self.seed = 0
        self.silent = 0
        # strict=1 turns the unconsumed-config-key report into an error
        self.strict = 0
        # no dev key = JAX's default backend, whatever it is; a dev key
        # that names a platform the process lacks is an error
        # (parallel.select_devices)
        self.dev: Optional[str] = None
        self.compute_dtype = "float32"
        self.model_parallel = 1
        self.seq_parallel = 1
        self.pipeline_parallel = 1
        self.zero = 0
        self.test_on_server = 0
        self.nan_guard = 0
        self.save_async = 0
        self.save_sharded = 0
        self.epoch_counter = 0
        self.sample_counter = 0
        self.round = 0
        self.metric = MetricSet()
        self.train_metric = MetricSet()
        self.eval_nodes: List[Tuple[str, int]] = []
        self.net_cfg: Optional[NetConfig] = None
        self.net: Optional[Network] = None
        self._psh = self._osh = None
        self.params = self.opt_state = self.grad_accum = None
        self.last_loss = None
        # (step number, {(layer, name): device array}) of the steps whose
        # layer counters have not been read yet (Trainer._drain_stats)
        self._stats_flight = collections.deque()
        self._step_count = 0
        self._step_specs = None
        self._train_multi = None
        self._eval_multi = None
        self._forward_multi = None
        self._eval_gs = None
        self._gen_cache: Dict = {}
        self.decode_layout = "auto"
        self.decode_kv = "native"

    # keys the trainer itself consumes (set_param branches below plus
    # ones read from self.cfg later: dist_*, updater routing); the
    # unconsumed-key audit subtracts these
    TRAINER_KEYS = frozenset([
        "batch_size", "update_period", "fuse_steps", "fuse_unroll",
        "group_staging", "donate_inputs", "eval_train", "train_eval",
        "seed", "silent",
        "dev", "dtype",
        "model_parallel", "seq_parallel", "pipeline_parallel", "zero",
        "test_on_server", "nan_guard", "save_async", "save_sharded",
        "strict", "metric", "updater", "sync", "decode_layout",
        "decode_kv",
        "dist_coordinator", "dist_num_worker", "dist_worker_rank",
    ])
    # structural keys NetConfig.configure consumes (graph.py)
    STRUCTURAL_KEYS = frozenset([
        "netconfig", "input_shape", "extra_data_num", "label_width",
    ])
    STRUCTURAL_PREFIXES = ("layer[", "label_vec[", "extra_data_shape[",
                           "metric[")

    # ------------------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        """Config broadcast (reference: nnet_impl-inl.hpp:31-69)."""
        if val == "default":
            return
        if name == "strict":
            self.strict = int(val)
        elif name == "batch_size":
            self.batch_size = int(val)
        elif name == "update_period":
            self.update_period = int(val)
        elif name == "fuse_steps":
            self.fuse_steps = int(val)
        elif name == "fuse_unroll":
            self.fuse_unroll = int(val)
        elif name == "group_staging":
            self.group_staging = int(val)
        elif name == "donate_inputs":
            self.donate_inputs = int(val)
        elif name in ("eval_train", "train_eval"):
            # "train_eval" appears in the reference's own MNIST.conf but
            # its parser only reads eval_train (nnet_impl-inl.hpp:54) —
            # a latent upstream typo this rebuild's unconsumed-key audit
            # surfaced; honored here as the alias the author intended
            self.eval_train = int(val)
        elif name == "seed":
            self.seed = int(val)
        elif name == "silent":
            self.silent = int(val)
        elif name == "dev":
            self.dev = val
        elif name == "dtype":
            self.compute_dtype = val
        elif name == "model_parallel":
            self.model_parallel = int(val)
        elif name == "seq_parallel":
            self.seq_parallel = int(val)
        elif name == "pipeline_parallel":
            self.pipeline_parallel = int(val)
        elif name == "zero":
            self.zero = int(val)
        elif name == "test_on_server":
            self.test_on_server = int(val)
        elif name == "nan_guard":
            self.nan_guard = int(val)
        elif name == "save_async":
            self.save_async = int(val)
        elif name == "save_sharded":
            self.save_sharded = int(val)
        elif name == "decode_layout":
            if val not in ("auto", "slot", "slott", "slotk",
                           "blend"):
                raise ValueError("decode_layout must be "
                                 "auto|slot|slott|slotk|blend")
            self.decode_layout = val
        elif name == "decode_kv":
            if val not in ("native", "int8"):
                raise ValueError("decode_kv must be native|int8")
            self.decode_kv = val
        if name.startswith("metric"):
            import re
            m = re.match(r"metric\[([^,\]]+),([^\]]+)\]", name)
            if m:
                self.metric.add_metric(val, m.group(1))
                self.train_metric.add_metric(val, m.group(1))
                self.eval_nodes.append((m.group(2), 0))
            else:
                m2 = re.match(r"metric\[([^,\]]+)\]", name)
                field = m2.group(1) if m2 else "label"
                self.metric.add_metric(val, field)
                self.train_metric.add_metric(val, field)
                self.eval_nodes.append(("", -1))
        self.cfg.append((name, val))

    # ------------------------------------------------------------------
    def init_model(self) -> None:
        """Parse structure, init params, build jitted steps
        (reference: nnet_impl-inl.hpp:70-81,339-390)."""
        with _trace.phase("trainer.init", "train"):
            self._init_model()

    def _init_model(self) -> None:
        self.net_cfg = NetConfig()
        self.net_cfg.configure(self.cfg)
        self._build_network()
        rng = jax.random.PRNGKey(self.seed)
        opt = NetUpdater(self.net)

        def make(rng):
            params = self.net.init_params(rng)
            return params, opt.init_state(params)
        try:
            # one compiled program instead of an eager per-op compile
            # storm (a ~60M-param net pays ~35 tiny compiles ≈ 30s of
            # startup on a 1-core host when run eagerly)
            params, opt_state = jax.jit(make)(rng)
        except (jax.errors.JAXTypeError, TypeError):
            # a user layer's init may be untraceable (host-side file
            # reads, tracer->numpy conversions) — eager init is always
            # correct, just slower
            params, opt_state = make(rng)
        self._finish_init(params, opt, opt_state)

    # ------------------------------------------------------------------
    def unconsumed_keys(self, extra_known=()) -> list:
        """Config keys NO component consumed — the typo detector the
        reference's broadcast-and-ignore SetParam lacks (reference:
        neural_net-inl.hpp:252-264; a silently ignored
        ``warmup_epochs=100`` corrupted a recorded r3 convergence run).

        Call after init_model. A key counts as consumed if the trainer,
        the updater family (UpdaterParam.claims — tag scoping and the
        lr:/eta: schedule keys included), the netconfig structure
        parser, or AT LEAST ONE layer recognized it (per-layer ledger:
        keys a layer saw minus its LayerParam.unknown_keys terminal).
        ``extra_known`` extends the claimed set with caller-level keys
        (the CLI passes its task/io keys). The CLI prints the result
        once; ``strict = 1`` makes it fatal there."""
        names = {k for k, _ in self.cfg}
        claimed = set(self.TRAINER_KEYS) | set(self.STRUCTURAL_KEYS)
        claimed |= set(extra_known)
        for mod in getattr(self.net, "modules", []):
            passed = getattr(mod, "_cfg_keys", set())
            claimed |= passed - mod.param.unknown_keys
        out = []
        for k in sorted(names - claimed):
            if k.startswith(self.STRUCTURAL_PREFIXES):
                continue
            if UpdaterHyperParams.claims(k):
                continue
            out.append(k)
        return out

    def _build_network(self) -> None:
        # batch_size is per-process, like the reference's per-worker batch
        # in dist-PS mode (same config file on every worker); the jitted
        # step sees the global batch
        self.global_batch = self.batch_size * jax.process_count()
        self.net = Network(self.net_cfg, self.global_batch,
                           update_period=self.update_period,
                           compute_dtype=self.compute_dtype)
        # device mesh (replaces InitParamServer + per-device threads)
        devices = parallel.select_devices(self.dev)
        mp = self.model_parallel
        sp = self.seq_parallel
        pp = self.pipeline_parallel
        inner = mp * sp * pp
        if len(devices) % inner != 0:
            raise ValueError(
                "model_parallel=%d * seq_parallel=%d * pipeline_parallel"
                "=%d does not divide %d devices"
                % (mp, sp, pp, len(devices)))
        if jax.process_count() > 1:
            # trimming devices could orphan a whole process's chips;
            # require an even split instead, with data shards aligned to
            # process boundaries so each process feeds exactly its rows
            dp = len(devices) // inner
            if self.global_batch % dp != 0:
                raise ValueError(
                    "global batch %d not divisible over %d data-parallel "
                    "devices" % (self.global_batch, dp))
            if dp % jax.process_count() != 0:
                raise ValueError(
                    "data-parallel degree %d must be a multiple of the "
                    "process count %d (shrink model_parallel)"
                    % (dp, jax.process_count()))
            ndev = len(devices)
        else:
            ndata = parallel.fit_devices_to_batch(
                len(devices) // inner, self.global_batch)
            ndev = ndata * inner
            if ndev != len(devices) and self.silent == 0:
                print("Warning: using %d of %d devices to split "
                      "batch_size=%d" % (ndev, len(devices), self.batch_size))
        self.mesh = parallel.make_mesh(devices[:ndev], model_parallel=mp,
                                       seq_parallel=sp,
                                       pipeline_parallel=pp)
        self.n_devices = ndev
        # the platform the step's jit actually targets — may differ from
        # the process default backend (dev=cpu on a TPU-default box)
        self.net.platform = devices[0].platform
        if ndev > 1:
            # layers need the mesh wherever one exists: sequence and
            # pipeline parallelism build their shard_maps from it, and
            # every Pallas kernel runs per shard (pallas_env.per_shard)
            self.net.mesh = self.mesh
        if sp > 1:
            self.net.seq_axis = parallel.SEQ_AXIS
        # resolve eval node requests (reference nnet_impl-inl.hpp:363-374)
        self.eval_req: List[int] = []
        for name, kind in self.eval_nodes:
            if kind < 0:
                self.eval_req.append(self.net.out_node)
            else:
                if name not in self.net_cfg.node_name_map:
                    raise ValueError("Cannot find node name: %s" % name)
                self.eval_req.append(self.net_cfg.node_name_map[name])
        if not self.eval_req:
            self.eval_req = [self.net.out_node]

    def _param_shardings(self, params):
        """Per-tensor placement: replicated on a 1D mesh, tensor-parallel
        over the model axis on a 2D mesh (parallel.param_sharding); with
        ``zero = 3`` the parameters themselves additionally shard over
        the data axis (FSDP — GSPMD all-gathers each weight where used
        and reduce-scatters its gradient)."""
        out = []
        for li, p in enumerate(params):
            if p is None:
                out.append(None)
                continue
            ltype = self.net_cfg.layers[li].type
            sh = {}
            for tag, w in p.items():
                s = parallel.param_sharding(
                    self.mesh, ltype, tag, tuple(np.shape(w)))
                if self.zero >= 3:
                    s = parallel.zero_sharding(
                        self.mesh, s, tuple(np.shape(w)))
                sh[tag] = s
            out.append(sh)
        return out

    def _finish_init(self, params, opt, opt_state) -> None:
        self.opt = opt
        rep = parallel.replicated(self.mesh)
        dsh = parallel.batch_sharding(self.mesh)
        # input node: additionally sharded over the seq axis when present
        xsh = parallel.input_sharding(self.mesh, self.net.node_shapes[0])
        psh = self._param_shardings(params)
        # optimizer slots shard like their weights; with zero=1 they
        # additionally shard over the data axis (ZeRO-1,
        # parallel.zero_sharding)
        def slot_sharding(li, tag):
            base = psh[li][tag]
            if not self.zero:
                return base
            return parallel.zero_sharding(
                self.mesh, base, tuple(np.shape(params[li][tag])))
        osh = [None if s is None else
               {tag: {slot: slot_sharding(li, tag) for slot in slots}
                for tag, slots in s.items()}
               for li, s in enumerate(opt_state)]
        self._psh, self._osh, self._dsh, self._xsh = psh, osh, dsh, xsh
        self.params, self.opt_state = params, opt_state
        gsh = [s or {} for s in psh]  # grad tree shardings (None -> {})
        if self.zero >= 2:
            # ZeRO-2: the gradient-accumulation buffers shard over the
            # data axis too (each accum step becomes a reduce-scatter
            # into the local shard); no-op at zero=3 where the params —
            # and hence gsh — are already data-sharded
            gsh = [{tag: parallel.zero_sharding(
                        self.mesh, s, tuple(np.shape(params[li][tag])))
                    for tag, s in d.items()} if d else {}
                   for li, d in enumerate(gsh)]
        if self.update_period > 1:
            zeros = jax.tree.map(jnp.zeros_like, _strip_nones(self.params))
            self.grad_accum = jax.device_put(zeros, gsh)
        # rng + epoch live ON DEVICE and are carried (donated) through the
        # step: a host-side fold_in / scalar upload would cost an extra
        # dispatch and a small transfer per step, for nothing
        self._rng = jax.device_put(
            jax.random.PRNGKey(self.seed * 2243 + 7), rep)
        # int32: float32 +1 would freeze at 2^24 updates
        self._epoch_dev = jax.device_put(
            jnp.asarray(self.epoch_counter, jnp.int32), rep)

        net, opt_ = self.net, self.opt
        eval_req = tuple(self.eval_req)

        # device-side metric accumulation: a (n_metrics, 2) (sum, cnt)
        # buffer rides the step and is fetched ONCE per round, replacing
        # the reference's per-batch score copy-off (nnet_impl-inl.hpp:174)
        self._use_dev_metric = (self.eval_train != 0
                                and bool(self.train_metric.evals))
        gbatch = self.global_batch
        label_names = dict(self.net_cfg.label_name_map)

        def metric_stats(metric_set, evals, labels, mask):
            lab = {name: labels[idx] for name, idx in label_names.items()}
            preds = [e.reshape(e.shape[0], -1) for e in evals]
            return metric_set.device_stats(preds, lab, mask)

        nan_guard = self.nan_guard != 0

        def fold_train_metric(maccum, evals, labels, loss):
            rows = []
            if self._use_dev_metric:
                mask = jnp.ones((gbatch,), jnp.float32)
                rows.append(metric_stats(self.train_metric, evals,
                                         labels, mask))
            if nan_guard:
                # an extra (nan-steps, steps) row so the watchdog works
                # even with eval_train=0 / no train metric configured
                isnan = jnp.isnan(loss).astype(jnp.float32)
                rows.append(jnp.stack([isnan, jnp.asarray(1.0)])[None, :])
            if not rows:
                return maccum
            return MetricSet.device_fold(maccum, jnp.concatenate(rows))

        nrows = (len(self.train_metric.evals)
                 if self._use_dev_metric else 0) + (1 if nan_guard else 0)
        self._maccum_zero = np.zeros((nrows, 2, 2), np.float32)
        self._maccum = jax.device_put(jnp.asarray(self._maccum_zero), rep)
        self._eaccum_zero = self.metric.accum_zero()

        def fwd_bwd(params, data, extras, labels, rng, epoch):
            def loss_fn(p):
                supd, seen = {}, {}
                values, loss = net.apply(
                    p, data, extra_data=extras, labels=labels, train=True,
                    rng=rng, epoch=epoch, state_out=supd, stats_out=seen)
                return loss, (tuple(values[i] for i in eval_req), supd,
                              seen)
            (loss, (evals, supd, seen)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return loss, evals, supd, grads, seen

        def train_step(params, opt_state, rng, epoch, maccum,
                       data, extras, labels):
            use, nxt = jax.random.split(rng)
            loss, evals, supd, grads, stats = fwd_bwd(
                params, data, extras, labels, use, epoch)
            grads = _strip_nones(grads)
            params2, opt2 = opt_.apply(params, grads, opt_state, epoch)
            params2 = _merge_state(params2, supd)
            maccum = fold_train_metric(maccum, evals, labels, loss)
            # the layers' device-side counters ride out with the loss
            # (an empty dict for a net that has none: the same program)
            return params2, opt2, nxt, epoch + 1, maccum, loss, stats

        def accum_step(grad_accum, rng, maccum, params, epoch,
                       data, extras, labels):
            use, nxt = jax.random.split(rng)
            loss, evals, supd, grads, _ = fwd_bwd(params, data, extras,
                                                  labels, use, epoch)
            grads = _strip_nones(grads)
            acc = jax.tree.map(jnp.add, grad_accum, grads)
            maccum = fold_train_metric(maccum, evals, labels, loss)
            # state writes (small vectors) surface as outputs; the host
            # folds them into self.params since params aren't an output
            # of the accumulation-only step
            return acc, nxt, maccum, loss, supd

        def eval_step(params, eaccum, data, extras, labels, mask):
            # mask is built host-side per process (each process's padding
            # sits at its LOCAL tail, so no global index threshold works
            # multi-host) and ships sharded like the labels
            values, _ = net.apply(params, data, extra_data=extras,
                                  train=False)
            evals = tuple(values[i] for i in eval_req)
            stats = metric_stats(self.metric, evals, labels, mask)
            return MetricSet.device_fold(eaccum, stats)

        def apply_accum(params, opt_state, grad_accum, epoch):
            params2, opt2 = opt_.apply(params, grad_accum, opt_state, epoch)
            zeros = jax.tree.map(jnp.zeros_like, grad_accum)
            return params2, opt2, zeros, epoch + 1

        def forward_step(params, data, extras, node_ids):
            values, _ = net.apply(params, data, extra_data=extras,
                                  train=False)
            return tuple(values[i] for i in node_ids)

        # donate_inputs: the data args sit at positions 5-7 in BOTH
        # per-step programs (and in the fused multi-step below) — with
        # the device-prefetch feed every staged batch is dispatched
        # exactly once, so its buffer can be handed straight to XLA.
        # Donation is input-output aliasing: where no step output
        # matches a data arg's shape/dtype XLA cannot use the gift and
        # jax emits an advisory per compile — expected here (the win is
        # exactly the cases that DO alias, e.g. f32 data matching an
        # activation-shaped output), so that one advisory is silenced
        don_data = (5, 6, 7) if self.donate_inputs else ()
        if self.donate_inputs:
            # process-global by nature (warnings has no narrower scope
            # that survives jit tracing). Re-checked per init rather
            # than once-flagged: a warnings.catch_warnings context
            # (pytest wraps every test in one) pops the installed
            # filter, so presence in warnings.filters — not a module
            # flag — is the idempotence test.
            import warnings
            msg = "Some donated buffers were not usable"
            if not any(getattr(f[1], "pattern", None) == msg
                       for f in warnings.filters):
                warnings.filterwarnings("ignore", message=msg)
        # out_shardings pin params/opt-state to their declared placement:
        # without them XLA's sharding propagation may reshard an output
        # (e.g. over the seq axis), desyncing from in_shardings next step
        #
        # every donating step goes through the jitcheck donation seam
        # (docs/analysis.md): disabled (the default) make_donating
        # returns the jitted callable untouched; under the monitor a
        # donated-then-reused buffer raises an immediate DonationError
        # naming this site instead of jax's deferred buffer-deleted.
        # every step ALSO goes through the shardcheck reshard seam
        # with the same in_shardings handed to jax.jit: armed, a
        # caller whose argument placement would force an implicit
        # reshard gets an attributed ReshardError instead of a silent
        # per-step all-gather
        from .analysis import jitcheck as _jitcheck
        from .analysis import shardcheck as _shardcheck
        in_train = (psh, osh, rep, rep, rep, xsh, dsh, dsh)
        self._train_step = _shardcheck.make_sharded(
            _jitcheck.make_donating(jax.jit(
                train_step, donate_argnums=(0, 1, 2, 3, 4) + don_data,
                in_shardings=in_train,
                out_shardings=(psh, osh, rep, rep, rep, None, None)),
                argnums=(0, 1, 2, 3, 4) + don_data,
                site="Trainer._train_step"),
            in_shardings=in_train, site="Trainer._train_step")
        # state writes fold back into self.params host-side, so their
        # output shardings must match the params' declared placement
        ssh = {(li, tag): psh[li][tag]
               for li, mod in enumerate(net.modules)
               for tag in getattr(mod, "state_tags", ())
               if psh[li] and tag in psh[li]}
        in_accum = (gsh, rep, rep, psh, rep, xsh, dsh, dsh)
        self._accum_step = _shardcheck.make_sharded(
            _jitcheck.make_donating(jax.jit(
                accum_step, donate_argnums=(0, 1, 2) + don_data,
                in_shardings=in_accum,
                out_shardings=(gsh, rep, rep, None, ssh)),
                argnums=(0, 1, 2) + don_data,
                site="Trainer._accum_step"),
            in_shardings=in_accum, site="Trainer._accum_step")
        in_eval = (psh, rep, xsh, dsh, dsh, dsh)
        self._eval_step = _shardcheck.make_sharded(
            _jitcheck.make_donating(jax.jit(
                eval_step, donate_argnums=(1,),
                in_shardings=in_eval, out_shardings=rep),
                argnums=(1,), site="Trainer._eval_step"),
            in_shardings=in_eval, site="Trainer._eval_step")
        in_apply = (psh, osh, gsh, rep)
        self._apply_accum = _shardcheck.make_sharded(
            _jitcheck.make_donating(jax.jit(
                apply_accum, donate_argnums=(0, 1, 2, 3),
                in_shardings=in_apply,
                out_shardings=(psh, osh, gsh, rep)),
                argnums=(0, 1, 2, 3), site="Trainer._apply_accum"),
            in_shardings=in_apply, site="Trainer._apply_accum")
        self._forward = jax.jit(
            forward_step, in_shardings=(psh, xsh, dsh),
            static_argnums=(3,))

        if self.fuse_steps > 1:
            if self.fuse_steps % self.update_period != 0:
                raise ValueError(
                    "fuse_steps (%d) must be a multiple of update_period "
                    "(%d): each fused dispatch carries whole "
                    "accumulation windows so the gradient buffer is "
                    "always zero at group boundaries"
                    % (self.fuse_steps, self.update_period))
            if jax.process_count() > 1:
                raise ValueError(
                    "fuse_steps > 1 is single-process: the stacked group "
                    "transfer has no multi-host batch assembly (and a "
                    "local chip has no dispatch floor to amortize)")

            period = self.update_period
            unroll = max(1, min(self.fuse_unroll, self.fuse_steps))

            def train_multi(params, opt_state, rng, epoch, maccum,
                            data_s, extras_s, labels_s):
                # lax.scan the SAME train_step over a stacked (K, ...)
                # group: K optimizer steps, metric folds and rng
                # advances — identical math to K update() calls
                # (test_fuse_steps pins the trajectories equal) — in
                # ONE host dispatch. Amortizes the host's per-dispatch
                # cost over K steps, which matters when the step is
                # short against it (not measured on the chip).
                def body(carry, x):
                    p, o, r, e, m = carry
                    # (the layers' counters stay inside a fused group)
                    p, o, r, e, m, loss, _ = train_step(p, o, r, e, m,
                                                        *x)
                    return (p, o, r, e, m), loss

                # fuse_unroll > 1 unrolls the scan body: the group
                # becomes straight-line XLA, free to overlap one step's
                # tail with the next one's input convert — a boundary
                # back-to-back dispatched programs cannot cross.
                # Costs compile time proportional to the unroll factor.
                (params, opt_state, rng, epoch, maccum), losses = \
                    jax.lax.scan(
                        body, (params, opt_state, rng, epoch, maccum),
                        (data_s, extras_s, labels_s), unroll=unroll)
                return params, opt_state, rng, epoch, maccum, losses[-1]

            def train_multi_accum(params, opt_state, rng, epoch, maccum,
                                  data_s, extras_s, labels_s):
                # fuse_steps composed with update_period (VERDICT r3
                # #6): the (K, ...) group regroups into K/P whole
                # accumulation windows; each macro iteration runs P
                # accumulate-only micro-steps (grads summed, BN state
                # merged, metric folded — the exact _accum_step math)
                # then one optimizer apply. Static structure: no
                # traced cond, and the gradient buffer is born zero
                # inside the trace, so groups stay independent.
                kp = self.fuse_steps // period

                def regroup(t):
                    return jax.tree.map(
                        lambda x: x.reshape((kp, period) + x.shape[1:]),
                        t)

                def macro(carry, x):
                    p, o, r, e, m = carry
                    ga = jax.tree.map(jnp.zeros_like, _strip_nones(p))

                    def micro(c2, x2):
                        ga2, r2, m2, p2 = c2
                        ga2, r2, m2, loss, supd = accum_step(
                            ga2, r2, m2, p2, e, *x2)
                        return (ga2, r2, m2,
                                _merge_state(p2, supd)), loss

                    (ga, r, m, p), losses = jax.lax.scan(
                        micro, (ga, r, m, p), x,
                        unroll=max(1, min(self.fuse_unroll, period)))
                    p, o, ga, e = apply_accum(p, o, ga, e)
                    return (p, o, r, e, m), losses[-1]

                (params, opt_state, rng, epoch, maccum), losses = \
                    jax.lax.scan(
                        macro, (params, opt_state, rng, epoch, maccum),
                        (regroup(data_s), regroup(extras_s),
                         regroup(labels_s)))
                return params, opt_state, rng, epoch, maccum, losses[-1]

            if period > 1:
                train_multi = train_multi_accum

            xsh_s = parallel.stacked_sharding(xsh)
            dsh_s = parallel.stacked_sharding(dsh)
            # data args are NOT donated by default: a group staged once
            # may legally be dispatched again (callers that cycle a
            # fixed staged set); donate_inputs=1 (the single-dispatch
            # device-prefetch feed) hands the group's HBM to XLA
            in_multi = (psh, osh, rep, rep, rep, xsh_s, dsh_s, dsh_s)
            self._train_multi = _shardcheck.make_sharded(
                _jitcheck.make_donating(jax.jit(
                    train_multi,
                    donate_argnums=(0, 1, 2, 3, 4) + don_data,
                    in_shardings=in_multi,
                    out_shardings=(psh, osh, rep, rep, rep, None)),
                    argnums=(0, 1, 2, 3, 4) + don_data,
                    site="Trainer._train_multi"),
                in_shardings=in_multi, site="Trainer._train_multi")

            def eval_multi(params, eaccum, data_s, extras_s, labels_s,
                           mask_s):
                # the eval stream fused the same way: one dispatch per
                # K eval batches, metric stats folding through the
                # scan carry (padding masks ride per batch)
                def body(acc, x):
                    data, extras, labels, mask = x
                    return eval_step(params, acc, data, extras,
                                     labels, mask), None

                eaccum, _ = jax.lax.scan(
                    body, eaccum,
                    (data_s, extras_s, labels_s, mask_s),
                    unroll=max(1, min(self.fuse_unroll,
                                      self.fuse_steps)))
                return eaccum

            in_emulti = (psh, rep, xsh_s, dsh_s, dsh_s, dsh_s)
            self._eval_multi = _shardcheck.make_sharded(
                _jitcheck.make_donating(jax.jit(
                    eval_multi, donate_argnums=(1,),
                    in_shardings=in_emulti, out_shardings=rep),
                    argnums=(1,), site="Trainer._eval_multi"),
                in_shardings=in_emulti, site="Trainer._eval_multi")

            def forward_multi(params, data_s, extras_s, node_ids):
                # the prediction stream fused the same way: one
                # dispatch (and one D2H fetch) per K batches
                def body(_, x):
                    data, extras = x
                    return None, forward_step(params, data, extras,
                                              node_ids)

                _, outs = jax.lax.scan(
                    body, None, (data_s, extras_s),
                    unroll=max(1, min(self.fuse_unroll,
                                      self.fuse_steps)))
                return outs

            self._forward_multi = jax.jit(
                forward_multi, in_shardings=(psh, xsh_s, dsh_s),
                static_argnums=(3,))

    # ------------------------------------------------------------------
    def _put_data(self, arr, sharding=None) -> jnp.ndarray:
        """Host array -> device array under the batch sharding. Multi-host:
        each process holds its local shard of the global batch, so assemble
        a global jax.Array (the PS-era per-worker data sharding,
        reference iter_thread_imbin-inl.hpp:199-219, maps to per-process
        local data here)."""
        arr = np.asarray(arr)
        if arr.dtype != np.uint8:   # raw-pixel batches stay 1 byte/px
            arr = np.asarray(arr, np.float32)
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(
                sharding or self._dsh, arr)
        return jnp.asarray(arr)

    def _fetch_local(self, x) -> np.ndarray:
        """Device array -> host numpy. Multi-host: a batch-sharded output
        spans non-addressable devices, so assemble this process's rows from
        its addressable shards (they are exactly the rows this process fed
        in via _put_data); metrics/predictions stay process-local, like the
        reference's per-worker eval."""
        if jax.process_count() > 1 and not x.is_fully_replicated:
            shards = x.addressable_shards
            r0 = min((s.index[0].start or 0) for s in shards)
            r1 = max((s.index[0].stop if s.index[0].stop is not None
                      else x.shape[0]) for s in shards)
            out = np.zeros((r1 - r0,) + x.shape[1:], x.dtype)
            for s in shards:
                idx = (slice((s.index[0].start or 0) - r0,
                             (s.index[0].stop or x.shape[0]) - r0),
                       ) + tuple(s.index[1:])
                out[idx] = np.asarray(s.data)
            return out
        return np.asarray(x)

    def _host_fields(self, batch: DataBatch):
        """Host-side batch decomposition shared by both ingest paths:
        (data, extra input nodes in_1.., label fields). Extras per
        attachtxt + extra_data_num (reference nnet_config.h:223-235);
        label fields per GetLabelInfo (reference nnet_impl-inl.hpp:271-285)."""
        n = self.net_cfg.extra_data_num
        if n and len(batch.extra_data) < n:
            raise ValueError(
                "net declares extra_data_num=%d but batch carries %d extra "
                "arrays (chain an attachtxt iterator)"
                % (n, len(batch.extra_data)))
        data = np.asarray(batch.data)
        if data.dtype != np.uint8:   # raw-pixel batches stay 1 byte/px
            data = np.asarray(data, np.float32)
        if getattr(self.net, "input_s2d", 0) and \
                data.ndim == 4 and \
                data.shape[1] == self.net_cfg.input_shape[0]:
            # pack on the host (cheap strided copy; the equivalent device
            # transpose is lane-hostile) — see ConvolutionLayer docstring
            from .layers import s2d_pack
            data = s2d_pack(data, self.net.input_s2d)
        extras = tuple(np.asarray(batch.extra_data[i], np.float32)
                       for i in range(n))
        labels = ([] if batch.label is None else
                  [np.asarray(batch.label[:, a:b], np.float32)
                   for (a, b) in self.net_cfg.label_range])
        return data, extras, labels

    def _put_batch(self, batch: DataBatch):
        """Ship data + extra inputs + label fields in ONE batched
        device_put: per-array puts each cost a host dispatch of their
        own, which is wasted work."""
        data, extras, labels = self._host_fields(batch)
        return self._put_fields(data, extras, labels)

    def _put_fields(self, data, extras, labels):
        """Placement policy for one batch's (data, extras, labels) —
        the single source shared by stage(), GroupStager.flush and any
        future ingest path."""
        if jax.process_count() > 1:
            # multi-host assembly needs per-array process-local puts
            return (self._put_data(data, self._xsh),
                    tuple(self._put_data(e) for e in extras),
                    [self._put_data(l) for l in labels])
        if self.n_devices == 1:
            # uncommitted put: a 1-device mesh needs no placement (the
            # step's in_shardings put an uncommitted array on that one
            # device), and a plain put skips the per-leaf sharded commit
            return jax.device_put((data, extras, labels))
        shard = (self._xsh, tuple([self._dsh] * len(extras)),
                 [self._dsh] * len(labels))
        return jax.device_put((data, extras, labels), shard)

    def stage(self, batch: DataBatch) -> "StagedBatch":
        """Start the host->device transfer of a batch ahead of time.

        The returned handle can be passed to update() in place of the raw
        batch; staging batch k+1 (typically from a helper thread) while
        batch k computes double-buffers the H2D transfer behind the MXU
        work — the device-side analogue of the reference's ThreadBuffer
        prefetch stages (src/utils/thread_buffer.h:22).

        Everything update() consumes is in the device tuple (metrics
        accumulate on device), so no host field outlives this call and
        iterators may legally reuse their buffers afterwards — the
        wait below is what makes that guarantee backend-independent
        (device_put is async; an in-flight transfer could still be
        reading the host buffer on return, ADVICE r3). stage() runs on
        helper threads in every hot path, so blocking here IS the
        overlap, as in GroupStager.stage."""
        with _trace.span("trainer.stage", "h2d"):
            self._maybe_set_norm(batch)
            dev = self._put_batch(batch)
            jax.block_until_ready(dev)
            return StagedBatch(dev, batch)

    def stage_fused(self, batches) -> "StagedBatch":
        """Stage a full fuse_steps group as ONE stacked host->device
        transfer: (K, batch, ...) arrays, one put. K-fold fewer
        transfer round trips than per-batch stage() — the difference
        matters exactly where fuse_steps itself does (remote chips,
        small batches). The caller must own the batches' host buffers
        (they are read at call time); iterators that reuse buffers
        across next() must go through per-batch stage() instead, as the
        CLI loop does."""
        batches = list(batches)
        if self.fuse_steps <= 1 or len(batches) != self.fuse_steps:
            raise ValueError(
                "stage_fused needs exactly fuse_steps=%d batches, got %d"
                % (self.fuse_steps, len(batches)))
        fields = []
        for b in batches:
            self._maybe_set_norm(b)
            fields.append(self._host_fields(b))
        data_s = np.stack([f[0] for f in fields])
        extras_s = tuple(np.stack(col)
                         for col in zip(*(f[1] for f in fields)))
        labels_s = [np.stack(col)
                    for col in zip(*(f[2] for f in fields))]
        return self._put_group(data_s, extras_s, labels_s, batches[0])

    def _put_group(self, data_s, extras_s, labels_s,
                   host=None) -> "StagedBatch":
        """Ship already-stacked (K, ...) host fields as one transfer."""
        if self.n_devices == 1:
            dev = jax.device_put((data_s, tuple(extras_s),
                                  list(labels_s)))
        else:
            xsh_s = parallel.stacked_sharding(self._xsh)
            dsh_s = parallel.stacked_sharding(self._dsh)
            dev = jax.device_put(
                (data_s, tuple(extras_s), list(labels_s)),
                (xsh_s, tuple([dsh_s] * len(extras_s)),
                 [dsh_s] * len(labels_s)))
        return StagedBatch(dev, host, fused=int(data_s.shape[0]))

    def start_round(self, round_: int) -> None:
        self.round = round_
        if self.test_on_server:
            bad = self.check_replica_consistency()
            if bad:
                raise RuntimeError(
                    "replica consistency check failed for: %s"
                    % ", ".join(bad))

    def check_replica_consistency(self, atol: float = 0.0) -> List[str]:
        """Verify every device's copy of each replicated weight agrees —
        the mesh-native form of the reference's ``test_on_server`` check
        (workers pull the PS's weights and diff them against their local
        replica, async_updater-inl.hpp:148-153). With XLA collectives,
        divergence means a broken collective / bad donation, so this is a
        debugging aid, enabled per round with ``test_on_server = 1``.
        Returns the names of divergent tensors."""
        bad = []
        for li, p in enumerate(self.params):
            if p is None:
                continue
            lname = self.net_cfg.layers[li].name or ("layer%d" % li)
            for tag, w in p.items():
                if not w.is_fully_replicated:
                    continue  # intentionally sharded (tp/ep/pipe)
                shards = w.addressable_shards
                if len(shards) < 2:
                    continue
                ref = np.asarray(shards[0].data)
                for sh in shards[1:]:
                    # equal_nan: bitwise-identical NaN replicas are
                    # *consistent* — a NaN weight is a divergence problem,
                    # not a broken collective, and must not be misreported
                    if not np.allclose(np.asarray(sh.data), ref,
                                       rtol=0.0, atol=atol,
                                       equal_nan=True):
                        bad.append("%s.%s" % (lname, tag))
                        break
        return bad

    def _maybe_set_norm(self, batch: DataBatch) -> None:
        """Adopt the pipeline's deferred normalization (DataBatch.norm).
        Must happen before the first trace of the step functions — jit
        closes over net.input_norm as a compile-time constant, so every
        iterator feeding this trainer must agree on (mean, scale)."""
        if batch.norm is None:
            return
        mean, scale = batch.norm
        mean = np.asarray(mean, np.float32)
        if self.net.input_norm is None:
            self.net.input_norm = (mean, float(scale))
            return
        cur_mean, cur_scale = self.net.input_norm
        if cur_scale != float(scale) or cur_mean.shape != mean.shape \
                or not np.allclose(cur_mean, mean):
            raise ValueError(
                "on_device_norm mismatch: this batch wants (mean %s, scale "
                "%g) but the step was compiled with (mean %s, scale %g); "
                "all iterators feeding one net must share the same "
                "normalization" % (mean.reshape(-1)[:4], scale,
                                   cur_mean.reshape(-1)[:4], cur_scale))

    # ------------------------------------------------------------------
    def update(self, batch) -> None:
        """One minibatch of training (reference: nnet_impl-inl.hpp:141-185).
        Accepts a DataBatch or a StagedBatch from stage()."""
        if isinstance(batch, StagedBatch) and batch.fused:
            return self.update_fused(batch)
        self._step_count += 1
        args = {"step_num": self._step_count, "fused": 0,
                "step": getattr(batch, "step", None)}
        if self._stats_flight:
            args.update(self._drain_stats())
        with _trace.phase("trainer.update", "train", args):
            self._update(batch)

    def _drain_stats(self) -> dict:
        """The layers' counters (``ApplyContext.stats``) of the steps
        that have ended since the last call, read without waiting on the
        device (a step still running stays in flight). Each value goes
        to the registry by the name its layer gave it, one series a row:
        a name that ends in ``_max`` or ``_loss`` to the gauge
        ``cxxnet_<name>`` (a loss is the step's own value, not a
        count), any other to the counter ``cxxnet_<name>_total``. The
        newest ended step's come back as span arguments: ``stats_step``
        and, for every name, its sum (its largest, for ``_max``) over
        the layers."""
        from .obs.registry import get_registry
        reg, newest = get_registry(), {}
        while self._stats_flight:
            step, stats = self._stats_flight[0]
            if not all(x.is_ready() for x in jax.tree.leaves(stats)):
                break
            self._stats_flight.popleft()
            newest = {"stats_step": step}
            for (layer, name), v in sorted(stats.items()):
                largest = name.endswith("_max")
                rows = np.asarray(v, np.float64).reshape(-1)
                for i, x in enumerate(rows):
                    where = "%d.%d" % (layer, i)
                    if largest or name.endswith("_loss"):
                        reg.gauge("cxxnet_" + name, _STAT_HELP,
                                  ("layer",)).set(float(x), layer=where)
                    else:
                        reg.counter("cxxnet_%s_total" % name, _STAT_HELP,
                                    ("layer",)).inc(float(x), layer=where)
                fold = max if largest else sum
                seen = [newest[name]] if name in newest else []
                newest[name] = float(fold(seen + [fold(rows)]))
        return newest

    def _update(self, batch) -> None:
        if isinstance(batch, StagedBatch):
            data, extras, labels = batch.device
        else:
            self._maybe_set_norm(batch)
            data, extras, labels = self._put_batch(batch)
        if self.update_period == 1:
            if self._step_specs is None:
                # abstract arg specs for step_cost_analysis (captured
                # before the call: donation invalidates the buffers)
                self._step_specs = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    (self._params, self._opt_state, self._rng,
                     self._epoch_dev, self._maccum, data, extras, labels))
                # what a reader of a device trace lowers again to name
                # the step's instructions (obs.trace.device_scopes)
                _trace.note_program("train_step", self._train_step,
                                    self._step_specs)
            (self._params, self._opt_state, self._rng, self._epoch_dev,
             self._maccum, loss, stats) = self._train_step(
                self._params, self._opt_state, self._rng, self._epoch_dev,
                self._maccum, data, extras, labels)
            if stats:
                self._stats_flight.append((self._step_count, stats))
        else:
            (self.grad_accum, self._rng, self._maccum,
             loss, supd) = self._accum_step(
                self.grad_accum, self._rng, self._maccum, self._params,
                self._epoch_dev, data, extras, labels)
            self._params = _merge_state(self._params, supd)
            if (self.sample_counter + 1) % self.update_period == 0:
                (self._params, self._opt_state, self.grad_accum,
                 self._epoch_dev) = self._apply_accum(
                    self._params, self._opt_state, self.grad_accum,
                    self._epoch_dev)
        # the step's loss, kept as a device scalar (no sync): readable
        # by whoever needs per-step values (chip_smoke.py)
        self.last_loss = loss
        self.sample_counter += 1
        if self.sample_counter >= self.update_period:
            self.sample_counter = 0
            self.epoch_counter += 1

    # ------------------------------------------------------------------
    def update_fused(self, staged) -> None:
        """Run ``len(staged)`` training steps in ONE jitted dispatch.

        With ``fuse_steps = K`` configured, a full group of K staged
        batches dispatches the fused lax.scan step compiled in
        _finish_init; partial groups (a round's tail, or fuse_steps=1)
        fall back to per-step update() calls. The K-step trajectory is
        identical to K update() calls — only the host<->device dispatch
        count changes. The reference has no analogue: its trainer is
        host-driven batch by batch (cxxnet_main.cpp:344-412); one
        dispatch per K steps is the XLA-native training-loop shape."""
        if isinstance(staged, StagedBatch) and staged.fused:
            group = staged
        else:
            staged = list(staged)
            if self.fuse_steps <= 1 or len(staged) != self.fuse_steps:
                for s in staged:
                    self.update(s)
                return
            if self._train_multi is None:
                # fuse_steps was raised AFTER init_model compiled the
                # steps (set_param alone cannot rebuild the jitted
                # programs, and the update_period compatibility check
                # lives at init)
                raise RuntimeError(
                    "fuse_steps=%d was set after init_model(); configure "
                    "it before init so the fused step is compiled"
                    % self.fuse_steps)
            for s in staged:
                if not isinstance(s, StagedBatch):
                    raise TypeError("update_fused takes staged batches "
                                    "(Trainer.stage)")
            # stack the per-batch device arrays into the (K, ...) group
            # layout outside the step (one async concat dispatch per
            # group; stage_fused skips even that by stacking on host)
            group = StagedBatch(
                (jnp.stack([s.device[0] for s in staged]),
                 tuple(jnp.stack(col)
                       for col in zip(*(s.device[1] for s in staged))),
                 [jnp.stack(col)
                  for col in zip(*(s.device[2] for s in staged))]),
                staged[0].host, fused=len(staged))
            group.step = staged[-1].step
        if self._train_multi is None:
            raise RuntimeError(
                "fuse_steps was not configured before init_model()")
        if self.update_period > 1 and self.sample_counter != 0:
            raise RuntimeError(
                "fused dispatch with update_period=%d needs the "
                "accumulation window aligned to the group (%d "
                "micro-batches pending from per-step update() calls); "
                "feed whole groups or finish the window unfused"
                % (self.update_period, self.sample_counter))
        k = group.fused
        self._step_count += k
        with _trace.phase("trainer.update", "train",
                          {"step_num": self._step_count, "fused": k,
                           "step": group.step}):
            self._update_group(group)

    def _update_group(self, group) -> None:
        data_s, extras_s, labels_s = group.device
        k = group.fused
        if self._step_specs is None:
            # per-step abstract specs (group element 0), so
            # step_cost_analysis reports ONE step's flops either path
            def specs(tree, lead=0):
                return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                    x.shape[lead:], x.dtype), tree)
            state = specs((self._params, self._opt_state, self._rng,
                           self._epoch_dev, self._maccum))
            self._step_specs = state + specs(group.device, 1)
            # (the program that runs is the group's: what a reader of a
            # device trace lowers again, obs.trace.device_scopes)
            _trace.note_program("train_step", self._train_multi,
                                state + specs(group.device))
        (self._params, self._opt_state, self._rng, self._epoch_dev,
         self._maccum, self.last_loss) = self._train_multi(
            self._params, self._opt_state, self._rng, self._epoch_dev,
            self._maccum, data_s, extras_s, labels_s)
        # one epoch (= optimizer apply) per accumulation window
        self.epoch_counter += k // self.update_period

    # ------------------------------------------------------------------
    def step_cost_analysis(self) -> dict:
        """Cost model for one training step: XLA's HLO count plus the
        analytic corrections it needs (VERDICT r3 #2).

        XLA's ``cost_analysis()['flops']`` under-counts two program
        shapes, both verified on this tree: a ``lax.scan`` body is
        counted ONCE regardless of trip count (the transformer_stack
        scans over depth), and a Pallas kernel lowers to an opaque
        custom_call counted as zero. The returned dict therefore adds:

        * ``model_flops`` — analytic model flops (MFU basis: matmul
          terms, bwd at 2x fwd, causal half, no remat replay;
          Network.analytic_model_flops). THE number to divide by step
          time for a published MFU.
        * ``model_flops_fwd`` — its forward-only part (eval streams).
        * ``pallas_hw_flops`` / ``pallas_kernels`` — analytic hardware
          flops of the Pallas kernels in the last train trace and which
          kernels XLA could not see (empty = no Pallas kernels ran;
          the scan undercount can still apply).
        * ``flops`` — XLA's own count, unchanged, as the cross-check:
          for scan-free Pallas-free nets it agrees with model_flops to
          within the elementwise tail (pinned by
          tests/test_flops_accounting.py).

        Uses a fresh lowering from the recorded arg specs — no
        recompile, no device traffic. Requires one prior update()."""
        if self._step_specs is None:
            raise RuntimeError("run at least one update() first "
                               "(update_period=1 path)")
        lowered = self._train_step.lower(*self._step_specs)
        ca = dict(lowered.cost_analysis() or {})
        if not ca.get("flops"):
            # some backends only report at the executable level;
            # identical shapes usually hit the compilation cache so
            # this is cheap after the first step
            ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
        ca = dict(ca or {})
        af = self.net.analytic_model_flops(train=True)
        ca["model_flops"] = af["total"]
        ca["model_flops_fwd"] = af["fwd"]
        rec = self.net.pallas_flops_record.get(True, [])
        ca["pallas_hw_flops"] = float(
            sum(e["fwd"] + e["bwd"] for e in rec))
        ca["pallas_kernels"] = sorted({e["kernel"] for e in rec})
        return ca

    # ------------------------------------------------------------------
    def forward_nodes(self, batch: DataBatch,
                      node_ids: Sequence[int]) -> List[np.ndarray]:
        self._maybe_set_norm(batch)
        data, extras, _ = self._put_batch(batch)
        values = self._forward(self.params, data, extras, tuple(node_ids))
        out = [self._fetch_local(v) for v in values]
        s2d = getattr(self.net, "input_s2d", 0)
        if s2d:
            # extracting the data node must return the caller-visible
            # (N,C,H,W) layout, not the packed conv feed
            from .layers import s2d_unpack
            _, h, w = self.net_cfg.input_shape
            out = [s2d_unpack(v, s2d, (h, w)) if ni == 0 else v
                   for ni, v in zip(node_ids, out)]
        return out

    def _resolve_decode(self, kv_plan, B, P, max_new):
        """Resolve the (decode_layout, decode_kv) knobs for a decode
        build — shared by ``generate`` and ``serving.export_generate``
        so both ship the same measured policy.

        ``auto`` layout: slotk (the fused Pallas decode-attend) on TPU
        at B >= 16 when the kernel's VMEM row budget fits; the plain
        slot layout otherwise. Measured crossover
        (docs/performance.md r5): the kernel's per-program fixed cost
        loses at B=8 (-6%), wins +27% at B=32 and +54% at B=64. The
        same B >= 16 crossover holds for decode_kv=int8 — measured
        B=8: the XLA attend is bandwidth-limited there (not
        MXU-issue-bound like B >= 32), so int8 helps it directly
        (15.5k vs the kernel's 13.2k steady tok/s), while at B >= 32
        int8 through XLA is the recorded negative."""
        layout = getattr(self, "decode_layout", "auto")
        kv = getattr(self, "decode_kv", "native")
        if kv == "int8" and layout in ("slott", "blend"):
            raise ValueError(
                "decode_kv=int8 requires decode_layout auto|slot|slotk"
                " (got %s)" % layout)
        if layout == "auto":
            layout = "slot"
            if kv_plan is not None and B >= 16 \
                    and getattr(self.net, "platform", "cpu") == "tpu":
                try:
                    from .ops import decode_attend as da
                    st0 = self.net.modules[kv_plan["stacks"][0]]
                    e = self.net.modules[
                        kv_plan["embed"]].param.num_hidden
                    da._plan(
                        B, st0.nhead,
                        da.cache_slots(P, int(max_new)),
                        e // st0.nhead,
                        1 if kv == "int8" else
                        jnp.dtype(self.net.compute_dtype).itemsize,
                        scale_bytes_per_slot=4 if kv == "int8" else 0)
                    layout = "slotk"
                except ValueError:
                    # the intended over-budget fallback; anything else
                    # (a real bug) must surface, not silently pin the
                    # slower path
                    pass
        return layout, kv

    def _warn_moe_capacity(self, kv_plan, who: str) -> None:
        """Cached decode routes only the B new tokens per step; under
        capacity pressure (factor below nexpert/topk no longer
        guarantees zero drops) the cached and full-forward paths can
        drop DIFFERENT tokens — warn once per build. Shared by
        ``generate`` and ``serving.export_generate`` (the exported
        decoder bakes the behavior in with no use_cache=never
        fallback, so the warning matters MORE there)."""
        for si in kv_plan["stacks"]:
            st = self.net.modules[si]
            if st.moe and st.capacity_factor < st.nexpert / st.topk:
                sys.stderr.write(
                    "%s: MoE capacity_factor %g < nexpert/moe_topk = "
                    "%g — under capacity pressure the cached decode "
                    "can drop different tokens than the full-forward "
                    "path\n"
                    % (who, st.capacity_factor, st.nexpert / st.topk))

    def generate(self, tokens: np.ndarray, lens: np.ndarray,
                 max_new: int, temperature: float = 0.0,
                 seed: int = 0, use_cache: str = "auto") -> np.ndarray:
        """Autoregressive decoding on a causal token net (task=generate).

        No reference counterpart (cxxnet has no sequence models,
        SURVEY.md §5); this completes the LM story: train ->
        checkpoint -> generate. ``tokens`` is (B, S) int prompt ids
        left-aligned with per-row prompt lengths ``lens``; ``max_new``
        tokens are appended per row (greedy at temperature 0, else
        softmax sampling). Returns the completed (B, S) array.

        The whole decode loop runs ON DEVICE as one jitted
        ``fori_loop`` — each step re-runs the causal forward at the
        net's fixed sequence length and samples the next position, so
        there are no per-token host round trips and any causal config
        works, attention layers
        and stacks alike, with no KV-cache plumbing through the graph.
        Cost is O(max_new) full forwards; at the LM recipes' lengths
        the forward is a few ms, and correctness holds for every layer
        the graph interpreter supports.

        For the canonical embed -> dense causal transformer_stack ->
        fullc(seq=1) head -> softmax graph, ``use_cache`` ("auto"
        default) switches to KV-cache decoding (cxxnet_tpu/generate.py):
        one prefill then O(seq) per token instead of O(seq^2), still a
        single jitted program. "never" forces the general path (the
        tests pin both paths to identical greedy output).
        """
        if jax.process_count() > 1:
            raise NotImplementedError(
                "task=generate is single-process (serve from one host; "
                "the decode loop does not assemble multi-host batches)")
        S = self.net.node_shapes[0][2]
        B = self.global_batch
        tokens = np.asarray(tokens)
        lens = np.asarray(lens, np.int32)
        nrow = tokens.shape[0]
        if tokens.shape[1] != S:
            raise ValueError("prompts must be padded to the net's "
                             "seq_len %d (got %d)" % (S, tokens.shape[1]))
        if nrow and int(lens.min()) < 1:
            raise ValueError("every prompt needs at least 1 token "
                             "(a 0 length would silently corrupt its row)")
        if int(lens.max()) + max_new > S:
            raise ValueError(
                "longest prompt (%d) + max_new (%d) exceeds seq_len %d"
                % (int(lens.max()), max_new, S))
        if nrow > B:
            raise ValueError("at most batch_size=%d prompts per call"
                             % B)
        if nrow < B:   # pad rows to the compiled batch
            tokens = np.concatenate(
                [tokens, np.zeros((B - nrow, S), tokens.dtype)])
            lens = np.concatenate([lens, np.ones(B - nrow, np.int32)])

        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if use_cache not in ("auto", "never"):
            raise ValueError("use_cache must be 'auto' or 'never'")
        kv_plan, why = None, ""
        if use_cache != "never":
            from . import generate as G
            kv_plan, why = G.plan_or_reason(self.net)
        P = None
        if kv_plan is not None:
            from . import generate as G
            P = G.prompt_slots(int(lens.max()) if nrow else 1, S)
        layout, kv = self._resolve_decode(kv_plan, B, P, max_new)
        key = (int(max_new), float(temperature), kv_plan is not None,
               layout, P, kv)
        fn = self._gen_cache.get(key)
        if fn is None and kv_plan is not None:
            self._warn_moe_capacity(kv_plan, "generate")
            fn = G.build(self.net, kv_plan, int(max_new),
                         float(temperature), B, S, P=P, layout=layout,
                         platform=getattr(self.net, "platform", "cpu"),
                         kv=kv)
            self._gen_cache[key] = fn
        if fn is None:
            if use_cache != "never":
                # no silent quadratic decode (VERDICT r2 weak #3): the
                # fallback is correct for any causal graph but costs
                # O(max_new) full forwards. Emitted only on first
                # compile of this fallback, not per serving call.
                sys.stderr.write(
                    "generate: KV cache declined (%s); falling back to "
                    "%d full forwards\n" % (why, int(max_new)))
            net, out_node = self.net, self.net.out_node

            def gen(params, toks, lens, rng):
                def body(i, carry):
                    toks, rng = carry
                    data = toks[:, None, :, None].astype(jnp.float32)
                    values, _ = net.apply(params, data, train=False)
                    probs = values[out_node].reshape(B, S, -1)
                    pos = lens - 1 + i               # predict from here
                    p = jnp.take_along_axis(
                        probs, pos[:, None, None], axis=1)[:, 0]
                    if temperature == 0.0:
                        nxt = jnp.argmax(p, axis=-1)
                    else:
                        rng, k = jax.random.split(rng)
                        nxt = jax.random.categorical(
                            k, jnp.log(p + 1e-9) / temperature)
                    toks = toks.at[jnp.arange(B), pos + 1].set(
                        nxt.astype(toks.dtype))
                    return toks, rng
                return jax.lax.fori_loop(0, max_new, body, (toks, rng))[0]
            fn = jax.jit(gen)
            self._gen_cache[key] = fn
        out = fn(self.params, jnp.asarray(tokens, jnp.int32),
                 jnp.asarray(lens), jax.random.PRNGKey(seed))
        return np.asarray(out)[:nrow]

    def predict(self, batch: DataBatch) -> np.ndarray:
        """Argmax (or raw scalar) of the final node
        (reference: nnet_impl-inl.hpp:186-199,286-299)."""
        out = self.forward_nodes(batch, [self.net.out_node])[0]
        return self._pred_values(out)

    @staticmethod
    def _pred_values(out: np.ndarray) -> np.ndarray:
        mat = out.reshape(out.shape[0], -1)
        if mat.shape[1] != 1:
            return mat.argmax(axis=1).astype(np.float32)
        return mat[:, 0]

    def predict_fused(self, staged) -> np.ndarray:
        """predict() over a fuse_steps group in ONE dispatch + fetch.

        Accepts a stacked group (stage_fused / GroupStager.stage) or a
        list of per-batch staged batches: a full list stacks on device
        (like update_fused); a partial list — the pred stream's tail —
        runs per batch. Returns the flattened predictions in feed
        order (callers trim per-batch padding themselves, as the CLI
        pred writer does)."""
        node_ids = (self.net.out_node,)

        def from_stacked(data_s, extras_s):
            values = self._forward_multi(self.params, data_s, extras_s,
                                         node_ids)
            out = self._fetch_local(values[0])
            return self._pred_values(
                out.reshape((-1,) + out.shape[2:]))

        if isinstance(staged, StagedBatch):
            if staged.fused:
                if self._forward_multi is None:
                    raise RuntimeError(
                        "fuse_steps was set after init_model(); "
                        "configure it before init so the fused forward "
                        "is compiled")
                data_s, extras_s, _ = staged.device
                return from_stacked(data_s, extras_s)
            staged = [staged]   # a plain staged batch: per-batch path
        staged = list(staged)
        if self._forward_multi is not None \
                and len(staged) == self.fuse_steps:
            data_s = jnp.stack([s.device[0] for s in staged])
            extras_s = tuple(
                jnp.stack(col)
                for col in zip(*(s.device[1] for s in staged)))
            return from_stacked(data_s, extras_s)
        outs = []
        for s in staged:
            data, extras, _ = s.device
            values = self._forward(self.params, data, extras, node_ids)
            outs.append(self._pred_values(self._fetch_local(values[0])))
        return (np.concatenate(outs) if outs
                else np.zeros((0,), np.float32))

    def extract_feature(self, batch: DataBatch, node_name: str) -> np.ndarray:
        """Copy out a named node or top[-k]
        (reference: nnet_impl-inl.hpp:200-223)."""
        import re
        m = re.match(r"top\[-(\d+)\]", node_name)
        if m:
            offset = int(m.group(1))
            nnode = self.net_cfg.num_nodes
            if not (1 <= offset <= nnode):
                raise ValueError("ExtractFeature: offset out of range")
            node_id = nnode - offset
        else:
            if node_name not in self.net_cfg.node_name_map:
                raise ValueError(
                    "ExtractFeature: cannot find node name: %s" % node_name)
            node_id = self.net_cfg.node_name_map[node_name]
        return self.forward_nodes(batch, [node_id])[0]

    # ------------------------------------------------------------------
    def evaluate(self, iter_eval: Optional[DataIterator],
                 data_name: str) -> str:
        # traced as a span: evaluate is the round-boundary host<->device
        # sync point, i.e. exactly the gap between dispatch bursts a
        # trace viewer would otherwise show as unexplained idle
        with _trace.span("trainer.evaluate", "train",
                         {"name": data_name}):
            return self._evaluate(iter_eval, data_name)

    def _evaluate(self, iter_eval: Optional[DataIterator],
                  data_name: str) -> str:
        """Round-end metric report (reference: nnet_impl-inl.hpp:224-245).

        Both halves run on accumulated device statistics: the train
        metric buffer rode the train steps; the eval set streams through
        a jitted forward+metric step. Exactly one small D2H fetch per
        MetricSet per round."""
        rep = parallel.replicated(self.mesh)
        ret = ""
        if self._use_dev_metric or self.nan_guard:
            acc = np.asarray(self._maccum)
            self._maccum = jax.device_put(
                jnp.asarray(self._maccum_zero), rep)
            if self.nan_guard:
                # round-end NaN containment: the per-element NaN-zeroing
                # clip (updater._clip_nan) stops weight corruption; this
                # stops a silently-NaN loss from burning further rounds.
                # The last accum row counted NaN losses, so the guard
                # works even with eval_train=0 / no train metric.
                nan_steps = float(acc[-1, 0, 0] - acc[-1, 0, 1])
                acc = acc[:-1]
                if nan_steps > 0:
                    raise RuntimeError(
                        "nan_guard: the loss was NaN on %d step(s) this "
                        "round; lower eta or set clip_gradient, and "
                        "resume from the last checkpoint (continue=1)"
                        % int(round(nan_steps)))
        if self._use_dev_metric:
            self.train_metric.add_stats(acc)
            if self.nan_guard:
                bad = [m.name for m in self.train_metric.evals
                       if m.cnt_inst and np.isnan(m.get())]
                if bad:
                    # clear BEFORE raising: a stale NaN sum would poison
                    # every later round, defeating nan_guard=2 recovery
                    self.train_metric.clear()
                    raise RuntimeError(
                        "nan_guard: train metric '%s' is NaN (bad "
                        "labels or diverged loss)" % bad[0])
            ret += self.train_metric.print("train")
            self.train_metric.clear()
        if iter_eval is None:
            return ret
        if not self.metric.evals:
            return ret
        self.metric.clear()
        eaccum = jax.device_put(jnp.asarray(self._eaccum_zero), rep)
        iter_eval.before_first()
        fuse = (self.fuse_steps
                if self._eval_multi is not None
                and self.group_staging != 0 else 1)
        if fuse > 1:
            # cached across rounds so the stacked host buffers stay
            # warm, like the CLI's train-side stagers
            if self._eval_gs is None:
                self._eval_gs = GroupStager(self)
            gs = self._eval_gs
        else:
            gs = None
        masks: List[np.ndarray] = []

        def batch_mask(batch):
            nvalid = batch.batch_size - batch.num_batch_padd
            hmask = np.zeros((batch.batch_size,), np.float32)
            hmask[:nvalid] = 1.0
            return hmask

        def eval_one(data, extras, labels, hmask):
            mask = self._put_data(hmask, self._dsh)
            return self._eval_step(self.params, eaccum, data, extras,
                                   labels, mask)

        while iter_eval.next():
            batch = iter_eval.value
            if gs is None:
                self._maybe_set_norm(batch)  # gs.add runs it itself
                eaccum = eval_one(*self._put_batch(batch),
                                  batch_mask(batch))
                continue
            # fused eval: groups of K batches ship as one stacked
            # transfer and fold through one scanned dispatch
            gs.add(batch)
            masks.append(batch_mask(batch))
            if gs.full:
                staged = gs.stage()
                mask_s = self._put_data(
                    np.stack(masks),
                    parallel.stacked_sharding(self._dsh))
                eaccum = self._eval_multi(
                    self.params, eaccum, *staged.device, mask_s)
                masks = []
        if gs is not None:
            # tail: partial group per-batch
            for s, hmask in zip(gs.flush(), masks):
                eaccum = eval_one(*s.device, hmask)
        self.metric.add_stats(np.asarray(eaccum))
        ret += self.metric.print(data_name)
        return ret

    # ------------------------------------------------------------------
    @staticmethod
    def _fetch_global(x) -> np.ndarray:
        """Full global value on this host. A weight sharded across
        processes (multi-host tensor parallelism or zero=3 FSDP) has
        shards this process cannot address, so it must be all-gathered —
        every process must call this collectively."""
        if jax.process_count() == 1 or x.is_fully_replicated:
            return np.asarray(x)
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    # ------------------------------------------------------------------
    # weight access (reference: nnet_impl-inl.hpp:246-268 + visitor.h)
    def get_weight(self, layer_name: str, tag: str) -> np.ndarray:
        """Full (global) weight as (rows, cols).

        Multi-host note: when the weight is sharded across processes
        (cross-host tensor parallelism or ``zero = 3``), this is a
        COLLECTIVE — every process must call it together, like
        ``save_model``; a lone ``if rank == 0: get_weight(...)`` call
        hangs in the all-gather."""
        idx = self.net_cfg.get_layer_index(layer_name)
        if self.params[idx] is None or tag not in self.params[idx]:
            raise ValueError("layer %s has no %s" % (layer_name, tag))
        w = self._fetch_global(self.params[idx][tag])
        return w.reshape(w.shape[0], -1) if w.ndim > 1 else w.reshape(1, -1)

    def set_weight(self, weight: np.ndarray, layer_name: str,
                   tag: str) -> None:
        idx = self.net_cfg.get_layer_index(layer_name)
        if self.params[idx] is None or tag not in self.params[idx]:
            raise ValueError("layer %s has no %s" % (layer_name, tag))
        shape = self.params[idx][tag].shape
        self.params = _merge_state(self.params, {
            (idx, tag): jnp.asarray(weight, jnp.float32).reshape(shape)})

    # ------------------------------------------------------------------
    # checkpointing (reference: nnet_impl-inl.hpp:82-134, SURVEY.md §3.3)
    def save_model(self, path: str) -> None:
        from . import checkpoint

        if self.save_sharded:
            # each process writes only its addressable shards into a
            # .model directory — no allgather collective and no one-host
            # serialization of the whole model (path on a shared
            # filesystem, like the reference's model_dir in dist-PS
            # mode). Shards snapshot to host synchronously (the next
            # step donates the device buffers); with save_async=1 the
            # file writes then run behind the next round's training.
            self.wait_for_save()
            # every rank stamps its shards with a per-save-attempt nonce
            # agreed via broadcast: rank 0's pre-meta barrier then only
            # accepts THIS attempt's manifests, so a reused directory's
            # stale shards (torn earlier save at the same counter) can
            # neither release the barrier early nor mix into a load
            nonce = int.from_bytes(os.urandom(8), 'little') >> 2
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                nonce = int(multihost_utils.broadcast_one_to_all(
                    np.int64(nonce)))
            arrays, manifest = checkpoint.collect_shards(
                self.params, self.opt_state)
            self._write_checkpoint(
                checkpoint.write_shards, path, arrays, manifest,
                self.net_cfg, self.epoch_counter,
                self.opt_state is not None, 0, jax.process_index(),
                jax.process_count(), nonce)
            return

        def fetch(t):
            # unlike _fetch_local, cross-process-sharded weights must be
            # all-gathered or the checkpoint would be silently truncated
            return jax.tree.map(self._fetch_global, t)
        # every process joins the allgather collectives; only process 0
        # writes (the path normally sits on a shared filesystem in a pod
        # job — concurrent writers would corrupt the file)
        params = fetch(self.params)
        opt_state = fetch(self.opt_state)
        if jax.process_index() == 0:
            self.wait_for_save()
            self._write_checkpoint(checkpoint.save_model, path,
                                   self.net_cfg, self.epoch_counter,
                                   params, opt_state)

    def _write_checkpoint(self, write_fn, *args) -> None:
        """Run one checkpoint write, on a background thread when
        save_async=1 (the args are immutable host snapshots, so
        serialization + disk IO run behind the next round's training;
        one writer at a time keeps files whole, and wait_for_save
        re-raises any failure)."""
        if not self.save_async:
            write_fn(*args)
            return
        import threading

        def write():
            try:
                write_fn(*args)
            except BaseException as e:  # surfaced by wait_for_save
                self._save_error = e
        self._save_error = None
        self._save_thread = threading.Thread(
            target=write, name="ckpt-save", daemon=False)
        self._save_thread.start()

    def wait_for_save(self) -> None:
        """Block until a pending async checkpoint write finishes; re-raise
        its failure (a silently missing checkpoint would surface rounds
        later as a stale continue=1 resume)."""
        t = getattr(self, "_save_thread", None)
        if t is not None:
            t.join()
            self._save_thread = None
            err = getattr(self, "_save_error", None)
            if err is not None:
                self._save_error = None
                raise RuntimeError("async checkpoint write failed") from err

    def load_model(self, path: str) -> None:
        """Restore structure + epoch + weights (+ optimizer state, which
        the reference loses on resume — SURVEY.md §5)."""
        with _trace.phase("trainer.init", "train"):
            self._load_model(path)

    def _load_model(self, path: str) -> None:
        from . import checkpoint
        self.wait_for_save()
        net_cfg, epoch, params, opt_state, _ = checkpoint.load_model(path)
        self.net_cfg = net_cfg
        # refresh training-param buckets + verify declared structure
        self.net_cfg.configure(self.cfg)
        self.epoch_counter = epoch
        self._build_network()
        params = jax.tree.map(jnp.asarray, params)
        # seed state tags absent from the checkpoint (e.g. bn_running
        # newly enabled on a model saved without running stats)
        fresh_p = None
        for li, mod in enumerate(self.net.modules):
            missing = [t for t in getattr(mod, "state_tags", ())
                       if params[li] is not None and t not in params[li]]
            if missing:
                if fresh_p is None:
                    fresh_p = self.net.init_params(jax.random.PRNGKey(0))
                for t in missing:
                    params[li][t] = fresh_p[li][t]
        opt = NetUpdater(self.net)
        # merge loaded slots onto a freshly initialized structure: empty
        # slot dicts (non-trainable state tags) are not serialized, and a
        # structural mismatch would desync the jitted step's out_shardings
        fresh = opt.init_state(params)
        if opt_state is not None:
            for li, loaded in enumerate(opt_state):
                if loaded is None or fresh[li] is None:
                    continue
                for tag, slots in loaded.items():
                    if tag in fresh[li] and slots:
                        fresh[li][tag] = jax.tree.map(jnp.asarray, slots)
        opt_state = fresh
        self._finish_init(params, opt, opt_state)

    def copy_model_from(self, path: str) -> None:
        """Finetune: fresh init, then copy params of layers whose names
        match the old net (reference: nnet_impl-inl.hpp:101-134)."""
        from . import checkpoint
        self.init_model()
        old_cfg, _, old_params, _, _ = checkpoint.load_model(path)
        params = list(self.params)
        for i, old in enumerate(old_cfg.layers):
            if not old.name or old_params[i] is None:
                continue
            j = self.net_cfg.layer_name_map.get(old.name)
            if j is None or params[j] is None:
                continue
            if self.silent == 0:
                print("Copying layer %s" % old.name)
            cur = dict(params[j])
            # only tags the fresh net also has: copying e.g. a bias into a
            # no_bias layer would desync params from their shardings
            for tag, arr in old_params[i].items():
                if tag not in cur:
                    continue
                if tuple(cur[tag].shape) != tuple(arr.shape):
                    raise ValueError(
                        "finetune: layer %s %s shape mismatch %s vs %s"
                        % (old.name, tag, cur[tag].shape, arr.shape))
                cur[tag] = jnp.asarray(arr)
            params[j] = cur
        self.params = params


_STAT_HELP = ("a counter a layer computes on the device in the train "
              "step (ApplyContext.stats): layer = <net layer>.<row>")


def _strip_nones(tree):
    """Replace per-layer None slots with empty dicts so tree ops line up."""
    return [({} if t is None else t) for t in tree]


def _merge_state(params, supd):
    """Fold non-trainable state writes {(layer, tag): value} (BN running
    stats) into a params list. Works both inside a jit trace and on host
    arrays."""
    if not supd:
        return params
    params = list(params)
    for (li, tag), v in supd.items():
        params[li] = dict(params[li], **{tag: v})
    return params
