"""Functional network: the netconfig DAG as one pure forward function.

The reference walks ``connections`` mutating device ``Node`` buffers and
hand-chains backprop (reference: src/nnet/neural_net-inl.hpp:107-153).
Here the DAG is *interpreted into a pure function* ``apply(params, ...)``
whose gradient is taken by ``jax.grad`` — the whole fwd+bwd+update compiles
into a single XLA program.

Semantics preserved from the reference:
  * connection order = config order; a node's value is whatever the last
    connection wrote to it (self-loop layers update in place)
  * loss layers transform their node (softmax probs visible to eval) and
    contribute  grad_scale * L / (batch_size * update_period)  to the
    scalar loss (loss_layer_base-inl.hpp:62)
  * shared layers reuse the primary connection's parameters
    (nnet_config.h:57-59, neural_net-inl.hpp:238-244)
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from . import layers as L
from .graph import NetConfig, SHARED_LAYER

ConfigEntry = Tuple[str, str]


class Network:
    """Static model structure + pure init/apply.

    Mirrors NeuralNet (reference: src/nnet/neural_net-inl.hpp:23-302) minus
    device plumbing: no streams, no per-device threads — XLA owns scheduling.
    """

    def __init__(self, net_cfg: NetConfig, batch_size: int,
                 update_period: int = 1,
                 compute_dtype: str = "float32") -> None:
        self.cfg = net_cfg
        self.batch_size = batch_size
        self.update_period = update_period
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.modules: List[L.Layer] = []
        self.node_shapes: List[Optional[Tuple[int, ...]]] = (
            [None] * net_cfg.num_nodes)
        self.mesh = None       # the trainer's mesh, when it spans > 1 device
        self.seq_axis: Optional[str] = None
        # the jit target platform, set by the trainer from its devices;
        # gates compiled-vs-interpreted Pallas kernels
        self.platform: str = "cpu"
        # deferred input normalization (mean, scale): applied on-device to
        # uint8 input batches so raw pixels cross host->device as 1 byte
        # (set by the trainer from DataBatch.norm before the first trace)
        self.input_norm: Optional[Tuple] = None
        # {train_flag: [{kernel, fwd, bwd}, ...]} — analytic hardware
        # flops of Pallas kernels recorded at trace time (XLA's cost
        # model counts 0 for a pallas_call); written by apply()
        self.pallas_flops_record: Dict[bool, list] = {}

        c, h, w = net_cfg.input_shape
        self.node_shapes[0] = (batch_size, c, h, w)
        for i in range(net_cfg.extra_data_num):
            ec, eh, ew = net_cfg.extra_shape[3 * i: 3 * i + 3]
            self.node_shapes[i + 1] = (batch_size, ec, eh, ew)

        # build modules + infer shapes in connection order
        for li, info in enumerate(net_cfg.layers):
            type_name = info.type
            if type_name == SHARED_LAYER:
                type_name = net_cfg.layers[info.primary_layer_index].type
            if type_name == "pairtest":
                from . import pairtest
                # a share[...] of a pairtest layer carries pair=None itself;
                # the pair lives on the primary, like type_name and cfg
                pair = (info.pair if info.type != SHARED_LAYER
                        else net_cfg.layers[info.primary_layer_index].pair)
                mod = pairtest.PairTestLayer(
                    pair, net_cfg.effective_layer_cfg(li),
                    net_cfg.label_name_map)
            else:
                mod = L.create_layer(
                    type_name, net_cfg.effective_layer_cfg(li),
                    net_cfg.label_name_map)
            if isinstance(mod, L.SplitLayer):
                mod.n_out = len(info.nindex_out)
            in_shapes = []
            for ni in info.nindex_in:
                if self.node_shapes[ni] is None:
                    raise ValueError(
                        "node %s used before it is produced"
                        % net_cfg.node_names[ni])
                in_shapes.append(self.node_shapes[ni])
            out_shapes = mod.infer_shape(in_shapes)
            if len(out_shapes) != len(info.nindex_out):
                raise ValueError("layer %d produced %d outputs, expected %d"
                                 % (li, len(out_shapes), len(info.nindex_out)))
            for no, shp in zip(info.nindex_out, out_shapes):
                if self.node_shapes[no] is not None and \
                        self.node_shapes[no] != shp and no not in info.nindex_in:
                    raise ValueError(
                        "conflicting shapes for node %s: %s vs %s"
                        % (net_cfg.node_names[no], self.node_shapes[no], shp))
                self.node_shapes[no] = shp
            self.modules.append(mod)

        # space-to-depth input packing: when a conv on the data node asks
        # for it, the trainer packs batches on the host and the conv uses
        # the packed kernel path; every other consumer of node 0 would
        # see the packed layout, so require exclusivity
        self.input_s2d = 0
        consumers = [li for li, info in enumerate(net_cfg.layers)
                     if 0 in info.nindex_in]
        for li, (info, mod) in enumerate(zip(net_cfg.layers, self.modules)):
            b = getattr(mod, "s2d", 0)
            if not b:
                continue
            if 0 not in info.nindex_in:
                raise ValueError(
                    "space_to_depth is only supported on a conv reading "
                    "the input node (layer %d reads nodes %s) — inner "
                    "nodes are never host-packed, so it would silently "
                    "be a no-op" % (li, info.nindex_in))
            if len(consumers) != 1:
                raise ValueError(
                    "space_to_depth conv must be the only consumer of the "
                    "input node (layers %s all read it)" % consumers)
            self.input_s2d = b

    # ------------------------------------------------------------------
    def init_params(self, rng) -> List[Optional[dict]]:
        """Per-layer parameter dicts; shared layers hold None and read the
        primary's slot (reference: neural_net-inl.hpp:216-250 InitModel)."""
        params: List[Optional[dict]] = []
        for li, (info, mod) in enumerate(zip(self.cfg.layers, self.modules)):
            if info.type == SHARED_LAYER or not mod.has_params:
                params.append(None)
            else:
                params.append(mod.init_params(jax.random.fold_in(rng, li)))
        return params

    def _layer_params(self, params, li: int):
        info = self.cfg.layers[li]
        if info.type == SHARED_LAYER:
            return params[info.primary_layer_index]
        return params[li]

    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def traced_on(self, mesh):
        """Within the context ``apply`` traces for ``mesh`` instead of
        the trainer's: an export's own, None for a single-device
        artifact. The layers build their shard_maps (``pallas_env.
        per_shard``, sequence parallelism) from the mesh they are
        handed, and a program traced over the training mesh can only
        be called on that many devices."""
        prev, self.mesh = self.mesh, mesh
        try:
            yield
        finally:
            self.mesh = prev

    def apply(self, params, data: jnp.ndarray,
              extra_data: Sequence[jnp.ndarray] = (),
              labels: Optional[List[jnp.ndarray]] = None,
              train: bool = False,
              rng: Optional[jnp.ndarray] = None,
              epoch=0,
              state_out: Optional[Dict] = None,
              stats_out: Optional[Dict] = None
              ) -> Tuple[Dict[int, jnp.ndarray], jnp.ndarray]:
        """Run the DAG; returns ({node_index: value}, scalar_loss).

        ``labels`` is the list of label-field arrays in label_range order
        (reference GetLabelInfo, nnet_impl-inl.hpp:271-285).
        ``state_out``, when given, receives {(layer_index, tag): value}
        non-trainable state writes (BN running stats) for the trainer to
        fold back into params. ``stats_out`` receives the layers'
        device-side counters, {(layer_index, name): array}
        (``ApplyContext.stats``).
        """
        ctx = L.ApplyContext(
            train=train, rng=rng, labels=labels,
            batch_size=self.batch_size, update_period=self.update_period,
            epoch=epoch, compute_dtype=self.compute_dtype,
            mesh=self.mesh, seq_axis=self.seq_axis,
            platform=self.platform)
        if data.dtype == jnp.uint8:
            # raw-pixel feed: normalize on device, fused into the step
            # (the reference normalizes on the host and ships float32,
            # iter_augment_proc-inl.hpp:98-162 — 4x the PCIe/ICI bytes)
            x = data.astype(self.compute_dtype)
            if self.input_norm is not None:
                mean, scale = self.input_norm
                mean = np.asarray(mean, np.float32)
                c = self.cfg.input_shape[0]
                if self.input_s2d and data.shape[1] != c:
                    # batch arrived host-packed: pack the mean the same
                    # way (trace-time constant; packed zero rows subtract
                    # mean but only zero kernel taps ever read them)
                    from .layers import s2d_pack
                    full = np.broadcast_to(
                        mean, tuple(self.cfg.input_shape))
                    mean = s2d_pack(full[None], self.input_s2d)[0]
                x = (x - jnp.asarray(mean, x.dtype)) * jnp.asarray(
                    scale, x.dtype)
            data = x
        values: Dict[int, jnp.ndarray] = {0: data}
        for i, x in enumerate(extra_data):
            values[i + 1] = x
        # needs-input-grad propagation (mirrors analytic_model_flops):
        # lets Pallas layers skip charging a dX their custom-vjp output
        # XLA will dead-code-eliminate (the classic first-conv case)
        has_grad = [False] * self.cfg.num_nodes
        for li, (info, mod) in enumerate(zip(self.cfg.layers, self.modules)):
            upstream = any(has_grad[ni] for ni in info.nindex_in)
            inputs = [values[ni] for ni in info.nindex_in]
            # the layer's type on every operation it makes (metadata:
            # obs.trace.scope_of reads it off a device trace)
            with jax.named_scope(mod.type_name):
                layer_ctx = dataclasses.replace(
                    ctx, layer_index=li, needs_input_grad=upstream,
                    rng=(jax.random.fold_in(rng, li)
                         if rng is not None else None))
                outputs = mod.apply(self._layer_params(params, li),
                                    inputs, layer_ctx)
            for no, v in zip(info.nindex_out, outputs):
                values[no] = v
            flag = upstream or mod.has_params
            for no in info.nindex_out:
                has_grad[no] = flag
        if ctx.losses:
            loss = sum(ctx.losses[1:], ctx.losses[0])
        else:
            loss = jnp.zeros((), jnp.float32)
        if state_out is not None:
            state_out.update(ctx.state_updates)
        if stats_out is not None:
            stats_out.update(ctx.stats)
        # trace-time side record (plain Python floats; tracing runs once
        # per compiled program, so this survives for step_cost_analysis)
        self.pallas_flops_record[bool(train)] = list(ctx.pallas_flops)
        return values, loss

    # ------------------------------------------------------------------
    def analytic_model_flops(self, train: bool = True) -> dict:
        """Analytic MODEL flops of one step over the whole DAG.

        The MFU basis (matmul-dominant terms, backward at the standard
        2x-forward rate, causal attention at the useful half, no
        rematerialization replay — the literature definition, PaLM
        appendix B). This exists because XLA's own cost model
        (Trainer.step_cost_analysis) under-counts two program shapes,
        both verified on this tree: a ``lax.scan`` body is counted ONCE
        regardless of trip count (the transformer_stack scans depth),
        and a Pallas kernel is an opaque custom_call counted as zero
        flops. Per-layer formulas live on Layer.analytic_flops.

        Returns {"fwd", "bwd", "total", "per_layer"} where per_layer is
        a [{layer, type, fwd, bwd}] breakdown of nonzero contributors.
        """
        # dX of a layer is dead code unless some layer strictly upstream
        # holds trainable parameters (the classic first-conv case):
        # propagate a needs-input-grad flag through the DAG in
        # connection order (self-loops overwrite, like node values)
        has_grad = [False] * self.cfg.num_nodes
        fwd = bwd = 0.0
        per_layer = []
        for li, (info, mod) in enumerate(zip(self.cfg.layers,
                                             self.modules)):
            upstream = any(has_grad[ni] for ni in info.nindex_in)
            f, b = mod.analytic_flops(skip_dx=not upstream)
            fwd += f
            bwd += b
            if f or b:
                per_layer.append({"layer": li, "type": mod.type_name,
                                  "fwd": f, "bwd": b})
            flag = upstream or mod.has_params
            for no in info.nindex_out:
                has_grad[no] = flag
        out_bwd = bwd if train else 0.0
        return {"fwd": fwd, "bwd": out_bwd, "total": fwd + out_bwd,
                "per_layer": per_layer}

    # ------------------------------------------------------------------
    def loss_fn(self, params, data, labels, rng, epoch,
                extra_data=()) -> jnp.ndarray:
        """Scalar training loss — the jax.grad entry point."""
        _, loss = self.apply(params, data, extra_data=extra_data,
                             labels=labels, train=True, rng=rng, epoch=epoch)
        return loss

    @property
    def out_node(self) -> int:
        """Default eval/predict node = last node (reference
        nnet_impl-inl.hpp:190 nodes.back())."""
        return self.cfg.num_nodes - 1
