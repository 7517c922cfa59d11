"""Layer library: every cxxnet layer as a pure ``init``/``apply`` function.

Design. The reference's ``ILayer`` (reference: src/layer/layer.h:162-279)
is an imperative fwd/bwd pair mutating device nodes in place, with
gradients accumulated by hand. Here each layer is a *pure function
module*:

  * ``infer_shape(in_shapes) -> out_shapes``   (mirrors InitConnection)
  * ``init_params(rng) -> dict[str, jnp.ndarray]``  (mirrors InitModel)
  * ``apply(params, inputs, ctx) -> outputs``   (mirrors Forward)

Backprop is *derived*, not written: the graph interpreter (model.py)
differentiates the composed forward with ``jax.grad``. Loss layers add a
scalar term to ``ctx.losses`` whose gradient w.r.t. their input equals the
reference's hand-set gradient, including the
``grad_scale/(batch_size*update_period)`` scaling
(reference: src/layer/loss/loss_layer_base-inl.hpp:62).

Node layout matches the reference (reference: src/layer/layer.h:31-46):
4D ``(batch, channel, height, width)``; flat vectors are
``(batch, 1, 1, n)``. The "mat view" is the reshape to ``(batch, n)``.

Every shape is static, control flow is trace-friendly, and the matmuls /
convs sit directly on the MXU via ``jnp.dot`` / ``lax.conv_general_dilated``.
"""

from __future__ import annotations

import collections
import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

Shape4 = Tuple[int, int, int, int]
Params = Dict[str, jnp.ndarray]

_REGISTRY: Dict[str, Callable[..., "Layer"]] = {}


def register(name: str):
    def deco(cls):
        _REGISTRY[name] = cls
        cls.type_name = name
        return cls
    return deco


def create_layer(type_name: str, cfg: Sequence[Tuple[str, str]],
                 label_name_map: Optional[Dict[str, int]] = None) -> "Layer":
    """Factory mirroring CreateLayer_ (reference: src/layer/layer_impl-inl.hpp:37-79)."""
    if type_name not in _REGISTRY:
        raise ValueError('unknown layer type: "%s"' % type_name)
    layer = _REGISTRY[type_name]()
    layer.label_name_map = label_name_map or {"label": 0}
    for k, v in cfg:
        layer.set_param(k, v)
    # keys this layer SAW (globals + its bucket); with
    # LayerParam.unknown_keys this yields the keys it consumed — the
    # per-layer half of Trainer.unconsumed_keys
    layer._cfg_keys = {k for k, _ in cfg}
    return layer


def _part(name: str):
    """``jax.named_scope(name)``: a word of ``obs.trace.PARTS`` on every
    operation made inside it (a context manager or a decorator), so that
    a device trace can be split by the part of the block that made each
    (``obs.trace.scope_of``). Metadata only: the lowered program is the
    same text without it. A Pallas kernel's own scope stays innermost."""
    return jax.named_scope(name)


# ----------------------------------------------------------------------
@dataclass
class LayerParam:
    """Common hyper-parameters (reference: src/layer/param.h:15-111)."""
    num_hidden: int = 0
    init_sigma: float = 0.01
    init_uniform: float = -1.0
    init_bias: float = 0.0
    num_channel: int = 0
    random_type: int = 0        # 0 gaussian, 1 uniform/xavier, 2 kaiming
    num_group: int = 1
    kernel_height: int = 0
    kernel_width: int = 0
    stride: int = 1
    pad_y: int = 0
    pad_x: int = 0
    no_bias: int = 0
    silent: int = 0
    num_input_channel: int = 0
    num_input_node: int = 0
    # keys no set_param branch recognized — the terminal of every
    # layer's set_param chain records them here so the trainer's
    # unconsumed-key audit can tell a typo'd knob from a consumed one
    # (the reference broadcast-and-ignores, neural_net-inl.hpp:252-264;
    # a silently no-op'd warmup_epochs corrupted a recorded r3 run)
    unknown_keys: set = field(default_factory=set)

    def set_param(self, name: str, val: str) -> bool:
        ok = True
        if name == "init_sigma":
            self.init_sigma = float(val)
        elif name == "init_uniform":
            self.init_uniform = float(val)
        elif name == "init_bias":
            self.init_bias = float(val)
        elif name == "random_type":
            if val == "gaussian":
                self.random_type = 0
            elif val in ("uniform", "xavier"):
                self.random_type = 1
            elif val == "kaiming":
                self.random_type = 2
            else:
                raise ValueError("invalid random_type %s" % val)
        elif name == "nhidden":
            self.num_hidden = int(val)
        elif name == "nchannel":
            self.num_channel = int(val)
        elif name == "ngroup":
            self.num_group = int(val)
        elif name == "kernel_size":
            self.kernel_height = self.kernel_width = int(val)
        elif name == "kernel_height":
            self.kernel_height = int(val)
        elif name == "kernel_width":
            self.kernel_width = int(val)
        elif name == "stride":
            self.stride = int(val)
        elif name == "pad":
            self.pad_y = self.pad_x = int(val)
        elif name == "pad_y":
            self.pad_y = int(val)
        elif name == "pad_x":
            self.pad_x = int(val)
        elif name == "no_bias":
            self.no_bias = int(val)
        elif name == "silent":
            self.silent = int(val)
        else:
            ok = False
            self.unknown_keys.add(name)
        return ok

    def rand_init_weight(self, rng, shape, in_num: int, out_num: int):
        """Weight init (reference: src/layer/param.h:113-138)."""
        if self.random_type == 0:
            return jax.random.normal(rng, shape, jnp.float32) * self.init_sigma
        if self.random_type == 1:
            a = math.sqrt(3.0 / (in_num + out_num))
            if self.init_uniform > 0:
                a = self.init_uniform
            return jax.random.uniform(rng, shape, jnp.float32, -a, a)
        if self.random_type == 2:
            if self.num_hidden > 0:
                sigma = math.sqrt(2.0 / self.num_hidden)
            else:
                sigma = math.sqrt(
                    2.0 / (self.num_channel * self.kernel_width
                           * self.kernel_height))
            return jax.random.normal(rng, shape, jnp.float32) * sigma
        raise ValueError("unsupported random_type %d" % self.random_type)


@dataclass
class ApplyContext:
    """Per-step context threaded through layer application.

    Replaces the reference's LabelInfo + global SetParam broadcast
    (reference: src/layer/layer.h:96-121, loss_layer_base-inl.hpp:22-27).
    """
    train: bool = False
    rng: Optional[jnp.ndarray] = None         # folded per layer by the model
    labels: Optional[List[jnp.ndarray]] = None  # one (batch, w) per label field
    batch_size: int = 1                        # GLOBAL batch size
    update_period: int = 1
    epoch: jnp.ndarray = 0                     # update counter (may be traced)
    losses: List[jnp.ndarray] = field(default_factory=list)
    compute_dtype: jnp.dtype = jnp.float32
    # non-trainable layer-state writes (running BN stats): layers record
    # {(layer_index, tag): new_value}; the trainer folds them back into
    # params after the optimizer step
    layer_index: int = -1
    state_updates: Dict = field(default_factory=dict)
    # device-side counters a layer computes in the step itself
    # ({(layer_index, name): array}, e.g. the routed layer's loads): the
    # trainer returns them from the train step and reads a finished
    # step's without waiting on the device
    stats: Dict = field(default_factory=dict)
    # sequence parallelism: when set, attention layers run ring attention
    # sharded over this mesh axis (cxxnet_tpu/ops/ring_attention.py)
    mesh: Optional[object] = None
    seq_axis: Optional[str] = None
    # the platform the surrounding jit targets ("tpu"/"cpu"/...), set by
    # the trainer from its mesh — gates compiled-vs-interpreted Pallas
    # (the process default backend can differ from the jit target)
    platform: str = "cpu"
    # analytic hardware-flop records for Pallas kernels, appended at
    # trace time by layers that invoke one (XLA's cost model sees a
    # pallas_call as an opaque custom_call and counts 0 flops for it —
    # VERDICT r3 #2). model.py copies the list onto the Network after
    # each trace so step_cost_analysis can report what XLA missed.
    pallas_flops: List = field(default_factory=list)
    # False when no layer strictly upstream holds trainable params, so
    # XLA dead-code-eliminates this layer's input gradient (set per
    # layer by model.py; mirrors Network.analytic_model_flops skip_dx)
    needs_input_grad: bool = True

    def add_pallas_flops(self, kernel: str, fwd: float, bwd: float,
                         interpret: bool) -> None:
        """Record one Pallas kernel's analytic (fwd, bwd) hardware flops
        for this trace. ``bwd`` should be 0 outside training traces.
        ``interpret`` is the flag the caller hands that kernel, so the
        record says which form the traced program holds."""
        self.pallas_flops.append({"kernel": kernel, "fwd": float(fwd),
                                  "bwd": float(bwd),
                                  "interpret": bool(interpret)})


def _mat(x: jnp.ndarray) -> jnp.ndarray:
    """Flat 2D view of a node (reference: layer.h:48-50 FlatTo2D)."""
    return x.reshape(x.shape[0], -1)


def _is_mat(shape: Shape4) -> bool:
    return shape[1] == 1 and shape[2] == 1


class Layer:
    """Base class; one instance per connection, holding static config only."""
    type_name = "?"
    has_params = False
    is_loss = False
    # parameter tags that are STATE, not trainable weights: excluded from
    # the optimizer; written via ctx.state_updates (e.g. BN running stats)
    state_tags: Tuple[str, ...] = ()

    def __init__(self) -> None:
        self.param = LayerParam()
        self.label_name_map: Dict[str, int] = {"label": 0}
        self.in_shapes: List[Shape4] = []
        self.out_shapes: List[Shape4] = []

    # -- config ---------------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        self.param.set_param(name, val)

    # -- structure ------------------------------------------------------
    def infer_shape(self, in_shapes: List[Shape4]) -> List[Shape4]:
        self._check_arity(in_shapes, 1, 1)
        out = self._infer(in_shapes)
        self.in_shapes = list(in_shapes)
        self.out_shapes = out
        return out

    def _infer(self, in_shapes: List[Shape4]) -> List[Shape4]:
        return [in_shapes[0]]

    def _check_arity(self, in_shapes, nin, nout) -> None:
        if nin is not None and len(in_shapes) != nin:
            raise ValueError("%s: layer only supports %d input(s)"
                             % (self.type_name, nin))

    # -- params ---------------------------------------------------------
    def init_params(self, rng) -> Params:
        return {}

    # -- compute --------------------------------------------------------
    def apply(self, params: Params, inputs: List[jnp.ndarray],
              ctx: ApplyContext) -> List[jnp.ndarray]:
        raise NotImplementedError

    # -- accounting -----------------------------------------------------
    def analytic_flops(self, skip_dx: bool = False
                       ) -> Tuple[float, float]:
        """Analytic MODEL flops of one apply: ``(fwd, bwd)``.

        MFU basis (the literature definition, e.g. the PaLM paper's
        appendix): matmul-dominant terms only, each matmul charged 2x
        forward in the backward pass (dX + dW), causal attention at the
        useful half — NO rematerialization replays and NO
        flash-recompute extras (those are hardware flops, HFU).
        Elementwise / pooling / norm layers return (0, 0): their VPU
        flops are negligible against the MXU terms an MFU compares to
        peak, and excluding them keeps the definition implementation-
        independent.

        ``skip_dx`` — no layer upstream holds trainable parameters, so
        XLA dead-code-eliminates this layer's input gradient (the
        classic first-conv case); the dX half of the backward is then
        not charged. Called after infer_shape (uses in/out_shapes).
        """
        return 0.0, 0.0


# ======================================================================
# dense / structural layers
# ======================================================================
@register("fullc")
class FullConnectLayer(Layer):
    """out = in . W^T + bias (reference: src/layer/fullc_layer-inl.hpp:100-117).

    Weight stored as (nhidden, ninput) exactly like the reference wmat_.
    """
    has_params = True

    def __init__(self):
        super().__init__()
        self.seq = 0

    def set_param(self, name, val):
        if name == "seq":
            self.seq = int(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        (n, c, h, w) = in_shapes[0]
        # matrix input like the reference; ``seq = 1`` opts into
        # position-wise application on (b, 1, s, e) sequence nodes —
        # the per-token projection a language-model head needs. The
        # opt-in keeps the reference's forgot-the-flatten error for
        # image nodes.
        if self.seq:
            if c != 1:
                raise ValueError("FullcLayer(seq): input must be "
                                 "(b,1,s,e)")
        elif not _is_mat(in_shapes[0]):
            raise ValueError("FullcLayer: input needs to be a matrix "
                             "(or set seq = 1 for position-wise use)")
        if self.param.num_hidden <= 0:
            raise ValueError("FullcLayer: must set nhidden correctly")
        if self.param.num_input_node == 0:
            self.param.num_input_node = w
        elif self.param.num_input_node != w:
            raise ValueError("FullcLayer: input hidden nodes inconsistent")
        return [(n, 1, h, self.param.num_hidden)]

    def init_params(self, rng) -> Params:
        nh, ni = self.param.num_hidden, self.param.num_input_node
        wmat = self.param.rand_init_weight(rng, (nh, ni), ni, nh)
        p = {"wmat": wmat}
        if self.param.no_bias == 0:
            p["bias"] = jnp.full((nh,), self.param.init_bias, jnp.float32)
        return p

    def analytic_flops(self, skip_dx=False):
        n, _, s, e = self.in_shapes[0]
        f = 2.0 * n * s * e * self.param.num_hidden
        return f, f if skip_dx else 2.0 * f

    def apply(self, params, inputs, ctx):
        n, _, s, e = inputs[0].shape
        x = inputs[0].reshape(n * s, e)
        # bf16 operands, f32 result: the MXU accumulates f32 internally;
        # avoiding preferred_element_type keeps the grad transposes
        # same-dtype (their f32 accumulation is likewise implicit)
        w = params["wmat"].astype(ctx.compute_dtype)
        out = jnp.dot(x.astype(ctx.compute_dtype), w.T).astype(jnp.float32)
        if self.param.no_bias == 0:
            out = out + params["bias"]
        return [out.reshape(n, 1, s, self.param.num_hidden)]


@register("embed")
class EmbeddingLayer(Layer):
    """Token embedding lookup: (b, 1, s, 1) ids -> (b, 1, s, nhidden).

    No reference analogue (cxxnet is a vision framework); this is the
    entry point for token models feeding the attention /
    transformer_stack layers. Ids arrive as the float data tensor (the
    pipeline's uniform dtype) and are cast to int32. ``learn_pos = 1``
    adds a learned positional embedding (attention is otherwise
    permutation-equivariant). Config: ``vocab_size``, ``nhidden``,
    ``learn_pos``, ``init_sigma`` (the table's normal init; default
    nhidden^-0.5). Tags: ``wmat`` (vocab, nhidden), ``pos``
    (seq, nhidden).
    """
    has_params = True
    param_tags = ("wmat", "pos")

    def __init__(self):
        super().__init__()
        self.vocab_size = 0
        self.learn_pos = 0
        self.sigma = 0.0

    def set_param(self, name, val):
        if name == "vocab_size":
            self.vocab_size = int(val)
        elif name == "learn_pos":
            self.learn_pos = int(val)
        elif name == "init_sigma":
            self.sigma = float(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        n, c, s, w = in_shapes[0]
        if c != 1 or w != 1:
            raise ValueError("embed: input must be (batch,1,seq,1) ids")
        if self.vocab_size <= 0 or self.param.num_hidden <= 0:
            raise ValueError("embed: must set vocab_size and nhidden")
        self.seq_len = s
        return [(n, 1, s, self.param.num_hidden)]

    def init_params(self, rng) -> Params:
        e = self.param.num_hidden
        r1, r2 = jax.random.split(rng)
        p = {"wmat": jax.random.normal(r1, (self.vocab_size, e),
                                       jnp.float32)
             * (self.sigma or e ** -0.5)}
        if self.learn_pos:
            p["pos"] = jax.random.normal(r2, (self.seq_len, e),
                                         jnp.float32) * 0.02
        return p

    def apply(self, params, inputs, ctx):
        n, _, s, _ = inputs[0].shape
        ids = jnp.clip(inputs[0].reshape(n, s).astype(jnp.int32),
                       0, self.vocab_size - 1)
        # gather first, cast after: converting the whole (vocab, e) table
        # per step would touch V*e elements to use b*s rows
        out = jnp.take(params["wmat"], ids,
                       axis=0).astype(ctx.compute_dtype)  # (b, s, e)
        if self.learn_pos:
            out = out + params["pos"].astype(ctx.compute_dtype)[None]
        return [out.astype(jnp.float32).reshape(
            n, 1, s, self.param.num_hidden)]


@register("bd_noise")
class BlockDiffusionNoiseLayer(Layer):
    """The noising step of block-diffusion training (BD3-LM), ahead of
    ``embed``: (b, 1, s, 1) clean ids -> two nodes, the model's input
    ``[x_t ; x_0]`` as (b, 1, 2s, 1) ids and (b, 1, s, 2) holding each
    position's target (the clean id) and loss weight for ``lm_head``
    under ``objective = block_diffusion``.

    For each row and block b of ``block_len`` tokens a level
    ``t_b ~ U(0, 1)`` floored at ``t_floor``; token i of the block
    becomes ``mask_token`` where ``u_i < t_b``, ``u ~ U(0, 1)`` a
    position; the weight is ``[masked_i] / t_b``. Both draws come from
    the step's key (``ctx.rng``, which the trainer carries on the device
    and splits once a step, folded with this layer's index):
    ``t = uniform(fold_in(key, 0), (b, s / block_len))``,
    ``u = uniform(fold_in(key, 1), (b, s))``. Config: ``mask_token``,
    ``block_len`` (default 4), ``t_floor`` (default 1e-3). No params.
    """

    def __init__(self):
        super().__init__()
        self.mask_token = -1
        self.block_len = 4
        self.t_floor = 1e-3

    def set_param(self, name, val):
        if name == "mask_token":
            self.mask_token = int(val)
        elif name == "block_len":
            self.block_len = int(val)
        elif name == "t_floor":
            self.t_floor = float(val)
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes):
        self._check_arity(in_shapes, 1, 2)
        n, c, s, w = in_shapes[0]
        if c != 1 or w != 1:
            raise ValueError("bd_noise: input must be (batch,1,seq,1) ids")
        if self.mask_token < 0:
            raise ValueError("bd_noise: must set mask_token")
        if s % self.block_len:
            raise ValueError("bd_noise: seq %d is not whole blocks of %d"
                             % (s, self.block_len))
        self.in_shapes = list(in_shapes)
        self.out_shapes = [(n, 1, 2 * s, 1), (n, 1, s, 2)]
        return self.out_shapes

    def apply(self, params, inputs, ctx):
        if ctx.rng is None:
            raise ValueError(
                "bd_noise: no key to draw the noise from: the "
                "block-diffusion objective is a training objective; "
                "evaluating or predicting through it is not implemented")
        n, _, s, _ = inputs[0].shape
        ids = inputs[0].reshape(n, s)
        t = jnp.maximum(jax.random.uniform(
            jax.random.fold_in(ctx.rng, 0), (n, s // self.block_len)),
            self.t_floor)
        t = jnp.repeat(t, self.block_len, axis=1)
        masked = jax.random.uniform(jax.random.fold_in(ctx.rng, 1),
                                    (n, s)) < t
        noisy = jnp.where(masked, jnp.float32(self.mask_token),
                          ids.astype(jnp.float32))
        both = jnp.concatenate([noisy, ids.astype(jnp.float32)], axis=1)
        side = jnp.stack([ids.astype(jnp.float32),
                          masked.astype(jnp.float32) / t], axis=-1)
        return [both.reshape(n, 1, 2 * s, 1), side.reshape(n, 1, s, 2)]


@register("im2seq")
class Im2SeqLayer(Layer):
    """(b, c, h, w) feature grid -> (b, 1, h*w, c) patch-token sequence.

    The patchify bridge for vision transformers: a strided conv
    produces (b, embed, H/p, W/p); this layer lays that grid out as
    H*W/p² tokens of width embed so the attention / transformer_stack
    layers apply unchanged. ``learn_pos = 1`` (default) adds a learned
    positional embedding (tag ``pos`` — the encoder is otherwise
    permutation-equivariant over patches). No reference analogue
    (SURVEY.md §5: the reference predates vision transformers; this
    extends the same config dialect).
    """
    has_params = True
    param_tags = ("pos",)

    def __init__(self):
        super().__init__()
        self.learn_pos = 1

    def set_param(self, name, val):
        if name == "learn_pos":
            self.learn_pos = int(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        n, c, h, w = in_shapes[0]
        self.seq_len, self.embed = h * w, c
        return [(n, 1, h * w, c)]

    def init_params(self, rng) -> Params:
        if not self.learn_pos:
            return {}
        return {"pos": jax.random.normal(
            rng, (self.seq_len, self.embed), jnp.float32) * 0.02}

    def apply(self, params, inputs, ctx):
        n, c, h, w = inputs[0].shape
        out = inputs[0].reshape(n, c, h * w).transpose(0, 2, 1)
        if self.learn_pos:
            out = out + params["pos"].astype(out.dtype)[None]
        return [out.reshape(n, 1, h * w, c)]


@register("seq_pool")
class SeqPoolLayer(Layer):
    """(b, 1, s, e) -> (b, 1, 1, e): mean over the token axis — the
    mean-pool classifier head for patch-token encoders (ViT-style);
    no reference analogue (sequence nodes postdate the reference)."""

    def _infer(self, in_shapes):
        n, c, s, e = in_shapes[0]
        if c != 1:
            raise ValueError(
                "seq_pool: input must be (batch,1,seq,embed)")
        return [(n, 1, 1, e)]

    def apply(self, params, inputs, ctx):
        return [jnp.mean(inputs[0], axis=2, keepdims=True)]


def moe_capacity(topk: int, n_tokens: int, nexpert: int,
                 factor: float) -> int:
    """Per-expert slot count for token-choice routing (shared by
    moe_fullc and the MoE transformer blocks)."""
    return max(int(math.ceil(topk * n_tokens / nexpert * factor)), 1)


def moe_route(x, gate, topk: int, capacity: int, dt):
    """GShard-style top-k token-choice routing, shared by moe_fullc and
    the MoE transformer blocks.

    x (B, i) tokens, gate (E, i) router weights. Returns (dispatch
    (B, E, C) one-hot slots, combine (B, E, C) gate-weighted slots,
    aux load-balance loss scalar — GShard eq.4). All shapes static
    (MXU-friendly one-hot einsum dispatch); tokens over an expert's
    capacity drop.
    """
    B, E = x.shape[0], gate.shape[0]
    C = capacity
    logits = jnp.dot(x.astype(dt), gate.astype(dt).T)      # (B, E)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # iterative top-k selection (k small): one-hot choice per round,
    # chosen experts masked out for the next round
    masked = gates
    dispatch = jnp.zeros((B, E, C), jnp.float32)
    combine = jnp.zeros((B, E, C), jnp.float32)
    # position counters per expert accumulate across rounds so that
    # round-2 tokens take slots after round-1 tokens
    base_count = jnp.zeros((E,), jnp.int32)
    frac_routed = jnp.zeros((E,), jnp.float32)
    for _ in range(topk):
        idx = jnp.argmax(masked, axis=-1)               # (B,)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        frac_routed = frac_routed + onehot.mean(axis=0)
        # slot position of each token within its chosen expert
        pos = jnp.cumsum(onehot, axis=0) - onehot + base_count
        keep = (pos < C) * onehot                       # drop overflow
        slot = jax.nn.one_hot(pos.astype(jnp.int32), C,
                              dtype=jnp.float32) * keep[..., None]
        gate_w = (gates * onehot).sum(-1, keepdims=True)  # (B, 1)
        dispatch = dispatch + slot
        combine = combine + slot * gate_w[..., None]
        base_count = base_count + keep.sum(0).astype(jnp.int32)
        masked = masked * (1.0 - onehot)

    aux = E * jnp.sum(gates.mean(axis=0) * frac_routed / topk)
    return dispatch, combine, aux


def moe_mlp(tok, lp, topk: int, nexpert: int, cap_f: float, dt):
    """Routed-expert relu MLP on (N, e) tokens -> ((N, e) out, aux loss).

    The SINGLE implementation of the scatter -> expert matmul -> gather
    einsum chain, shared by TransformerStackLayer's training forward and
    generate.py's cached decode — the KV-cache path's output parity with
    training holds by construction instead of by duplicated math.
    ``lp`` carries one layer's ``gate`` (E, e), ``w1`` (E, m, e),
    ``w2`` (E, e, m)."""
    C = moe_capacity(topk, tok.shape[0], nexpert, cap_f)
    dispatch, combine, aux = moe_route(tok, lp["gate"], topk, C, dt)
    xin = jnp.einsum("bec,bi->eci", dispatch.astype(dt), tok)
    hmid = jax.nn.relu(
        jnp.einsum("eci,emi->ecm", xin, lp["w1"].astype(dt)))
    yexp = jnp.einsum("ecm,eom->eco", hmid, lp["w2"].astype(dt))
    y = jnp.einsum("bec,eco->bo", combine.astype(dt), yexp)
    return y, aux


@register("moe_fullc")
class MoEFullConnectLayer(Layer):
    """Mixture-of-experts fullc with top-k token-choice routing.

    No reference counterpart (cxxnet predates MoE; SURVEY.md §2.7 lists
    expert parallelism as absent) — TPU-first capability. GShard-style
    dense dispatch: a router picks top-``moe_topk`` experts per token,
    tokens are scattered to per-expert capacity slots with one-hot
    einsums (static shapes, MXU-friendly), each expert applies its own
    (nhidden, nin) fullc, and combine weights gather the results.
    Tokens over an expert's capacity are dropped (output 0 for that
    expert's contribution), the standard GShard behavior.

    Params: ``wmat`` (E, nhidden, nin), ``bias`` (E, nhidden), ``gate``
    (E, nin). On a 2D (data, model) mesh the experts shard over the
    ``model`` axis (expert parallelism): each device holds E/n experts
    and GSPMD inserts the dispatch/combine all-to-alls.

    Config: ``nexpert``, ``moe_topk`` (default 2), ``capacity_factor``
    (default 1.25), ``moe_loss`` (aux load-balance loss weight,
    default 0.01).
    """
    has_params = True
    param_tags = ("wmat", "bias", "gate")

    def __init__(self):
        super().__init__()
        self.nexpert = 0
        self.topk = 2
        self.capacity_factor = 1.25
        self.moe_loss = 0.01

    def set_param(self, name, val):
        if name == "nexpert":
            self.nexpert = int(val)
        elif name == "moe_topk":
            self.topk = int(val)
        elif name == "capacity_factor":
            self.capacity_factor = float(val)
        elif name == "moe_loss":
            self.moe_loss = float(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        (n, c, h, w) = in_shapes[0]
        if not _is_mat(in_shapes[0]):
            raise ValueError("MoEFullcLayer: input needs to be a matrix")
        if self.param.num_hidden <= 0 or self.nexpert <= 0:
            raise ValueError("MoEFullcLayer: must set nhidden and nexpert")
        if self.topk > self.nexpert:
            raise ValueError("MoEFullcLayer: moe_topk > nexpert")
        self.param.num_input_node = w
        return [(n, 1, 1, self.param.num_hidden)]

    def init_params(self, rng) -> Params:
        nh, ni, e = self.param.num_hidden, self.param.num_input_node, \
            self.nexpert
        rw, rg = jax.random.split(rng)
        return {
            "wmat": self.param.rand_init_weight(rw, (e, nh, ni), ni, nh),
            "bias": jnp.full((e, nh), self.param.init_bias, jnp.float32),
            "gate": jax.random.normal(rg, (e, ni), jnp.float32)
            * (ni ** -0.5)}


    def analytic_flops(self, skip_dx=False):
        n = self.in_shapes[0][0]
        ni, nh, E = self.param.num_input_node, self.param.num_hidden, \
            self.nexpert
        C = moe_capacity(self.topk, n, E, self.capacity_factor)
        # gate + dispatch/combine one-hot einsums + expert matmul
        fwd = 2.0 * n * E * ni + 2.0 * n * E * C * (ni + nh) \
            + 2.0 * E * C * ni * nh
        return fwd, fwd if skip_dx else 2.0 * fwd

    def apply(self, params, inputs, ctx):
        x = _mat(inputs[0])                         # (B, ni)
        dt = ctx.compute_dtype
        xc = x.astype(dt)
        C = moe_capacity(self.topk, x.shape[0], self.nexpert,
                         self.capacity_factor)
        dispatch, combine, aux = moe_route(
            xc, params["gate"], self.topk, C, dt)
        if ctx.train and self.moe_loss > 0.0:
            ctx.losses.append(self.moe_loss * aux)
        # scatter -> expert fullc -> gather (einsum dispatch, all static)
        xin = jnp.einsum("bec,bi->eci", dispatch.astype(dt), xc)
        h = jnp.einsum("eci,eoi->eco", xin, params["wmat"].astype(dt))
        h = h + params["bias"][:, None, :].astype(dt)
        out = jnp.einsum("bec,eco->bo", combine.astype(dt), h)
        n = inputs[0].shape[0]
        return [out.astype(jnp.float32).reshape(
            n, 1, 1, self.param.num_hidden)]


@register("flatten")
class FlattenLayer(Layer):
    """(b,c,h,w) -> (b,1,1,c*h*w) (reference: src/layer/flatten_layer-inl.hpp:14-29)."""

    def _infer(self, in_shapes):
        n, c, h, w = in_shapes[0]
        return [(n, 1, 1, c * h * w)]

    def apply(self, params, inputs, ctx):
        n = inputs[0].shape[0]
        return [inputs[0].reshape(n, 1, 1, -1)]


@register("bias")
class BiasLayer(Layer):
    """Self-loop additive bias for flat nodes
    (reference: src/layer/bias_layer-inl.hpp:14-86)."""
    has_params = True

    def _infer(self, in_shapes):
        if not _is_mat(in_shapes[0]):
            raise ValueError("BiasLayer only works on flat nodes")
        if self.param.num_input_node == 0:
            self.param.num_input_node = in_shapes[0][3]
        elif self.param.num_input_node != in_shapes[0][3]:
            raise ValueError("BiasLayer: input hidden nodes inconsistent")
        return [in_shapes[0]]

    def init_params(self, rng) -> Params:
        return {"bias": jnp.full((self.param.num_input_node,),
                                 self.param.init_bias, jnp.float32)}

    def apply(self, params, inputs, ctx):
        return [inputs[0] + params["bias"].reshape(1, 1, 1, -1)]


@register("split")
class SplitLayer(Layer):
    """1 -> N copy; gradient is the sum (derived automatically)
    (reference: src/layer/split_layer-inl.hpp:12-47)."""

    n_out = 1

    def infer_shape(self, in_shapes):
        out = [in_shapes[0]] * self.n_out
        self.in_shapes = list(in_shapes)
        self.out_shapes = out
        return out

    def apply(self, params, inputs, ctx):
        return [inputs[0]] * self.n_out


@register("elewise_add")
class ElementwiseAddLayer(Layer):
    """N -> 1 elementwise sum of same-shape nodes.

    No reference analogue (cxxnet predates residual networks); this is
    the residual-connection primitive: ``layer[a,b->c] = elewise_add``
    closes a skip connection, enabling ResNet-family configs with the
    existing split/conv/batch_norm zoo.
    """

    def infer_shape(self, in_shapes):
        if len(in_shapes) < 2:
            raise ValueError("elewise_add needs at least 2 inputs")
        for s in in_shapes[1:]:
            if s != in_shapes[0]:
                raise ValueError(
                    "elewise_add shapes must match: %s vs %s"
                    % (in_shapes[0], s))
        self.in_shapes = list(in_shapes)
        self.out_shapes = [in_shapes[0]]
        return self.out_shapes

    def apply(self, params, inputs, ctx):
        out = inputs[0]
        for x in inputs[1:]:
            out = out + x
        return [out]


class _ConcatBase(Layer):
    """N -> 1 concat along an axis (reference: src/layer/concat_layer-inl.hpp:12-82)."""
    axis = 3

    def infer_shape(self, in_shapes):
        if len(in_shapes) < 2 or len(in_shapes) > 4:
            raise ValueError("Concat layer supports 2-4 inputs")
        base = list(in_shapes[0])
        total = 0
        for s in in_shapes:
            total += s[self.axis]
            for j in range(4):
                if j != self.axis and s[j] != base[j]:
                    raise ValueError("Concat shape doesn't match")
        base[self.axis] = total
        out = [tuple(base)]
        self.in_shapes = list(in_shapes)
        self.out_shapes = out
        return out

    def apply(self, params, inputs, ctx):
        return [jnp.concatenate(inputs, axis=self.axis)]


@register("concat")
class ConcatLayer(_ConcatBase):
    axis = 3


@register("ch_concat")
class ChConcatLayer(_ConcatBase):
    axis = 1


# ======================================================================
# activations
# ======================================================================
class _ActivationLayer(Layer):
    """Elementwise activation (reference: src/layer/activation_layer-inl.hpp:12-44).

    The reference computes the backward pass from the *activated* value;
    jax.grad derives the identical expression from this forward.
    """
    fn: Callable[[jnp.ndarray], jnp.ndarray] = staticmethod(lambda x: x)

    def apply(self, params, inputs, ctx):
        return [self.fn(inputs[0])]


@register("relu")
class ReluLayer(_ActivationLayer):
    fn = staticmethod(lambda x: jnp.maximum(x, 0.0))


@register("sigmoid")
class SigmoidLayer(_ActivationLayer):
    fn = staticmethod(jax.nn.sigmoid)


@register("tanh")
class TanhLayer(_ActivationLayer):
    fn = staticmethod(jnp.tanh)


@register("softplus")
class SoftplusLayer(_ActivationLayer):
    # enum exists in the reference (layer.h:290) but no factory case; we
    # provide the real op
    fn = staticmethod(jax.nn.softplus)


@register("xelu")
class XeluLayer(Layer):
    """Leaky relu with divisor b: x>0 ? x : x/b
    (reference: src/layer/xelu_layer-inl.hpp:15-60, op.h xelu)."""

    def __init__(self):
        super().__init__()
        self.b = 5.0

    def set_param(self, name, val):
        if name == "b":
            self.b = float(val)
        else:
            super().set_param(name, val)

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        return [jnp.where(x > 0, x, x / self.b)]


@register("insanity")
class InsanityLayer(Layer):
    """Randomized leaky relu (RReLU): slope divisor ~ U[lb, ub] at train,
    (lb+ub)/2 at eval (reference: src/layer/insanity_layer-inl.hpp:14-106).

    The reference anneals lb/ub toward their midpoint by a per-forward-call
    step counter between calm_start and calm_end; here the annealing step is
    ctx.epoch (the update counter), which is the same scale for
    update_period=1.
    """

    def __init__(self):
        super().__init__()
        self.lb = 5.0
        self.ub = 10.0
        self.calm_start = 0
        self.calm_end = 0

    def set_param(self, name, val):
        if name == "lb":
            self.lb = float(val)
        elif name == "ub":
            self.ub = float(val)
        elif name == "calm_start":
            self.calm_start = int(val)
        elif name == "calm_end":
            self.calm_end = int(val)
        else:
            super().set_param(name, val)

    def _bounds(self, ctx):
        lb = jnp.asarray(self.lb, jnp.float32)
        ub = jnp.asarray(self.ub, jnp.float32)
        if self.calm_end > self.calm_start:
            delta = (self.ub - self.lb) / 2.0 / (self.calm_end - self.calm_start)
            step = jnp.clip(ctx.epoch - self.calm_start, 0,
                            self.calm_end - self.calm_start)
            lb = lb + delta * step
            ub = ub - delta * step
        return lb, ub

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        lb, ub = self._bounds(ctx)
        if ctx.train:
            mask = jax.random.uniform(ctx.rng, x.shape) * (ub - lb) + lb
        else:
            mask = (lb + ub) / 2.0
        return [jnp.where(x > 0, x, x / mask)]


@register("prelu")
class PReluLayer(Layer):
    """Learnable per-channel slope, stored under the "bias" tag like the
    reference (reference: src/layer/prelu_layer-inl.hpp:48-177).

    Forward: mask = clip(slope * noise, 0, 1); out = x>0 ? x : x*mask.
    The slope gradient in the reference is d(out)/d(slope) = min(x,0)*gout
    (prelu_grad) — jax.grad of this forward yields min(x,0)*noise*gout
    which coincides for random=0 (noise==1), the default.
    """
    has_params = True

    def __init__(self):
        super().__init__()
        self.init_slope = 0.25
        self.init_random = 0
        self.random = 0.0
        self.channel = 0

    def set_param(self, name, val):
        if name == "init_slope":
            self.init_slope = float(val)
        elif name == "random_slope":
            self.init_random = int(val)
        elif name == "random":
            self.random = float(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        s = in_shapes[0]
        self.channel = s[3] if s[1] == 1 else s[1]
        self.bcast_axis = 3 if s[1] == 1 else 1
        return [s]

    def init_params(self, rng) -> Params:
        if self.init_random:
            slope = jax.random.uniform(rng, (self.channel,)) * self.init_slope
        else:
            slope = jnp.full((self.channel,), self.init_slope, jnp.float32)
        return {"bias": slope}

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        shape = [1, 1, 1, 1]
        shape[self.bcast_axis] = self.channel
        mask = params["bias"].reshape(shape)
        if ctx.train and self.random > 0:
            noise = (1 + jax.random.uniform(ctx.rng, x.shape)
                     * self.random * 2.0 - self.random)
            mask = mask * noise
        mask = jnp.clip(mask, 0.0, 1.0)
        return [jnp.where(x > 0, x, x * mask)]


@register("dropout")
class DropoutLayer(Layer):
    """Self-loop dropout (reference: src/layer/dropout_layer-inl.hpp:12-70):
    mask = (u < pkeep)/pkeep applied at train time only."""

    def __init__(self):
        super().__init__()
        self.threshold = 0.0

    def set_param(self, name, val):
        if name == "threshold":
            self.threshold = float(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        if not (0.0 <= self.threshold < 1.0):
            raise ValueError("DropoutLayer: invalid threshold")
        return [in_shapes[0]]

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        if not ctx.train or self.threshold == 0.0:
            return [x]
        pkeep = 1.0 - self.threshold
        mask = (jax.random.uniform(ctx.rng, x.shape) < pkeep) / pkeep
        return [x * mask.astype(x.dtype)]


# ======================================================================
# conv stack
# ======================================================================
@register("conv")
class ConvolutionLayer(Layer):
    """Grouped 2D convolution.

    The reference lowers conv to im2col + GEMM with a workspace budget
    (reference: src/layer/convolution_layer-inl.hpp:79-152); on TPU the
    entire loop collapses into one ``lax.conv_general_dilated`` that XLA
    tiles onto the MXU, with ``feature_group_count`` covering ngroup.
    Output shape formula matches InitNode
    (convolution_layer-inl.hpp:174-177): (h + 2p - k)//s + 1.

    Weights are stored reference-style as
    ``(ngroup, nchannel/ngroup, cin/ngroup*kh*kw)`` so checkpoints and the
    visitor API line up; the kernel is reshaped for XLA at apply time
    (free at compile time).

    ``space_to_depth = b`` (only for stride==b, pad==0 input convs, the
    AlexNet conv1 shape) accepts input pre-packed on the host into
    ``(N, cin*b*b, H/b, W/b)`` and convolves it stride-1 with the
    equivalently packed kernel. A 3-channel stride-4 11x11 conv runs at
    ~5% MXU utilization (the contraction dim starves the systolic
    array); packed, the same math has cin*b*b=48 channels and a 3x3
    kernel. Measured 2026-07 on v5e: conv1 fwd 5.28ms -> ~0.7ms at
    batch 256. The packing is exact (padded kernel taps are zero), and
    an unpacked input still takes the standard path, so CPU tests and
    direct Network use need no pipeline support.
    """
    has_params = True

    def __init__(self):
        super().__init__()
        self.s2d = 0
        # auto|xla|nhwc|pallas: xla = NCHW conv_general_dilated (XLA
        # re-lays out internally); nhwc = explicit NHWC/HWIO operands
        # (layout experiment, docs/performance.md r3); pallas =
        # hand-written kernel (ops/conv_pallas.py). auto resolves
        # per-platform from the recorded ablations.
        self.impl = "auto"

    def set_param(self, name, val):
        if name == "space_to_depth":
            self.s2d = int(val)
        elif name == "conv_impl":
            if val not in ("auto", "xla", "nhwc", "pallas", "split"):
                raise ValueError(
                    "conv_impl must be auto|xla|nhwc|pallas|split")
            self.impl = val
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        p = self.param
        n, c, h, w = in_shapes[0]
        if c % p.num_group != 0:
            raise ValueError("input channels must divide group size")
        if p.num_channel % p.num_group != 0:
            raise ValueError("output channels must divide group size")
        if p.num_channel <= 0:
            raise ValueError("must set nchannel correctly")
        if p.kernel_height <= 0 or p.kernel_width <= 0:
            raise ValueError("must set kernel_size correctly")
        if p.kernel_width > w or p.kernel_height > h:
            raise ValueError("kernel size exceeds input")
        if p.num_input_channel == 0:
            p.num_input_channel = c
        elif p.num_input_channel != c:
            raise ValueError("Conv: number of input channels inconsistent")
        oh = (h + 2 * p.pad_y - p.kernel_height) // p.stride + 1
        ow = (w + 2 * p.pad_x - p.kernel_width) // p.stride + 1
        if self.s2d:
            b = self.s2d
            if p.stride != b or p.pad_y or p.pad_x:
                raise ValueError(
                    "space_to_depth=%d needs stride=%d and pad=0" % (b, b))
            # the packed stride-1 conv must reproduce the original output
            # size: ceil(H/b) - ceil(kh/b) + 1 == (H - kh)//b + 1
            for dim, k in ((h, p.kernel_height), (w, p.kernel_width)):
                if -(-dim // b) - (-(-k // b)) + 1 != (dim - k) // b + 1:
                    raise ValueError(
                        "space_to_depth=%d incompatible with input %d / "
                        "kernel %d" % (b, dim, k))
        return [(n, p.num_channel, oh, ow)]

    def init_params(self, rng) -> Params:
        p = self.param
        g = p.num_group
        co_g = p.num_channel // g
        ci_g = p.num_input_channel // g
        kshape = (g, co_g, ci_g * p.kernel_height * p.kernel_width)
        # fan numbers as the reference passes them: in=size(2), out=size(1)
        wmat = p.rand_init_weight(rng, kshape, kshape[2], kshape[1])
        out = {"wmat": wmat}
        if p.no_bias == 0:
            out["bias"] = jnp.full((p.num_channel,), p.init_bias, jnp.float32)
        return out

    def analytic_flops(self, skip_dx=False):
        p = self.param
        n, co, oh, ow = self.out_shapes[0]
        # logical kernel taps: the s2d pack zero-pads the kernel to a
        # multiple of b (useful work is unchanged; the padded taps are
        # hardware flops, not model flops)
        f = (2.0 * n * oh * ow * co * (p.num_input_channel / p.num_group)
             * p.kernel_height * p.kernel_width)
        return f, f if skip_dx else 2.0 * f

    def apply(self, params, inputs, ctx):
        p = self.param
        x = inputs[0].astype(ctx.compute_dtype)
        g = p.num_group
        co_g = p.num_channel // g
        ci_g = p.num_input_channel // g
        # (g, co/g, ci/g*kh*kw) -> OIHW (co, ci/g, kh, kw)
        kernel = params["wmat"].reshape(
            g * co_g, ci_g, p.kernel_height, p.kernel_width)
        b = self.s2d
        if b and x.shape[1] == p.num_input_channel * b * b:
            # host-packed input: convolve with the equivalently packed
            # kernel, stride 1 (kernel zero-padded to a multiple of b, so
            # the pack is exact — padded taps contribute nothing)
            khp = -(-p.kernel_height // b) * b
            kwp = -(-p.kernel_width // b) * b
            kernel = jnp.pad(kernel, ((0, 0), (0, 0),
                                      (0, khp - p.kernel_height),
                                      (0, kwp - p.kernel_width)))
            kernel = kernel.reshape(g * co_g, ci_g, khp // b, b,
                                    kwp // b, b)
            kernel = kernel.transpose(0, 1, 3, 5, 2, 4).reshape(
                g * co_g, ci_g * b * b, khp // b, kwp // b)
            stride, pad_y, pad_x = 1, 0, 0
        else:
            stride, pad_y, pad_x = p.stride, p.pad_y, p.pad_x
        impl = self.impl
        if impl == "auto":
            # grouped convs: GSPMD cannot batch-partition a
            # feature_group_count conv (it all-gathers the sharded
            # batch — measured r4, docs/multichip_r4.json); lowering as
            # per-group convs + concat shards cleanly AND measured
            # faster single-chip (AlexNet step 24.6 vs 25.9 ms,
            # interleaved same-window r4), so it is the default
            impl = "split" if p.num_group > 1 else "xla"
        # no preferred_element_type: with a f32 result dtype the rhs-grad
        # transpose would convolve bf16 activations with a f32 cotangent,
        # which lax rejects; bf16-in/bf16-out still accumulates f32 on MXU
        if impl == "nhwc":
            # explicit NHWC/HWIO operands: the node contract stays NCHW,
            # the transposes sit at the conv boundary where XLA's layout
            # assignment can absorb them into its own relayouts
            out = lax.conv_general_dilated(
                x.transpose(0, 2, 3, 1),
                kernel.transpose(2, 3, 1, 0).astype(ctx.compute_dtype),
                window_strides=(stride, stride),
                padding=[(pad_y, pad_y), (pad_x, pad_x)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=g).astype(jnp.float32)
            out = out.transpose(0, 3, 1, 2)
        elif impl == "pallas":
            from .ops.conv_pallas import conv_pallas
            # hardware flops XLA's cost model cannot see (opaque
            # custom_call): fwd + the custom-vjp dw conv (+ dx unless
            # this is a first conv whose input grad is dead code); the
            # s2d pack's zero-padded taps count here (they are executed)
            _, co, oh, ow = self.out_shapes[0]
            n = x.shape[0]
            khw = kernel.shape[2] * kernel.shape[3]
            fhw = 2.0 * n * oh * ow * co * kernel.shape[1] * khw
            bwd_mult = 2.0 if ctx.needs_input_grad else 1.0
            interp = ctx.platform != "tpu"
            ctx.add_pallas_flops("conv_pallas", fhw,
                                 bwd_mult * fhw if ctx.train else 0.0,
                                 interp)
            out = conv_pallas(x, kernel.astype(ctx.compute_dtype),
                              stride=stride, pad=(pad_y, pad_x),
                              groups=g, interpret=interp
                              ).astype(jnp.float32)
        elif impl == "split" and g > 1:
            # per-group convs + channel concat: same math as
            # feature_group_count (the groups are independent), but
            # GSPMD batch-partitions each plain conv instead of
            # all-gathering the batch at the grouped one
            ci_g2 = x.shape[1] // g
            outs = []
            for gi in range(g):
                outs.append(lax.conv_general_dilated(
                    x[:, gi * ci_g2:(gi + 1) * ci_g2],
                    kernel[gi * co_g:(gi + 1) * co_g].astype(
                        ctx.compute_dtype),
                    window_strides=(stride, stride),
                    padding=[(pad_y, pad_y), (pad_x, pad_x)],
                    dimension_numbers=("NCHW", "OIHW", "NCHW")))
            out = jnp.concatenate(outs, axis=1).astype(jnp.float32)
        else:
            out = lax.conv_general_dilated(
                x, kernel.astype(ctx.compute_dtype),
                window_strides=(stride, stride),
                padding=[(pad_y, pad_y), (pad_x, pad_x)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=g).astype(jnp.float32)
        if p.no_bias == 0:
            out = out + params["bias"].reshape(1, -1, 1, 1)
        return [out]


@register("conv_pallas")
class ConvPallasLayer(ConvolutionLayer):
    """Convolution forced onto the hand-written Pallas kernel
    (ops/conv_pallas.py; interpreted off-TPU); exists so
    ``pairtest-conv-conv_pallas`` differential-tests the kernel against
    the XLA lowering (the reference ran the same master/slave pattern
    for cudnn-vs-mshadow convs)."""

    _pinned = "pallas"

    def __init__(self):
        super().__init__()
        self.impl = self._pinned

    def set_param(self, name, val):
        if name == "conv_impl":
            return  # pinned: this type exists to force one impl
        super().set_param(name, val)


def s2d_pack(data: np.ndarray, block: int) -> np.ndarray:
    """Space-to-depth pack a host batch (N,C,H,W) -> (N, C*b*b, H', W')
    with H' = ceil(H/b); channel order ((c*b + di)*b + dj) matches the
    kernel pack in ConvolutionLayer.apply. Runs on the host (numpy):
    the same shuffle costs ~3.7ms/batch as a device transpose on v5e
    (lane-hostile), but is a cheap strided copy here and folds into the
    input pipeline's augment stage."""
    n, c, h, w = data.shape
    hp, wp = -(-h // block) * block, -(-w // block) * block
    if (hp, wp) != (h, w):
        data = np.pad(data, ((0, 0), (0, 0), (0, hp - h), (0, wp - w)))
    out = data.reshape(n, c, hp // block, block, wp // block, block)
    out = out.transpose(0, 1, 3, 5, 2, 4)
    return np.ascontiguousarray(
        out.reshape(n, c * block * block, hp // block, wp // block))


def s2d_unpack(data: np.ndarray, block: int,
               orig_hw: Tuple[int, int]) -> np.ndarray:
    """Inverse of :func:`s2d_pack`: (N, C*b*b, H', W') -> (N, C, H, W),
    cropping the zero pad. Used when a packed input node is extracted
    back to the host (task=extract of the data node)."""
    n, cbb, hp, wp = data.shape
    c = cbb // (block * block)
    out = data.reshape(n, c, block, block, hp, wp)
    out = out.transpose(0, 1, 4, 2, 5, 3)
    out = out.reshape(n, c, hp * block, wp * block)
    return np.ascontiguousarray(out[:, :, :orig_hw[0], :orig_hw[1]])


class _PoolingLayer(Layer):
    """Spatial pooling with the reference's edge semantics
    (reference: src/layer/pooling_layer-inl.hpp:17-118).

    The reference output size min(h-k+s-1, h-1)//s + 1 permits partial
    windows at the bottom/right edge; we reproduce that by explicit
    asymmetric padding into ``lax.reduce_window`` with the reducer's
    identity element. avg pooling divides by k*k regardless of clipping,
    exactly like the reference's * (1/(ksize_y*ksize_x)).
    """
    reducer = "max"
    pre_relu = False  # relu_max_pooling fuses a relu before pooling

    def __init__(self):
        super().__init__()
        # auto: window everywhere. The r3 hypothesis that reduce_window
        # is the pool1 bottleneck (+2.3 ms marginal) was tested with a
        # k*k-strided-slice elementwise reduce and REJECTED on-chip:
        # stride-2 slices across the NCHW lane dim each force a
        # relayout, and the AlexNet step went 21.2 -> 45.1 ms
        # (docs/performance.md r3 ablation). reduce_window is the
        # fast path; `slice` stays selectable as the recorded evidence.
        # Max results are identical either way (same window elements);
        # gradients at exact ties differ (elementwise max splits ties
        # per pair, select_and_scatter picks one winner) — both valid
        # subgradients.
        self.impl = "auto"

    def set_param(self, name, val):
        if name == "pool_impl":
            if val not in ("auto", "window", "slice"):
                raise ValueError("pool_impl must be auto|window|slice")
            self.impl = val
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        p = self.param
        n, c, h, w = in_shapes[0]
        if p.kernel_height <= 0 or p.kernel_width <= 0:
            raise ValueError("must set kernel_size correctly")
        # `pad` extends the reference semantics (its pooling has no
        # padding; pad defaults to 0 = exact parity). Symmetric padding
        # applies before the reference's partial-edge-window rule —
        # pad=(k-1)/2 with stride 1 gives "same" pooling (inception).
        h2, w2 = h + 2 * p.pad_y, w + 2 * p.pad_x
        if p.kernel_width > w2 or p.kernel_height > h2:
            raise ValueError("kernel size exceeds input")
        oh = min(h2 - p.kernel_height + p.stride - 1, h2 - 1) // p.stride + 1
        ow = min(w2 - p.kernel_width + p.stride - 1, w2 - 1) // p.stride + 1
        self._pad = ((oh - 1) * p.stride + p.kernel_height - h2,
                     (ow - 1) * p.stride + p.kernel_width - w2)
        return [(n, c, oh, ow)]

    def _resolve_impl(self, ctx) -> str:
        if self.impl != "auto":
            return self.impl
        return "window"

    def apply(self, params, inputs, ctx):
        p = self.param
        x = inputs[0]
        if self.pre_relu:
            x = jnp.maximum(x, 0.0)
        pad_h, pad_w = self._pad
        if self._resolve_impl(ctx) == "slice":
            return [self._apply_slice(x, pad_h, pad_w)]
        dims = (1, 1, p.kernel_height, p.kernel_width)
        strides = (1, 1, p.stride, p.stride)
        padding = ((0, 0), (0, 0), (p.pad_y, pad_h + p.pad_y),
                   (p.pad_x, pad_w + p.pad_x))
        if self.reducer == "max":
            init = -jnp.inf
            out = lax.reduce_window(x, init, lax.max, dims, strides, padding)
        else:
            out = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
            if self.reducer == "avg":
                out = out * (1.0 / (p.kernel_height * p.kernel_width))
        return [out]

    def _apply_slice(self, x, pad_h, pad_w):
        """Window reduction as an elementwise reduce over k*k strided
        slices of the (identity-padded) input — no reduce_window, so
        nothing crosses the TPU lane dimension serially. Same window
        membership as the reduce_window path: identical max/sum values
        up to addition order."""
        p = self.param
        n, c, h, w = x.shape
        kh, kw, s = p.kernel_height, p.kernel_width, p.stride
        init = -jnp.inf if self.reducer == "max" else 0.0
        xp = jnp.pad(x, ((0, 0), (0, 0),
                         (p.pad_y, pad_h + p.pad_y),
                         (p.pad_x, pad_w + p.pad_x)),
                     constant_values=init)
        oh = (xp.shape[2] - kh) // s + 1
        ow = (xp.shape[3] - kw) // s + 1
        red = jnp.maximum if self.reducer == "max" else jnp.add
        out = None
        for dy in range(kh):
            for dx in range(kw):
                part = lax.slice(
                    xp, (0, 0, dy, dx),
                    (n, c, dy + (oh - 1) * s + 1, dx + (ow - 1) * s + 1),
                    (1, 1, s, s))
                out = part if out is None else red(out, part)
        if self.reducer == "avg":
            out = out * (1.0 / (kh * kw))
        return out


@register("max_pooling")
class MaxPoolingLayer(_PoolingLayer):
    reducer = "max"


@register("sum_pooling")
class SumPoolingLayer(_PoolingLayer):
    reducer = "sum"


@register("avg_pooling")
class AvgPoolingLayer(_PoolingLayer):
    reducer = "avg"


@register("relu_max_pooling")
class ReluMaxPoolingLayer(_PoolingLayer):
    """Fused relu + max pooling (reference: src/layer/layer_impl-inl.hpp:55-56;
    note the reference's template args leave this combination broken — we
    implement the intended fusion)."""
    reducer = "max"
    pre_relu = True


@register("insanity_max_pooling")
class InsanityPoolingLayer(_PoolingLayer):
    """Stochastic pooling (reference: src/layer/insanity_pooling_layer-inl.hpp:223).

    At train time samples a window element with probability proportional
    to its (relu'd) activation; at eval computes the activation-weighted
    average — the standard Zeiler&Fergus stochastic pooling the reference's
    custom InsanityPoolingExp expression implements.
    """
    reducer = "max"

    def _infer(self, in_shapes):
        if self.param.pad_y or self.param.pad_x:
            # padding has no defined semantics for probability-weighted
            # window sampling (a -inf/zero pad would skew the weights);
            # the window-slicing apply below doesn't support it either
            raise ValueError("insanity pooling does not support pad")
        return super()._infer(in_shapes)

    def apply(self, params, inputs, ctx):
        p = self.param
        x = jnp.maximum(inputs[0], 0.0)
        n, c, h, w = x.shape
        kh, kw = p.kernel_height, p.kernel_width
        pad_h, pad_w = self._pad
        oh, ow = self.out_shapes[0][2], self.out_shapes[0][3]
        xp = jnp.pad(x, ((0, 0), (0, 0), (0, pad_h), (0, pad_w)))
        # gather all windows: (n, c, oh, ow, kh*kw)
        patches = jnp.stack([
            lax.slice(xp, (0, 0, dy, dx),
                      (n, c, dy + (oh - 1) * p.stride + 1,
                       dx + (ow - 1) * p.stride + 1),
                      (1, 1, p.stride, p.stride))
            for dy in range(kh) for dx in range(kw)], axis=-1)
        probs = patches / jnp.maximum(
            patches.sum(axis=-1, keepdims=True), 1e-12)
        if ctx.train:
            idx = jax.random.categorical(
                ctx.rng, jnp.log(jnp.maximum(probs, 1e-12)), axis=-1)
            out = jnp.take_along_axis(
                patches, idx[..., None], axis=-1)[..., 0]
        else:
            out = (patches * probs).sum(axis=-1)
        return [out]


@register("lrn")
class LRNLayer(Layer):
    """AlexNet-style cross-channel local response normalization
    (reference: src/layer/lrn_layer-inl.hpp:12-93):
    out = in * (knorm + alpha/n * chpool_sum(in^2, n))^-beta.
    The backward pass is derived by jax.grad (the reference hand-derives
    the identical expression)."""

    def __init__(self):
        super().__init__()
        self.nsize = 3
        self.alpha = 0.0
        self.beta = 0.0
        self.knorm = 1.0
        # auto: band on TPU (the cross-channel window rides the MXU as a
        # banded matmul — measured 2026-07 on v5e: band 20.8ms AlexNet
        # step vs 24.4 pallas vs 28.5 reduce_window), window elsewhere
        self.impl = "auto"
        # f32 | compute: dtype of the normalize/scale math AFTER the
        # squared-sum (the sum itself always accumulates f32). compute
        # (bf16 on TPU) halves the layer's HBM traffic; perf experiment
        # knob, docs/performance.md r3
        self.dtype_mode = "f32"

    def set_param(self, name, val):
        if name == "local_size":
            self.nsize = int(val)
        elif name == "alpha":
            self.alpha = float(val)
        elif name == "beta":
            self.beta = float(val)
        elif name == "knorm":
            self.knorm = float(val)
        elif name == "lrn_impl":
            if val not in ("auto", "window", "band", "pallas"):
                raise ValueError("lrn_impl must be auto|window|band|pallas")
            self.impl = val
        elif name == "lrn_dtype":
            if val not in ("f32", "compute"):
                raise ValueError("lrn_dtype must be f32|compute")
            self.dtype_mode = val
        elif name == "use_pallas":   # legacy knob: -1 auto, 0 never, 1 always
            self.impl = {0: "window", 1: "pallas"}.get(int(val), "auto")
        else:
            super().set_param(name, val)

    def _resolve_impl(self, ctx) -> str:
        if self.impl != "auto":
            return self.impl
        return "band" if ctx.platform == "tpu" else "window"

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        impl = self._resolve_impl(ctx)
        if impl == "pallas":
            from .ops import lrn_pallas
            # VPU flops invisible to XLA (opaque custom_call): ~2*nsize
            # window ops + a pow per element; listed for kernel
            # visibility, negligible against any MXU term
            elems = float(np.prod(x.shape))
            fhw = elems * (2.0 * self.nsize + 20.0)
            interp = ctx.platform != "tpu"
            ctx.add_pallas_flops("lrn_pallas", fhw,
                                 2.0 * fhw if ctx.train else 0.0, interp)
            return [lrn_pallas(x, self.nsize, self.alpha, self.beta,
                               self.knorm, interpret=interp)]
        salpha = self.alpha / self.nsize
        lo = self.nsize // 2
        hi = self.nsize - 1 - lo
        if impl == "band":
            # windowed channel sum as a C x C banded-ones matmul: the MXU
            # does the reduction nearly for free, where reduce_window
            # crosses the lane dimension serially (band[c,d]=1 iff
            # channel c lies in d's window [d-lo, d+hi]). The matmul runs
            # in the net's compute dtype (bf16 on TPU — 8x the f32 MXU
            # rate; f32 accumulate) and everything after stays f32.
            c = np.arange(x.shape[1])
            band = ((c[None, :] - lo <= c[:, None])
                    & (c[:, None] <= c[None, :] + hi))
            band = jnp.asarray(band, ctx.compute_dtype)
            sq = jnp.square(x.astype(ctx.compute_dtype))
            norm = jnp.einsum("nchw,cd->ndhw", sq, band,
                              preferred_element_type=jnp.float32)
            if self.dtype_mode == "compute":
                norm = norm.astype(ctx.compute_dtype)
        else:
            # centered cross-channel window, zero-padded (chpool<sum>)
            sq = jnp.square(x)
            norm = lax.reduce_window(
                sq, 0.0, lax.add, (1, self.nsize, 1, 1), (1, 1, 1, 1),
                ((0, 0), (lo, hi), (0, 0), (0, 0)))
            if self.dtype_mode == "compute":
                # same semantics as the band path: the normalize tail
                # runs in the compute dtype (the Pallas kernel computes
                # f32 internally and ignores this knob)
                norm = norm.astype(ctx.compute_dtype)
        norm = norm * salpha + self.knorm
        return [(x.astype(norm.dtype)
                 * jnp.power(norm, -self.beta)).astype(x.dtype)]


@register("lrn_pallas")
class LRNPallasLayer(LRNLayer):
    """LRN forced onto the Pallas kernel path (interpreted off-TPU);
    exists so ``pairtest-lrn-lrn_pallas`` differential-tests the kernel
    against the XLA lowering."""

    _pinned = "pallas"

    def __init__(self):
        super().__init__()
        self.impl = self._pinned

    def set_param(self, name, val):
        if name in ("use_pallas", "lrn_impl"):
            return  # pinned: these types exist to force one impl
        super().set_param(name, val)


@register("lrn_band")
class LRNBandLayer(LRNPallasLayer):
    """LRN forced onto the banded-matmul path, so
    ``pairtest-lrn-lrn_band`` differential-tests the MXU formulation
    (the TPU auto default) against the reduce_window lowering."""

    _pinned = "band"


@register("batch_norm")
class BatchNormLayer(Layer):
    """Batch normalization (reference: src/layer/batch_norm_layer-inl.hpp:14-201).

    Faithful to the reference's (nonstandard) eval semantics: *batch*
    statistics are used in both train and eval mode — there are no running
    averages in the reference model format. Channel axis is 1 for conv
    nodes and 3 for flat nodes, like the reference's size(1)==1 dispatch.

    ``bn_running = 1`` opts into standard running statistics (an
    improvement over the reference, SURVEY.md §7 hard part e): training
    still normalizes with batch stats but maintains EMA running
    mean/variance (``bn_momentum``, default 0.9) as non-trainable state
    tags ``rmean``/``rvar``; eval normalizes with them. The state rides
    the checkpoint like any other parameter.
    """
    has_params = True

    def __init__(self):
        super().__init__()
        self.init_slope = 1.0
        self.init_bias = 0.0
        self.eps = 1e-10
        self.bn_running = 0
        self.bn_momentum = 0.9

    def set_param(self, name, val):
        if name == "init_slope":
            self.init_slope = float(val)
        elif name == "init_bias":
            self.init_bias = float(val)
        elif name == "eps":
            self.eps = float(val)
        elif name == "bn_running":
            self.bn_running = int(val)
            self.state_tags = ("rmean", "rvar") if self.bn_running else ()
        elif name == "bn_momentum":
            self.bn_momentum = float(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        s = in_shapes[0]
        self.channel = s[3] if s[1] == 1 else s[1]
        self.axis = 3 if s[1] == 1 else 1
        return [s]

    def init_params(self, rng) -> Params:
        p = {"wmat": jnp.full((self.channel,), self.init_slope, jnp.float32),
             "bias": jnp.full((self.channel,), self.init_bias, jnp.float32)}
        if self.bn_running:
            p["rmean"] = jnp.zeros((self.channel,), jnp.float32)
            p["rvar"] = jnp.ones((self.channel,), jnp.float32)
        return p

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        axes = tuple(i for i in range(4) if i != self.axis)
        shape = [1, 1, 1, 1]
        shape[self.axis] = self.channel
        if self.bn_running and not ctx.train:
            mean = params["rmean"]
            var = params["rvar"]
        else:
            mean = x.mean(axis=axes)
            var = jnp.square(x - mean.reshape(shape)).mean(axis=axes)
            if self.bn_running and ctx.train:
                m = self.bn_momentum
                ctx.state_updates[(ctx.layer_index, "rmean")] = \
                    jax.lax.stop_gradient(
                        m * params["rmean"] + (1.0 - m) * mean)
                ctx.state_updates[(ctx.layer_index, "rvar")] = \
                    jax.lax.stop_gradient(
                        m * params["rvar"] + (1.0 - m) * var)
        xhat = (x - mean.reshape(shape)) / jnp.sqrt(
            var.reshape(shape) + self.eps)
        return [xhat * params["wmat"].reshape(shape)
                + params["bias"].reshape(shape)]


@register("fixconn")
class FixConnectLayer(Layer):
    """Fixed (non-learned) sparse connection loaded from a text file
    (reference: src/layer/fixconn_layer-inl.hpp:14-96). The weight matrix
    is a constant: it is excluded from the optimizer by having no params;
    the matrix is baked into the layer at config time."""

    def __init__(self):
        super().__init__()
        self.weight_file = ""
        self.num_hidden = 0
        self._wmat = None

    def set_param(self, name, val):
        if name == "weight_file":
            self.weight_file = val
        elif name == "nhidden":
            self.num_hidden = int(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        n, c, h, w = in_shapes[0]
        if not _is_mat(in_shapes[0]):
            raise ValueError("FixConnectLayer: input needs to be a matrix")
        if self.num_hidden <= 0:
            raise ValueError("FixConnectLayer: must set nhidden")
        import numpy as np
        wmat = np.zeros((self.num_hidden, w), np.float32)
        if self.weight_file:
            with open(self.weight_file) as f:
                for line in f:
                    parts = line.split()
                    if len(parts) == 3:
                        i, j, v = int(parts[0]), int(parts[1]), float(parts[2])
                        wmat[i, j] = v
        self._wmat = jnp.asarray(wmat)
        return [(n, 1, 1, self.num_hidden)]

    def analytic_flops(self, skip_dx=False):
        n, _, _, w = self.in_shapes[0]
        f = 2.0 * n * w * self.num_hidden
        # the weight is stop_gradient'd: backward is dX only
        return f, 0.0 if skip_dx else f

    def apply(self, params, inputs, ctx):
        x = _mat(inputs[0])
        out = jnp.dot(x, lax.stop_gradient(self._wmat).T)
        n = inputs[0].shape[0]
        return [out.reshape(n, 1, 1, self.num_hidden)]


# ======================================================================
# loss layers (self-loop)
# ======================================================================
class _LossLayer(Layer):
    """Self-loop loss (reference: src/layer/loss/loss_layer_base-inl.hpp:11-133).

    Forward transforms the node (softmax/sigmoid/identity) so that eval
    and Predict see scores. The scalar added to ctx.losses is chosen so
    jax.grad reproduces the reference gradient
    (p - y) * grad_scale / (batch_size * update_period) at this node's
    *input* — i.e. loss = grad_scale * L(input, y) / (batch*period).
    """
    is_loss = True

    def __init__(self):
        super().__init__()
        self.target = "label"
        self.grad_scale = 1.0

    def set_param(self, name, val):
        if name == "target":
            self.target = val
        elif name == "grad_scale":
            self.grad_scale = float(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        if self.target not in self.label_name_map:
            raise ValueError("LossLayer: unknown target=%s" % self.target)
        self.target_index = self.label_name_map[self.target]
        return [in_shapes[0]]

    def _scale(self, ctx: ApplyContext):
        return self.grad_scale / (ctx.batch_size * ctx.update_period)

    def _label(self, ctx: ApplyContext):
        return ctx.labels[self.target_index]

    def apply(self, params, inputs, ctx):
        raise NotImplementedError


@register("attention")
class AttentionLayer(Layer):
    """Multi-head self-attention over a (batch, 1, seq, embed) node.

    The reference has no sequence models (SURVEY.md §5), but long-context
    is first-class here: node layout (b, 1, s, e) treats h as the sequence
    axis and w as the embedding. Config keys: ``nhead`` (default 1),
    ``causal`` (0/1). Parameters: ``wqkv`` (3e, e) and ``wo`` (e, e),
    reference-style (out, in) row-major matrices.

    When the trainer builds a mesh with a ``seq`` axis (``seq_parallel``
    config), the score computation is sharded over that axis by one of two
    strategies selected with ``seq_algo``:

      * ``ring`` (default) — ring attention: K/V shards rotate via
        ppermute while each chip holds only its local sequence block
        (cxxnet_tpu/ops/ring_attention.py); scales to sequences longer
        than one chip's HBM.
      * ``alltoall`` (a.k.a. ``ulysses``) — two lax.all_to_all collectives
        re-partition seq-sharded tensors to head-sharded, full attention
        runs locally per head group (cxxnet_tpu/ops/ulysses.py); needs
        nhead divisible by the shard count.
    """
    has_params = True
    param_tags = ("wqkv", "wo")  # tag-scoped hyperparams: wqkv:lr etc.

    def __init__(self):
        super().__init__()
        self.nhead = 1
        self.causal = 0
        self.seq_algo = "ring"
        self.attn_impl = "auto"

    def set_param(self, name, val):
        if name == "nhead":
            self.nhead = int(val)
        elif name == "causal":
            self.causal = int(val)
        elif name == "seq_algo":
            if val not in ("ring", "alltoall", "ulysses"):
                raise ValueError("seq_algo must be ring|alltoall|ulysses")
            self.seq_algo = val
        elif name == "attn_impl":
            if val not in ("auto", "xla", "pallas"):
                raise ValueError("attn_impl must be auto|xla|pallas")
            self.attn_impl = val
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        n, c, s, e = in_shapes[0]
        if c != 1:
            raise ValueError("attention: input must be (batch,1,seq,embed)")
        if e % self.nhead != 0:
            raise ValueError("attention: embed %d not divisible by nhead %d"
                             % (e, self.nhead))
        return [(n, 1, s, e)]

    def init_params(self, rng) -> Params:
        e = self.in_shapes[0][3]
        p = self.param
        r1, r2 = jax.random.split(rng)
        return {"wqkv": p.rand_init_weight(r1, (3 * e, e), e, 3 * e),
                "wo": p.rand_init_weight(r2, (e, e), e, e)}

    def analytic_flops(self, skip_dx=False):
        n, _, s, e = self.in_shapes[0]
        proj_in = 2.0 * n * s * e * (3 * e)          # wqkv
        proj_out = 2.0 * n * s * e * e               # wo
        c = 0.5 if self.causal else 1.0              # useful causal half
        attend = 4.0 * c * n * s * s * e             # QK^T + PV, all heads
        fwd = proj_in + proj_out + attend
        # bwd: 2x per matmul, minus the input-gradient half of the one
        # matmul touching the layer input when nothing upstream needs it
        bwd = 2.0 * fwd - (proj_in if skip_dx else 0.0)
        return fwd, bwd

    def apply(self, params, inputs, ctx):
        from .ops import flash_attention as fa
        from .ops import ring_attention as ra
        b, _, s, e = inputs[0].shape
        nh, d = self.nhead, e // self.nhead
        dt = ctx.compute_dtype
        impl = fa.resolve_impl(self.attn_impl, ctx.platform, s)
        interp = ctx.platform != "tpu"

        def record_flash():
            fhw, bhw = fa.analytic_flops(b, nh, s, d, bool(self.causal))
            ctx.add_pallas_flops("flash_attention", fhw,
                                 bhw if ctx.train else 0.0, interp)
        x = inputs[0].reshape(b, s, e).astype(dt)
        qkv = jnp.einsum("bse,fe->bsf", x, params["wqkv"].astype(dt))
        qkv = qkv.reshape(b, s, 3, nh, d).transpose(2, 0, 3, 1, 4)
        q, k, v = qkv[0], qkv[1], qkv[2]
        mesh, axis = ctx.mesh, ctx.seq_axis
        if mesh is not None and axis is not None \
                and mesh.shape.get(axis, 1) > 1:
            if self.seq_algo in ("alltoall", "ulysses"):
                from .ops import ulysses
                if impl == "pallas":
                    record_flash()   # flash is the local attend
                out = ulysses.sharded_ulysses(
                    mesh, q, k, v, seq_axis=axis,
                    causal=bool(self.causal), impl=impl,
                    interpret=interp)
            elif self.attn_impl == "pallas":
                raise ValueError(
                    "attention: attn_impl=pallas composes with "
                    "seq_algo=alltoall (flash is the local attend after "
                    "the head re-partition); ring attention uses its own "
                    "online-softmax block attend")
            else:
                # auto under seq sharding: ring has no head-divisibility
                # requirement, so it stays the safe default
                out = ra.sharded_attention(mesh, q, k, v, seq_axis=axis,
                                           causal=bool(self.causal))
        elif impl == "pallas":
            # flash attention: VMEM-blocked online softmax, O(s*d) memory
            # (cxxnet_tpu/ops/flash_attention.py); on a mesh each device
            # attends its own batch rows (pallas_env.per_shard)
            from .ops import pallas_env
            record_flash()
            rows = pallas_env.rows_spec(mesh)
            out = pallas_env.per_shard(
                mesh, lambda q, k, v: fa.flash_attention(
                    q, k, v, bool(self.causal), interpret=interp),
                (rows, rows, rows), rows)(q, k, v)
        else:
            out = ra.attention(q, k, v, causal=bool(self.causal))
        out = out.transpose(0, 2, 1, 3).reshape(b, s, e)
        out = jnp.einsum("bse,fe->bsf", out, params["wo"].astype(dt))
        return [out.reshape(b, 1, s, e).astype(jnp.float32)]


@register("transformer_stack")
class TransformerStackLayer(Layer):
    """A stack of ``nlayer`` identical pre-norm transformer blocks with
    parameters stacked on a leading depth dimension.

    No reference counterpart (SURVEY.md §5: no sequence models). Depth as
    a stacked axis is the TPU-native shape for deep stacks: one block is
    traced once and either scanned over depth (single device — compile
    time stays O(1) in depth) or pipelined over the mesh's ``pipe`` axis
    (``pipeline_parallel`` config): each device owns nlayer/P consecutive
    blocks and microbatches stream stage-to-stage via ppermute
    (cxxnet_tpu/ops/pipeline.py).

    Block: x += attn(rmsnorm(x)); x += mlp(rmsnorm(x)) with a ReLU MLP of
    width ``nhidden_mlp`` (default 4*embed). Config: ``nlayer``,
    ``nhead``, ``causal``, ``nhidden_mlp``, ``n_microbatch`` (pipeline
    microbatches per local batch, default = pipe size), ``remat``
    (rematerialize each block in the backward pass — jax.checkpoint —
    except what its body hands out under the names ``ops.kept.KEPT``, a
    constant of the code and the one list of what is kept beside the
    block's input: (1) its attention kernel's output and log-sum-exp (a
    head's row a position), so the backward pass never replays the
    forward kernel, the costliest value of a block per byte kept: with
    the input two (b, s, e)-sized activations a layer, all that the
    plain and the grouped-query block keep — their ``wqkv`` / ``w1``
    results have no name, because a user who sets ``remat = 1`` there
    does so for the 3e- and 4e-wide hidden; where no kernel is taken —
    the XLA twins, ring, ulysses — the attend is replayed with the
    rest; (2) the narrow values of latent attention's block and of the
    sorted dispatch that cost a replay much, each as it is made:
    ``attn = mla``'s ``wqa``, ``wkc`` and ``wkr`` products
    (``attn_latent``: the ``q_rank`` + ``kv_rank`` + ``d_rope`` wide
    latents and shared key) and its ``wo`` product (``attn_wo``: embed
    wide), ``moe_dispatch = sorted``'s router logits
    (``router_logits``: float32, ``nexpert`` wide) with the chosen
    experts and their scores (``router_topk``: no product, but the
    top-k and the gather that make them cost more than the router's
    product) and its shared expert's first product (``mlp_gate_up``:
    twice that MLP's width; ``dense_first``'s layer hands its own out
    under the same name, ``2 nhidden_dense`` wide, once a stack). The
    kernel's operands (the ``wqn``, ``wqr``, ``wkn`` and ``wv``
    products, ``nhead (2 d_nope + d_rope + d_v)`` wide) and the routed
    experts' rows are replayed: they are what ``remat = 1`` is rid of.
    In compute-dtype values a position and block that is ``embed +
    nhead d_v`` before (2) and ``q_rank + kv_rank + d_rope + nhead d_v
    + 2 embed + 2 nexpert + 2 moe_shared nhidden_mlp`` (and 12 bytes a
    chosen expert) with it: 12.4 kB -> 24.9 kB a position at
    DeepSeek-V3-style widths in bfloat16 (docs/config.md), against
    every intra-block tensor under ``remat = 0``. The standard
    FLOPs-for-HBM trade for deep stacks; a traced step says what it
    keeps, by name and in bytes, in a ``remat.plan`` span).

    Options of the same block (each off by default; any of them takes
    the grouped block, ``_block_fn``'s second body):
    ``nkvhead`` (kv heads, each shared by nhead / nkvhead q heads),
    ``head_dim`` (head size, where it is not embed / nhead), ``qk_norm``
    (an RMSNorm with a learned gain over each q and k head before the
    rotation: tags ``qnorm``, ``knorm``), ``rope_theta`` (rotary
    positions over the whole head, rotate-half pairing), ``mlp_act =
    relu|swiglu`` (``swiglu``: ``w2 (silu(W1g x) * W1u x)``, ``w1``
    holding W1g's rows then W1u's), ``final_norm`` (an RMSNorm after the
    last block: tag ``normf``), ``attn_mask = full|causal|
    block_diffusion`` with ``block_len`` (``block_diffusion``: the input
    is ``[x_t ; x_0]``, both halves at positions 0..s/2-1, masked as
    ``ops.flash_attention.gq_pairs`` says), and ``moe_dispatch =
    onehot|sorted``: ``sorted`` is the dropless dispatch of
    ``ops/moe_sorted.py`` over this share's experts, ``expert_first`` and
    ``expert_held`` of ``nexpert`` (the router stays ``nexpert`` wide),
    ``moe_norm_topk`` (renormalise the chosen weights).

    ``attn = mla`` (latent attention as DeepSeek-V2/V3 train it, causal):
    queries through a latent of ``q_rank`` (tags ``wqa``, its RMSNorm's
    gain ``qanorm``, ``wqb``: a head's rows ``d_nope`` then ``d_rope``),
    keys and values through one of ``kv_rank`` (``wkva``: the latent's
    rows then ``d_rope`` rows of the one rotated key a position that all
    heads share; ``kvnorm``; ``wkvb``: a head's rows ``d_nope`` of key
    then ``d_v`` of value), rotary positions (``rope_theta``, neighbour
    pairs) on the ``d_rope`` dims alone, scores over ``d_nope + d_rope``
    dims, ``wo`` from ``nhead * d_v``. Kernels: ``ops.flash_attention.
    flash_attention_mla``; its dense twin off the TPU and for heads the
    kernels refuse. On the sorted dispatch: ``moe_score = softmax |
    sigmoid``, ``moe_bias = 1`` (a selection bias, tag ``gbias``: it
    enters the choice and never a weight; no gradient reaches it and no
    rule here updates it), ``moe_scale`` (the chosen weights' factor),
    ``moe_shared = n`` (a shared expert ``n * nhidden_mlp`` wide beside
    the routed ones: tags ``ws1``, ``ws2``), ``moe_load`` (pairs a
    position this share is planned for, for ``analytic_flops`` alone;
    default the mean, ``moe_topk * expert_held / nexpert``).
    ``dense_first = 1`` with ``nhidden_dense``: layer 0 has a dense
    gated MLP (tags ``w1d``, ``w2d``) in place of experts and runs
    outside the scan; the expert leaves are then ``nlayer - 1`` deep.
    ``raw_out = 1``: a second output node, the residual stream before
    the final norm (what an ``mtp`` layer reads).

    ``attn_sparse = dsa`` (learned sparse attention: DeepSeek-V3.2-Exp's
    lightning indexer over the grouped-query heads, ``attn_mask =
    causal``): beside the main heads an indexer reads the block's normed
    input DETACHED: ``idx_heads`` query heads of ``idx_dim`` (tag
    ``wiq``), one key head (``wik``) under a LayerNorm (``iknorm``: its
    gain's row, then its bias's), both rotated over all their dims
    (``rope_theta``, the first position stream), and a weight a head
    (``wiw``, times ``idx_heads^-1/2 idx_dim^-1/2``); query t scores the
    causal keys ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``,
    keeps the ``idx_topk`` largest (ties to the lower index; all where
    it has no more) and the main heads attend over those alone
    (``ops.dsa_attention``: kernels where a head is whole lane tiles,
    the dense twin elsewhere). On the kernels the mask is decided once a
    layer, by ``dsa_select``, which writes the kept pairs as a bit a
    pair, and the attend's three passes and the KL term read it: the one
    array that grows as the square of the row, ``seq^2 / 8`` bytes a row
    and layer (33.5 MB at 16,384 positions, 134 MB at 32,768), held from
    a layer's forward to its backward pass (a layer at a time under
    ``remat = 1``). The indexer learns from ``idx_loss``
    times the mean over positions, summed over the layers, of ``KL(p_t
    || softmax over the kept keys of I[t, .])``, ``p_t`` the heads' mean
    probability, detached: it joins the step's loss and rides out as the
    stat ``dsa_index_loss`` a layer, beside the counters ``dsa_pairs``
    (pairs the attend kept) and ``dsa_pairs_causal``. The indexer's four
    leaves take their gradient from that term alone, every other leaf
    none of it. ``mrope_section = t,h,w``: the rotation's ``head_dim /
    2`` frequency pairs split over three position streams, read from an
    optional second input node (batch,1,seq,3); without it the input is
    text, the three streams are equal and the rotation is the plain one.
    """
    has_params = True
    param_tags = ("wqkv", "wo", "w1", "w2", "norm1", "norm2", "gate",
                  "qnorm", "knorm", "normf", "wqa", "qanorm", "wqb",
                  "wkva", "kvnorm", "wkvb", "gbias", "ws1", "ws2", "w1d",
                  "w2d", "wiq", "wik", "iknorm", "wiw")
    # the leaves of the routed MLP (``nlayer - dense_first`` deep)
    _EXPERT_TAGS = ("gate", "gbias", "w1", "w2", "ws1", "ws2")

    def __init__(self):
        super().__init__()
        self.nlayer = 1
        self.nhead = 1
        self.causal = 0
        self.nhidden_mlp = 0
        self.n_microbatch = 0
        self.remat = 0
        self.moe = 0
        self.nexpert = 0
        self.topk = 2
        self.capacity_factor = 1.25
        self.moe_loss = None        # unset: 0.01 one-hot, 0 sorted
        self.attn_impl = "auto"
        self.scan_unroll = 1
        self.nkvhead = 0
        self.head_dim = 0
        self.qk_norm = 0
        self.rope_theta = 0.0
        self.mlp_act = "relu"
        self.final_norm = 0
        self.attn_mask = ""         # unset: by ``causal``
        self.block_len = 4
        self.moe_dispatch = "onehot"
        self.expert_first = 0
        self.expert_held = 0
        self.moe_norm_topk = 0
        self.attn = "mha"
        self.q_rank = self.kv_rank = 0
        self.d_nope = self.d_rope = self.d_v = 0
        self.moe_score = "softmax"
        self.moe_bias = 0
        self.moe_scale = 1.0
        self.moe_shared = 0
        self.moe_load = 0.0
        self.dense_first = 0
        self.nhidden_dense = 0
        self.raw_out = 0
        self.attn_sparse = "none"
        self.idx_heads = self.idx_dim = self.idx_topk = 0
        self.idx_loss = 1.0
        self.mrope_section = ()

    _INT_KEYS = ("nkvhead", "head_dim", "qk_norm", "final_norm",
                 "block_len", "expert_first", "expert_held",
                 "moe_norm_topk", "q_rank", "kv_rank", "d_nope", "d_rope",
                 "d_v", "moe_bias", "moe_shared", "dense_first",
                 "nhidden_dense", "raw_out", "idx_heads", "idx_dim",
                 "idx_topk")
    _FLOAT_KEYS = ("rope_theta", "moe_scale", "moe_load", "idx_loss")
    _CHOICES = {"mlp_act": ("relu", "swiglu"),
                "attn_mask": ("full", "causal", "block_diffusion"),
                "moe_dispatch": ("onehot", "sorted"),
                "attn": ("mha", "mla"),
                "moe_score": ("softmax", "sigmoid"),
                "attn_sparse": ("none", "dsa")}

    def set_param(self, name, val):
        if name == "mrope_section":
            self.mrope_section = tuple(int(x) for x in val.split(","))
        elif name in self._INT_KEYS:
            setattr(self, name, int(val))
        elif name in self._FLOAT_KEYS:
            setattr(self, name, float(val))
        elif name in self._CHOICES:
            if val not in self._CHOICES[name]:
                raise ValueError("%s must be %s" % (
                    name, "|".join(self._CHOICES[name])))
            setattr(self, name, val)
        elif name == "nlayer":
            self.nlayer = int(val)
        elif name == "scan_unroll":
            # unroll factor for the layer scan (straight-line XLA can
            # overlap across block boundaries; costs compile time)
            self.scan_unroll = int(val)
        elif name == "nhead":
            self.nhead = int(val)
        elif name == "causal":
            self.causal = int(val)
        elif name == "nhidden_mlp":
            self.nhidden_mlp = int(val)
        elif name == "n_microbatch":
            self.n_microbatch = int(val)
        elif name == "remat":
            self.remat = int(val)
        elif name == "moe":
            self.moe = int(val)
        elif name == "nexpert":
            self.nexpert = int(val)
        elif name == "moe_topk":
            self.topk = int(val)
        elif name == "capacity_factor":
            self.capacity_factor = float(val)
        elif name == "moe_loss":
            self.moe_loss = float(val)
        elif name == "attn_impl":
            if val not in ("auto", "xla", "pallas"):
                raise ValueError("attn_impl must be auto|xla|pallas")
            self.attn_impl = val
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes):
        if len(in_shapes) != 2 or not self.mrope_section:
            return super().infer_shape(in_shapes)
        # the second input: a position a stream (temporal, height, width)
        n, _, s, _ = in_shapes[0]
        if in_shapes[1] != (n, 1, s, 3):
            raise ValueError(
                "transformer_stack: mrope_section reads a second input "
                "(batch,1,seq,3), a position a stream; got %s beside %s"
                % (in_shapes[1], in_shapes[0]))
        out = self._infer(in_shapes[:1])
        self.in_shapes, self.out_shapes = list(in_shapes), out
        return out

    def _infer(self, in_shapes):
        n, c, s, e = in_shapes[0]
        if c != 1:
            raise ValueError(
                "transformer_stack: input must be (batch,1,seq,embed)")
        if not self.head_dim and e % self.nhead != 0:
            raise ValueError("transformer_stack: embed %d vs nhead %d"
                             % (e, self.nhead))
        if self.nhidden_mlp == 0:
            self.nhidden_mlp = 4 * e
        if self.moe_loss is None:
            self.moe_loss = 0.0 if self.sorted else 0.01
        if self.grouped:
            self._check_grouped(s, e)
        return [(n, 1, s, e)] * (2 if self.raw_out else 1)

    @property
    def mask(self) -> str:
        return self.attn_mask or ("causal" if self.causal else "full")

    @property
    def sorted(self) -> bool:
        return bool(self.moe) and self.moe_dispatch == "sorted"

    @property
    def grouped(self) -> bool:
        """Does any option ask for the grouped block?"""
        return bool(
            self.nkvhead or self.head_dim or self.qk_norm
            or self.rope_theta or self.final_norm or self.sorted
            or self.mlp_act != "relu" or self.mask == "block_diffusion"
            or self.attn == "mla" or self.dense_first or self.raw_out
            or self.dsa or self.mrope_section)

    @property
    def dsa(self) -> bool:
        return self.attn_sparse == "dsa"

    def _check_grouped(self, s, e):
        """The grouped block's sizes, and what it does not do, said
        here where the conf is read."""
        err = lambda msg: ValueError("transformer_stack: " + msg)
        self.nkv = self.nkvhead or self.nhead
        self.hd = self.head_dim or e // self.nhead
        if self.nhead % self.nkv:
            raise err("nhead %d is not whole groups of nkvhead %d"
                      % (self.nhead, self.nkv))
        if self.hd % 2 and self.rope_theta:
            raise err("rope_theta needs an even head size, not %d"
                      % self.hd)
        if self.mask == "block_diffusion" and (
                s % 2 or (s // 2) % self.block_len):
            raise err("attn_mask = block_diffusion reads [x_t ; x_0]: "
                      "%d positions are not two halves of whole blocks "
                      "of %d" % (s, self.block_len))
        if self.moe and not self.sorted:
            raise err("the options of the grouped block (nkvhead, "
                      "head_dim, qk_norm, rope_theta, mlp_act, "
                      "final_norm, attn_mask = block_diffusion) route "
                      "by moe_dispatch = sorted only; the one-hot "
                      "dispatch runs in the plain block")
        if self.sorted:
            self.held = self.expert_held or self.nexpert
            if self.mlp_act != "swiglu":
                raise err("moe_dispatch = sorted has swiglu experts "
                          "only (set mlp_act = swiglu)")
            if self.moe_loss > 0.0:
                raise err("moe_dispatch = sorted computes no auxiliary "
                          "load-balance loss (moe_loss must be 0)")
            if not 0 <= self.expert_first \
                    <= self.nexpert - self.held < self.nexpert:
                raise err("experts %d..%d are not a share of nexpert %d"
                          % (self.expert_first,
                             self.expert_first + self.held, self.nexpert))
            if self.topk > self.nexpert:
                raise err("moe_topk %d > nexpert %d"
                          % (self.topk, self.nexpert))
        elif (self.moe_score != "softmax" or self.moe_bias
              or self.moe_scale != 1.0 or self.moe_shared):
            raise err("moe_score, moe_bias, moe_scale and moe_shared are "
                      "options of moe_dispatch = sorted: the one-hot "
                      "dispatch scores by softmax and has no shared "
                      "expert")
        if self.attn == "mla":
            if not (self.q_rank and self.kv_rank and self.d_nope
                    and self.d_rope and self.d_v and self.rope_theta):
                raise err("attn = mla needs q_rank, kv_rank, d_nope, "
                          "d_rope, d_v and rope_theta")
            if self.d_rope % 2:
                raise err("d_rope %d is not whole pairs" % self.d_rope)
            if self.nkvhead or self.head_dim or self.qk_norm:
                raise err("attn = mla has one key and value a head from "
                          "its latent and norms on the latents: nkvhead, "
                          "head_dim and qk_norm do not apply")
            if self.mask != "causal":
                raise err("attn = mla is causal (set causal = 1): its "
                          "kernels know no other mask, not attn_mask = %s"
                          % self.mask)
        elif self.q_rank or self.kv_rank or self.d_nope or self.d_rope \
                or self.d_v:
            raise err("q_rank, kv_rank, d_nope, d_rope and d_v are "
                      "options of attn = mla")
        if self.dsa:
            if self.attn == "mla" or self.mask != "causal":
                raise err("attn_sparse = dsa selects keys over the "
                          "grouped-query heads under attn_mask = causal "
                          "(not attn = %s, attn_mask = %s): the selection "
                          "is by a causal query's own scores"
                          % (self.attn, self.mask))
            if not (self.idx_heads > 0 and self.idx_dim > 0
                    and self.idx_topk > 0 and self.rope_theta) \
                    or self.idx_dim % 2:
                raise err("attn_sparse = dsa needs idx_heads, an even "
                          "idx_dim, idx_topk and rope_theta")
            if self.dense_first:
                raise err("attn_sparse = dsa with dense_first = 1: the "
                          "leading layer's KL term has no way out of the "
                          "stack yet")
        elif self.idx_heads or self.idx_dim or self.idx_topk:
            raise err("idx_heads, idx_dim and idx_topk are options of "
                      "attn_sparse = dsa")
        if self.mrope_section:
            if len(self.mrope_section) != 3 or not self.rope_theta \
                    or sum(self.mrope_section) * 2 != self.hd \
                    or self.attn == "mla":
                raise err("mrope_section = t,h,w splits the %d frequency "
                          "pairs of a rotated head of %d over three "
                          "position streams (rope_theta; not attn = mla): "
                          "got %s" % (self.hd // 2, self.hd,
                                      self.mrope_section))
        if self.dense_first:
            if not self.sorted or self.nlayer < 2 \
                    or self.nhidden_dense <= 0:
                raise err("dense_first = 1 puts a dense gated MLP "
                          "(nhidden_dense) in layer 0 of a stack whose "
                          "other layers route by moe_dispatch = sorted: "
                          "it needs moe = 1, nlayer >= 2 and "
                          "nhidden_dense")

    def decode_blocker(self) -> str:
        """Why ``task = generate``, ``export_model`` and ``serve`` cannot
        run this stack ('' where they can): they decode through
        generate.py's own copy of the block, which has none of the
        grouped block's mechanisms."""
        if not self.grouped:
            return ""
        what = [name for name, on in (
            ("learned sparse attention (attn_sparse = dsa): the decode "
             "would cache the index keys beside the kv pages and select "
             "inside the paged attend", self.dsa),
            ("three-stream rotary positions (mrope_section)",
             self.mrope_section),
            ("latent attention (attn = mla): the decode would cache the "
             "latent and absorb the up-projections", self.attn == "mla"),
            ("a sigmoid router with a selection bias (moe_score, "
             "moe_bias, moe_scale)", self.moe_score != "softmax"
             or self.moe_bias or self.moe_scale != 1.0),
            ("a shared expert (moe_shared)", self.moe_shared),
            ("a leading dense layer (dense_first): two kinds of layer "
             "in one cache", self.dense_first),
            ("a multi-token prediction module (mtp): a training loss, "
             "or a draft head the decode does not run",
             isinstance(self, MTPLayer) or self.raw_out),
            ("rotary positions (rope_theta)", self.rope_theta),
            ("grouped-query heads (nkvhead / head_dim)",
             self.nkvhead or self.head_dim),
            ("q/k norms (qk_norm)", self.qk_norm),
            ("a gated MLP (mlp_act = swiglu)", self.mlp_act != "relu"),
            ("the sorted expert dispatch (moe_dispatch = sorted)",
             self.sorted),
            ("a final norm (final_norm)", self.final_norm),
            ("the block-diffusion objective (attn_mask = "
             "block_diffusion): a decode step fills a block of tokens, "
             "not one token a lane", self.mask == "block_diffusion"))
            if on]
        return ("the KV-cache decode (generate.py, serving.py) has its "
                "own copy of the transformer block and lacks: "
                + "; ".join(what))

    def init_params(self, rng) -> Params:
        e, m, L = self.in_shapes[0][3], self.nhidden_mlp, self.nlayer
        p = self.param
        ks = jax.random.split(rng, 5)
        if self.grouped:
            return self._init_grouped(ks, e, m, L)
        out = {
            "wqkv": p.rand_init_weight(ks[0], (L, 3 * e, e), e, 3 * e),
            "wo": p.rand_init_weight(ks[1], (L, e, e), e, e),
            "norm1": jnp.ones((L, e), jnp.float32),
            "norm2": jnp.ones((L, e), jnp.float32)}
        if self.moe:
            if self.nexpert <= 0:
                raise ValueError("transformer_stack: moe=1 needs nexpert")
            if self.topk > self.nexpert:
                # excess rounds would silently re-route to expert 0 with
                # full gate weight (moe_fullc rejects this too)
                raise ValueError(
                    "transformer_stack: moe_topk %d > nexpert %d"
                    % (self.topk, self.nexpert))
            E = self.nexpert
            out["w1"] = p.rand_init_weight(ks[2], (L, E, m, e), e, m)
            out["w2"] = p.rand_init_weight(ks[3], (L, E, e, m), m, e)
            out["gate"] = jax.random.normal(
                ks[4], (L, E, e), jnp.float32) * (e ** -0.5)
        else:
            out["w1"] = p.rand_init_weight(ks[2], (L, m, e), e, m)
            out["w2"] = p.rand_init_weight(ks[3], (L, e, m), m, e)
        return out

    def _init_grouped(self, ks, e, m, L):
        """The grouped block's tree: ``wqkv`` holds the q heads' rows,
        then the k heads', then the v heads'; a gated ``w1`` W1g's rows,
        then W1u's (columns, in the sorted dispatch's experts)."""
        p = self.param
        nq, nkv = self.nhead * self.hd, self.nkv * self.hd
        wide = m * (2 if self.mlp_act == "swiglu" else 1)
        if self.attn == "mla":
            nh, dn, dr, dv = self.nhead, self.d_nope, self.d_rope, self.d_v
            qr, kr = self.q_rank, self.kv_rank
            ka = jax.random.split(ks[0], 4)
            out = {
                "wqa": p.rand_init_weight(ka[0], (L, qr, e), e, qr),
                "qanorm": jnp.ones((L, qr), jnp.float32),
                "wqb": p.rand_init_weight(ka[1], (L, nh * (dn + dr), qr),
                                          qr, nh * (dn + dr)),
                "wkva": p.rand_init_weight(ka[2], (L, kr + dr, e), e,
                                           kr + dr),
                "kvnorm": jnp.ones((L, kr), jnp.float32),
                "wkvb": p.rand_init_weight(ka[3], (L, nh * (dn + dv), kr),
                                           kr, nh * (dn + dv)),
                "wo": p.rand_init_weight(ks[1], (L, e, nh * dv), nh * dv,
                                         e)}
        else:
            out = {
                "wqkv": p.rand_init_weight(ks[0], (L, nq + 2 * nkv, e), e,
                                           nq + 2 * nkv),
                "wo": p.rand_init_weight(ks[1], (L, e, nq), nq, e)}
        out["norm1"] = jnp.ones((L, e), jnp.float32)
        out["norm2"] = jnp.ones((L, e), jnp.float32)
        if self.dsa:
            ih, idim = self.idx_heads, self.idx_dim
            ki = jax.random.split(jax.random.fold_in(ks[0], 2), 3)
            out["wiq"] = p.rand_init_weight(ki[0], (L, ih * idim, e), e,
                                            ih * idim)
            out["wik"] = p.rand_init_weight(ki[1], (L, idim, e), e, idim)
            out["wiw"] = p.rand_init_weight(ki[2], (L, ih, e), e, ih)
            # the index key's LayerNorm: its gain's row, then its bias's
            out["iknorm"] = jnp.broadcast_to(
                jnp.asarray([[1.0], [0.0]], jnp.float32), (L, 2, idim))
        if self.qk_norm:
            out["qnorm"] = jnp.ones((L, self.hd), jnp.float32)
            out["knorm"] = jnp.ones((L, self.hd), jnp.float32)
        if self.final_norm:
            out["normf"] = jnp.ones((e,), jnp.float32)
        if self.sorted:
            H = self.held
            if self.dense_first:
                kd = jax.random.split(jax.random.fold_in(ks[2], 1))
                md = self.nhidden_dense
                out["w1d"] = p.rand_init_weight(kd[0], (2 * md, e), e,
                                                2 * md)
                out["w2d"] = p.rand_init_weight(kd[1], (e, md), md, e)
                L -= 1
            # an expert's matrices as (in, out), the grouped products'
            # own layout (ops/moe_sorted.py)
            out["w1"] = p.rand_init_weight(ks[2], (L, H, e, wide), e, wide)
            out["w2"] = p.rand_init_weight(ks[3], (L, H, m, e), m, e)
            out["gate"] = p.rand_init_weight(
                ks[4], (L, self.nexpert, e), e, self.nexpert)
            if self.moe_bias:
                out["gbias"] = jnp.zeros((L, self.nexpert), jnp.float32)
            if self.moe_shared:
                ms_ = self.moe_shared * m
                kh = jax.random.split(jax.random.fold_in(ks[3], 1))
                out["ws1"] = p.rand_init_weight(kh[0], (L, 2 * ms_, e), e,
                                                2 * ms_)
                out["ws2"] = p.rand_init_weight(kh[1], (L, e, ms_), ms_, e)
        else:
            out["w1"] = p.rand_init_weight(ks[2], (L, wide, e), e, wide)
            out["w2"] = p.rand_init_weight(ks[3], (L, e, m), m, e)
        return out

    def _attend_pairs(self, s):
        """Query-key pairs one row's mask allows, as a share of s * s."""
        if self.mask == "block_diffusion":
            from .ops import flash_attention as fa
            return fa.gq_pairs_allowed(self.mask, s // 2,
                                       self.block_len) / float(s * s)
        return 0.5 if self.mask == "causal" else 1.0

    def analytic_flops(self, skip_dx=False):
        n, _, s, e = self.in_shapes[0]
        m = self.nhidden_mlp or 4 * e
        if self.grouped:
            if self.attn == "mla":
                nh, dn, dr, dv = (self.nhead, self.d_nope, self.d_rope,
                                  self.d_v)
                proj = 2.0 * n * s * (
                    e * self.q_rank + self.q_rank * nh * (dn + dr)
                    + e * (self.kv_rank + dr)
                    + self.kv_rank * nh * (dn + dv) + nh * dv * e)
                attend = 2.0 * self._attend_pairs(s) * n * s * s * nh * (
                    dn + dr + dv)
            else:
                nq, nkv = self.nhead * self.hd, self.nkv * self.hd
                proj = 2.0 * n * s * e * (nq + 2 * nkv) \
                    + 2.0 * n * s * nq * e
                attend = 4.0 * self._attend_pairs(s) * n * s * s * nq
                if self.dsa:
                    # the indexer's projections, its scores over every
                    # causal pair, the attend over the pairs kept (the
                    # KL term is the indexer's training, not counted)
                    from .ops.dsa_attention import pairs_kept
                    iw = self.idx_heads * self.idx_dim
                    proj += 2.0 * n * s * e * (iw + self.idx_dim
                                               + self.idx_heads)
                    attend = n * (2.0 * iw * (s * (s + 1) // 2) + 4.0 * nq
                                  * pairs_kept(s, self.idx_topk))
            wide = m * (3 if self.mlp_act == "swiglu" else 2)
            if self.sorted:     # this share's load: planned, or the mean
                load = self.moe_load or (self.topk * self.held
                                         / self.nexpert)
                mlp = 2.0 * n * s * self.nexpert * e + 2.0 * n * s * (
                    load + self.moe_shared) * wide * e
            else:
                mlp = 2.0 * n * s * wide * e
            fwd = self.nlayer * (proj + attend + mlp)
            if self.dense_first:
                fwd += 2.0 * n * s * 3 * self.nhidden_dense * e - mlp
            return fwd, 2.0 * fwd
        c = 0.5 if self.causal else 1.0              # useful causal half
        proj = 2.0 * n * s * e * (3 * e) + 2.0 * n * s * e * e
        attend = 4.0 * c * n * s * s * e             # QK^T + PV, all heads
        if self.moe:
            B, E = float(n * s), self.nexpert
            C = moe_capacity(self.topk, n * s, E, self.capacity_factor)
            # gate + one-hot dispatch/combine einsums + expert matmuls
            mlp = 2.0 * B * E * e + 4.0 * B * E * C * e \
                + 4.0 * E * C * m * e
        else:
            mlp = 4.0 * n * s * e * m
        fwd = self.nlayer * (proj + attend + mlp)
        # dX is needed at every inner layer regardless of skip_dx (the
        # residual stream chains through all nlayer blocks)
        return fwd, 2.0 * fwd

    def _block_fn(self, dt, interpret=True, mesh=None, seq_axis=None,
                  use_flash=False, positions=None):
        from .ops import pallas_env
        from .ops import ring_attention as ra
        nh, causal = self.nhead, bool(self.causal)
        # the local flash kernels run per device on its own batch rows
        # (a Mosaic kernel cannot be partitioned by XLA); mesh is None
        # inside the pipeline's own shard_map
        rows = pallas_env.rows_spec(mesh)
        seq_sharded = (mesh is not None and seq_axis is not None
                       and mesh.shape.get(seq_axis, 1) > 1)
        # under seq sharding only an EXPLICIT pallas selects
        # ulysses+flash (it needs nhead divisible by the shard count);
        # auto keeps ring, which has no such requirement
        if seq_sharded and self.attn_impl != "pallas":
            use_flash = False

        @_part("norm")
        def rmsnorm(x, g):
            # g=None: the learned gain is folded into the following
            # weight matrix (_fold_norms — one L*e*f multiply at trace
            # time instead of a (b, s, e) VPU pass per norm per step);
            # the MoE branch keeps the explicit gain (its router gates
            # on the gained activations — folding into w1 alone would
            # change the routing math and break decode parity)
            ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                          keepdims=True)
            xn = (x.astype(jnp.float32)
                  * jax.lax.rsqrt(ms + 1e-6)).astype(dt)
            return xn if g is None else xn * g.astype(dt)

        moe = self.moe
        topk, cap_f = self.topk, self.capacity_factor
        nexpert = self.nexpert

        @_part("mlp")
        def mlp(lp, x):
            b, s, e = x.shape
            if not moe:
                y = jax.nn.relu(
                    jnp.einsum("bse,me->bsm", x, lp["w1"].astype(dt)))
                return jnp.einsum("bsm,em->bse", y,
                                  lp["w2"].astype(dt)), 0.0
            # mixture-of-experts MLP: tokens route to per-layer experts
            # (experts shard over the model axis — expert parallelism
            # inside the stack)
            y, aux = moe_mlp(x.reshape(b * s, e), lp, topk, nexpert,
                             cap_f, dt)
            return y.reshape(b, s, e), aux

        if self.grouped:
            return self._grouped_block(dt, interpret, mesh, seq_sharded,
                                       use_flash, rmsnorm, positions)

        def rest(lp, h, att):
            """The block after its attend."""
            with _part("attn_proj"):
                h = h + jnp.einsum("bse,fe->bsf", att, lp["wo"].astype(dt))
            y, aux = mlp(lp, rmsnorm(h, lp["norm2"] if moe else None))
            return h + y, aux

        def block(lp, h):
            b, s, e = h.shape
            d = e // nh
            x = rmsnorm(h, None)          # gain folded into wqkv
            with _part("attn_proj"):
                qkv = jnp.einsum("bse,fe->bsf", x, lp["wqkv"].astype(dt))
            if use_flash and not seq_sharded:
                from .ops import flash_attention as fa
                if fa.supports_flat(s, nh, d) \
                        or fa.flat_blocked_plan(s, nh, d):
                    # flat kernels: read the projection's (b, s, 3e)
                    # output and emit (b, s, e) directly — no
                    # (3, b, h, s, d) relayouts on either pass.
                    # Single-block s takes the fused backward; longer
                    # s the r5 blocked flat kernels (flat_blocked_plan)
                    with _part("attn_core"):
                        att = pallas_env.per_shard(
                            mesh, lambda qkv: fa.flash_attention_flat(
                                qkv, nh, causal, interpret=interpret),
                            (rows,), rows)(qkv)
                    return rest(lp, h, att)
            with _part("attn_core"):
                att = heads_attend(qkv.reshape(b, s, 3, nh, d).transpose(
                    2, 0, 3, 1, 4)).transpose(0, 2, 1, 3).reshape(b, s, e)
            return rest(lp, h, att)

        def heads_attend(qkv):
            """(3, b, heads, s, d) -> the attention (b, heads, s, d)."""
            if seq_sharded:
                # sequence parallelism: the attend must stay sharded —
                # calling the local kernels on seq-sharded arrays would
                # make GSPMD all-gather the full sequence per chip
                if use_flash:
                    from .ops import ulysses
                    return ulysses.sharded_ulysses(
                        mesh, qkv[0], qkv[1], qkv[2], seq_axis=seq_axis,
                        causal=causal, impl="pallas", interpret=interpret)
                return ra.sharded_attention(mesh, qkv[0], qkv[1], qkv[2],
                                            seq_axis=seq_axis, causal=causal)
            if use_flash:
                # VMEM-blocked online-softmax kernel: O(s*d) memory
                from .ops import flash_attention as fa
                return pallas_env.per_shard(
                    mesh, lambda q, k, v: fa.flash_attention(
                        q, k, v, causal, interpret=interpret),
                    (rows, rows, rows), rows)(qkv[0], qkv[1], qkv[2])
            return ra.attention(qkv[0], qkv[1], qkv[2], causal=causal)
        return block

    def _grouped_block(self, dt, interpret, mesh, seq_sharded, use_flash,
                       rmsnorm, positions=None):
        """The block with the options of the class docstring: grouped
        heads of their own size, q/k norms, rotary positions, a gated
        MLP or the sorted expert dispatch, a scheduled mask or a learned
        selection. -> block(lp, h) -> (h, aux), aux the routed layer's
        counters (``ops.moe_sorted.STATS``) or 0; under ``attn_sparse =
        dsa`` a dict of that (``moe``), the layer's KL term summed
        (``dsa_kl``) and the pairs its attend kept (``dsa_pairs``).
        ``positions`` (b, s, 3): the three position streams of
        ``mrope_section`` (None: text)."""
        from jax.sharding import PartitionSpec as P
        from .ops import flash_attention as fa
        from .ops import moe_sorted as ms
        from .ops import pallas_env
        from .ops import qk_prep as qp
        nh, nkv, d = self.nhead, self.nkv, self.hd
        mask, blen = self.mask, self.block_len
        rows = pallas_env.rows_spec(mesh)
        if seq_sharded:
            raise ValueError(
                "transformer_stack: the grouped block (rotary positions, "
                "grouped heads, attn_mask = block_diffusion, attn_sparse = "
                "dsa) does not run under sequence sharding: ring and "
                "ulysses attention know neither the mask nor shared kv "
                "heads, and a learned selection reads every causal key of "
                "a row")
        if self.sorted and mesh is not None and any(
                n > 1 for ax, n in mesh.shape.items() if ax != "data"):
            raise ValueError(
                "transformer_stack: moe_dispatch = sorted runs on one "
                "device or on data-parallel replicas of one share, not "
                "on a mesh %s: experts sharded over a mesh axis need the "
                "exchange of routed rows, which is not implemented"
                % dict(mesh.shape))
        flash = use_flash and d % 128 == 0 and mask != "full"

        # the q/k norms and the rotary positions between the projection
        # and the attend: one Pallas pass each way where the kernels are
        # in use and a head is whole lane tiles, plain XLA elsewhere
        prep = dict(rope_theta=float(self.rope_theta or 0.0),
                    segments=2 if mask == "block_diffusion" else 1)
        fused = use_flash and d % 128 == 0 and bool(
            self.qk_norm or self.rope_theta)

        # three position streams that differ: the plain path with the
        # angles of each (text takes the tables, and the kernels)
        with _part("attn_prep"):
            angles = None if positions is None else qp.rope_angles(
                positions, d, float(self.rope_theta), self.mrope_section)

        @_part("attn_prep")
        def prepare(lp, qkv):
            """qkv -> (q, k, v), q and k normed and rotated."""
            gains = (lp["qnorm"], lp["knorm"]) if self.qk_norm \
                else (None, None)
            if angles is not None:
                return qp.qk_prep_plain(qkv, *gains, nh, nkv, angles=angles,
                                        **prep)
            if not fused:
                return qp.qk_prep_plain(qkv, *gains, nh, nkv, **prep)
            return pallas_env.per_shard(
                mesh, lambda qkv, gains: qp.qk_prep(
                    qkv, *gains, nh, nkv, interpret=interpret, **prep),
                (rows, P()), (rows, rows, rows))(qkv, gains)

        @_part("attn_core")
        def attend(q, k, v):
            if flash:
                return pallas_env.per_shard(
                    mesh, lambda q, k, v: fa.flash_attention_gq(
                        q, k, v, nkv, mask, blen, interpret=interpret),
                    (rows, rows, rows), rows)(q, k, v)
            return fa.attention_gq_dense(q, k, v, nkv, mask, blen)

        def attention(lp, h):
            """h + the block's attention."""
            x = rmsnorm(h, None)          # gain folded into wqkv
            with _part("attn_proj"):
                qkv = jnp.einsum("bse,fe->bsf", x, lp["wqkv"].astype(dt))
            att = attend(*prepare(lp, qkv))
            with _part("attn_proj"):
                return h + jnp.einsum("bsf,ef->bse", att,
                                      lp["wo"].astype(dt))

        if self.attn == "mla":
            attention = self._mla_attention(dt, interpret, mesh, use_flash,
                                            rmsnorm)
        if self.dsa:
            attention = self._dsa_attention(dt, interpret, mesh, use_flash,
                                            rmsnorm, prepare, positions)

        m = self.nhidden_mlp

        def mlp(lp, x):
            b, s, e = x.shape
            if self.sorted:
                @_part("moe_dispatch")
                def routed(x, ep):
                    # each data-parallel replica routes its own rows
                    # (the grouped products are Pallas kernels)
                    n = x.shape[0] * s
                    y, stats = ms.moe_sorted(
                        x.reshape(n, e), ep,
                        topk=self.topk, total=self.nexpert,
                        first=self.expert_first, held=self.held,
                        norm_topk=bool(self.moe_norm_topk), dt=dt,
                        interpret=interpret, score=self.moe_score,
                        scale=self.moe_scale)
                    return y.reshape(x.shape), stats[None]
                y, stats = pallas_env.per_shard(
                    mesh, routed, (rows, P()), (rows, rows))(
                        x, {k: lp[k] for k in self._EXPERT_TAGS
                            if k in lp})
                # the replicas' counters: sums, and the largest load
                stats = jnp.where(
                    jnp.arange(len(ms.STATS)) == ms.STATS.index(
                        "load_max"), stats.max(0), stats.sum(0))
                return y, stats
            with _part("mlp"):
                a = jnp.einsum("bse,me->bsm", x, lp["w1"].astype(dt))
                if self.mlp_act == "swiglu":
                    a = (jax.nn.silu(a[..., :m].astype(jnp.float32))
                         * a[..., m:].astype(jnp.float32)).astype(dt)
                else:
                    a = jax.nn.relu(a)
                return jnp.einsum("bsm,em->bse", a,
                                  lp["w2"].astype(dt)), 0.0

        def block(lp, h):
            h = attention(lp, h)
            if self.dsa:
                h, (kl, kept) = h
            if "w1d" in lp:
                # the leading dense layer (dense_first): a gated MLP of
                # its own width where the others route
                x = rmsnorm(h, lp["norm2"])
                with _part("mlp"):
                    return h + ms.shared_expert(
                        x.reshape(-1, x.shape[-1]), lp["w1d"], lp["w2d"],
                        dt).reshape(x.shape), 0.0
            # the routed layer gates on the gained activations: its
            # gain is applied, not folded (_fold_norms)
            y, aux = mlp(lp, rmsnorm(h, lp["norm2"] if self.moe
                                     else None))
            if self.dsa:
                aux = {"moe": jnp.asarray(aux, jnp.float32),
                       "dsa_kl": kl, "dsa_pairs": kept}
            return h + y, aux
        return block

    def _dsa_attention(self, dt, interpret, mesh, use_flash, rmsnorm,
                       prepare, positions):
        """-> attention(lp, h) -> (h + the learned sparse attention, (the
        layer's KL term summed over rows and positions, the pairs its
        attend kept)) (``attn_sparse = dsa``), on ``_fold_norms``' leaves
        (``norm1``'s gain folded, detached, into the indexer's three
        projections)."""
        from .ops import dsa_attention as da
        from .ops import pallas_env
        from .ops import qk_prep as qp
        nh, nkv, d = self.nhead, self.nkv, self.hd
        ih, idim, topk = self.idx_heads, self.idx_dim, self.idx_topk
        theta = float(self.rope_theta)
        rows = pallas_env.rows_spec(mesh)
        # the indexer turns by the first (temporal) stream alone
        with _part("idx"):
            turn = None if positions is None else qp.rope_angles(
                positions[..., 0], idim, theta)

        @_part("attn_core")
        def attend(*ops):
            S = ops[0].shape[1]
            if not use_flash:
                return da.dsa_attention_dense(*ops, nkv, topk)
            if not da.dsa_supported(S, nh * d, nkv * d, nkv, ih * idim,
                                    ih):
                raise ValueError(
                    "transformer_stack: attn_sparse = dsa on the kernels "
                    "(attn_impl = pallas, or auto on a TPU) takes heads "
                    "of whole 128 lanes (head_dim %d), index heads that "
                    "fill whole lane tiles (idx_dim %d, idx_heads %d) and "
                    "whole tiles of positions (%d); attn_impl = xla takes "
                    "the dense twin, which holds a row's every pair"
                    % (d, idim, ih, S))
            return pallas_env.per_shard(
                mesh, lambda *ops: da.flash_attention_dsa(
                    *ops, nkv, topk, interpret=interpret),
                (rows,) * 6, (rows,) * 3)(*ops)

        def attention(lp, h):
            b, s, _ = h.shape
            x = rmsnorm(h, None)          # gain folded into wqkv
            with _part("attn_proj"):
                qkv = jnp.einsum("bse,fe->bsf", x, lp["wqkv"].astype(dt))
            xb = jax.lax.stop_gradient(x)
            proj = _part("idx_proj")(lambda w, **kw: jnp.einsum(
                "bse,fe->bsf", xb, lp[w].astype(dt), **kw))
            with _part("idx"):
                qi = qp.rotate_half(
                    proj("wiq").reshape(b, s, ih, idim), turn,
                    theta).astype(dt).reshape(b, s, ih * idim)
                kf = proj("wik").astype(jnp.float32)
                mu = jnp.mean(kf, -1, keepdims=True)
                var = jnp.mean(jnp.square(kf - mu), -1, keepdims=True)
                kf = (kf - mu) * jax.lax.rsqrt(var + 1e-6) \
                    * lp["iknorm"][0] + lp["iknorm"][1]
                ki = qp.rotate_half(kf[:, :, None], turn, theta
                                    )[:, :, 0].astype(dt)
                wi = proj("wiw", preferred_element_type=jnp.float32) \
                    * (ih ** -0.5 * idim ** -0.5)
            att, kl, kept = attend(*prepare(lp, qkv), qi, ki, wi)
            with _part("attn_proj"):
                out = h + jnp.einsum("bsf,ef->bse", att,
                                     lp["wo"].astype(dt))
            return out, (jnp.sum(kl), jnp.sum(kept))
        return attention

    def _mla_attention(self, dt, interpret, mesh, use_flash, rmsnorm):
        """-> attention(lp, h) -> h + latent attention (``attn = mla``),
        on the leaves ``_fold_norms`` derives: ``wqn``, ``wqr`` (the
        heads' nope and rope rows of ``wqb``), ``wkc``, ``wkr`` (the
        latent's and the shared key's rows of ``wkva``), ``wkn``, ``wv``
        (the heads' key and value rows of ``wkvb``), the rope rows with
        their even dims first (``ops.flash_attention.rope_pairs``)."""
        from jax.sharding import PartitionSpec as P
        from .ops import flash_attention as fa
        from .ops import kept, pallas_env
        nh, dr = self.nhead, self.d_rope
        theta = float(self.rope_theta)
        rows = pallas_env.rows_spec(mesh)
        flash = use_flash and fa.mla_supported(nh, self.d_nope, dr,
                                               self.d_v)

        @_part("attn_core")
        def attend(*ops):
            if flash:
                return pallas_env.per_shard(
                    mesh, lambda *ops: fa.flash_attention_mla(
                        *ops, nh, interpret=interpret,
                        mark=(("q_rank", self.q_rank),
                              ("kv_rank", self.kv_rank))),
                    (rows,) * 5, rows)(*ops)
            return fa.attention_mla_dense(*ops, nh)

        def attention(lp, h):
            b, s, _ = h.shape
            @_part("attn_proj")
            def proj(x, w, name=None):
                # the narrow products go out under the name a block
                # under remat = 1 keeps them by (ops/kept.py), as they
                # leave the matmul: the norms, the rotation and the
                # kernel's wide operands are what a replay computes
                y = jnp.einsum("bse,fe->bsf", x, lp[w].astype(dt))
                return kept.keep(y, name) if name else y
            pos = jnp.arange(s)
            x = rmsnorm(h, lp["norm1"])
            cq = rmsnorm(proj(x, "wqa", "attn_latent"), lp["qanorm"])
            ckv = rmsnorm(proj(x, "wkc", "attn_latent"), lp["kvnorm"])
            with _part("attn_prep"):
                qr = fa.rope_pairs(
                    proj(cq, "wqr").reshape(b, s, nh, dr), pos, theta,
                    True).reshape(b, s, nh * dr)
                kr = fa.rope_pairs(
                    proj(x, "wkr", "attn_latent")[:, :, None], pos, theta,
                    True)[:, :, 0]
            att = attend(proj(cq, "wqn"), qr, proj(ckv, "wkn"), kr,
                         proj(ckv, "wv"))
            with _part("attn_proj"):
                return h + proj(att, "wo", "attn_wo")
        return attention

    def _fold_norms(self, params, dt):
        """Fold the rmsnorm gains into the weight matrices they feed:
        (g * x) . W^T == x . (W * g)^T, so norm1 rides wqkv and norm2
        rides the dense w1 — one (L, f, e) multiply at trace time (it
        fuses into the bf16 weight cast) replaces a (b, s, e)
        elementwise pass per norm per step. Gradients for the gains
        flow through the fold automatically (jax.grad of the multiply).
        The MoE norm2 is NOT folded: the router gates on the gained
        activations, so folding into w1 alone would change expert
        selection (and diverge from generate.py's cached decode) —
        the block applies that gain explicitly instead."""
        out = dict(params)
        if self.attn == "mla":
            # the gains stay where they are (norm1 feeds two products);
            # the projections' rows are split by what the kernels read,
            # the rope rows' even dims first, once a step on the weights
            nh, dn, dr, dv = self.nhead, self.d_nope, self.d_rope, self.d_v
            L, kr = params["wqb"].shape[0], self.kv_rank
            evens_first = lambda w: w.reshape(
                w.shape[:-2] + (dr // 2, 2, w.shape[-1])).swapaxes(
                    -2, -3).reshape(w.shape)
            wqb = out.pop("wqb").reshape(L, nh, dn + dr, -1)
            out["wqn"] = wqb[:, :, :dn].reshape(L, nh * dn, -1)
            out["wqr"] = evens_first(wqb[:, :, dn:]).reshape(
                L, nh * dr, -1)
            wkva = out.pop("wkva")
            out["wkc"] = wkva[:, :kr]
            out["wkr"] = evens_first(wkva[:, kr:])
            wkvb = out.pop("wkvb").reshape(L, nh, dn + dv, -1)
            out["wkn"] = wkvb[:, :, :dn].reshape(L, nh * dn, -1)
            out["wv"] = wkvb[:, :, dn:].reshape(L, nh * dv, -1)
            for k in ("wqa", "wqn", "wqr", "wkc", "wkr", "wkn", "wv",
                      "ws1", "ws2"):
                if k in out:
                    out[k] = out[k].astype(dt)
        else:
            out["wqkv"] = (params["wqkv"]
                           * params["norm1"][:, None, :]).astype(dt)
        if self.dsa:
            # the indexer reads the normed input detached: the gain it
            # folds in is a constant to it
            g1 = jax.lax.stop_gradient(params["norm1"])[:, None, :]
            for k in ("wiq", "wik", "wiw"):
                out[k] = (params[k] * g1).astype(dt)
        if not self.moe:
            out["w1"] = (params["w1"]
                         * params["norm2"][:, None, :]).astype(dt)
        # pre-cast the remaining stacked weights outside the scan too:
        # one pass over (L, ...) instead of a per-iteration cast the
        # scan body re-does every layer. Covers the MoE stacks' w1
        # (unfolded — router-gain constraint) and gate as well; the
        # in-block astype(dt) calls become no-ops, and the routing
        # math already runs in dt
        # (the sorted dispatch routes in float32: its gate stays as it is)
        for k in ("wo", "w2", "w1") + (() if self.sorted else ("gate",)):
            if k in out and out[k].dtype != dt and out[k].ndim > 2:
                out[k] = out[k].astype(dt)
        # the leaves that are not stacked over depth
        for k in ("normf", "w1d", "w2d", "enorm", "hnorm", "ehproj"):
            out.pop(k, None)
        return out

    def apply(self, params, inputs, ctx):
        b, _, s, e = inputs[0].shape
        dt = ctx.compute_dtype
        h = inputs[0].reshape(b, s, e).astype(dt)
        mesh = ctx.mesh
        pipe = mesh.shape.get("pipe", 1) if mesh is not None else 1
        from .obs import trace
        from .ops import flash_attention as fa
        from .ops import kept
        use_flash = fa.resolve_impl(self.attn_impl, ctx.platform,
                                    s) == "pallas"
        interp = ctx.platform != "tpu"
        # analytic hardware flops of the flash kernels XLA cannot count
        # (opaque custom_call AND a scan body it would count only once):
        # flash runs in every block unless seq sharding fell back to
        # ring; under remat too the forward kernel runs once a block
        # (its output and log-sum-exp are kept, below)
        seq_axis = getattr(ctx, "seq_axis", None)
        seq_sharded = (pipe == 1 and mesh is not None
                       and seq_axis is not None
                       and mesh.shape.get(seq_axis, 1) > 1)
        if self.grouped and pipe > 1:
            raise ValueError(
                "transformer_stack: the grouped block (rotary positions, "
                "grouped heads, attn_mask = block_diffusion, attn_sparse = "
                "dsa, the sorted dispatch) does not run under "
                "pipeline_parallel: the stages' shard_map hands a block "
                "neither its mask's schedule nor its counters nor its "
                "KL term")
        if use_flash and not self.grouped \
                and (not seq_sharded or self.attn_impl == "pallas"):
            fhw, bhw = fa.analytic_flops(b, self.nhead, s,
                                         e // self.nhead,
                                         bool(self.causal))
            ctx.add_pallas_flops(
                "flash_attention", fhw * self.nlayer,
                bhw * self.nlayer if ctx.train else 0.0, interp)
        # the pipeline path reshards x to P(data) in its shard_map
        # in_specs, so only the scan path runs seq-parallel attends
        positions = inputs[1].reshape(b, s, 3) if len(inputs) > 1 \
            and self.mrope_section else None
        block = self._block_fn(dt, interpret=interp,
                               mesh=None if pipe > 1 else mesh,
                               seq_axis=getattr(ctx, "seq_axis", None),
                               use_flash=use_flash, positions=positions)
        depth = self.nlayer
        folded = self._fold_norms(params, dt)
        if self.remat:
            # replayed in the backward pass except what a block hands
            # out under kept.KEPT's names (the class docstring): the
            # attend's two results, so the forward kernel runs once a
            # block, and the narrow values that cost a replay much
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.save_only_these_names(
                    *kept.KEPT))
        lp0 = None
        if self.dense_first:
            # layer 0, with the dense MLP's leaves; the scan (or the
            # unrolled loop) takes the rest, whose expert leaves are
            # nlayer - 1 deep already
            lp0 = {k: v[0] for k, v in folded.items()
                   if k not in self._EXPERT_TAGS}
            lp0.update(w1d=params["w1d"], w2d=params["w2d"])
        if self.remat:
            with trace.span("remat.plan", "kernel") as sp:
                if sp is not trace.NOOP_SPAN:
                    # layer 0's own names once, the others' by a block
                    # of theirs (a leaf's shape less its depth)
                    one = jax.tree.map(lambda v: jax.ShapeDtypeStruct(
                        v.shape[1:], v.dtype), folded)
                    held = collections.Counter()
                    for n, lp in ((1, lp0), (depth - bool(lp0), one)):
                        if lp:
                            for name, nbytes in kept.kept_bytes(
                                    block, lp, h).items():
                                held[name] += n * nbytes
                    sp.note(layer=ctx.layer_index, blocks=depth,
                            kept=",".join(k for k in kept.KEPT if k in held),
                            kept_bytes=sum(held.values()))
        if lp0:
            h, _ = block(lp0, h)
            folded = {k: v if k in self._EXPERT_TAGS else v[1:]
                      for k, v in folded.items()}
            depth -= 1
        if pipe > 1:
            if self.nlayer % pipe != 0:
                raise ValueError(
                    "transformer_stack: nlayer %d not divisible by "
                    "pipeline_parallel %d" % (self.nlayer, pipe))
            if self.moe:
                raise ValueError(
                    "transformer_stack: moe=1 does not compose with "
                    "pipeline_parallel yet (the per-block aux loss needs "
                    "a cross-stage reduction); use expert parallelism "
                    "via model_parallel instead")
            from .ops import pipeline
            nmb = self.n_microbatch or pipe
            cast = {k: v.astype(dt) if v.ndim > 2 else v
                    for k, v in folded.items()}
            h = pipeline.sharded_pipeline(
                mesh, lambda lp, hh: block(lp, hh)[0], cast, h, nmb,
                contains_pallas=use_flash)
        elif self.scan_unroll >= depth > 1:
            # FULL Python unroll (scan_unroll >= nlayer): no lax.scan
            # at all — each layer's weights become independent
            # constants XLA can schedule and prefetch freely, where
            # the scan must dynamic-slice one (L, ...) stack per
            # iteration. Measured r4 at the ViT-S/16 encoder shape:
            # 16.6 vs 23.3 ms for the 12-layer matmul stack fwd+bwd
            # (the partially-unrolled scan is the WORST of both —
            # r3's scan_unroll=4 lost 22% — because it keeps the
            # sliced-stack access without removing the loop).
            # Costs compile time ~linear in depth; opt-in by knob.
            auxs = []
            for i in range(depth):
                lp = jax.tree.map(lambda v, i=i: v[i], folded)
                h, a = block(lp, h)
                auxs.append(a if self.dsa else jnp.asarray(a, jnp.float32))
            auxs = jax.tree.map(lambda *a: jnp.stack(a), *auxs)
        else:
            def body(hh, lp):
                h2, a = block(lp, hh)
                return h2, a if self.dsa else jnp.asarray(a, jnp.float32)
            h, auxs = jax.lax.scan(
                body, h, folded,
                unroll=max(1, min(self.scan_unroll, depth)))
        # a layer's aux, one a layer: the one-hot dispatch's load-balance
        # loss, or the sorted dispatch's counters (the pipeline branch
        # rejects moe above)
        if self.dsa:
            # the indexer's KL term: into the step's loss (the mean over
            # positions, summed over the layers, idx_loss times) and out
            # as a stat a layer, beside the counters of the selection
            kl, kept = auxs["dsa_kl"], auxs["dsa_pairs"]
            auxs = auxs["moe"]
            ctx.losses.append(self.idx_loss * jnp.sum(kl) / (
                ctx.batch_size * ctx.update_period * s))
            ctx.stats[(ctx.layer_index, "dsa_index_loss")] = kl / (b * s)
            ctx.stats[(ctx.layer_index, "dsa_pairs")] = kept
            ctx.stats[(ctx.layer_index, "dsa_pairs_causal")] = jnp.full(
                kept.shape, b * (s * (s + 1) // 2), kept.dtype)
        if pipe == 1 and self.moe and self.sorted:
            from .ops.moe_sorted import STATS
            for j, name in enumerate(STATS):
                ctx.stats[(ctx.layer_index, "moe_" + name)] = auxs[:, j]
        elif pipe == 1 and self.moe and ctx.train and self.moe_loss > 0.0:
            ctx.losses.append(self.moe_loss * jnp.sum(auxs) / self.nlayer)
        raw = h
        if self.final_norm:
            h = (h.astype(jnp.float32) * jax.lax.rsqrt(jnp.mean(
                jnp.square(h.astype(jnp.float32)), -1, keepdims=True)
                + 1e-6) * params["normf"])
        out = [h.astype(jnp.float32).reshape(b, 1, s, e)]
        if self.raw_out:
            out.append(raw.astype(jnp.float32).reshape(b, 1, s, e))
        return out


@register("seq_shift")
class SeqShiftLayer(Layer):
    """(b, 1, s, w) -> the same sequence ``shift`` positions on:
    ``out[i] = in[i + shift]``, the last ``shift`` positions 0 (a token
    model's ids one step ahead, for an ``mtp`` layer's embedding: those
    positions have no target and enter no loss). No parameters."""

    def __init__(self):
        super().__init__()
        self.shift = 1

    def set_param(self, name, val):
        if name == "shift":
            self.shift = int(val)
        else:
            super().set_param(name, val)

    def _infer(self, in_shapes):
        n, c, s, w = in_shapes[0]
        if c != 1 or not 0 < self.shift < s:
            raise ValueError("seq_shift: input must be (batch,1,seq,w) "
                             "and 0 < shift < seq")
        return [in_shapes[0]]

    def apply(self, params, inputs, ctx):
        x = inputs[0]
        return [jnp.pad(x[:, :, self.shift:],
                        ((0, 0), (0, 0), (0, self.shift), (0, 0)))]


@register("mtp")
class MTPLayer(TransformerStackLayer):
    """Multi-token prediction module, depth 1 (DeepSeek-V3 section 2.2):
    inputs the trunk's residual stream before its final norm (a
    ``transformer_stack`` with ``raw_out = 1``) and the embedding of the
    NEXT token (``share`` of the embedding on ``seq_shift``-ed ids);
    ``h' = W_eh [rmsnorm(emb; enorm) ; rmsnorm(h; hnorm)]`` (tag
    ``ehproj``, (e, 2e)), one block of the stack's own function under
    this layer's options (every option of ``transformer_stack``; the
    block's leaves stacked 1 deep), and the final norm (``normf``).
    Output: the hidden stream an ``lm_head`` with ``mtp_weight`` reads
    as its second input, against the labels one step on. A routed block
    counts into the same counters as the trunk's."""
    param_tags = TransformerStackLayer.param_tags + ("enorm", "hnorm",
                                                     "ehproj")

    def infer_shape(self, in_shapes):
        self._check_arity(in_shapes, 2, 1)
        if in_shapes[0] != in_shapes[1]:
            raise ValueError("mtp: reads the trunk's (batch,1,seq,embed) "
                             "and the next tokens' embedding of the same "
                             "shape; got %s and %s" % tuple(in_shapes))
        if self.nlayer != 1 or self.dense_first or self.raw_out:
            raise ValueError("mtp: one block (nlayer = 1), no dense_first, "
                             "no raw_out")
        self.final_norm = 1
        out = self._infer(in_shapes[:1])
        self.in_shapes, self.out_shapes = list(in_shapes), out
        return out

    def init_params(self, rng) -> Params:
        e = self.in_shapes[0][3]
        out = super().init_params(jax.random.fold_in(rng, 0))
        out["enorm"] = jnp.ones((e,), jnp.float32)
        out["hnorm"] = jnp.ones((e,), jnp.float32)
        out["ehproj"] = self.param.rand_init_weight(
            jax.random.fold_in(rng, 1), (e, 2 * e), 2 * e, e)
        return out

    def analytic_flops(self, skip_dx=False):
        n, _, s, e = self.in_shapes[0]
        fwd, bwd = super().analytic_flops(skip_dx)
        return fwd + 4.0 * n * s * e * e, bwd + 8.0 * n * s * e * e

    def apply(self, params, inputs, ctx):
        dt = ctx.compute_dtype

        def normed(x, g):
            x = x.astype(jnp.float32)
            return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1,
                                               keepdims=True) + 1e-6)
                    ).astype(dt) * params[g].astype(dt)
        both = jnp.concatenate([normed(inputs[1], "enorm"),
                                normed(inputs[0], "hnorm")], -1)
        h = jnp.einsum("bcsf,ef->bcse", both, params["ehproj"].astype(dt))
        return super().apply(params, [h], ctx)


def _stable_logits(logits: jnp.ndarray) -> jnp.ndarray:
    """Pre-subtract the row max before softmax/log_softmax.

    jax.nn.softmax is mathematically max-stabilized, but on the TPU
    backend XLA may reassociate the stabilization into exp(x)/exp(max),
    which overflows for large-but-FINITE logits (observed: finite
    logits of ~1.4e6 -> NaN probs, silently killing a converging
    AlexNet run the moment its margins grew). With the max subtracted
    up front every exp argument is <= 0, so no reassociation can
    overflow. stop_gradient keeps the backward pass the standard
    softmax gradient."""
    return logits - jax.lax.stop_gradient(
        jnp.max(logits, axis=-1, keepdims=True))


@register("softmax")
class SoftmaxLayer(_LossLayer):
    """Softmax + cross entropy (reference: src/layer/loss/softmax_layer-inl.hpp:12-36).

    Node value becomes softmax probabilities; loss term is
    scale * sum_i -log p_i[y_i] whose input-gradient is scale*(p - onehot),
    the reference's p[y] -= 1 rescaled.
    """

    def apply(self, params, inputs, ctx):
        n, c, s, v = inputs[0].shape
        if c == 1 and s > 1:
            # sequence node (b, 1, s, V): per-position softmax CE against
            # an s-wide label field — the language-model objective (no
            # reference analogue; cxxnet's softmax is per-instance only).
            # Loss normalized per token so grad_scale semantics carry over.
            logits = _stable_logits(inputs[0].reshape(n, s, v))
            probs = jax.nn.softmax(logits, axis=-1)
            if ctx.labels is not None:
                y = self._label(ctx).astype(jnp.int32)      # (n, s)
                if y.shape[1] != s:
                    # a narrower field would silently broadcast one label
                    # across every position — a wrong objective
                    raise ValueError(
                        "softmax on a %d-position sequence needs an "
                        "equally wide label field (declare "
                        "label_vec[0,%d) = %s and set label_width); got "
                        "width %d" % (s, s, self.target, y.shape[1]))
                logp = jax.nn.log_softmax(logits, axis=-1)
                ce = -jnp.take_along_axis(logp, y[..., None],
                                          axis=2).sum()
                ctx.losses.append(ce * self._scale(ctx) / s)
            return [probs.reshape(inputs[0].shape)]
        logits = _stable_logits(_mat(inputs[0]))
        probs = jax.nn.softmax(logits, axis=-1)
        if ctx.labels is not None:
            y = self._label(ctx)[:, 0].astype(jnp.int32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            ce = -jnp.take_along_axis(logp, y[:, None], axis=1).sum()
            ctx.losses.append(ce * self._scale(ctx))
        return [probs.reshape(inputs[0].shape)]


@register("lm_head")
class LMHeadLayer(_LossLayer):
    """Fused vocabulary head: position-wise projection + softmax CE in
    one layer — trajectory-equivalent to the ``fullc(seq=1)+softmax``
    pair (pinned by tests/test_lm.py::test_lm_head_matches_pair) with
    the training loss computed CHUNKED over token rows under
    ``jax.checkpoint``, so the (tokens, vocab) logits+grad pair is
    never resident at once. At GPT-2-small scale (16k tokens x 32k
    vocab) that pair is ~4 GB of f32 HBM; the chunked loss caps it at
    rows/ce_chunk, measured faster than the unfused head on v5e AND
    the difference between batch 64 fitting on one chip or OOMing
    (docs/performance.md r4).

    The node value stays the pair's surface — softmax probabilities —
    and XLA dead-code-eliminates that full-vocab matmul in training
    traces where nothing reads the output node (eval_train=0; with a
    train metric the probs are consumed and both paths run).

    Config: ``nhidden`` (vocab size), ``ce_chunk`` (chunk count over
    token rows; 0 = auto for ~256 MB logit slabs), ``logit_dtype``
    (``compute``|``float32``, default compute — the CE upcasts to f32
    after the bf16 matmul, standard LM practice), plus the loss keys
    (``target``, ``grad_scale``). Params ``wmat``/``bias`` in fullc
    layout. No reference analogue (cxxnet has no token models,
    SURVEY.md §5).

    ``mtp_weight = w`` (> 0): a second input node, an ``mtp`` layer's
    hidden stream, passes the same head; its cross entropy against the
    labels one step on (position i against label i + 1, over the s - 1
    positions that have one) is added ``w`` times. The unweighted value
    rides out as the step's stat ``mtp_loss``.
    """
    has_params = True

    def __init__(self):
        super().__init__()
        self.ce_chunk = 0
        self.logit_dtype = "compute"
        self.objective = "next_token"
        self.mtp_weight = 0.0

    def set_param(self, name, val):
        if name == "objective":
            # block_diffusion: two inputs, the stack's output over
            # [x_t ; x_0] and bd_noise's (target, weight) node; the loss
            # is over the noisy half, each position's CE against its
            # clean token times its weight; the label field is not read
            if val not in ("next_token", "block_diffusion"):
                raise ValueError(
                    "lm_head: objective must be next_token|block_diffusion")
            self.objective = val
        elif name == "mtp_weight":
            self.mtp_weight = float(val)
        elif name == "ce_chunk":
            self.ce_chunk = int(val)
        elif name == "logit_dtype":
            if val not in ("compute", "float32"):
                raise ValueError(
                    "lm_head: logit_dtype must be compute|float32")
            self.logit_dtype = val
        else:
            super().set_param(name, val)

    def infer_shape(self, in_shapes):
        if self.mtp_weight > 0.0:
            if self.objective != "next_token" or len(in_shapes) != 2 \
                    or in_shapes[0] != in_shapes[1] or in_shapes[0][2] < 2:
                raise ValueError(
                    "lm_head: mtp_weight reads the trunk's and an mtp "
                    "layer's (batch,1,seq,embed) under objective = "
                    "next_token; got %s" % (in_shapes,))
            out = self._infer(in_shapes[:1])
            self.in_shapes, self.out_shapes = list(in_shapes), out
            return out
        if self.objective != "block_diffusion":
            return super().infer_shape(in_shapes)
        self._check_arity(in_shapes, 2, 1)
        n, c, s2, e = in_shapes[0]
        if in_shapes[1] != (n, 1, s2 // 2, 2):
            raise ValueError(
                "lm_head: objective = block_diffusion reads the stack's "
                "(batch,1,2*seq,embed) and bd_noise's (batch,1,seq,2); "
                "got %s and %s" % (in_shapes[0], in_shapes[1]))
        out = self._infer([(n, c, s2 // 2, e)])
        self.in_shapes, self.out_shapes = list(in_shapes), out
        return out

    def _infer(self, in_shapes):
        n, c, s, e = in_shapes[0]
        if c != 1:
            raise ValueError("lm_head: input must be (batch,1,seq,embed)")
        if self.param.num_hidden <= 0:
            raise ValueError("lm_head: must set nhidden (vocab size)")
        if self.param.num_input_node == 0:
            self.param.num_input_node = e
        elif self.param.num_input_node != e:
            raise ValueError("lm_head: input hidden nodes inconsistent")
        super()._infer(in_shapes)       # resolves target_index
        return [(n, 1, s, self.param.num_hidden)]

    def init_params(self, rng) -> Params:
        nh, ni = self.param.num_hidden, self.param.num_input_node
        p = {"wmat": self.param.rand_init_weight(rng, (nh, ni), ni, nh)}
        if self.param.no_bias == 0:
            p["bias"] = jnp.full((nh,), self.param.init_bias,
                                 jnp.float32)
        return p

    def analytic_flops(self, skip_dx=False):
        n, _, s, e = self.in_shapes[0]
        if self.objective == "block_diffusion":
            s //= 2                     # the noisy half alone
        if self.mtp_weight > 0.0:
            s += s - 1                  # the second stream's positions
        f = 2.0 * n * s * e * self.param.num_hidden
        return f, f if skip_dx else 2.0 * f

    def _chunks(self, rows: int, v: int) -> int:
        # chunk COUNT sized so each chunk's f32 logits stay ~64 MB; the
        # count need not divide rows (apply pads + masks the tail) — a
        # divisor walk here degenerated to chunk-size-1 scans on
        # prime-ish row counts (ADVICE r4)
        if self.ce_chunk > 0:
            c = self.ce_chunk
        else:
            c = max(1, int(round(rows * v * 4 / 268e6)))
        return min(c, rows)

    def apply(self, params, inputs, ctx):
        n, _, s, e = inputs[0].shape
        v = self.param.num_hidden
        dt = ctx.compute_dtype if self.logit_dtype == "compute" \
            else jnp.float32
        hidden, side = inputs[0], None
        if self.objective == "block_diffusion":
            s //= 2
            hidden, side = hidden[:, :, :s], inputs[1].reshape(n * s, 2)
        x = hidden.reshape(n * s, e).astype(dt)
        w = params["wmat"].astype(dt)
        bias = params.get("bias")

        def logits_of(rows):
            lg = jnp.dot(rows, w.T)
            if bias is not None:
                lg = lg + bias.astype(lg.dtype)
            return lg

        # eval/predict surface (dead code in fused-loss train traces)
        probs = jax.nn.softmax(
            _stable_logits(logits_of(x).astype(jnp.float32)), axis=-1)
        if ctx.labels is not None or side is not None:
            y = (side[:, 0] if side is not None
                 else self._label(ctx)).astype(jnp.int32)
            if side is None and s > 1 and y.shape[1] != s:
                raise ValueError(
                    "lm_head on a %d-position sequence needs an equally "
                    "wide label field (declare label_vec[0,%d) = %s and "
                    "set label_width); got width %d"
                    % (s, s, self.target, y.shape[1]))
            rows = n * s
            c = self._chunks(rows, v)
            chunk = -(-rows // c)        # pad + mask the ragged tail

            def chunked_ce(x, yf, wf):
                """Summed weighted cross entropy of ``rows`` rows."""
                if c * chunk != rows:
                    extra = c * chunk - rows
                    x = jnp.pad(x, ((0, extra), (0, 0)))
                    yf = jnp.pad(yf, (0, extra))
                    wf = jnp.pad(wf, (0, extra))
                xc = x.reshape(c, chunk, e)
                yc = yf.reshape(c, chunk)
                wc = wf.reshape(c, chunk)

                def chunk_ce(acc, t):
                    xx, yy, ww = t
                    # max-subtract in the matmul dtype, upcast after:
                    # every exp argument is <= 0 (the r2 TPU softmax
                    # hazard)
                    lg = logits_of(xx)
                    lg = (lg - jax.lax.stop_gradient(
                        lg.max(-1, keepdims=True))).astype(jnp.float32)
                    lp = jax.nn.log_softmax(lg, axis=-1)
                    picked = jnp.take_along_axis(lp, yy[:, None], axis=1)
                    return acc - (picked[:, 0] * ww).sum(), None

                return jax.lax.scan(jax.checkpoint(chunk_ce),
                                    jnp.zeros((), jnp.float32),
                                    (xc, yc, wc))[0]

            ce = chunked_ce(x, y.reshape(rows),
                            side[:, 1] if side is not None
                            else jnp.ones((rows,), jnp.float32))
            ctx.losses.append(ce * self._scale(ctx) / (s if s > 1 else 1))
            if self.mtp_weight > 0.0:
                # the mtp stream: position i against label i + 1, the
                # last position of a row against nothing
                y2 = jnp.pad(y.reshape(n, s)[:, 1:], ((0, 0), (0, 1)))
                w2 = jnp.broadcast_to(
                    (jnp.arange(s) < s - 1).astype(jnp.float32), (n, s))
                ce2 = chunked_ce(inputs[1].reshape(rows, e).astype(dt),
                                 y2.reshape(rows), w2.reshape(rows))
                ctx.losses.append(self.mtp_weight * ce2 * self._scale(ctx)
                                  / (s - 1))
                ctx.stats[(ctx.layer_index, "mtp_loss")] = \
                    ce2 / (n * (s - 1))
        return [probs.reshape(n, 1, s, v)]


@register("l2_loss")
class L2LossLayer(_LossLayer):
    """L2 loss (reference: src/layer/loss/l2_loss_layer-inl.hpp:12-37):
    identity forward, gradient pred - label."""

    def apply(self, params, inputs, ctx):
        pred = _mat(inputs[0])
        if ctx.labels is not None:
            y = self._label(ctx)
            l2 = 0.5 * jnp.square(pred - y).sum()
            ctx.losses.append(l2 * self._scale(ctx))
        return [inputs[0]]


@register("multi_logistic")
class MultiLogisticLayer(_LossLayer):
    """Elementwise sigmoid + BCE
    (reference: src/layer/loss/multi_logistic_layer-inl.hpp:12-38)."""

    def apply(self, params, inputs, ctx):
        logits = _mat(inputs[0])
        probs = jax.nn.sigmoid(logits)
        if ctx.labels is not None:
            y = self._label(ctx)
            bce = jnp.sum(jnp.logaddexp(0.0, logits) - logits * y)
            ctx.losses.append(bce * self._scale(ctx))
        return [probs.reshape(inputs[0].shape)]
