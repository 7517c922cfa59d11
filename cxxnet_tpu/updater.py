"""Updaters: sgd / nag / adam with the reference's LR + momentum schedules.

The reference pairs each weight tensor with an IUpdater object holding
mutable momentum buffers (reference: src/updater/updater.h:22-66,
sgd_updater-inl.hpp, nag_updater-inl.hpp, adam_updater-inl.hpp). Here each
updater is a *pure transform*: ``update(state, w, grad, epoch) ->
(new_w, new_state)`` — an optax-style function whose state pytree lives in
the jitted train step. Learning-rate schedules are computed inside the
trace from the epoch scalar so changing epoch never recompiles.

Hyper-parameter resolution preserves the reference's tag scoping
(reference: src/updater/param.h:100-131): plain keys (``eta``, ``wd``,
``momentum``) apply to every tensor; ``wmat:lr`` / ``bias:wd`` apply only
to tensors with that tag; later entries win. The gradient clip functor
also zeroes NaNs (sgd_updater-inl.hpp:15-22).

The async push/pull machinery (async_updater-inl.hpp) has no equivalent
here: gradient exchange is an XLA all-reduce emitted by sharding, and
compute/communication overlap comes from XLA's latency-hiding scheduler.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

ConfigEntry = Tuple[str, str]


@dataclass
class UpdaterHyperParams:
    """Mirrors UpdaterParam (reference: src/updater/param.h:13-132)."""
    tag: str = ""
    base_lr: float = 0.01
    wd: float = 0.0
    decoupled_wd: int = 0   # adam only: true AdamW decay (see AdamUpdater)
    momentum: float = 0.9
    lr_schedule: int = 0        # 0 const, 1 expdecay, 2 polydecay,
                                # 3 factor, 4 cosine (TPU-first addition)
    warmup_epochs: int = 0      # linear LR warmup over the first N
                                # updates (composes with any schedule)
    total_epochs: int = 0       # horizon for the cosine schedule
    momentum_schedule: int = 0
    lr_step: int = 1
    lr_gamma: float = 0.5
    lr_alpha: float = 0.5
    lr_factor: float = 0.1
    lr_minimum: float = 0.00001
    start_epoch: int = 0
    base_momentum: float = 0.5
    final_momentum: float = 0.90
    saturation_epoch: int = 0
    clip_gradient: float = 0.0
    recovery_lr_scale: float = 1.0
    # ^ internal multiplier on every EFFECTIVE rate, compounded by
    #   nan_guard=2 recovery. Deliberately its own key (not eta/lr): it
    #   must reach rates that re-appended globals never could —
    #   tag-scoped and layer-bucket lr entries — and it multiplies the
    #   rate in Adam's bit-exact constant-rate fast path too.
    silent: int = 0
    # adam extras (reference adam_updater-inl.hpp:21-22)
    beta1: float = 0.1
    beta2: float = 0.001

    # flat keys this parameter block recognizes — the trainer's
    # unconsumed-key audit consults this (plus the lr:/eta: prefixes
    # and <tag>: scoping) instead of replaying set_param
    KNOWN_KEYS = frozenset([
        "lr", "eta", "wd", "decoupled_wd", "momentum", "silent",
        "momentum_schedule", "clip_gradient", "recovery_lr_scale",
        "final_momentum", "base_momentum", "saturation_epoch",
        "beta1", "beta2", "clip_global_norm",
    ])
    KNOWN_SUBKEYS = frozenset([
        "schedule", "warmup", "total", "gamma", "alpha", "step",
        "factor", "minimum_lr", "start_epoch",
    ])

    @classmethod
    def claims(cls, name: str) -> bool:
        """Would SOME updater parameter block consume this key? Covers
        tag scoping ("wmat:lr") and the lr:/eta: schedule family."""
        if name in cls.KNOWN_KEYS:
            return True
        if ":" in name:
            head, sub = name.split(":", 1)
            if head in ("lr", "eta"):
                return sub in cls.KNOWN_SUBKEYS
            # tag-scoped: wmat:lr, bias:wd, wqkv:lr:schedule, ...
            return cls.claims(sub)
        return False

    def set_param(self, name: str, val: str) -> None:
        # tag scoping: "wmat:lr = ..." applies only when tag == "wmat"
        # (reference param.h:103-105)
        if self.tag and name.startswith(self.tag + ":"):
            name = name[len(self.tag) + 1:]
        if name in ("lr", "eta"):
            self.base_lr = float(val)
        elif name == "wd":
            self.wd = float(val)
        elif name == "decoupled_wd":
            self.decoupled_wd = int(val)
        elif name == "momentum":
            self.momentum = float(val)
        elif name == "silent":
            self.silent = int(val)
        elif name == "momentum_schedule":
            self.momentum_schedule = int(val)
        elif name == "clip_gradient":
            self.clip_gradient = float(val)
        elif name == "recovery_lr_scale":
            self.recovery_lr_scale = float(val)
        elif name == "final_momentum":
            self.final_momentum = float(val)
        elif name == "base_momentum":
            self.base_momentum = float(val)
        elif name == "saturation_epoch":
            self.saturation_epoch = int(val)
        elif name == "beta1":
            self.beta1 = float(val)
        elif name == "beta2":
            self.beta2 = float(val)
        elif name.startswith("lr:") or name.startswith("eta:"):
            sub = name.split(":", 1)[1]
            if sub == "schedule":
                self.lr_schedule = {"constant": 0, "expdecay": 1,
                                    "polydecay": 2, "factor": 3,
                                    "cosine": 4}.get(
                                        val, self.lr_schedule)
            elif sub == "warmup":
                self.warmup_epochs = int(val)
            elif sub == "total":
                self.total_epochs = int(val)
            elif sub == "gamma":
                self.lr_gamma = float(val)
            elif sub == "alpha":
                self.lr_alpha = float(val)
            elif sub == "step":
                self.lr_step = int(val)
            elif sub == "factor":
                self.lr_factor = float(val)
            elif sub == "minimum_lr":
                self.lr_minimum = float(val)
            elif sub == "start_epoch":
                self.start_epoch = int(val)

    # ------------------------------------------------------------------
    def schedule(self, epoch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(learning_rate, momentum) at ``epoch`` updates — traced-friendly
        version of ScheduleEpoch (reference: param.h:76-94)."""
        e = jnp.asarray(epoch, jnp.float32)
        if self.lr_schedule == 0:
            lr = jnp.asarray(self.base_lr, jnp.float32)
        elif self.lr_schedule == 1:
            lr = self.base_lr * jnp.power(self.lr_gamma, e / self.lr_step)
        elif self.lr_schedule == 2:
            lr = self.base_lr * jnp.power(
                1.0 + jnp.floor(e / self.lr_step) * self.lr_gamma,
                -self.lr_alpha)
        elif self.lr_schedule == 3:
            lr = self.base_lr * jnp.power(
                self.lr_factor, jnp.floor(e / self.lr_step))
        elif self.lr_schedule == 4:
            # cosine decay to lr_minimum over lr:total updates (warmup
            # excluded from the decay horizon) — the standard LM recipe;
            # no reference analogue (its schedules are param.h:76-94)
            if self.total_epochs <= 0:
                raise ValueError("lr:schedule = cosine needs lr:total")
            if self.warmup_epochs >= self.total_epochs:
                raise ValueError(
                    "lr:warmup (%d) must be smaller than lr:total (%d) — "
                    "both count UPDATES, not rounds"
                    % (self.warmup_epochs, self.total_epochs))
            span = max(self.total_epochs - self.warmup_epochs, 1)
            frac = jnp.clip((e - self.warmup_epochs) / span, 0.0, 1.0)
            lr = self.lr_minimum + (self.base_lr - self.lr_minimum) \
                * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
        else:
            raise ValueError("unknown schedule type")
        mom = jnp.asarray(self.momentum, jnp.float32)
        if self.momentum_schedule and self.saturation_epoch:
            # reproduced as written in the reference (param.h:84-86)
            mom = mom + ((self.final_momentum - self.base_momentum)
                         / self.saturation_epoch * e + self.base_momentum)
        # the reference clamps unconditionally (param.h:87)
        mom = jnp.minimum(mom, self.final_momentum)
        lr = jnp.maximum(lr, self.lr_minimum)
        if self.start_epoch > 0:
            lr = jnp.where(e < self.start_epoch, self.base_lr, lr)
        if self.warmup_epochs > 0:
            # linear ramp 0 -> scheduled lr over the first warmup updates
            lr = lr * jnp.clip((e + 1.0) / self.warmup_epochs, 0.0, 1.0)
        # applied last so it scales past lr_minimum too: recovery must be
        # able to reduce EVERY effective rate
        if self.recovery_lr_scale != 1.0:
            lr = lr * self.recovery_lr_scale
        return lr, mom


def _clip_nan(g: jnp.ndarray, bound: float) -> jnp.ndarray:
    """clip functor: NaN -> 0, clamp to [-bound, bound]
    (reference: sgd_updater-inl.hpp:15-22)."""
    g = jnp.where(jnp.isnan(g), 0.0, g)
    return jnp.clip(g, -bound, bound)


class TensorUpdater:
    """Pure update rule for one weight tensor."""

    def __init__(self, hp: UpdaterHyperParams) -> None:
        self.hp = hp

    def init_state(self, w: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def update(self, state, w, grad, epoch):
        raise NotImplementedError


class SGDUpdater(TensorUpdater):
    """m = mom*m - lr*(clip(g) + wd*w); w += m
    (reference: src/updater/sgd_updater-inl.hpp:73-84)."""

    def init_state(self, w):
        return {"m": jnp.zeros_like(w)}

    def update(self, state, w, grad, epoch):
        lr, mom = self.hp.schedule(epoch)
        if self.hp.clip_gradient != 0.0:
            grad = _clip_nan(grad, self.hp.clip_gradient)
        m = mom * state["m"] - lr * (grad + self.hp.wd * w)
        return w + m, {"m": m}


class NAGUpdater(TensorUpdater):
    """Nesterov via old/new momentum (reference: src/updater/nag_updater-inl.hpp:64-71)."""

    def init_state(self, w):
        return {"m": jnp.zeros_like(w)}

    def update(self, state, w, grad, epoch):
        lr, mom = self.hp.schedule(epoch)
        old_m = state["m"]
        m = mom * old_m - lr * (grad + self.hp.wd * w)
        return w + (1 + mom) * m - mom * old_m, {"m": m}


class AdamUpdater(TensorUpdater):
    """Bias-corrected Adam exactly as the reference writes it
    (reference: src/updater/adam_updater-inl.hpp:66-81), including the
    ``grad -= wd*w`` pre-step — note that the reference's sign makes
    coupled wd ANTI-regularizing under its descent update (a faithfully
    reproduced quirk). ``decoupled_wd = 1`` applies true AdamW decay
    instead: ``w -= lr * wd * w`` outside the adaptive normalization.
    The reference has no Adam LR schedule; here a configured
    ``lr:schedule`` / ``lr:warmup`` scales the rate (the transformer-LM
    recipe), and with neither set the reference's constant-rate behavior
    is preserved exactly."""

    def init_state(self, w):
        return {"m1": jnp.zeros_like(w), "m2": jnp.zeros_like(w)}

    def update(self, state, w, grad, epoch):
        hp = self.hp
        if hp.wd > 0.0 and not hp.decoupled_wd:
            grad = grad - hp.wd * w
        e = jnp.asarray(epoch, jnp.float32)
        fix1 = 1.0 - jnp.power(1.0 - hp.beta1, e + 1)
        fix2 = 1.0 - jnp.power(1.0 - hp.beta2, e + 1)
        if hp.lr_schedule or hp.warmup_epochs:
            base, _ = hp.schedule(epoch)
        else:   # no floor/clamp applied — bit-exact reference behavior
            base = hp.base_lr * hp.recovery_lr_scale
        lr_t = base * jnp.sqrt(fix2) / fix1
        m1 = state["m1"] + hp.beta1 * (grad - state["m1"])
        m2 = state["m2"] + hp.beta2 * (jnp.square(grad) - state["m2"])
        w = w - lr_t * (m1 / (jnp.sqrt(m2) + 1e-8))
        if hp.wd > 0.0 and hp.decoupled_wd:
            w = w - base * hp.wd * w
        return w, {"m1": m1, "m2": m2}


_UPDATERS = {"sgd": SGDUpdater, "nag": NAGUpdater, "adam": AdamUpdater}


def create_tensor_updater(kind: str, tag: str,
                          cfgs: Sequence[Sequence[ConfigEntry]]
                          ) -> TensorUpdater:
    """Build one tensor's updater; ``cfgs`` are applied in order
    (globals first, then layer bucket — later wins), mirroring
    CreateUpdater + SetParam streams (reference: updater_impl-inl.hpp:18-45,
    neural_net-inl.hpp:177-204)."""
    if kind not in _UPDATERS:
        raise ValueError("unknown updater type %s" % kind)
    hp = UpdaterHyperParams(tag=tag)
    for cfg in cfgs:
        for k, v in cfg:
            hp.set_param(k, v)
    return _UPDATERS[kind](hp)


class NetUpdater:
    """All per-(layer, tag) updaters for a network; one pure step.

    Replaces CreateAsyncUpdaters + the PS push/pull cycle
    (reference: src/updater/updater_impl-inl.hpp:57-116,
    async_updater-inl.hpp:94-143): grads arrive already reduced across the
    mesh (XLA collective), the update applies on-device, fused into the
    train step.
    """

    def __init__(self, net) -> None:
        # net: model.Network
        self.net = net
        cfg = net.cfg
        kind = cfg.updater_type
        self.updaters: List[Optional[Dict[str, TensorUpdater]]] = []
        for li, info in enumerate(cfg.layers):
            mod = net.modules[li]
            if info.type == "share" or not mod.has_params:
                self.updaters.append(None)
                continue
            layer_cfgs = (cfg.defcfg, cfg.layercfg[li])
            tags = getattr(mod, "param_tags", ("wmat", "bias"))
            self.updaters.append({
                tag: create_tensor_updater(kind, tag, layer_cfgs)
                for tag in tags})
        self._kind = kind
        # clip_global_norm: rescale the WHOLE gradient to a maximum L2
        # norm before the per-tensor updates — the modern LM recipe, on
        # top of (not replacing) the reference's per-element clip
        # (clip_gradient, sgd_updater-inl.hpp:15-22)
        self.clip_global_norm = 0.0
        for k, v in cfg.defcfg:
            if k == "clip_global_norm":
                self.clip_global_norm = float(v)
        for li, bucket in enumerate(cfg.layercfg):
            if any(k == "clip_global_norm" for k, _ in bucket):
                raise ValueError(
                    "clip_global_norm is a GLOBAL key (it rescales the "
                    "whole gradient); move it out of layer %d's netconfig "
                    "bucket" % li)
            if any(k == "recovery_lr_scale" for k, _ in bucket):
                # a bucket entry replays after the appended global and
                # would exempt that layer from nan_guard=2 recovery
                raise ValueError(
                    "recovery_lr_scale is reserved for nan_guard=2 "
                    "recovery and must not appear in layer %d's "
                    "netconfig bucket" % li)

    def init_state(self, params):
        states = []
        for li, p in enumerate(params):
            if p is None:
                states.append(None)
            else:
                # tags without an updater are non-trainable state (BN
                # running stats): no optimizer slots
                states.append({
                    tag: (self.updaters[li][tag].init_state(w)
                          if tag in self.updaters[li] else {})
                    for tag, w in p.items()})
        return states

    def apply(self, params, grads, opt_state, epoch):
        """One optimizer step over the whole net (pure), under the one
        word of ``obs.trace.PARTS`` outside any layer."""
        with jax.named_scope("opt"):
            return self._apply(params, grads, opt_state, epoch)

    def _apply(self, params, grads, opt_state, epoch):
        if self.clip_global_norm > 0.0:
            sq = jnp.zeros((), jnp.float32)
            for li, g in enumerate(grads):
                if not g or self.updaters[li] is None:
                    continue
                for tag, gv in g.items():
                    if self.updaters[li].get(tag) is not None:
                        sq = sq + jnp.sum(
                            jnp.square(gv.astype(jnp.float32)))
            gnorm = jnp.sqrt(sq)
            scale = jnp.minimum(
                1.0, self.clip_global_norm / jnp.maximum(gnorm, 1e-12))
            # non-finite norm (NaN grads, or Inf incl. f32 overflow of
            # the squared sum): leave grads to the per-element clip /
            # nan_guard rather than silently zeroing the whole step
            # (and minting inf*0 NaNs)
            scale = jnp.where(jnp.isfinite(gnorm), scale, 1.0)
            grads = [({tag: gv * scale for tag, gv in g.items()}
                      if g else g) for g in grads]
        new_params, new_state = [], []
        for li, p in enumerate(params):
            if p is None:
                new_params.append(None)
                new_state.append(None)
                continue
            np_, ns_ = {}, {}
            for tag, w in p.items():
                upd = self.updaters[li].get(tag)
                if upd is None:   # non-trainable state tag: passthrough
                    np_[tag], ns_[tag] = w, {}
                    continue
                np_[tag], ns_[tag] = upd.update(
                    opt_state[li][tag], w, grads[li][tag], epoch)
            new_params.append(np_)
            new_state.append(ns_)
        return new_params, new_state
