"""Evaluation metrics (reference: src/utils/metric.h:20-236).

Two execution paths with identical math and the identical
``\\tname-metric:value`` stderr format:

* host path (``add_eval``) — numpy on arrays copied off-device, like the
  reference's CPU metric path; used by the wrapper API.
* device path (``device_eval`` / ``MetricSet.device_stats``) — the same
  statistics computed inside the jitted step and accumulated into a tiny
  (n_metrics, 2) running (sum, count) buffer carried on device; the host
  fetches it ONCE per round instead of copying every batch's scores
  off-device (a per-step D2H round trip the reference pays by design,
  nnet_impl-inl.hpp:174-180).

``StreamingQuantile`` (bounded-window p50/p90/p99) lives here too: the
serving telemetry (serve/stats.py) shares this module's statistics
conventions rather than growing its own. ``StallClock`` (per-stage
wait/busy wall-time ledger) is the feed-pipeline counterpart: the
overlapped input pipeline (io/prefetch.py) and the train loop both
account stall time through it.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np


class Metric:
    name = "?"

    def __init__(self) -> None:
        self.clear()

    def clear(self) -> None:
        self.sum_metric = 0.0
        self.cnt_inst = 0

    def add_eval(self, pred: np.ndarray, label: np.ndarray) -> None:
        """pred: (n, k) scores; label: (n, w) label field."""
        for i in range(pred.shape[0]):
            self.sum_metric += self._calc(pred[i], label[i])
            self.cnt_inst += 1

    def get(self) -> float:
        return self.sum_metric / self.cnt_inst if self.cnt_inst else float("nan")

    def _calc(self, pred: np.ndarray, label: np.ndarray) -> float:
        raise NotImplementedError

    def device_eval(self, pred, label, mask):
        """jnp (sum, cnt) over the masked rows — same math as add_eval.
        pred (n, k), label (n, w), mask (n,) f32 row-validity weights."""
        raise NotImplementedError


class MetricRMSE(Metric):
    """Summed squared error per instance (reference: metric.h:73-89 —
    despite the name it accumulates squared error without the root)."""
    name = "rmse"

    def add_eval(self, pred, label):
        if pred.shape[1] != label.shape[1]:
            raise ValueError("RMSE: size of prediction and label must match")
        self.sum_metric += float(((pred - label) ** 2).sum())
        self.cnt_inst += pred.shape[0]

    def device_eval(self, pred, label, mask):
        import jax.numpy as jnp
        res = jnp.square(pred - label).sum(axis=1)
        # where, not multiply: garbage in masked-out padding rows (NaN/Inf)
        # must not poison the sum (the host path slices them off)
        s = jnp.sum(jnp.where(mask > 0, res, 0.0))
        return s, jnp.sum(mask)


class MetricError(Metric):
    """argmax != label (reference: metric.h:92-110); for 1-col predictions,
    thresholds at 0."""
    name = "error"

    def add_eval(self, pred, label):
        if pred.shape[1] != 1:
            maxidx = pred.argmax(axis=1)
        else:
            maxidx = (pred[:, 0] > 0.0).astype(np.int64)
        self.sum_metric += float((maxidx != label[:, 0].astype(np.int64)).sum())
        self.cnt_inst += pred.shape[0]

    def device_eval(self, pred, label, mask):
        import jax.numpy as jnp
        if pred.shape[1] != 1:
            maxidx = jnp.argmax(pred, axis=1)
        else:
            maxidx = (pred[:, 0] > 0.0).astype(jnp.int32)
        wrong = (maxidx != label[:, 0].astype(jnp.int32)).astype(jnp.float32)
        return jnp.sum(jnp.where(mask > 0, wrong, 0.0)), jnp.sum(mask)


class MetricLogloss(Metric):
    """-log p[target], clipped to [1e-15, 1-1e-15] (reference: metric.h:113-132)."""
    name = "logloss"

    def add_eval(self, pred, label):
        n = pred.shape[0]
        if pred.shape[1] != 1:
            tgt = label[:, 0].astype(np.int64)
            py = pred[np.arange(n), tgt]
            py = np.clip(py, 1e-15, 1.0 - 1e-15)
            self.sum_metric += float(-np.log(py).sum())
        else:
            py = np.clip(pred[:, 0], 1e-15, 1.0 - 1e-15)
            y = label[:, 0]
            res = -(y * np.log(py) + (1.0 - y) * np.log(1.0 - py))
            if np.isnan(res).any():
                raise ValueError("NaN detected!")
            self.sum_metric += float(res.sum())
        self.cnt_inst += n

    def device_eval(self, pred, label, mask):
        import jax.numpy as jnp
        if pred.shape[1] != 1:
            tgt = label[:, 0].astype(jnp.int32)
            py = jnp.take_along_axis(pred, tgt[:, None], axis=1)[:, 0]
            py = jnp.clip(py, 1e-15, 1.0 - 1e-15)
            res = -jnp.log(py)
        else:
            py = jnp.clip(pred[:, 0], 1e-15, 1.0 - 1e-15)
            y = label[:, 0]
            # note: the host path raises on NaN here (a data-bug guard);
            # a jitted program cannot raise, so a NaN label surfaces as a
            # nan metric at round end instead of an immediate error
            res = -(y * jnp.log(py) + (1.0 - y) * jnp.log(1.0 - py))
        return jnp.sum(jnp.where(mask > 0, res, 0.0)), jnp.sum(mask)


class MetricTokenError(Metric):
    """Mean per-position argmax error for sequence predictions: pred is
    the flattened (n, s*V) per-position distribution, label the (n, s)
    target ids. No reference analogue (cxxnet has no sequence models);
    the language-model companion to `error`."""
    name = "token_error"

    def add_eval(self, pred, label):
        n, k = pred.shape
        s = label.shape[1]
        if k % s != 0:
            raise ValueError(
                "token_error: pred width %d not a multiple of label "
                "width %d" % (k, s))
        idx = pred.reshape(n, s, k // s).argmax(axis=2)
        wrong = (idx != label.astype(np.int64)).mean(axis=1)
        self.sum_metric += float(wrong.sum())
        self.cnt_inst += n

    def device_eval(self, pred, label, mask):
        import jax.numpy as jnp
        n, k = pred.shape
        s = label.shape[1]
        if k % s != 0:
            raise ValueError(
                "token_error: pred width %d not a multiple of label "
                "width %d" % (k, s))
        idx = jnp.argmax(pred.reshape(n, s, k // s), axis=2)
        wrong = (idx != label.astype(jnp.int32)).astype(
            jnp.float32).mean(axis=1)
        return jnp.sum(jnp.where(mask > 0, wrong, 0.0)), jnp.sum(mask)


class MetricRecall(Metric):
    """rec@n (reference: metric.h:135-172)."""

    def __init__(self, name: str) -> None:
        m = re.match(r"rec@(\d+)", name)
        if not m:
            raise ValueError("must specify n for rec@n")
        self.topn = int(m.group(1))
        self.name = name
        super().__init__()

    def _calc(self, pred, label):
        if pred.shape[0] < self.topn:
            raise ValueError(
                "rec@%d meaningless for list of %d" % (self.topn, pred.shape[0]))
        top = np.argsort(-pred, kind="stable")[: self.topn]
        hit = sum(1 for lab in label if lab in top)
        return float(hit) / label.shape[0]

    def device_eval(self, pred, label, mask):
        import jax
        import jax.numpy as jnp
        if pred.shape[1] < self.topn:
            raise ValueError(
                "rec@%d meaningless for list of %d"
                % (self.topn, pred.shape[1]))
        _, top = jax.lax.top_k(pred, self.topn)        # (n, topn)
        hit = (top[:, None, :] == label[:, :, None].astype(jnp.int32)
               ).any(axis=2).sum(axis=1).astype(jnp.float32)
        rec = hit / label.shape[1]
        return jnp.sum(jnp.where(mask > 0, rec, 0.0)), jnp.sum(mask)


class StreamingQuantile:
    """Bounded-window streaming quantile estimator (p50/p90/p99 ...).

    Keeps the most recent ``window`` observations in a ring buffer and
    answers any quantile exactly over that window via ``np.percentile``
    — O(window) memory, O(1) add, no approximation sketch. Recency is
    the point for serving telemetry (serve/stats.py): the /metrics
    latency percentiles describe current behaviour, not a whole-uptime
    average that a warmup spike would poison forever. Not thread-safe;
    callers that share one instance across threads hold their own lock
    (ServeStats does)."""

    def __init__(self, window: int = 1024) -> None:
        if window < 1:
            raise ValueError("window must be >= 1, got %d" % window)
        self.window = window
        self._buf = np.empty(window, np.float64)
        self._n = 0          # observations ever seen

    def add(self, x: float) -> None:
        self._buf[self._n % self.window] = float(x)
        self._n += 1

    def __len__(self) -> int:
        return min(self._n, self.window)

    @property
    def count(self) -> int:
        """Total observations ever added (window overflow included)."""
        return self._n

    def quantile(self, q: float) -> float:
        """Exact q-quantile (0 <= q <= 1) of the retained window; nan
        when no observation has been added yet."""
        k = len(self)
        if k == 0:
            return float("nan")
        return float(np.percentile(self._buf[:k], 100.0 * q))

    def quantiles(self, qs: List[float]) -> List[float]:
        k = len(self)
        if k == 0:
            return [float("nan")] * len(qs)
        vals = np.percentile(self._buf[:k], [100.0 * q for q in qs])
        return [float(v) for v in vals]

    def clear(self) -> None:
        self._n = 0

    def bind_registry(self, name: str, registry=None,
                      quantiles=(0.5, 0.9, 0.99), **labels):
        """Publish this window's quantiles into an obs registry (a
        gauge with a ``q`` label, pulled at scrape time — the add()
        hot path is untouched). Returns the hook for
        ``Registry.remove_hook``. See obs/registry.py."""
        from .obs.registry import watch_quantile
        return watch_quantile(self, name, registry=registry,
                              quantiles=quantiles, labels=labels)


class StallClock:
    """Wall-time ledger for one pipeline stage: how long it spent
    *waiting* (blocked on a neighbour stage) versus *busy* (doing its
    own work). The feed pipeline (io/prefetch.py) keeps one per
    boundary — producer-waits-on-decoder, producer-waits-on-queue-slot
    (backpressure: the device is the bottleneck), consumer-waits-on-
    queue (feed stall: the device starves) — so `wait_frac` answers
    directly which stage bounds the pipeline. Shares this module's
    statistics conventions the way StreamingQuantile does for serving.

    Each clock is written by exactly one thread (its stage); readers on
    other threads see a consistent-enough snapshot for telemetry (a
    torn read loses at most one sample, never corrupts a total)."""

    __slots__ = ("wait_s", "busy_s", "waits", "events")

    def __init__(self) -> None:
        self.clear()

    def clear(self) -> None:
        self.wait_s = 0.0
        self.busy_s = 0.0
        self.waits = 0       # number of waits recorded
        self.events = 0      # number of busy spans recorded

    def add_wait(self, dt: float) -> None:
        self.wait_s += float(dt)
        self.waits += 1

    def add_busy(self, dt: float) -> None:
        self.busy_s += float(dt)
        self.events += 1

    @property
    def total_s(self) -> float:
        return self.wait_s + self.busy_s

    @property
    def wait_frac(self) -> float:
        """Fraction of this stage's accounted wall time spent blocked;
        0.0 when nothing has been recorded yet."""
        t = self.total_s
        return self.wait_s / t if t > 0 else 0.0

    def snapshot(self) -> Dict[str, float]:
        return {"wait_s": self.wait_s, "busy_s": self.busy_s,
                "waits": self.waits, "events": self.events,
                "wait_frac": self.wait_frac}

    def bind_registry(self, name: str, registry=None, **labels):
        """Publish this clock into an obs registry as
        ``<name>_{wait_seconds,busy_seconds,waits,events,wait_frac}``
        gauges, pulled at scrape time — the add_wait/add_busy hot path
        is untouched. Returns the hook for ``Registry.remove_hook``.
        See obs/registry.py."""
        from .obs.registry import watch_stallclock
        return watch_stallclock(self, name, registry=registry,
                                labels=labels)


def create_metric(name: str) -> Optional[Metric]:
    if name == "rmse":
        return MetricRMSE()
    if name == "error":
        return MetricError()
    if name == "token_error":
        return MetricTokenError()
    if name == "logloss":
        return MetricLogloss()
    if name.startswith("rec@"):
        return MetricRecall(name)
    return None


class MetricSet:
    """Set of metrics with per-metric label fields
    (reference: metric.h:175-236)."""

    def __init__(self) -> None:
        self.evals: List[Metric] = []
        self.label_fields: List[str] = []

    def add_metric(self, name: str, field: str = "label") -> None:
        m = create_metric(name)
        if m is None:
            raise ValueError("Metric: unknown metric name: %s" % name)
        self.evals.append(m)
        self.label_fields.append(field)

    def clear(self) -> None:
        for m in self.evals:
            m.clear()

    def add_eval(self, predscores: List[np.ndarray],
                 labels: Dict[str, np.ndarray]) -> None:
        if len(predscores) != len(self.evals):
            raise ValueError("Metric: #scores must equal #metrics")
        for m, field, pred in zip(self.evals, self.label_fields, predscores):
            if field not in labels:
                raise ValueError("Metric: unknown target = %s" % field)
            m.add_eval(pred, labels[field])

    def device_stats(self, predscores, labels: Dict[str, "np.ndarray"],
                     mask):
        """Inside a jit trace: (n_metrics, 2) array of (sum, cnt) for one
        batch — the device half of the once-per-round metric path."""
        import jax.numpy as jnp
        if len(predscores) != len(self.evals):
            raise ValueError("Metric: #scores must equal #metrics")
        rows = []
        for m, field, pred in zip(self.evals, self.label_fields, predscores):
            if field not in labels:
                raise ValueError("Metric: unknown target = %s" % field)
            s, c = m.device_eval(pred, labels[field], mask)
            rows.append(jnp.stack([s.astype(jnp.float32),
                                   c.astype(jnp.float32)]))
        return jnp.stack(rows)

    def accum_zero(self) -> "np.ndarray":
        """Fresh device accumulator: (n_metrics, 2, 2) of Kahan
        (value, compensation) pairs for (sum, cnt)."""
        return np.zeros((len(self.evals), 2, 2), np.float32)

    @staticmethod
    def device_fold(accum, stats):
        """Kahan-compensated accumulate of one batch's (n_metrics, 2)
        stats into the (n_metrics, 2, 2) running buffer — f32 on device
        would otherwise drift over a long round (the host path sums in
        f64)."""
        import jax.numpy as jnp
        total, comp = accum[..., 0], accum[..., 1]
        y = stats - comp
        t = total + y
        comp = (t - total) - y
        return jnp.stack([t, comp], axis=-1)

    def add_stats(self, accum: "np.ndarray") -> None:
        """Fold a fetched (n_metrics, 2, 2) Kahan buffer into the running
        host totals."""
        accum = np.asarray(accum, np.float64)
        vals = accum[..., 0] - accum[..., 1]  # value minus pending comp
        for i, m in enumerate(self.evals):
            m.sum_metric += float(vals[i, 0])
            m.cnt_inst += int(round(float(vals[i, 1])))

    def print(self, evname: str) -> str:
        out = []
        for m, field in zip(self.evals, self.label_fields):
            tag = "%s-%s" % (evname, m.name)
            if field != "label":
                tag += "[%s]" % field
            out.append("\t%s:%g" % (tag, m.get()))
        return "".join(out)
