"""Runtime JAX-hygiene validation (docs/analysis.md): the recompile
sentinel and the donation validator — the runtime half of the static
JIT rule family (analysis/lint.py), in the lockcheck mold.

**Recompile sentinel.** Steady-state serving must never compile: a
compile on the hot path is a multi-second stall (and on this rig's
history, a poisoned-cache incident waiting to happen). The sentinel
hooks JAX's compile-event seam — ``jax_log_compiles`` raises a
``Compiling <program> with global shapes ...`` record on the
``jax._src.interpreters.pxla`` logger for every real compilation, and
a logging filter parses the program name out and suppresses the
chatter — and counts compiles per program. Lifecycle:

* :func:`enable` installs the seam (counting starts; nothing fails).
* warmup paths (``ServingEngine.warmup``, continuous-engine warmup,
  replica builds) run inside :func:`allow` — compiles there are
  recorded as warmup no matter the arm state. The allowance is
  thread-local: a replica warming on its build thread never excuses a
  compile on a dispatch thread.
* :meth:`JitMonitor.arm` declares steady state: from here, any
  compile outside an ``allow`` region is a **violation** (and the
  chaos/scenario smokes fail hard on it).

``obs/registry.py::watch_jitcheck`` exports the counts as
``cxxnet_jit_compiles_total`` / ``cxxnet_recompiles_total``.

**Donation validator.** A donated buffer (``donate_argnums``) is dead
the moment the call returns; touching it later raises jax's deferred
``Array has been deleted`` — far from the donation that killed it.
Donating call sites wrap their callable in :func:`make_donating`
(creation-time seam, exactly like ``lockcheck.make_lock``): with no
monitor enabled the callable is returned UNTOUCHED (zero overhead);
enabled, the wrapper (a) checks every incoming argument against the
record of previously-donated buffers and raises :class:`DonationError`
naming the original call site and argnum the moment a dead buffer is
passed back in, and (b) records this call's donated arguments.
Records hold strong references to the (already freed, shell-only)
array objects so ``id()`` reuse cannot mis-attribute, bounded by
``MAX_DONATION_RECORDS`` FIFO eviction.

Like lockcheck: objects/callables created *before* ``enable()`` stay
uninstrumented — enable the monitor before building engines/trainers.
(Two refinements over the lock seam: wrappers resolve the ACTIVE
monitor per call, so a wrapper cached across ``disable``/``enable``
cycles tracks the live monitor instead of a defunct one; and call
sites cached for the life of the process pass ``always=True`` to get
a wrapper even while disabled, so a later ``enable()`` still
validates them.)
This module must stay import-light (no jax import at module level);
jax is touched only inside ``enable``/``disable``.
"""

from __future__ import annotations

import logging
import re
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

from .lockcheck import Violation

MAX_VIOLATIONS = 200
MAX_DONATION_RECORDS = 4096

# the loggers jax_log_compiles raises compile records on: pxla emits
# "Compiling jit(<name>) with global shapes and types ..." (the module
# name; the api wrapper is stripped so counts key on the function's
# own name), and dispatch emits the tracing/lowering chatter we
# suppress
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")
_COMPILING_RE = re.compile(r"^Compiling (?:\w+\()?([^\s()]+)")
_CHATTER_PREFIXES = ("Finished tracing + transforming",
                     "Finished jaxpr to MLIR",
                     "Finished XLA compilation")


def _iter_leaves(obj, depth: int = 0):
    """Leaf (array-like) objects inside an argument, seeing through
    the containers the trainer donates (params is a list of per-module
    dicts, likewise opt state) — without this the validator only ever
    inspects the container objects, which are never 'deleted', and
    every pytree-shaped donating site is silently inert. Depth-bounded
    manual recursion keeps the module import-light (no jax.tree_util
    at module level)."""
    if depth > 4:
        return
    if isinstance(obj, dict):
        for v in obj.values():
            yield from _iter_leaves(v, depth + 1)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            yield from _iter_leaves(v, depth + 1)
    elif obj is not None:
        yield obj


def forward_introspection(wrapper, fn):
    """Keep the jitted introspection surface reachable through a
    validation wrapper: ``Trainer.step_cost_analysis`` and
    ``tools/multichip_report`` call ``.lower(...)`` on the wrapped
    step, and these entry points never execute the program, so
    routing them straight to ``fn`` skips no validation. ONE list,
    shared by every seam wrapper (``make_donating``,
    ``shardcheck.make_sharded``, serving's staging wrapper) so a new
    introspection attribute cannot drift between them."""
    for attr in ("lower", "eval_shape", "trace"):
        bound = getattr(fn, attr, None)
        if bound is not None:
            setattr(wrapper, attr, bound)
    return wrapper


class JitCheckError(RuntimeError):
    """Base for violations that cannot safely proceed."""


class DonationError(JitCheckError):
    """A previously-donated (deleted) buffer was passed into a call —
    the immediate, attributed form of jax's deferred
    'Array has been deleted'."""


class _CompileLogFilter(logging.Filter):
    """Parses compile events off the jax loggers and suppresses the
    jax_log_compiles chatter so enabling the sentinel does not spam
    stderr. Returns True (pass through) for anything it does not
    recognize."""

    def __init__(self, mon: "JitMonitor") -> None:
        super().__init__()
        self._mon = mon

    def filter(self, record: logging.LogRecord) -> bool:
        try:
            msg = record.getMessage()
        except Exception:
            return True
        m = _COMPILING_RE.match(msg)
        if m is not None:
            self._mon._on_compile(m.group(1))
            return False
        if msg.startswith(_CHATTER_PREFIXES):
            return False
        return True


class JitMonitor:
    """Both sentinels behind one monitor: per-program compile counts
    with an armed steady-state contract, and the donated-buffer
    record."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compiles: Dict[str, int] = {}     # program -> total
        self.steady: Dict[str, int] = {}       # compiles while armed
        self._violations: List[Violation] = []
        self.armed = False
        self._tls = threading.local()
        self._filter: Optional[_CompileLogFilter] = None
        self._prev_log_compiles: Optional[bool] = None
        # id(arr) -> (arr, site, argnum, t) — strong refs, see module
        # docstring
        self._donations: Dict[int, tuple] = {}
        self._donation_order: deque = deque()
        self.donating_calls = 0

    # -- compile seam --------------------------------------------------
    def _install(self) -> None:
        import jax
        self._prev_log_compiles = bool(jax.config.jax_log_compiles)
        jax.config.update("jax_log_compiles", True)
        self._filter = _CompileLogFilter(self)
        for name in _COMPILE_LOGGERS:
            logging.getLogger(name).addFilter(self._filter)

    def _uninstall(self) -> None:
        if self._filter is not None:
            for name in _COMPILE_LOGGERS:
                logging.getLogger(name).removeFilter(self._filter)
            self._filter = None
        if self._prev_log_compiles is not None:
            import jax
            jax.config.update("jax_log_compiles",
                              self._prev_log_compiles)
            self._prev_log_compiles = None

    def arm(self) -> None:
        """Declare steady state: from now on a compile outside an
        ``allow`` region is a violation."""
        self.armed = True

    def disarm(self) -> None:
        self.armed = False

    @contextmanager
    def allow(self, reason: str = "warmup"):
        """Thread-local allowance: compiles on THIS thread inside the
        region are sanctioned warmup even while armed."""
        depth = getattr(self._tls, "allow", 0)
        self._tls.allow = depth + 1
        try:
            yield
        finally:
            self._tls.allow = depth

    def _on_compile(self, program: str) -> None:
        with self._lock:
            self.compiles[program] = self.compiles.get(program, 0) + 1
            if self.armed and not getattr(self._tls, "allow", 0):
                self.steady[program] = self.steady.get(program, 0) + 1
                if len(self._violations) < MAX_VIOLATIONS:
                    self._violations.append(Violation(
                        "steady-state-compile",
                        "program %r compiled while the recompile "
                        "sentinel was armed (compile #%d of it) — "
                        "steady-state serving must not compile"
                        % (program, self.compiles[program])))

    @property
    def total_compiles(self) -> int:
        with self._lock:
            return sum(self.compiles.values())

    @property
    def steady_compiles(self) -> int:
        with self._lock:
            return sum(self.steady.values())

    def summary(self, **extra) -> Dict:
        """The ``recompile_sentinel`` dict the chaos smoke records
        (``extra`` carries per-consumer fields)."""
        with self._lock:
            total = sum(self.compiles.values())
            steady = sum(self.steady.values())
        out = {"warmup_compiles": total - steady,
               "steady_state_compiles": steady}
        out.update(extra)
        return out

    # -- donation seam -------------------------------------------------
    @staticmethod
    def _deleted(arr) -> bool:
        fn = getattr(arr, "is_deleted", None)
        try:
            return bool(fn()) if callable(fn) else False
        except Exception:
            return False

    def _record_donation_locked(self, site: str, argnum: int,
                                arr) -> None:
        if arr is None:
            return
        key = id(arr)
        if key not in self._donations:
            self._donation_order.append(key)
            while len(self._donation_order) > MAX_DONATION_RECORDS:
                self._donations.pop(self._donation_order.popleft(),
                                    None)
        self._donations[key] = (arr, site, argnum, time.time())

    def record_call(self, site: str, argnums: Sequence[int],
                    args: Sequence) -> None:
        """Account one completed donating call: bump the (otherwise
        racy) call counter and record its donated LEAVES under one
        lock hold. Only leaves jax actually deleted are recorded — an
        unusable donation (shape-mismatch advisory, jax keeps the
        buffer alive) can never raise in ``check_args`` anyway, and
        recording it would pin a full-size LIVE array for the whole
        enabled window while evicting records that can."""
        with self._lock:
            self.donating_calls += 1
            for i in argnums:
                if i < len(args):
                    for leaf in _iter_leaves(args[i]):
                        if self._deleted(leaf):
                            self._record_donation_locked(site, i, leaf)

    def check_args(self, site: str, args: Sequence,
                   kwargs: Optional[dict] = None) -> None:
        """Raise :class:`DonationError` (and record the violation) the
        moment a previously-donated, now-deleted buffer shows up as an
        argument (or inside a pytree argument) — naming where and at
        which argnum it was donated. Keyword arguments are scanned
        too: donation itself is positional (``donate_argnums``), but a
        dead buffer re-entering BY KEYWORD deserves the same immediate
        attributed diagnostic, not jax's deferred one."""
        labeled = [(str(pos), a) for pos, a in enumerate(args)]
        if kwargs:
            labeled.extend(("%s=" % k, v) for k, v in kwargs.items())
        for pos, a in labeled:
            for leaf in _iter_leaves(a):
                rec = self._donations.get(id(leaf))
                if rec is None or rec[0] is not leaf:
                    continue
                if self._deleted(leaf):
                    _, dsite, dnum, t0 = rec
                    msg = ("arg %s of %s holds a buffer donated to %s "
                           "(argnum %d) %.3fs ago — use-after-donate"
                           % (pos, site, dsite, dnum,
                              time.time() - t0))
                    with self._lock:
                        if len(self._violations) < MAX_VIOLATIONS:
                            self._violations.append(
                                Violation("use-after-donate", msg))
                    raise DonationError(msg)

    # -- inspection ----------------------------------------------------
    def violations(self) -> List[Violation]:
        with self._lock:
            return list(self._violations)

    def assert_clean(self) -> None:
        v = self.violations()
        if v:
            raise AssertionError(
                "jitcheck recorded %d violation(s):\n  %s"
                % (len(v), "\n  ".join(map(repr, v))))

    def reset(self) -> None:
        with self._lock:
            self.compiles.clear()
            self.steady.clear()
            self._violations.clear()
            self._donations.clear()
            self._donation_order.clear()


# ----------------------------------------------------------------------
# module seam

_active: Optional[JitMonitor] = None


def enable() -> JitMonitor:
    """Install a fresh process-global monitor: the compile seam goes
    live immediately (counting, not failing — call ``arm()`` after
    warmup); callables wrapped through :func:`make_donating` AFTER
    this call are validated."""
    global _active
    if _active is not None:
        _active._uninstall()
    m = JitMonitor()
    m._install()
    _active = m
    return m


def disable() -> Optional[JitMonitor]:
    """Uninstall and return the monitor (its counts/violations stay
    readable); ``jax_log_compiles`` is restored to its prior value and
    subsequent ``make_donating`` calls return the callable untouched."""
    global _active
    m = _active
    if m is not None:
        m._uninstall()
    _active = None
    return m


def active() -> Optional[JitMonitor]:
    return _active


def arm() -> None:
    m = _active
    if m is not None:
        m.arm()


@contextmanager
def allow(reason: str = "warmup"):
    """Sanctioned-warmup region on the calling thread; a no-op with no
    monitor enabled."""
    m = _active
    if m is None:
        yield
    else:
        with m.allow(reason):
            yield


def make_donating(fn, argnums: Sequence[int], site: Optional[str] = None,
                  always: bool = False):
    """Creation-time donation seam (the ``lockcheck.make_*`` pattern):
    with no monitor enabled, returns ``fn`` UNTOUCHED — production
    pays nothing, not even a wrapper frame. Enabled, returns a wrapper
    that validates incoming args against the donated-buffer record
    (immediate :class:`DonationError` instead of jax's deferred one)
    and records this call's donated arguments afterwards.

    The wrapper resolves the ACTIVE monitor per call, not the one
    alive at creation: a wrapper cached across :func:`disable` goes
    quiet (pass-through, no stale records pinned, no errors from a
    defunct monitor), and across a re-:func:`enable` it validates
    against the new monitor. ``always=True`` wraps even while no
    monitor is enabled — for call sites cached for the life of the
    process (``serving._SCATTER_CACHE``, ``ExportedStepDecoder``)
    that may be built before ``enable()``; the disabled cost is one
    global read per call."""
    if _active is None and not always:
        return fn
    nums: Tuple[int, ...] = tuple(int(i) for i in argnums)
    name = site or getattr(fn, "__name__", "donating-call")

    def wrapper(*args, **kwargs):
        mon = _active
        if mon is None:
            return fn(*args, **kwargs)
        mon.check_args(name, args, kwargs)
        out = fn(*args, **kwargs)
        mon.record_call(name, nums, args)
        return out

    wrapper.__name__ = "donating[%s]" % name
    wrapper.__wrapped__ = fn
    return forward_introspection(wrapper, fn)
