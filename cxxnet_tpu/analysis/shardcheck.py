"""Runtime SPMD sharding validation (docs/analysis.md): the implicit
transfer/resharding sentinel — the runtime half of the static SHARD
rule family (analysis/lint.py), in the jitcheck mold.

Two contracts, one monitor:

**Transfer sentinel.** Steady-state serving and the armed train legs
must never pay an IMPLICIT host transfer: a host array (or Python
scalar) flowing straight into a jitted/exported program is a silent
per-call upload, and on a sharded program XLA "fixes" it with a hidden
broadcast instead of an error. The sentinel rides JAX's own
``transfer_guard`` seam: :meth:`ShardMonitor.arm` flips the global
``jax_transfer_guard_host_to_device`` config to ``disallow`` (saved at
first arm, restored on :func:`disable`/:meth:`~ShardMonitor.disarm`),
so an implicit transfer raises at the exact call that would pay it.
Warmup paths run inside :func:`allow` — which layers jax's
THREAD-LOCAL ``jax.transfer_guard("allow")`` context under the
monitor's own thread-local allowance, so a replica warming on its
build thread never excuses a transfer on a dispatch thread. Explicit
placement (``jax.device_put``, ``jnp.asarray``) stays legal while
armed — the contract is "say where it goes", not "never move data".

**Reshard sentinel.** A compiled mesh program declares its input
placements (``in_shardings``); a caller passing an array whose actual
sharding differs gets a silent implicit reshard at dispatch — a hidden
all-gather/scatter per call, the exact bug class the ROADMAP's
sharded-serving item is blocked on. Mesh-program call sites wrap their
callable in :func:`make_sharded` (creation-time seam, exactly like
``jitcheck.make_donating``): with no monitor enabled the callable is
returned UNTOUCHED (zero overhead); enabled, the wrapper checks every
incoming argument's observed ``.sharding`` against the declared spec
(pytree-paired, depth-bounded, exactly the containers the trainer
passes) and — armed, outside an ``allow`` window — raises an
attributed :class:`ReshardError` naming the program, argnum/path, and
expected vs observed placement the moment a mismatch would force an
implicit reshard. Before arming, mismatches are counted as warmup
reshards (counting, not failing — the jitcheck lifecycle).

``obs/registry.py::watch_shardcheck`` exports the counts as
``cxxnet_implicit_transfers_total`` / ``cxxnet_reshards_total`` /
``cxxnet_shard_programs``; ``tools/multichip_report.py`` and
``tools/analysis_gate.py --sharded`` arm the sentinel and fail on a
nonzero steady state.

Like lockcheck/jitcheck: callables wrapped *before* ``enable()`` stay
uninstrumented unless they passed ``always=True``; wrappers resolve
the ACTIVE monitor per call, so a wrapper cached across
``disable``/``enable`` cycles tracks the live monitor. This module
must stay import-light (no jax import at module level); jax is
touched only inside ``arm``/``allow``/the enabled wrapper path.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple

from .lockcheck import Violation

MAX_VIOLATIONS = 200
_GUARD_FLAG = "jax_transfer_guard_host_to_device"
# "no saved config" marker distinct from a saved None: the flag's
# default IS None (inherit the jax_transfer_guard umbrella), and
# restoring an explicit "allow" over it would silently switch off a
# user's own umbrella logging/guarding
_GUARD_UNSAVED = object()
# the substrings jax's transfer guard uses in its errors — the wrapper
# recognizes a guard trip by message, not type (XlaRuntimeError lives
# in a private module)
_GUARD_ERROR_MARKER = "Disallowed "


class ShardCheckError(RuntimeError):
    """Base for sharding violations that cannot safely proceed."""


class ReshardError(ShardCheckError):
    """An argument's observed sharding mismatches the program's
    declared input placement — the call would pay a silent implicit
    reshard (hidden all-gather/scatter) at dispatch."""


class TransferError(ShardCheckError):
    """jax's transfer guard tripped inside a monitored program call —
    an implicit host transfer in armed steady state, re-raised with
    the program site attached."""


def _describe(sharding) -> str:
    """Compact human label for a sharding: NamedSharding(mesh, spec)
    with the mesh's axis dict, anything else by class name."""
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    if mesh is not None and spec is not None:
        try:
            return "NamedSharding(mesh=%s, spec=%s)" % (
                dict(mesh.shape), tuple(spec))
        except Exception:
            pass
    if sharding is None:
        return "host value (no sharding)"
    return type(sharding).__name__


def _pair_leaves(spec, arg, path="", depth=0):
    """Yield ``(spec leaf, arg leaf, path)`` pairs, walking the two
    trees together: matching containers recurse pairwise (dict keys,
    list/tuple positions); a spec LEAF over an arg container broadcasts
    to every arg leaf (jax's single-sharding-for-a-pytree-arg rule);
    a structure mismatch or a ``None`` spec is conservatively skipped.
    Depth-bounded manual recursion keeps the module import-light (no
    jax.tree_util at module level) — same discipline as jitcheck's
    ``_iter_leaves``."""
    if spec is None or depth > 6:
        return
    spec_is_container = isinstance(spec, (dict, list, tuple))
    if isinstance(arg, dict):
        if spec_is_container:
            if not isinstance(spec, dict):
                return
            for k, v in arg.items():
                yield from _pair_leaves(spec.get(k), v,
                                        "%s[%r]" % (path, k), depth + 1)
        else:
            for k, v in arg.items():
                yield from _pair_leaves(spec, v, "%s[%r]" % (path, k),
                                        depth + 1)
    elif isinstance(arg, (list, tuple)):
        if spec_is_container:
            if not isinstance(spec, (list, tuple)):
                return
            for j, (s, v) in enumerate(zip(spec, arg)):
                yield from _pair_leaves(s, v, "%s[%d]" % (path, j),
                                        depth + 1)
        else:
            for j, v in enumerate(arg):
                yield from _pair_leaves(spec, v, "%s[%d]" % (path, j),
                                        depth + 1)
    else:
        if spec_is_container or arg is None:
            return
        yield spec, arg, path


class ShardMonitor:
    """Both sharding sentinels behind one monitor: the transfer guard
    with an armed steady-state contract, and the per-program reshard
    record of the :func:`make_sharded` seam."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.programs: Dict[str, int] = {}        # site -> calls seen
        self.warmup_reshards: Dict[str, int] = {}
        self.steady_reshards: Dict[str, int] = {}
        self.steady_transfers: Dict[str, int] = {}
        self._violations = []
        self.armed = False
        self._tls = threading.local()
        self._prev_guard = _GUARD_UNSAVED

    # -- transfer-guard seam ------------------------------------------
    def arm(self) -> None:
        """Declare steady state: the global host->device transfer
        guard flips to ``disallow`` (prior value saved once, verbatim
        — an unset flag restores to unset), and a reshard mismatch at
        a :func:`make_sharded` site becomes a raised violation
        instead of a warmup count."""
        import jax
        with self._lock:
            if self._prev_guard is _GUARD_UNSAVED:
                self._prev_guard = getattr(jax.config, _GUARD_FLAG,
                                           None)
            self.armed = True
        jax.config.update(_GUARD_FLAG, "disallow")

    def disarm(self) -> None:
        with self._lock:
            self.armed = False
        self._restore_guard()

    def _restore_guard(self) -> None:
        with self._lock:
            prev, self._prev_guard = self._prev_guard, _GUARD_UNSAVED
        if prev is not _GUARD_UNSAVED:
            import jax
            jax.config.update(_GUARD_FLAG, prev)

    def _uninstall(self) -> None:
        self._restore_guard()

    @contextmanager
    def allow(self, reason: str = "warmup"):
        """Thread-local allowance: transfers AND reshard mismatches on
        THIS thread inside the region are sanctioned warmup even while
        armed (rides jax's own thread-local transfer_guard context, so
        the global ``disallow`` stays in force for every other
        thread)."""
        depth = getattr(self._tls, "allow", 0)
        self._tls.allow = depth + 1
        try:
            import jax
            with jax.transfer_guard("allow"):
                yield
        finally:
            self._tls.allow = depth

    # -- reshard seam -------------------------------------------------
    def _mismatch(self, spec, leaf) -> Optional[str]:
        """A description when ``leaf``'s placement mismatches the
        declared ``spec`` (an implicit reshard/transfer at dispatch),
        else None. Host values only mismatch when the spec spans more
        than one device — on a 1-device mesh a host input is the
        normal serving path, not a sharding hazard."""
        if not hasattr(spec, "is_equivalent_to"):
            return None
        observed = getattr(leaf, "sharding", None)
        if observed is None:
            mesh = getattr(spec, "mesh", None)
            try:
                ndev = int(mesh.devices.size) if mesh is not None \
                    else len(spec.device_set)
            except Exception:
                return None
            if ndev > 1:
                return ("host-resident value where %s is declared "
                        "(implicit host transfer + replication)"
                        % _describe(spec))
            return None
        ndim = getattr(leaf, "ndim", None)
        if ndim is None:
            return None
        try:
            if spec.is_equivalent_to(observed, int(ndim)):
                return None
        except Exception:
            return None
        return "expects %s, got %s" % (_describe(spec),
                                       _describe(observed))

    def check_args(self, site: str,
                   in_shardings: Optional[Sequence],
                   args: Sequence) -> None:
        """Validate one call's positional arguments against the
        program's declared input placements. Armed and outside an
        ``allow`` window a mismatch raises :class:`ReshardError`
        naming the program, argnum/path and expected vs observed
        placement; otherwise it is counted as a warmup reshard."""
        if not in_shardings:
            return
        excused = bool(getattr(self._tls, "allow", 0))
        for i, spec in enumerate(in_shardings):
            if spec is None or i >= len(args):
                continue
            for s, leaf, path in _pair_leaves(spec, args[i]):
                desc = self._mismatch(s, leaf)
                if desc is None:
                    continue
                msg = ("argnum %d%s of %s %s — implicit reshard"
                       % (i, path, site, desc))
                with self._lock:
                    if self.armed and not excused:
                        self.steady_reshards[site] = \
                            self.steady_reshards.get(site, 0) + 1
                        if len(self._violations) < MAX_VIOLATIONS:
                            self._violations.append(
                                Violation("implicit-reshard", msg))
                        fail = True
                    else:
                        self.warmup_reshards[site] = \
                            self.warmup_reshards.get(site, 0) + 1
                        fail = False
                if fail:
                    raise ReshardError(msg)

    def record_call(self, site: str) -> None:
        with self._lock:
            self.programs[site] = self.programs.get(site, 0) + 1

    def record_transfer(self, site: str, exc) -> TransferError:
        """Account a transfer-guard trip inside a monitored call and
        build the attributed error for the wrapper to raise."""
        msg = ("implicit transfer during %s: %s — steady state must "
               "place data explicitly (jax.device_put with the "
               "program's sharding)" % (site, exc))
        with self._lock:
            self.steady_transfers[site] = \
                self.steady_transfers.get(site, 0) + 1
            if len(self._violations) < MAX_VIOLATIONS:
                self._violations.append(
                    Violation("implicit-transfer", msg))
        return TransferError(msg)

    # -- inspection ---------------------------------------------------
    @property
    def steady_transfers_total(self) -> int:
        with self._lock:
            return sum(self.steady_transfers.values())

    @property
    def steady_reshards_total(self) -> int:
        with self._lock:
            return sum(self.steady_reshards.values())

    @property
    def warmup_reshards_total(self) -> int:
        with self._lock:
            return sum(self.warmup_reshards.values())

    def violations(self):
        with self._lock:
            return list(self._violations)

    def assert_clean(self) -> None:
        v = self.violations()
        if v:
            raise AssertionError(
                "shardcheck recorded %d violation(s):\n  %s"
                % (len(v), "\n  ".join(map(repr, v))))

    def summary(self, **extra) -> Dict:
        """The ``shard_sentinel`` dict the multichip report
        records."""
        with self._lock:
            out = {
                "steady_state_transfers":
                    sum(self.steady_transfers.values()),
                "steady_state_reshards":
                    sum(self.steady_reshards.values()),
                "warmup_reshards": sum(self.warmup_reshards.values()),
                "sharded_programs": len(self.programs),
                "sharded_calls": sum(self.programs.values()),
            }
        out.update(extra)
        return out

    def reset(self) -> None:
        with self._lock:
            self.programs.clear()
            self.warmup_reshards.clear()
            self.steady_reshards.clear()
            self.steady_transfers.clear()
            self._violations.clear()


# ----------------------------------------------------------------------
# module seam

_active: Optional[ShardMonitor] = None


def enable() -> ShardMonitor:
    """Install a fresh process-global monitor: callables wrapped
    through :func:`make_sharded` AFTER this call (or with
    ``always=True`` any time) validate their inputs; the transfer
    guard stays untouched until :func:`arm`."""
    global _active
    if _active is not None:
        _active._uninstall()
    m = ShardMonitor()
    _active = m
    return m


def disable() -> Optional[ShardMonitor]:
    """Uninstall and return the monitor (its counts/violations stay
    readable); the transfer-guard config is restored to its pre-arm
    value and subsequent :func:`make_sharded` calls return the
    callable untouched."""
    global _active
    m = _active
    if m is not None:
        m._uninstall()
    _active = None
    return m


def active() -> Optional[ShardMonitor]:
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


def make_sharded(fn, in_shardings: Optional[Sequence] = None,
                 site: Optional[str] = None, always: bool = False):
    """Creation-time sharding seam (the ``make_donating`` pattern):
    with no monitor enabled, returns ``fn`` UNTOUCHED — production
    pays nothing, not even a wrapper frame. Enabled, returns a wrapper
    that (a) validates each incoming argument's observed sharding
    against ``in_shardings`` (the same pytree handed to ``jax.jit``;
    ``None`` skips the reshard check but keeps the program registered
    for transfer attribution), (b) re-raises a transfer-guard trip
    inside the call as an attributed :class:`TransferError`, and (c)
    counts the call under ``site`` (the ``cxxnet_shard_programs``
    surface).

    The wrapper resolves the ACTIVE monitor per call (see jitcheck);
    ``always=True`` wraps even while disabled, for call sites cached
    for the life of the process — the disabled cost is one global read
    per call."""
    if _active is None and not always:
        return fn
    name = site or getattr(fn, "__name__", "sharded-call")
    specs: Optional[Tuple] = (tuple(in_shardings)
                              if in_shardings is not None else None)

    def wrapper(*args, **kwargs):
        mon = _active
        if mon is None:
            return fn(*args, **kwargs)
        mon.check_args(name, specs, args)
        try:
            out = fn(*args, **kwargs)
        except ShardCheckError:
            raise
        except Exception as e:
            # attribute a guard trip only when THIS monitor armed the
            # guard (and outside an allow window): a user's own
            # JAX_TRANSFER_GUARD=disallow tripping pre-arm is not a
            # steady-state violation of ours — pass it through raw
            if mon.armed \
                    and not getattr(mon._tls, "allow", 0) \
                    and _GUARD_ERROR_MARKER in str(e) \
                    and "transfer" in str(e):
                raise mon.record_transfer(name, e) from e
            raise
        mon.record_call(name)
        return out

    wrapper.__name__ = "sharded[%s]" % name
    wrapper.__wrapped__ = fn
    from .jitcheck import forward_introspection
    return forward_introspection(wrapper, fn)
