"""AST lint framework: concurrency & hot-path correctness checkers.

Run by ``tools/analysis_gate.py`` over the whole tree (and by
``tests/test_analysis.py`` as a standing tier-1 gate). Three checker
families, each a :class:`Checker` the gate composes — adding a rule is
adding a class to :data:`ALL_CHECKERS`:

**CONC — lock discipline** (the static half of analysis/lockcheck.py)
  The checker models each class's locks from ``self.X = Lock()/
  RLock()/Condition()`` (or the ``lockcheck.make_*`` seam) and walks
  every method with a held-lock stack from ``with self.X:`` nesting,
  propagating one class's ``self.method()`` calls to a fixpoint:

  * CONC001 — a cycle in a module's lock-acquisition graph (A taken
    under B somewhere, B under A elsewhere): the classic AB/BA.
  * CONC002 — a blocking call while a lock is held: ``time.sleep``,
    thread ``.join()``, future/request ``.result()``, ``.wait()`` on
    anything but the held condition itself, blocking ``get/put`` on a
    queue attribute, engine ``submit``/``submit_tokens``, known
    blocking ops (``serve_forever``, ``urlopen``, ``drain``,
    ``drain_replica``, ``spawn``) — directly or via a same-class
    method call.
  * CONC003 — re-acquiring a held non-reentrant lock (self-deadlock).

**SYNC — host syncs out of hot paths**
  Functions marked ``@analysis.hot_path`` (or listed in the gate's
  ``extra_hot`` config) must not force a device→host sync:

  * SYNC001 — ``.block_until_ready()``
  * SYNC002 — ``np.asarray(...)`` / ``np.array(...)``
  * SYNC003 — ``.item()``
  * SYNC004 — ``float(...)``/``int(...)`` of a computed value (a call
    or subscript — ``float(x[0])`` syncs; ``float(timeout_ms)`` of a
    plain name does not and is not flagged).
  * SYNC005 — ``.tolist()`` / ``jax.device_get(...)`` (whole-array
    host transfers the SYNC001-004 set misses).
  * SYNC006 — ``.copy_to_host_async()`` immediately awaited: the next
    statement materializes the same value (``np.asarray``/``.item()``/
    ``float``/``block_until_ready``), so the async copy bought no
    overlap — checked everywhere, not just hot paths (the call is
    always deliberate, so a hit is always misuse).

**JIT — jax jit/donation hygiene** (the static half of
analysis/jitcheck.py)
  The checker models *donating* and *static-arged* jitted callables it
  can see: a local/module name or ``self.X`` attribute assigned from
  ``jax.jit(fn, donate_argnums=...)`` / ``pjit`` / the
  ``jitcheck.make_donating`` seam, a method that directly returns such
  a call with its own params at donated positions (argnums mapped
  through), and the gate's ``extra_donating`` config for cross-module
  APIs (leaf name + donated argnums + a minimum call arity so e.g.
  ``trace.step(n)`` never matches ``decoder.step(pool_k, ...)``):

  * JIT001 — use-after-donate: a name passed at a donated position of
    a known donating call and then READ later in the same function
    without being rebound first (jax's deferred "Array has been
    deleted" made immediate and attributable). Intra-function
    dataflow: branches fork/merge, loop bodies are walked twice so a
    donate-at-bottom/read-at-top back edge is caught; metadata reads
    (``.shape``/``.dtype``/...) of a donated array are legal and
    exempt.
  * JIT002 — ``jax.jit``/``pjit`` CONSTRUCTION inside a loop or a
    hot-path function: every call re-traces and re-compiles; build
    once outside, or cache-guard the construction.
  * JIT003 — recompile storm: a loop-varying name passed at a
    ``static_argnums`` position of a known jitted callable — each new
    value is a fresh trace + compile, per iteration.
  * JIT004 — a known donating call whose result is DISCARDED (a bare
    expression statement): the donated inputs are consumed but
    nothing rebinds the outputs — the caller is left holding dead
    buffers (the drop-aliasing-on-export bug class).

**SHARD — SPMD sharding hygiene** (the static half of
analysis/shardcheck.py)
  The checker models mesh-in-scope like the lock model: a class that
  assigns ``self.X = make_mesh(...)``/``Mesh(...)`` is mesh-aware, and
  so is the body of a ``with Mesh(...):`` block; the axis-name
  vocabulary is the ``parallel.py`` constants (``data``/``model``/
  ``seq``/``pipe``) plus any axis tuple a ``Mesh(...)`` construction
  in the same module declares:

  * SHARD001 — a jit/pjit built (stored or returned) under a mesh
    without explicit ``in_shardings``/``out_shardings``: XLA's
    propagation then picks the placement, and a propagation change
    silently reshards — mesh programs must declare both sides.
    (An immediately-invoked ``jax.jit(f)(x)`` init one-shot is not
    a cached program and is exempt.)
  * SHARD002 — a ``PartitionSpec`` naming an axis absent from the
    module's mesh vocabulary: the spec silently no-ops (jax treats an
    unknown axis as an error only at use; a typo'd axis in a helper
    replicates instead of sharding).
  * SHARD003 — host materialization (``np.asarray``, ``.item()``,
    ``jax.device_get``, ``.__array__()``) of a MESH-PROGRAM result
    inside ``@hot_path`` code — the sharded twin of SYNC001: on a
    sharded output this is a hidden all-gather plus a host copy.
  * SHARD004 — a ``shard_map``/``pjit``-wrapped function containing a
    host callback or Python-side branching on a traced parameter:
    per-shard callbacks serialize the mesh, and ``if traced:`` is a
    tracer error that only fires at run time.
  * SHARD005 — ``device_put`` with no sharding/device argument in a
    mesh-aware module: the array lands wherever the default device
    points (implicit replication on first use) — the silent-placement
    foot-gun mesh code must not ship.

**OBS — observability conventions** (obs/registry.py, obs/trace.py)
  * OBS001 — a ``span(...)`` call that is not the context expression
    of a ``with`` (an unmanaged span never records its exit: the
    trace shows a lane that silently loses time).
  * OBS002 — a literal metric name not matching ``cxxnet_[a-z0-9_]+``.
  * OBS003 — a literal counter name not ending in ``_total``.
  * OBS004 — more than %(max)d labels on one metric (label cardinality
    is a product, not a sum; keep series enumerable).
  * OBS005 — a literal ``cxxnet_attrib_*`` metric name outside the
    closed series set obs/attrib.py declares: the attribution
    taxonomy is a partition (fractions sum to 1.0), so a stray series
    under the prefix means some tool invented a category the ledger
    does not account for.
  * OBS006 — dict/str work on an ``obs/`` hot path: a ``@hot_path``
    function in an ``obs/`` module builds a dict/f-string/%%-format/
    ``.format`` or appends a non-tuple — accounting on the dispatch
    path must append ONE plain tuple; rendering (labels, dicts)
    belongs at scrape time. Scoped to obs/ because serving hot paths
    legitimately pass dict literals as trace-span args.

Checkers only see what is statically there: dynamically-built metric
names are skipped, locks on foreign objects are invisible, and the
runtime validator (lockcheck) covers what the AST cannot.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

METRIC_NAME_RE = re.compile(r"^cxxnet_[a-z0-9_]+$")
MAX_LABELS = 4

# method names on FOREIGN objects treated as blocking when called
# under a held lock (same-class calls are resolved precisely instead)
BLOCKING_METHOD_NAMES = {
    "serve_forever", "urlopen", "drain", "drain_replica", "spawn",
    "submit", "submit_tokens", "result",
}
# receiver-name heuristic separating thread.join() from str.join():
# flag .join() only when the receiver's last name segment looks like a
# thread/process handle
_JOINABLE_RE = re.compile(r"(^t$|^th$|thread|proc|worker)", re.I)

LOCK_FACTORY_KINDS = {
    "Lock": "lock", "RLock": "rlock", "Condition": "cond",
    "make_lock": "lock", "make_rlock": "rlock",
    "make_condition": "cond",
}
QUEUE_FACTORY_NAMES = {"Queue", "LifoQueue", "PriorityQueue",
                       "SimpleQueue", "make_queue"}


class Finding:
    """One lint finding. ``key`` (rule + file + qualified function) is
    the waiver granularity — stable across unrelated edits, unlike a
    line number."""

    __slots__ = ("rule", "path", "line", "func", "msg")

    def __init__(self, rule: str, path: str, line: int, func: str,
                 msg: str) -> None:
        self.rule = rule
        self.path = path
        self.line = int(line)
        self.func = func
        self.msg = msg

    @property
    def key(self) -> str:
        return "%s %s::%s" % (self.rule, self.path, self.func)

    def __repr__(self) -> str:
        return "%s %s:%d %s — %s" % (self.rule, self.path, self.line,
                                     self.func, self.msg)


class Module:
    """One parsed source file handed to every checker."""

    def __init__(self, path: str, source: str) -> None:
        self.path = path          # repo-relative, forward slashes
        self.source = source
        self.tree = ast.parse(source, filename=path)


# ----------------------------------------------------------------------
# shared AST helpers

def dotted(node) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(call: ast.Call) -> Optional[str]:
    return dotted(call.func)


def _self_attr(node) -> Optional[str]:
    """``X`` for an expression ``self.X``, else None."""
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


def _contains_call(node: ast.AST, names: Set[str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = _call_name(sub)
            if d is not None and d.rsplit(".", 1)[-1] in names:
                return True
    return False


class Checker:
    name = "base"

    def check(self, mod: Module) -> List[Finding]:
        raise NotImplementedError


# ----------------------------------------------------------------------
# CONC

class _MethodSummary:
    __slots__ = ("acquires", "blocking", "self_calls", "findings",
                 "edges")

    def __init__(self) -> None:
        self.acquires: Set[str] = set()       # lock attrs taken inside
        self.blocking: List[Tuple[int, str]] = []  # any depth
        # (held locks at call, callee method name, line)
        self.self_calls: List[Tuple[Tuple[str, ...], str, int]] = []
        self.findings: List[Finding] = []     # direct blocking-under-lock
        self.edges: List[Tuple[str, str, int]] = []  # (held, taken, ln)


class _ClassModel:
    def __init__(self, name: str) -> None:
        self.name = name
        self.locks: Dict[str, str] = {}    # attr -> lock|rlock|cond
        self.queues: Set[str] = set()
        self.methods: Dict[str, _MethodSummary] = {}


def _lock_kind_of(value: ast.AST) -> Optional[str]:
    """Lock kind when ``value`` (an assignment RHS) constructs one,
    looking through ternaries/boolops for the factory call."""
    for sub in ast.walk(value):
        if isinstance(sub, ast.Call):
            d = _call_name(sub)
            if d is not None:
                kind = LOCK_FACTORY_KINDS.get(d.rsplit(".", 1)[-1])
                if kind is not None:
                    return kind
    return None


def _is_queue_factory(value: ast.AST) -> bool:
    for sub in ast.walk(value):
        if isinstance(sub, ast.Call):
            d = _call_name(sub)
            if d is not None \
                    and d.rsplit(".", 1)[-1] in QUEUE_FACTORY_NAMES:
                return True
    return False


class ConcChecker(Checker):
    name = "CONC"

    # -- per-method walk ----------------------------------------------
    def _walk_fn(self, cls: _ClassModel, mod: Module, qual: str,
                 fn, summary: _MethodSummary) -> None:
        self._walk_body(cls, mod, qual, fn.body, [], summary)

    def _walk_body(self, cls, mod, qual, body, held, summary) -> None:
        for stmt in body:
            self._walk_stmt(cls, mod, qual, stmt, held, summary)

    def _walk_stmt(self, cls, mod, qual, stmt, held, summary) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a closure runs later, on its own stack: fresh held set,
            # findings attributed to the nested qualname
            inner = _MethodSummary()
            nested_q = "%s.%s" % (qual, stmt.name)
            self._walk_body(cls, mod, nested_q, stmt.body, [], inner)
            summary.findings.extend(inner.findings)
            summary.edges.extend(inner.edges)
            # nested acquisitions/blocking do NOT propagate to the
            # enclosing method (it only defines, not runs, them)
            for held_at, callee, ln in inner.self_calls:
                if held_at:   # closures holding locks calling methods
                    summary.self_calls.append((held_at, callee, ln))
            return
        if isinstance(stmt, ast.With):
            taken = []
            for item in stmt.items:
                attr = _self_attr(item.context_expr)
                if attr is not None and attr in cls.locks:
                    for h in held + taken:
                        summary.edges.append(
                            (h, attr, item.context_expr.lineno))
                    if attr in held + taken:
                        if cls.locks[attr] != "rlock":
                            summary.findings.append(Finding(
                                "CONC003", mod.path,
                                item.context_expr.lineno, qual,
                                "re-acquiring held non-reentrant "
                                "lock self.%s (self-deadlock)" % attr))
                    taken.append(attr)
                    summary.acquires.add(attr)
                else:
                    # non-lock context manager: still scan its
                    # expression for blocking calls under held locks
                    self._scan_expr(cls, mod, qual, item.context_expr,
                                    held, summary)
            self._walk_body(cls, mod, qual, stmt.body, held + taken,
                            summary)
            return
        # every other statement: scan expressions, recurse into
        # compound bodies with the same held set
        for field in ("test", "value", "iter", "exc", "cause", "msg"):
            sub = getattr(stmt, field, None)
            if isinstance(sub, ast.AST):
                self._scan_expr(cls, mod, qual, sub, held, summary)
        for field in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field, None)
            if isinstance(sub, list):
                self._walk_body(cls, mod, qual, sub, held, summary)
        for handler in getattr(stmt, "handlers", ()):
            self._walk_body(cls, mod, qual, handler.body, held, summary)

    def _scan_expr(self, cls, mod, qual, expr, held, summary) -> None:
        # manual walk so a Lambda SUBTREE is skipped whole (it runs
        # later, on its own stack — ast.walk would descend into it)
        stack = [expr]
        while stack:
            sub = stack.pop()
            if isinstance(sub, ast.Lambda):
                continue
            if isinstance(sub, ast.Call):
                self._scan_call(cls, mod, qual, sub, held, summary)
            stack.extend(ast.iter_child_nodes(sub))

    def _scan_call(self, cls, mod, qual, call, held, summary) -> None:
        d = _call_name(call)
        if d is None:
            return
        leaf = d.rsplit(".", 1)[-1]
        # same-class method call: resolved precisely at fixpoint time
        if isinstance(call.func, ast.Attribute) \
                and _self_attr(call.func) is not None \
                and leaf in cls.methods:
            summary.self_calls.append(
                (tuple(held), leaf, call.lineno))
        desc = self._blocking_desc(cls, call, d, leaf, held)
        if desc is None:
            return
        summary.blocking.append((call.lineno, desc))
        if held:
            summary.findings.append(Finding(
                "CONC002", mod.path, call.lineno, qual,
                "%s while holding self.%s" % (desc, held[-1])))

    def _blocking_desc(self, cls, call, d, leaf, held) -> Optional[str]:
        """A human description when ``call`` is a blocking operation,
        else None."""
        if d in ("time.sleep", "sleep"):
            return "time.sleep(...)"
        if leaf == "join" and isinstance(call.func, ast.Attribute):
            recv = call.func.value
            if isinstance(recv, ast.Constant):
                return None       # ", ".join(...) — string join
            rd = dotted(recv)
            seg = rd.rsplit(".", 1)[-1] if rd else ""
            if _JOINABLE_RE.search(seg):
                return "thread %s.join(...)" % (rd or "?")
            return None
        if leaf == "wait" and isinstance(call.func, ast.Attribute):
            attr = _self_attr(call.func.value)
            if attr is not None and attr in held \
                    and cls.locks.get(attr) == "cond":
                return None   # cond.wait on the held condition releases
            return "blocking .wait(...)"
        if leaf in ("get", "put") and isinstance(call.func,
                                                 ast.Attribute):
            attr = _self_attr(call.func.value)
            if attr is None or attr not in cls.queues:
                return None
            for kw in call.keywords:
                if kw.arg == "block" \
                        and isinstance(kw.value, ast.Constant) \
                        and kw.value.value is False:
                    return None
            return "blocking queue .%s(...) on self.%s" % (leaf, attr)
        if leaf in BLOCKING_METHOD_NAMES \
                and isinstance(call.func, ast.Attribute):
            # same-class calls are resolved precisely; only foreign
            # receivers use the name heuristic
            if _self_attr(call.func) is not None:
                return None
            return "blocking call .%s(...)" % leaf
        if leaf in ("urlopen",):
            return "network call %s(...)" % d
        return None

    # -- module-level assembly ----------------------------------------
    def _model_class(self, node: ast.ClassDef) -> _ClassModel:
        cls = _ClassModel(node.name)
        for fn in node.body:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Assign) and sub.targets:
                    attr = _self_attr(sub.targets[0])
                    if attr is None:
                        continue
                    kind = _lock_kind_of(sub.value)
                    if kind is not None:
                        cls.locks[attr] = kind
                    elif _is_queue_factory(sub.value):
                        cls.queues.add(attr)
        return cls

    def check(self, mod: Module) -> List[Finding]:
        findings: List[Finding] = []
        graph: Dict[str, Set[str]] = {}
        edge_lines: Dict[Tuple[str, str], int] = {}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            cls = self._model_class(node)
            if not cls.locks and not cls.queues:
                continue
            for fn in node.body:
                if isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                    cls.methods[fn.name] = _MethodSummary()
            for fn in node.body:
                if isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                    qual = "%s.%s" % (cls.name, fn.name)
                    self._walk_fn(cls, mod, qual, fn,
                                  cls.methods[fn.name])
            self._fixpoint(cls, mod, findings, graph, edge_lines)
        findings.extend(self._cycles(mod, graph, edge_lines))
        return findings

    def _fixpoint(self, cls, mod, findings, graph, edge_lines) -> None:
        # transitive acquires/blocking through same-class calls
        acq_all = {m: set(s.acquires) for m, s in cls.methods.items()}
        blk_all = {m: list(s.blocking) for m, s in cls.methods.items()}
        changed = True
        while changed:
            changed = False
            for m, s in cls.methods.items():
                for _held, callee, _ln in s.self_calls:
                    if callee not in acq_all:
                        continue
                    if not acq_all[callee] <= acq_all[m]:
                        acq_all[m] |= acq_all[callee]
                        changed = True
                    for b in blk_all[callee]:
                        if b not in blk_all[m]:
                            blk_all[m].append(b)
                            changed = True
        for m, s in cls.methods.items():
            findings.extend(s.findings)
            qual = "%s.%s" % (cls.name, m)
            for held, callee, ln in s.self_calls:
                if not held or callee not in acq_all:
                    continue
                for taken in acq_all[callee]:
                    for h in held:
                        s.edges.append((h, taken, ln))
                    if taken in held \
                            and cls.locks.get(taken) != "rlock":
                        findings.append(Finding(
                            "CONC003", mod.path, ln, qual,
                            "call to self.%s() re-acquires held "
                            "non-reentrant lock self.%s" %
                            (callee, taken)))
                if blk_all[callee]:
                    ln2, desc = blk_all[callee][0]
                    findings.append(Finding(
                        "CONC002", mod.path, ln, qual,
                        "call to self.%s() (%s at line %d) while "
                        "holding self.%s" %
                        (callee, desc, ln2, held[-1])))
            for h, t, ln in s.edges:
                if h == t:
                    continue
                a = "%s.%s" % (cls.name, h)
                b = "%s.%s" % (cls.name, t)
                graph.setdefault(a, set()).add(b)
                edge_lines.setdefault((a, b), ln)

    def _cycles(self, mod, graph, edge_lines) -> List[Finding]:
        findings: List[Finding] = []
        seen_cycles: Set[frozenset] = set()
        state: Dict[str, int] = {}   # 0 unseen 1 on-stack 2 done

        def dfs(node, path):
            state[node] = 1
            for nxt in sorted(graph.get(node, ())):
                if state.get(nxt, 0) == 1:
                    cyc = path[path.index(nxt):] + [nxt]
                    key = frozenset(cyc)
                    if key not in seen_cycles:
                        seen_cycles.add(key)
                        ln = edge_lines.get((node, nxt), 0)
                        findings.append(Finding(
                            "CONC001", mod.path, ln, "<module>",
                            "lock-acquisition cycle: %s"
                            % " -> ".join(cyc)))
                elif state.get(nxt, 0) == 0:
                    dfs(nxt, path + [nxt])
            state[node] = 2

        for n in sorted(graph):
            if state.get(n, 0) == 0:
                dfs(n, [n])
        return findings


# ----------------------------------------------------------------------
# SYNC

class SyncChecker(Checker):
    name = "SYNC"

    def __init__(self, extra_hot: Sequence[str] = ()) -> None:
        # extra_hot: "path::qualname" entries for hot paths that cannot
        # carry the decorator (the config-list alternative)
        self.extra_hot = set(extra_hot)

    @staticmethod
    def _is_hot(fn) -> bool:
        for dec in fn.decorator_list:
            d = dotted(dec) or (dotted(dec.func)
                                if isinstance(dec, ast.Call) else None)
            if d is not None and d.rsplit(".", 1)[-1] == "hot_path":
                return True
        return False

    def check(self, mod: Module) -> List[Finding]:
        findings: List[Finding] = []

        # SYNC006 needs pair scans per statement list — only pay for
        # them in modules that mention the call at all
        scan_async = "copy_to_host_async" in mod.source

        def visit(node, qual):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    visit(child, qual + [child.name])
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    q = ".".join(qual + [child.name])
                    if scan_async:
                        self._check_async_copy(mod, q, child, findings)
                    if self._is_hot(child) \
                            or "%s::%s" % (mod.path, q) \
                            in self.extra_hot:
                        self._check_hot(mod, q, child, findings)
                    else:
                        visit(child, qual + [child.name])

        visit(mod.tree, [])
        return findings

    # host builtins whose result is a plain Python number — float()
    # of these is arithmetic, not a device sync
    _HOST_BUILTINS = {"max", "min", "len", "abs", "round", "sum",
                      "ord", "str"}

    @classmethod
    def _computes_on_device(cls, node) -> bool:
        """True when ``node`` could force a device value to host: a
        subscript (``loss[0]``) or a call that is not a bare host
        builtin — ``max(a, b)`` is arithmetic, ``out.mean()`` is a
        device reduce (the builtin exemption is Name-calls only)."""
        if isinstance(node, ast.Subscript):
            return True
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name):
                return node.func.id not in cls._HOST_BUILTINS
            return True
        return False

    def _check_hot(self, mod, qual, fn, findings) -> None:
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            d = _call_name(sub)
            leaf = d.rsplit(".", 1)[-1] if d else None
            if leaf == "block_until_ready":
                findings.append(Finding(
                    "SYNC001", mod.path, sub.lineno, qual,
                    "block_until_ready() in hot path"))
            elif d in ("np.asarray", "numpy.asarray", "np.array",
                       "numpy.array"):
                findings.append(Finding(
                    "SYNC002", mod.path, sub.lineno, qual,
                    "%s(...) materializes to host in hot path" % d))
            elif leaf == "item" and not sub.args \
                    and isinstance(sub.func, ast.Attribute):
                findings.append(Finding(
                    "SYNC003", mod.path, sub.lineno, qual,
                    ".item() host sync in hot path"))
            elif leaf == "tolist" and not sub.args \
                    and isinstance(sub.func, ast.Attribute):
                findings.append(Finding(
                    "SYNC005", mod.path, sub.lineno, qual,
                    ".tolist() whole-array host transfer in hot "
                    "path"))
            elif d in ("jax.device_get", "device_get"):
                findings.append(Finding(
                    "SYNC005", mod.path, sub.lineno, qual,
                    "%s(...) forces a device->host transfer in hot "
                    "path" % d))
            elif isinstance(sub.func, ast.Name) \
                    and sub.func.id in ("float", "int") and sub.args:
                arg = sub.args[0]
                if any(self._computes_on_device(x)
                       for x in ast.walk(arg)):
                    findings.append(Finding(
                        "SYNC004", mod.path, sub.lineno, qual,
                        "%s(...) of a computed value syncs in hot "
                        "path" % sub.func.id))

    # -- SYNC006: copy_to_host_async immediately awaited ---------------
    @staticmethod
    def _async_copy_recv(stmt) -> Optional[Tuple[str, int]]:
        """(receiver name, line) when ``stmt`` contains
        ``X.copy_to_host_async()``."""
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr == "copy_to_host_async":
                recv = dotted(sub.func.value)
                if recv is not None:
                    return recv, sub.lineno
        return None

    @staticmethod
    def _materializes(stmt, name: str) -> bool:
        """``stmt`` forces ``name`` to host: np.asarray/np.array of
        it, ``.item()``/``.block_until_ready()`` on it, or
        float()/int() over an expression reading it."""
        for sub in ast.walk(stmt):
            if not isinstance(sub, ast.Call):
                continue
            d = _call_name(sub)
            leaf = d.rsplit(".", 1)[-1] if d else None
            if leaf in ("item", "block_until_ready") \
                    and isinstance(sub.func, ast.Attribute) \
                    and dotted(sub.func.value) == name:
                return True
            if (d in ("np.asarray", "numpy.asarray", "np.array",
                      "numpy.array")
                    or (isinstance(sub.func, ast.Name)
                        and sub.func.id in ("float", "int"))) \
                    and sub.args:
                for x in ast.walk(sub.args[0]):
                    if dotted(x) == name:
                        return True
        return False

    def _check_async_copy(self, mod, qual, fn, findings) -> None:
        # own statements only: nested defs are visited on their own
        stack = list(ast.iter_child_nodes(fn))
        nodes = [fn]
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                continue
            nodes.append(n)
            stack.extend(ast.iter_child_nodes(n))
        for node in nodes:
            for field in ("body", "orelse", "finalbody"):
                body = getattr(node, field, None)
                if not isinstance(body, list):
                    continue
                for a, b in zip(body, body[1:]):
                    hit = self._async_copy_recv(a)
                    if hit and self._materializes(b, hit[0]):
                        findings.append(Finding(
                            "SYNC006", mod.path, hit[1], qual,
                            "%s.copy_to_host_async() is materialized "
                            "by the very next statement — the async "
                            "copy bought no overlap" % hit[0]))


# ----------------------------------------------------------------------
# JIT

JIT_CONSTRUCTORS = {"jax.jit", "jit", "pjit"}

# attribute reads that are metadata, legal on a donated (deleted) array
_METADATA_ATTRS = {"shape", "dtype", "ndim", "size", "sharding",
                   "aval", "nbytes"}

# cross-module donating APIs the per-module model cannot see:
# (callable leaf name, donated argnums, minimum positional arity).
# The arity floor keeps generic leaves from matching unrelated calls
# (trace.step(n) is 1-ary; ExportedStepDecoder.step(pool_k, ...) is 7).
DEFAULT_EXTRA_DONATING = (
    # r12: scatter_prefill_kv takes the rung's pool-buffer TUPLE at
    # arg 0 (2 arrays native, 4 on the int8 rung), all donated
    ("scatter_prefill_kv", (0,), 4),
    ("step", (0, 1), 7),
)


def _is_jit_ctor(call: ast.Call) -> bool:
    d = _call_name(call)
    if d is None:
        return False
    return d in JIT_CONSTRUCTORS or d.rsplit(".", 1)[-1] == "pjit"


def _int_tuple(node) -> Optional[Tuple[int, ...]]:
    """Every int constant found inside ``node`` (handles ``(0, 1)``,
    ``3``, and ``(0, 1) + extra`` — the dynamic part is simply not
    seen; the model stays conservative)."""
    out = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) \
                and isinstance(sub.value, int) \
                and not isinstance(sub.value, bool):
            out.add(int(sub.value))
    return tuple(sorted(out)) if out else None


def _jit_specs(call: ast.Call):
    """(donate_argnums, static_argnums) declared on a jit/pjit
    construction, ints only; (None, None) when absent."""
    don = stat = None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            don = _int_tuple(kw.value)
        elif kw.arg == "static_argnums":
            stat = _int_tuple(kw.value)
    return don, stat


def _ctor_specs(expr):
    """Walk an assignment RHS for a jit/pjit construction (or a
    ``jitcheck.make_donating`` wrap) and return its (donate, static)
    argnums — sees through wrappers like ``make_donating(jax.jit(...,
    donate_argnums=(0, 1)), ...)``."""
    for sub in ast.walk(expr):
        if not isinstance(sub, ast.Call):
            continue
        d = _call_name(sub)
        if d is None:
            continue
        if _is_jit_ctor(sub):
            don, stat = _jit_specs(sub)
            if don is not None or stat is not None:
                return don, stat
        elif d.rsplit(".", 1)[-1] == "make_donating":
            for kw in sub.keywords:
                if kw.arg == "argnums":
                    t = _int_tuple(kw.value)
                    if t is not None:
                        return t, None
    return None, None


def _track(node) -> Optional[str]:
    """The dataflow-tracked name of an expression: a bare ``Name`` or
    a ``self.<attr...>`` chain (as a dotted string), else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        d = dotted(node)
        if d is not None and d.startswith("self."):
            return d
    return None


def _flat_targets(targets) -> List[ast.AST]:
    out: List[ast.AST] = []
    stack = list(targets)
    while stack:
        t = stack.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            stack.extend(t.elts)
        elif isinstance(t, ast.Starred):
            stack.append(t.value)
        else:
            out.append(t)
    return out


class _JitScope:
    """Known jitted callables of one scope: name -> argnums."""

    __slots__ = ("donating", "static")

    def __init__(self) -> None:
        self.donating: Dict[str, Tuple[int, ...]] = {}
        self.static: Dict[str, Tuple[int, ...]] = {}


class JitChecker(Checker):
    name = "JIT"

    def __init__(self, extra_hot: Sequence[str] = (),
                 extra_donating=DEFAULT_EXTRA_DONATING) -> None:
        self.extra_hot = set(extra_hot)
        self.extra_donating = tuple(extra_donating)

    # -- scope models --------------------------------------------------
    @staticmethod
    def _scan_assigns(root, scope: _JitScope, self_attrs: bool) -> None:
        """Collect ``NAME = jit-ctor`` (or ``self.X = jit-ctor`` when
        ``self_attrs``) assignments anywhere under ``root``."""
        for sub in ast.walk(root):
            if not (isinstance(sub, ast.Assign) and sub.targets):
                continue
            for tgt in _flat_targets(sub.targets):
                if self_attrs:
                    name = _track(tgt)
                    if name is None or not name.startswith("self."):
                        continue
                else:
                    if not isinstance(tgt, ast.Name):
                        continue
                    name = tgt.id
                don, stat = _ctor_specs(sub.value)
                if don is not None:
                    scope.donating[name] = don
                if stat is not None:
                    scope.static[name] = stat

    @staticmethod
    def _local_scope(fn) -> _JitScope:
        scope = _JitScope()
        JitChecker._scan_assigns(fn, scope, self_attrs=False)
        return scope

    def _propagate(self, fns, scope: _JitScope, method: bool) -> None:
        """A function that directly returns a known donating call with
        its own params at donated positions is itself donating (the
        ``ExportedStepDecoder.step`` shape): map the argnums through
        and register it in ``scope``."""
        for fn in fns:
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            local = self._local_scope(fn)
            params = [a.arg for a in fn.args.args]
            off = 1 if method and params[:1] == ["self"] else 0
            for stmt in ast.walk(fn):
                if not (isinstance(stmt, ast.Return)
                        and isinstance(stmt.value, ast.Call)):
                    continue
                call = stmt.value
                d = dotted(call.func)
                argnums = (local.donating.get(d)
                           or scope.donating.get(d)) if d else None
                if argnums is None \
                        or any(isinstance(a, ast.Starred)
                               for a in call.args):
                    continue
                mapped = []
                for i in argnums:
                    if i < len(call.args) \
                            and isinstance(call.args[i], ast.Name) \
                            and call.args[i].id in params:
                        p = params.index(call.args[i].id) - off
                        if p >= 0:
                            mapped.append(p)
                if mapped:
                    key = ("self." + fn.name) if method else fn.name
                    scope.donating.setdefault(
                        key, tuple(sorted(mapped)))

    def _class_scope(self, node: ast.ClassDef) -> _JitScope:
        scope = _JitScope()
        self._scan_assigns(node, scope, self_attrs=True)
        self._propagate(node.body, scope, method=True)
        return scope

    def _module_scope(self, tree) -> _JitScope:
        scope = _JitScope()
        for node in tree.body:
            if isinstance(node, ast.Assign):
                self._scan_assigns(node, scope, self_attrs=False)
        self._propagate(tree.body, scope, method=False)
        return scope

    # -- callee resolution --------------------------------------------
    def _resolve(self, call: ast.Call, ctx, kind: str):
        """(argnums, description) when ``call`` targets a known
        donating (kind='donating') or static-arged (kind='static')
        callable visible from ``ctx = (module, cls, local)``."""
        module, cls, local = ctx
        d = dotted(call.func)
        if d is None:
            # immediate jit(fn, ...)(args)
            if isinstance(call.func, ast.Call) \
                    and _is_jit_ctor(call.func):
                don, stat = _jit_specs(call.func)
                spec = don if kind == "donating" else stat
                if spec is not None:
                    return spec, _call_name(call.func.func) or "jit"
            return None, None
        for scope in (local, cls, module):
            if scope is None:
                continue
            spec = getattr(scope, kind).get(d)
            if spec is not None:
                return spec, d
        if kind == "donating":
            leaf = d.rsplit(".", 1)[-1]
            for lf, argnums, min_args in self.extra_donating:
                if leaf == lf and len(call.args) >= min_args:
                    return argnums, d
        return None, None

    # -- JIT001/JIT004: use-after-donate dataflow ---------------------
    def _flow_body(self, body, state, mod, qual, ctx, findings):
        for stmt in body:
            self._flow_stmt(stmt, state, mod, qual, ctx, findings)

    def _flow_stmt(self, stmt, state, mod, qual, ctx, findings):
        flow_expr = self._flow_expr
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return            # runs later / visited on its own
        if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
            targets = _flat_targets(
                stmt.targets if isinstance(stmt, ast.Assign)
                else [stmt.target])
            names = {n for n in map(_track, targets) if n}
            if stmt.value is not None:
                flow_expr(stmt.value, state, names, False, mod, qual,
                          ctx, findings)
            for n in names:
                state.pop(n, None)
            return
        if isinstance(stmt, ast.AugAssign):
            flow_expr(stmt.value, state, set(), False, mod, qual, ctx,
                      findings)
            # reads nested INSIDE the target (x[i] += 1 reads x and i
            # with Load ctx) go through the normal walk ...
            flow_expr(stmt.target, state, set(), False, mod, qual,
                      ctx, findings)
            n = _track(stmt.target)
            # ... but the target name itself carries Store ctx, so the
            # read half of the read-write needs a direct check
            if n is not None and n in state:
                ln, desc, argnum = state.pop(n)
                findings.append(Finding(
                    "JIT001", mod.path, stmt.target.lineno, qual,
                    "%r read after being donated to %s (argnum %d, "
                    "line %d) — use-after-donate" % (n, desc, argnum,
                                                     ln)))
            if n:
                state.pop(n, None)
            return
        if isinstance(stmt, ast.Expr):
            flow_expr(stmt.value, state, set(), True, mod, qual, ctx,
                      findings)
            return
        if isinstance(stmt, ast.If):
            flow_expr(stmt.test, state, set(), False, mod, qual, ctx,
                      findings)
            s1, s2 = dict(state), dict(state)
            self._flow_body(stmt.body, s1, mod, qual, ctx, findings)
            self._flow_body(stmt.orelse, s2, mod, qual, ctx, findings)
            state.clear()
            state.update(s2)
            state.update(s1)          # union: donated on either path
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            flow_expr(stmt.iter, state, set(), False, mod, qual, ctx,
                      findings)
            tnames = {n for n in map(_track,
                                     _flat_targets([stmt.target]))
                      if n}
            for _ in range(2):        # pass 2 catches back-edge reads
                # the back edge REBINDS the loop target from the
                # iterator, so clear it at the top of EVERY pass:
                # donating the loop variable each iteration (the
                # donate-each-batch pattern) is legal and must not
                # flag on pass 2
                for n in tnames:
                    state.pop(n, None)
                self._flow_body(stmt.body, state, mod, qual, ctx,
                                findings)
            self._flow_body(stmt.orelse, state, mod, qual, ctx,
                            findings)
            return
        if isinstance(stmt, ast.While):
            for _ in range(2):
                flow_expr(stmt.test, state, set(), False, mod, qual,
                          ctx, findings)
                self._flow_body(stmt.body, state, mod, qual, ctx,
                                findings)
            self._flow_body(stmt.orelse, state, mod, qual, ctx,
                            findings)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                flow_expr(item.context_expr, state, set(), False, mod,
                          qual, ctx, findings)
                if item.optional_vars is not None:
                    for t in _flat_targets([item.optional_vars]):
                        n = _track(t)
                        if n:
                            state.pop(n, None)
            self._flow_body(stmt.body, state, mod, qual, ctx, findings)
            return
        if isinstance(stmt, ast.Try):
            entry = dict(state)
            self._flow_body(stmt.body, state, mod, qual, ctx, findings)
            merged = dict(state)
            for h in stmt.handlers:
                hs = dict(entry)
                hs.update(state)      # may throw anywhere in the body
                self._flow_body(h.body, hs, mod, qual, ctx, findings)
                merged.update(hs)
            so = dict(state)
            self._flow_body(stmt.orelse, so, mod, qual, ctx, findings)
            merged.update(so)
            state.clear()
            state.update(merged)
            self._flow_body(stmt.finalbody, state, mod, qual, ctx,
                            findings)
            return
        if isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                n = _track(t)
                if n:
                    state.pop(n, None)
            return
        if isinstance(stmt, ast.Return):
            if stmt.value is not None:
                flow_expr(stmt.value, state, set(), False, mod, qual,
                          ctx, findings)
            return
        for field in ("test", "value", "exc", "cause", "msg"):
            sub = getattr(stmt, field, None)
            if isinstance(sub, ast.AST):
                flow_expr(sub, state, set(), False, mod, qual, ctx,
                          findings)

    def _flow_expr(self, expr, state, targets, discard, mod, qual,
                   ctx, findings):
        """One expression: reads are checked against the donated set
        FIRST (argument evaluation precedes the call), then this
        expression's donating calls update the set. ``targets`` are
        names being simultaneously rebound by the enclosing assignment
        (``pool, out = step(pool, x)`` is the sanctioned shape);
        ``discard`` marks a bare expression statement (JIT004)."""
        calls: List[ast.Call] = []
        stack = [expr]
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.Lambda, ast.FunctionDef,
                                ast.AsyncFunctionDef)):
                continue          # runs later, on its own frame
            if isinstance(sub, ast.Attribute) \
                    and sub.attr in _METADATA_ATTRS:
                inner = _track(sub.value)
                if inner is not None and inner in state:
                    continue      # metadata of a donated array: legal
            if isinstance(sub, ast.Call):
                calls.append(sub)
            n = _track(sub)
            if n is not None and n in state \
                    and isinstance(getattr(sub, "ctx", None), ast.Load):
                ln, desc, argnum = state.pop(n)
                findings.append(Finding(
                    "JIT001", mod.path, sub.lineno, qual,
                    "%r read after being donated to %s (argnum %d, "
                    "line %d) — use-after-donate" % (n, desc, argnum,
                                                     ln)))
                continue          # don't re-flag via the chain's parts
            stack.extend(ast.iter_child_nodes(sub))
        for call in calls:
            argnums, desc = self._resolve(call, ctx, "donating")
            if argnums is None \
                    or any(isinstance(a, ast.Starred)
                           for a in call.args):
                continue
            if discard and call is expr:
                findings.append(Finding(
                    "JIT004", mod.path, call.lineno, qual,
                    "donating call %s(...) discards its result — the "
                    "donated inputs are consumed but nothing rebinds "
                    "the outputs (the drop-aliasing shape)" % desc))
            for i in argnums:
                if i < len(call.args):
                    n = _track(call.args[i])
                    if n is not None and n not in targets:
                        state[n] = (call.lineno, desc, i)

    # -- JIT002/JIT003: constructions + static-arg storms -------------
    def _scan_ctor(self, mod, qual, fn, hot, findings):
        def visit(node, depth):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                return
            if isinstance(node, ast.Call) and _is_jit_ctor(node):
                if depth > 0:
                    findings.append(Finding(
                        "JIT002", mod.path, node.lineno, qual,
                        "jit/pjit constructed inside a loop — "
                        "every iteration re-traces and "
                        "re-compiles"))
                elif hot:
                    findings.append(Finding(
                        "JIT002", mod.path, node.lineno, qual,
                        "jit/pjit constructed inside a hot-path "
                        "function — every call re-traces; build "
                        "once outside or cache-guard it"))
            if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                # only what re-runs per iteration deepens the loop
                # depth: the body, and a While's test; a For's iter
                # and either loop's orelse evaluate exactly once
                for stmt in node.body:
                    visit(stmt, depth + 1)
                if isinstance(node, ast.While):
                    visit(node.test, depth + 1)
                else:
                    visit(node.iter, depth)
                for stmt in node.orelse:
                    visit(stmt, depth)
                return
            for child in ast.iter_child_nodes(node):
                visit(child, depth)
        for child in ast.iter_child_nodes(fn):
            visit(child, 0)

    def _scan_static_loops(self, mod, qual, fn, ctx, findings):
        for node in ast.walk(fn):
            if not isinstance(node, (ast.For, ast.AsyncFor,
                                     ast.While)):
                continue
            varying: Set[str] = set()
            if isinstance(node, (ast.For, ast.AsyncFor)):
                for t in _flat_targets([node.target]):
                    n = _track(t)
                    if n:
                        varying.add(n)
            for sub in ast.walk(node):
                if isinstance(sub, (ast.Name, ast.Attribute)) \
                        and isinstance(getattr(sub, "ctx", None),
                                       ast.Store):
                    n = _track(sub)
                    if n:
                        varying.add(n)
            if not varying:
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                argnums, desc = self._resolve(sub, ctx, "static")
                if argnums is None:
                    continue
                for i in argnums:
                    if i >= len(sub.args):
                        continue
                    reads = {_track(x)
                             for x in ast.walk(sub.args[i])}
                    hit = sorted((reads & varying) - {None})
                    if hit:
                        findings.append(Finding(
                            "JIT003", mod.path, sub.lineno, qual,
                            "loop-varying %s passed at static_argnums "
                            "position %d of %s — every new value is a "
                            "fresh trace + compile (recompile storm)"
                            % (", ".join(map(repr, hit)), i, desc)))

    # -- drive ---------------------------------------------------------
    def check(self, mod: Module) -> List[Finding]:
        findings: List[Finding] = []
        module_scope = self._module_scope(mod.tree)

        def visit(node, stack, cls_scope):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    visit(child, stack + [child.name],
                          self._class_scope(child))
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    qual = ".".join(stack + [child.name])
                    hot = SyncChecker._is_hot(child) \
                        or "%s::%s" % (mod.path, qual) in self.extra_hot
                    ctx = (module_scope, cls_scope,
                           self._local_scope(child))
                    # the dataflow walk is the expensive pass: run it
                    # only when this function can actually reach a
                    # donating callable (one cheap call scan)
                    if self._any_donating_call(child, ctx):
                        self._flow_fn(mod, qual, child, ctx, findings)
                    self._scan_ctor(mod, qual, child, hot, findings)
                    if module_scope.static or ctx[2].static \
                            or (cls_scope is not None
                                and cls_scope.static):
                        self._scan_static_loops(mod, qual, child, ctx,
                                                findings)
                    # nested defs keep the class scope: closures
                    # capture self
                    visit(child, stack + [child.name], cls_scope)

        visit(mod.tree, [], None)
        seen: Set[tuple] = set()
        out: List[Finding] = []
        for f in findings:          # loops are walked twice: dedupe
            k = (f.rule, f.line, f.func, f.msg)
            if k not in seen:
                seen.add(k)
                out.append(f)
        return out

    def _any_donating_call(self, fn, ctx) -> bool:
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call) \
                    and self._resolve(sub, ctx, "donating")[0] \
                    is not None:
                return True
        return False

    def _flow_fn(self, mod, qual, fn, ctx, findings):
        state: Dict[str, tuple] = {}
        self._flow_body(fn.body, state, mod, qual, ctx, findings)


# ----------------------------------------------------------------------
# SHARD

MESH_FACTORY_NAMES = {"Mesh", "make_mesh"}
# the parallel.py axis vocabulary: the names every mesh this codebase
# constructs can carry (make_mesh axes). Only LITERAL axis strings are
# checked — P(DATA_AXIS) through a constant is conservatively skipped,
# like every dynamically-built name in this file
MESH_AXIS_VOCAB = {"data", "model", "seq", "pipe"}
SHARD_CALLBACK_LEAVES = {"pure_callback", "io_callback",
                         "debug_callback", "callback"}


def _has_mesh_factory(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = _call_name(sub)
            if d is not None \
                    and d.rsplit(".", 1)[-1] in MESH_FACTORY_NAMES:
                return True
    return False


def _is_sharded_ctor(call: ast.Call) -> bool:
    """A jit/pjit construction that declares its placements (either
    side counts: pjit defaults the other to propagation from it)."""
    return any(kw.arg in ("in_shardings", "out_shardings")
               for kw in call.keywords)


class ShardChecker(Checker):
    name = "SHARD"

    def __init__(self, extra_hot: Sequence[str] = ()) -> None:
        self.extra_hot = set(extra_hot)

    # -- module vocabulary --------------------------------------------
    @staticmethod
    def _axis_vocab(mod: Module) -> Set[str]:
        """The axis names in scope for this module: the parallel.py
        constants plus every literal axis tuple a ``Mesh(...)``
        construction in the module declares (the second-mesh-in-class
        near miss: its axes join the vocabulary too)."""
        vocab = set(MESH_AXIS_VOCAB)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _call_name(node)
            if d is None or d.rsplit(".", 1)[-1] != "Mesh":
                continue
            axes = node.args[1] if len(node.args) >= 2 else None
            for kw in node.keywords:
                if kw.arg == "axis_names":
                    axes = kw.value
            if axes is not None:
                for sub in ast.walk(axes):
                    if isinstance(sub, ast.Constant) \
                            and isinstance(sub.value, str):
                        vocab.add(sub.value)
        return vocab

    @staticmethod
    def _class_has_mesh(node: ast.ClassDef) -> bool:
        """Mesh-in-scope, modeled like the lock model: some method
        assigns ``self.X = make_mesh(...)`` / ``Mesh(...)``."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) and sub.targets \
                    and _self_attr(sub.targets[0]) is not None \
                    and _has_mesh_factory(sub.value):
                return True
        return False

    @staticmethod
    def _mesh_prog_names(root, self_attrs: bool) -> Set[str]:
        """Names (``self.X`` or local/module NAME) assigned from a
        placement-declaring jit/pjit construction or a
        ``shardcheck.make_sharded`` wrap — the callables whose results
        SHARD003 tracks as mesh-program outputs."""
        out: Set[str] = set()
        for sub in ast.walk(root):
            if not (isinstance(sub, ast.Assign) and sub.targets):
                continue
            sharded = False
            for c in ast.walk(sub.value):
                if not isinstance(c, ast.Call):
                    continue
                d = _call_name(c)
                leaf = d.rsplit(".", 1)[-1] if d else None
                if leaf == "make_sharded" \
                        or (_is_jit_ctor(c) and _is_sharded_ctor(c)):
                    sharded = True
                    break
            if not sharded:
                continue
            for tgt in _flat_targets(sub.targets):
                name = _track(tgt)
                if name is None:
                    continue
                if self_attrs == name.startswith("self."):
                    out.add(name)
        return out

    # -- drive --------------------------------------------------------
    def check(self, mod: Module) -> List[Finding]:
        findings: List[Finding] = []
        vocab = self._axis_vocab(mod)
        mesh_aware = _has_mesh_factory(mod.tree)
        # treat leaf "P" as PartitionSpec only when the module actually
        # deals in PartitionSpec (the import-alias convention); a
        # foreign helper named P must not be mistaken for it
        p_leaves = {"PartitionSpec"}
        if "PartitionSpec" in mod.source:
            p_leaves.add("P")
        # calls that are immediately invoked: jit(f)(x) — the inner
        # ctor is somebody's .func, not a stored program
        invoked = {id(c.func) for c in ast.walk(mod.tree)
                   if isinstance(c, ast.Call)}
        module_progs = self._mesh_prog_names(mod.tree, self_attrs=False)

        def qual_of(stack):
            return ".".join(stack) if stack else "<module>"

        # SHARD001: statements under a mesh scope (mesh-holding class
        # or with-Mesh block)
        def walk001(node, stack, in_mesh):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    walk001(child, stack + [child.name],
                            in_mesh or self._class_has_mesh(child))
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    walk001(child, stack + [child.name], in_mesh)
                elif isinstance(child, (ast.With, ast.AsyncWith)):
                    wm = in_mesh or any(
                        _has_mesh_factory(i.context_expr)
                        for i in child.items)
                    walk001(child, stack, wm)
                else:
                    if in_mesh and isinstance(
                            child,
                            (ast.Assign, ast.AnnAssign, ast.Return)):
                        self._check_bare_jit(mod, qual_of(stack),
                                             child, invoked, findings)
                    walk001(child, stack, in_mesh)

        # SHARD002/SHARD005: every call, with its enclosing qualname
        def walk_calls(node, stack):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.ClassDef, ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    walk_calls(child, stack + [child.name])
                    continue
                if isinstance(child, ast.Call):
                    self._pspec_call(mod, qual_of(stack), child,
                                     vocab, p_leaves, findings)
                    if mesh_aware:
                        self._device_put_call(mod, qual_of(stack),
                                              child, findings)
                walk_calls(child, stack)

        # SHARD003: hot-path functions, with class-scoped mesh programs
        def walk_hot(node, stack, cls_progs):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    walk_hot(child, stack + [child.name],
                             self._mesh_prog_names(child,
                                                   self_attrs=True))
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    qual = ".".join(stack + [child.name])
                    if SyncChecker._is_hot(child) \
                            or "%s::%s" % (mod.path, qual) \
                            in self.extra_hot:
                        self._check_hot_materialize(
                            mod, qual, child, module_progs | cls_progs,
                            findings)
                    walk_hot(child, stack + [child.name], cls_progs)
                else:
                    walk_hot(child, stack, cls_progs)

        walk001(mod.tree, [], False)
        walk_calls(mod.tree, [])
        walk_hot(mod.tree, [], set())
        self._check_shard_map(mod, findings)
        return findings

    # -- SHARD001 -----------------------------------------------------
    def _check_bare_jit(self, mod, qual, stmt, invoked, findings):
        value = getattr(stmt, "value", None)
        if value is None:
            return
        for sub in ast.walk(value):
            if not (isinstance(sub, ast.Call) and _is_jit_ctor(sub)):
                continue
            if id(sub) in invoked:
                continue    # jit(f)(x): a one-shot, not a program
            if _is_sharded_ctor(sub):
                continue
            findings.append(Finding(
                "SHARD001", mod.path, sub.lineno, qual,
                "jit/pjit built under a mesh without in_shardings/"
                "out_shardings — XLA propagation picks the placement "
                "and a propagation change silently reshards"))

    # -- SHARD002 -----------------------------------------------------
    def _pspec_call(self, mod, qual, call, vocab, p_leaves, findings):
        d = _call_name(call)
        if d is None or d.rsplit(".", 1)[-1] not in p_leaves:
            return
        for arg in call.args:
            if isinstance(arg, ast.Starred):
                continue
            # manual walk so a nested Call's own strings (P(pick("x")))
            # are not mistaken for axis literals
            stack = [arg]
            while stack:
                node = stack.pop()
                if isinstance(node, ast.Call):
                    continue      # strings inside a nested call are
                                  # someone else's arguments
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str):
                    if node.value not in vocab:
                        findings.append(Finding(
                            "SHARD002", mod.path, node.lineno, qual,
                            "PartitionSpec axis %r is absent from "
                            "every mesh this module constructs "
                            "(vocabulary: %s) — the spec silently "
                            "misplaces" % (node.value, sorted(vocab))))
                    continue
                stack.extend(ast.iter_child_nodes(node))

    # -- SHARD003 -----------------------------------------------------
    def _check_hot_materialize(self, mod, qual, fn, progs, findings):
        if not progs:
            return

        def is_prog_call(node) -> bool:
            return isinstance(node, ast.Call) \
                and _track(node.func) in progs

        tainted: Set[str] = set()
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign) and sub.targets \
                    and is_prog_call(sub.value):
                for tgt in _flat_targets(sub.targets):
                    name = _track(tgt)
                    if name:
                        tainted.add(name)

        def reads_result(expr) -> bool:
            for node in ast.walk(expr):
                if is_prog_call(node):
                    return True
                name = _track(node)
                if name is not None and name in tainted:
                    return True
            return False

        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            d = _call_name(sub)
            leaf = d.rsplit(".", 1)[-1] if d else None
            hit = None
            if d in ("np.asarray", "numpy.asarray", "np.array",
                     "numpy.array", "jax.device_get", "device_get") \
                    and sub.args and reads_result(sub.args[0]):
                hit = d + "(...)"
            elif leaf in ("item", "__array__") and not sub.args \
                    and isinstance(sub.func, ast.Attribute) \
                    and reads_result(sub.func.value):
                hit = ".%s()" % leaf
            if hit:
                findings.append(Finding(
                    "SHARD003", mod.path, sub.lineno, qual,
                    "%s materializes a mesh-program result in a hot "
                    "path — on a sharded output this is a hidden "
                    "all-gather plus a host copy" % hit))

    # -- SHARD004 -----------------------------------------------------
    def _check_shard_map(self, mod, findings):
        wrapped: Set[str] = set()
        lambdas: List[ast.Lambda] = []
        for sub in ast.walk(mod.tree):
            if not isinstance(sub, ast.Call):
                continue
            d = _call_name(sub)
            leaf = d.rsplit(".", 1)[-1] if d else None
            if leaf not in ("shard_map", "pjit") or not sub.args:
                continue
            fn_arg = sub.args[0]
            if isinstance(fn_arg, ast.Name):
                wrapped.add(fn_arg.id)
            elif isinstance(fn_arg, ast.Lambda):
                lambdas.append(fn_arg)
        if not wrapped and not lambdas:
            return

        def flag_body(qual, fn, params):
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Call):
                    d = _call_name(sub)
                    leaf = d.rsplit(".", 1)[-1] if d else None
                    if leaf in SHARD_CALLBACK_LEAVES:
                        findings.append(Finding(
                            "SHARD004", mod.path, sub.lineno, qual,
                            "host callback %s(...) inside a shard_map/"
                            "pjit-wrapped function — every shard "
                            "round-trips the host per call" % (d,)))
                if isinstance(sub, (ast.If, ast.While)):
                    reads = {n for n in (
                        _track(x) for x in ast.walk(sub.test)
                        if isinstance(getattr(x, "ctx", None),
                                      ast.Load)) if n}
                    hit = sorted(reads & params)
                    if hit:
                        findings.append(Finding(
                            "SHARD004", mod.path, sub.lineno, qual,
                            "Python branch on traced parameter %s "
                            "inside a shard_map/pjit-wrapped function "
                            "— a TracerBoolConversionError at run "
                            "time; use lax.cond/where"
                            % ", ".join(map(repr, hit))))

        def visit(node, stack):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    if child.name in wrapped:
                        params = {a.arg for a in child.args.args
                                  if a.arg != "self"}
                        flag_body(".".join(stack + [child.name]),
                                  child, params)
                    visit(child, stack + [child.name])
                elif isinstance(child, ast.ClassDef):
                    visit(child, stack + [child.name])
                else:
                    visit(child, stack)

        visit(mod.tree, [])
        for lam in lambdas:
            params = {a.arg for a in lam.args.args}
            flag_body("<lambda>", lam, params)

    # -- SHARD005 -----------------------------------------------------
    def _device_put_call(self, mod, qual, call, findings):
        d = _call_name(call)
        if d is None or d.rsplit(".", 1)[-1] != "device_put":
            return
        if len(call.args) >= 2 or call.keywords:
            return        # explicit placement (or device=/src= kw)
        findings.append(Finding(
            "SHARD005", mod.path, call.lineno, qual,
            "device_put without a sharding in a mesh-aware module — "
            "the array lands on the default device and implicitly "
            "replicates/reshards on first sharded use"))


# ----------------------------------------------------------------------
# OBS

class ObsChecker(Checker):
    name = "OBS"

    METRIC_METHODS = {"counter", "gauge", "histogram"}

    # the closed cxxnet_attrib_* series set (obs/attrib.py
    # bind_registry): the taxonomy is a partition, so a series under
    # the prefix that is not one of these is a category the ledger
    # does not account for (OBS005)
    ATTRIB_SERIES = {
        "cxxnet_attrib_events_total",
        "cxxnet_attrib_slot_tokens_total",
        "cxxnet_attrib_goodput_tokens_total",
        "cxxnet_attrib_waste_tokens_total",
        "cxxnet_attrib_kv_pages_total",
        "cxxnet_attrib_goodput_frac",
        "cxxnet_attrib_waste_frac",
    }

    # the closed cxxnet_profile_* series set (obs/profile.py
    # bind_registry): same partition discipline as the attrib family —
    # an unlisted series under the prefix is accounting the profiler
    # does not define (OBS007)
    PROFILE_SERIES = {
        "cxxnet_profile_events_total",
        "cxxnet_profile_wall_ms_total",
        "cxxnet_profile_flops_total",
        "cxxnet_profile_uncosted_events_total",
        "cxxnet_profile_mfu",
        "cxxnet_profile_peak_flops",
    }

    def check(self, mod: Module) -> List[Finding]:
        if mod.path.endswith("obs/trace.py"):
            return []   # the tracer's own definitions
        findings: List[Finding] = []
        managed: Set[int] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.With):
                for item in node.items:
                    managed.add(id(item.context_expr))

        obs_mod = "obs/" in mod.path

        def visit(node, stack):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.ClassDef, ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    if obs_mod and isinstance(
                            child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)) \
                            and SyncChecker._is_hot(child):
                        self._check_obs_hot(
                            mod, ".".join(stack + [child.name]),
                            child, findings)
                    visit(child, stack + [child.name])
                    continue
                self._check_node(mod, child, stack, managed, findings)
                visit(child, stack)

        visit(mod.tree, [])
        return findings

    # -- OBS006 -------------------------------------------------------
    def _check_obs_hot(self, mod, qual, fn, findings) -> None:
        """Accounting on the dispatch path appends ONE plain tuple:
        no dict building, no string rendering, no non-tuple appends.
        Scoped to ``obs/`` modules' ``@hot_path`` functions — serving
        hot paths pass dict literals as trace-span args by design."""
        def flag(node, what):
            findings.append(Finding(
                "OBS006", mod.path, node.lineno, qual,
                "%s inside @hot_path obs accounting — the dispatch "
                "path appends one plain tuple; rendering belongs at "
                "scrape time" % what))
        for sub in ast.walk(fn):
            if isinstance(sub, (ast.Dict, ast.DictComp)):
                flag(sub, "dict built")
            elif isinstance(sub, ast.JoinedStr):
                flag(sub, "f-string rendered")
            elif isinstance(sub, ast.BinOp) \
                    and isinstance(sub.op, ast.Mod) \
                    and isinstance(sub.left, ast.Constant) \
                    and isinstance(sub.left.value, str):
                flag(sub, "%-format rendered")
            elif isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute):
                if sub.func.attr == "format" \
                        and isinstance(sub.func.value, ast.Constant) \
                        and isinstance(sub.func.value.value, str):
                    flag(sub, ".format rendered")
                elif sub.func.attr == "append" and sub.args \
                        and not isinstance(sub.args[0], ast.Tuple):
                    flag(sub, "non-tuple append")

    def _check_node(self, mod, node, stack, managed, findings) -> None:
        qual = ".".join(stack) if stack else "<module>"
        if not isinstance(node, ast.Call):
            return
        d = _call_name(node)
        leaf = d.rsplit(".", 1)[-1] if d else None
        if leaf == "span" and isinstance(node.func, ast.Attribute):
            if id(node) not in managed:
                findings.append(Finding(
                    "OBS001", mod.path, node.lineno, qual,
                    "span(...) not with-managed — an unmanaged span "
                    "never records its exit"))
            return
        if leaf in self.METRIC_METHODS \
                and isinstance(node.func, ast.Attribute) and node.args:
            name_arg = node.args[0]
            if isinstance(name_arg, ast.Constant) \
                    and isinstance(name_arg.value, str):
                name = name_arg.value
                if not METRIC_NAME_RE.match(name):
                    findings.append(Finding(
                        "OBS002", mod.path, node.lineno, qual,
                        "metric name %r breaks the cxxnet_[a-z0-9_]+ "
                        "convention" % name))
                elif leaf == "counter" and not name.endswith("_total"):
                    findings.append(Finding(
                        "OBS003", mod.path, node.lineno, qual,
                        "counter %r must end in _total" % name))
                elif name.startswith("cxxnet_attrib_") \
                        and name not in self.ATTRIB_SERIES:
                    findings.append(Finding(
                        "OBS005", mod.path, node.lineno, qual,
                        "metric %r outside the closed cxxnet_attrib_* "
                        "series set — the waste taxonomy is a "
                        "partition; add the series to obs/attrib.py "
                        "(and this set) or rename it" % name))
                elif name.startswith("cxxnet_profile_") \
                        and name not in self.PROFILE_SERIES:
                    findings.append(Finding(
                        "OBS007", mod.path, node.lineno, qual,
                        "metric %r outside the closed cxxnet_profile_* "
                        "series set — the profiler's accounting is a "
                        "partition; add the series to obs/profile.py "
                        "(and this set) or rename it" % name))
            labels = None
            if len(node.args) >= 3:
                labels = node.args[2]
            for kw in node.keywords:
                if kw.arg == "labelnames":
                    labels = kw.value
            if isinstance(labels, (ast.Tuple, ast.List)) \
                    and len(labels.elts) > MAX_LABELS:
                findings.append(Finding(
                    "OBS004", mod.path, node.lineno, qual,
                    "%d labels on one metric (max %d — cardinality "
                    "is a product)" % (len(labels.elts), MAX_LABELS)))


# ----------------------------------------------------------------------

def all_checkers(extra_hot: Sequence[str] = (),
                 extra_donating=DEFAULT_EXTRA_DONATING
                 ) -> List[Checker]:
    return [ConcChecker(), SyncChecker(extra_hot),
            JitChecker(extra_hot, extra_donating),
            ShardChecker(extra_hot), ObsChecker()]


def check_source(source: str, path: str = "<snippet>.py",
                 extra_hot: Sequence[str] = (),
                 extra_donating=DEFAULT_EXTRA_DONATING
                 ) -> List[Finding]:
    """Lint one source string (the fixture-test entry point)."""
    mod = Module(path, source)
    out: List[Finding] = []
    for c in all_checkers(extra_hot, extra_donating):
        out.extend(c.check(mod))
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))


def iter_py_files(root: str,
                  subdirs: Sequence[str] = ("cxxnet_tpu", "tools",
                                            "tests")
                  ) -> List[str]:
    """Repo-relative paths of the tree the gate lints. ``tests/`` is
    scanned too (r10): conftest + fixture helpers ship real seams
    (locks, engines) and the test modules themselves must not rot —
    sanctioned test-only constructs carry waivers like everything
    else."""
    out: List[str] = []
    for sub in subdirs:
        base = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.relpath(
                        os.path.join(dirpath, fn), root))
    return sorted(p.replace(os.sep, "/") for p in out)


def check_tree(root: str, paths: Optional[Sequence[str]] = None,
               extra_hot: Sequence[str] = (),
               extra_donating=DEFAULT_EXTRA_DONATING
               ) -> List[Finding]:
    """Lint every file (repo-relative ``paths``, default the standard
    tree) under ``root``; unparseable files become a PARSE finding
    rather than an exception."""
    findings: List[Finding] = []
    checkers = all_checkers(extra_hot, extra_donating)
    for rel in (paths if paths is not None else iter_py_files(root)):
        full = os.path.join(root, rel)
        try:
            with open(full, "r", encoding="utf-8") as f:
                mod = Module(rel, f.read())
        except (OSError, SyntaxError) as e:
            findings.append(Finding("PARSE", rel, 0, "<module>",
                                    "cannot lint: %s" % e))
            continue
        for c in checkers:
            findings.extend(c.check(mod))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))
