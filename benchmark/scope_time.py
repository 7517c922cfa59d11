"""Device time by the PROGRAM's own names: what the readers share that
split the traced window's operations by the part of the model that made
them and by the pass they ran in.

A device trace names an operation by its HLO instruction alone
(``%fusion.123 = ...``: ``trace_reduce._event``); the program says what
``fusion.123`` is. It opens a ``jax.named_scope`` at its seams (a layer's
type, the words of ``obs.trace.PARTS`` inside the transformer block,
``opt`` around the update), JAX writes them into every instruction's
``op_name``, and ``obs.trace.device_scopes()`` gives the table from
instruction to ``op_name`` of the train step that ran (a lowering and a
compilation, from the persistent cache after a checkout's first, after
the window, in the driver's own process). ``obs.trace.scope_of`` reads
(part, phase) off one: phase is ``fwd``, ``bwd``, ``replay`` (recomputed
in the backward pass under ``jax.checkpoint``), ``opt`` or ``other``.

The events are those ``trace_reduce.device_ops`` gave the driver
(``readings["trace"]["events"]``), the operations that only contain
others left out as ``trace_reduce.top_ops`` leaves them. Every share is
of their summed time, so none can pass 100.

Every function returns None, never 0, where there is nothing to read: a
program without ``device_scopes`` (a parent commit) or with no step
noted, a run without a trace, and a CPU, as ``program_spans`` has it.
Where there is a table, a share of nothing is a true 0 (no routed layer
in a dense model, nothing replayed): a metric with no ``workloads`` key
is owed in every training cell.
Once a traced run the whole table goes to standard error, part by phase
in milliseconds a step with calls, as the driver's ``benchmark:`` lines.
"""

import sys

import program_spans
import trace_reduce

PROGRAM = "train_step"          # the name the trainer notes its step by
PHASES = ("fwd", "bwd", "replay", "opt", "other")
UNSCOPED = "(unscoped)"         # no table entry, or one without a part

_last = (None, None)            # (the events' identity, their table)


def seconds_by_scope(r):
    """-> ({(part, phase, is a Mosaic call): [seconds, calls]}, summed
    seconds, traced steps) of the traced window, or None. part is
    ``UNSCOPED`` for an operation the table does not hold or whose
    ``op_name`` lies in no scope."""
    global _last
    t = r.get("trace")
    if r.get("kind") != "train" or not t or not t.get("events") \
            or not t.get("steps") or r.get("platform") == "cpu":
        return None
    if _last[0] is t["events"]:
        return _last[1]
    scopes = program_spans._program("device_scopes")
    scope_of = program_spans._program("scope_of")
    table = scopes().get(PROGRAM) if scopes and scope_of else None
    if not table:
        return None
    acc, by_kind, total, scoped = {}, {}, 0.0, {}
    for ev in t["events"]:
        name = ev["name"]
        kind = trace_reduce.op_kind(name)
        if kind in trace_reduce.CONTAINERS:
            continue
        instr = name.split(" = ", 1)[0].strip().lstrip("%")
        if instr not in scoped:     # an instruction runs once a step
            op = table.get(instr)
            scoped[instr] = scope_of(op) if op else (None, "other")
        part, phase = scoped[instr]
        part, secs = part or UNSCOPED, (ev["end"] - ev["start"]) * 1e-9
        for into, key in ((acc, (part, phase, trace_reduce.MOSAIC in name)),
                          (by_kind, (kind, part, phase))):
            row = into.setdefault(key, [0.0, 0])
            row[0] += secs
            row[1] += 1
        total += secs
    if not total:
        return None
    got = (acc, total, t["steps"])
    _last = (t["events"], got)
    _print(got, by_kind)
    return got


def _print(got, by_kind):
    """On standard error, milliseconds a step (calls a step): the
    operations by part (a part's Pallas calls on a row of their own) and
    phase; the row ``fusion`` of the driver's breakdown split the same
    way; what has no part, by kind of operation."""
    acc, total, steps = got

    def table(title, cells):
        """``cells``: {(row, phase): [seconds, calls]}."""
        rows = {}
        for (row, phase), (s, n) in cells.items():
            rows.setdefault(row, {})[phase] = (s, n)
        out = ["%-26s" % title + "".join(
            "%20s" % p for p in PHASES + ("all",))]
        for row, by in sorted(rows.items(), key=lambda kv: -sum(
                s for s, _ in kv[1].values())):
            by["all"] = [sum(x) for x in zip(*by.values())]
            out.append("%-26s" % row + "".join(
                "%20s" % ("%.3f (%.1f)" % (1e3 * by[p][0] / steps,
                                           by[p][1] / steps)
                          if p in by else "") for p in PHASES + ("all",)))
        return out

    lines = ["device time by the program's scopes, ms a step (calls a "
             "step): %d steps, %.3f ms a step in all"
             % (steps, 1e3 * total / steps)]
    lines += table("part", {
        (part + (" (mosaic)" if mosaic else ""), phase): v
        for (part, phase, mosaic), v in acc.items()})
    lines += table("of kind fusion: part", {
        (part, phase): v for (kind, part, phase), v in by_kind.items()
        if kind == "fusion"})
    lines += table(UNSCOPED + ": kind", {
        (kind, phase): v for (kind, part, phase), v in by_kind.items()
        if part == UNSCOPED})
    for text in lines:
        print("benchmark: " + text, file=sys.stderr, flush=True)


def share_pct(r, wanted):
    """Time of the operations for which ``wanted(part, phase, is a
    Mosaic call)`` holds, as a share of the window's summed operation
    time: a true 0 where the table holds the step and no operation is
    such (a part this configuration does not have)."""
    got = seconds_by_scope(r)
    if got is None:
        return None
    acc, total, _ = got
    return 100.0 * sum(s for key, (s, _) in acc.items()
                       if wanted(*key)) / total
