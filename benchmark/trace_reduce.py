"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
kernel time by name pattern, the operations that took most time and the
longest idle gaps, named by what the harness was doing.

Reads the trace with ``jax.profiler.ProfileData`` alone. A device plane
is one whose name starts with ``/device:`` and is not the host's; its
``XLA Ops`` line holds one event per executed HLO operation. A CPU run
has no device plane: there the events that carry an ``hlo_op`` stat
stand in, so that the rehearsal drives this code (its numbers are never
reported as a device's). The harness's own ``TraceAnnotation`` spans
(names starting with ``bench.``) are on the host planes, on the same
clock.
"""

import glob
import os
import re

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
MOSAIC = 'custom_call_target="tpu_custom_call"'     # a Pallas kernel
# operations that only contain others: their time is their children's
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    return ProfileData.from_file(path)


def op_kind(text):
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``fusion``: the
    instruction's name without its number, so that the unrolled layers'
    copies of one operation count together; Pallas kernels are marked."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    kind = re.sub(r"[.\d]+$", "", name) or name
    return kind + " (mosaic)" if MOSAIC in text else kind


def _event(ev):
    """On a TPU an event's name is its whole HLO instruction."""
    return {"name": ev.name, "start": float(ev.start_ns),
            "end": float(ev.start_ns) + float(ev.duration_ns)}


def device_ops(profile):
    """{device plane name: [event, ...]} of executed operations."""
    out = {}
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out[plane.name] = [_event(e) for e in line.events]
    if out:
        return out
    host = []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if any(k == "hlo_op" for k, _ in ev.stats):
                    host.append(_event(ev))
    return {"host-xla": host} if host else {}


def host_spans(profile):
    """The harness's own annotations, sorted by start."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((float(ev.start_ns),
                                float(ev.start_ns) + float(ev.duration_ns),
                                ev.name))
    return sorted(out)


def merged(events):
    """Union of the events' intervals -> sorted [(start, end)]."""
    out = []
    for s, e in sorted((ev["start"], ev["end"]) for ev in events):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_seconds(events, window=None):
    """Seconds in which some operation ran, clipped to ``window``
    ((start_ns, end_ns)) when given."""
    total = 0.0
    for s, e in merged(events):
        if window is not None:
            s, e = max(s, window[0]), min(e, window[1])
        if e > s:
            total += e - s
    return total * 1e-9


def kernel_seconds(events, pattern):
    """Summed durations of the events whose name (the HLO instruction)
    matches ``pattern``; -> (seconds, number of events)."""
    rx = re.compile(pattern)
    hit = [ev for ev in events if rx.search(ev["name"])]
    return sum(ev["end"] - ev["start"] for ev in hit) * 1e-9, len(hit)


def top_ops(events, n=10):
    """[[kind of operation, seconds], ...] by summed time, containers
    left out."""
    acc = {}
    for ev in events:
        kind = op_kind(ev["name"])
        if kind not in CONTAINERS:
            acc[kind] = acc.get(kind, 0.0) + ev["end"] - ev["start"]
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in rows]


def idle_gaps(events, spans, window=None, n=10):
    """The longest gaps between operations, [[what the host did, s]]:
    the harness span that covers the gap's middle, or ``unannotated``."""
    iv = merged(events)
    if window is not None:
        iv = [(window[0], window[0])] + [
            (max(s, window[0]), min(e, window[1])) for s, e in iv
            if e > window[0] and s < window[1]] + [(window[1], window[1])]
    gaps = [(b[0] - a[1], (a[1] + b[0]) / 2.0)
            for a, b in zip(iv, iv[1:]) if b[0] > a[1]]
    out = []
    for length, mid in sorted(gaps, reverse=True)[:n]:
        cover = [name for s, e, name in spans if s <= mid <= e]
        out.append([cover[-1] if cover else "unannotated", length * 1e-9])
    return out


def span_window(spans, name):
    """(start_ns, end_ns) of the first harness span called ``name``."""
    for s, e, n in spans:
        if n == name:
            return s, e
    return None


def reduce(path, window_span=SPAN_PREFIX + "window"):
    """Everything the harness reports from one trace."""
    profile = load(path)
    per_device = device_ops(profile)
    spans = host_spans(profile)
    window = span_window(spans, window_span)
    if window is None and per_device:
        starts = [ev["start"] for evs in per_device.values() for ev in evs]
        ends = [ev["end"] for evs in per_device.values() for ev in evs]
        window = (min(starts), max(ends)) if starts else None
    busy = [busy_seconds(evs, window) for evs in per_device.values()]
    first = next(iter(per_device.values()), [])
    return {
        "devices": sorted(per_device),
        "window_s": (window[1] - window[0]) * 1e-9 if window else 0.0,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "events": first,
        "spans": spans,
        "device_ops": top_ops(first),
        "idle_gaps": idle_gaps(first, spans, window),
    }
