"""Open-loop workload generator: replay recorded traffic traces
against a serving engine, router, or live HTTP server.

Every serving claim so far was measured under closed-loop steady
uniform load — each client waits for its answer before sending the
next request, so a slow server conveniently slows its own offered
load. Production traffic does not do that. This module drives the
**open-loop** protocol: requests fire at their scheduled instants
whatever the server is doing, so queueing delay compounds exactly as
it would for real users, and p99/SLO-attainment under bursts is an
honest number (the coordinated-omission trap closed-loop benches fall
into).

**Trace format** — one JSON object per line (JSONL), replayable and
recordable:

    {"t": 0.0125,            # seconds since trace start (arrival)
     "kind": "predict",      # or "generate"
     "rows": 1,              # request batch rows / prompt count
     "priority": "normal",   # high | normal | batch (router classes)
     "timeout_ms": 250.0,    # per-request deadline (optional)
     "slow_ms": 0,           # slow-client stall (optional, see below)
     "id": "..."}            # optional provenance (e.g. request_id)

``serve/server.py``'s access log is itself a recorder:
``trace_from_access_log`` turns the structured access-log records of a
real serving run into this format (arrival offsets from the first
record; rows default to 1 — the log does not carry body sizes), so
yesterday's production traffic is today's regression scenario.

**Scenario catalog** (``make_scenario``) — synthesized traces for the
shapes production traffic actually takes; all deterministic in
``seed``:

* ``steady``          — uniform arrivals (the old bench, for contrast)
* ``bursty``          — on/off arrivals: bursts at several times the
                        mean rate, then silence (queue drain test)
* ``mixed_priority``  — 1-row latency-sensitive ``high`` traffic
                        interleaved with multi-row ``batch`` bulk
                        (shedding must protect the former)
* ``mixed_kinds``     — predict + generate in one stream (two engines
                        in one process; decoder dispatches are slow
                        and lumpy next to forwards)
* ``slow_client``     — a fraction of clients stall mid-request
                        (``slow_ms``): over HTTP the body dribbles in
                        two halves (pins a handler thread), in-process
                        the answer is collected late (holds the
                        response buffer)
* ``mixed_prompt_len``— all-generate streaming traffic interleaving
                        short and long prompts (``prompt_len`` per
                        entry, ``stream`` set) AND short and long
                        completions (``max_new`` per entry) — the
                        continuous-batching yardstick: a fixed-shape
                        decoder stalls short prompts behind long
                        ones' prefill+decode program and burns its
                        full exported max_new on requests that asked
                        for a few tokens, an iteration-level
                        scheduler must not (TTFT and goodput tell)
* ``shared_prefix``   — all-generate streaming traffic where a
                        ``template_share`` fraction of requests
                        follow one of ``n_templates`` long prompt
                        templates (same leading ``template_len``
                        tokens, per-user suffixes), the rest carry
                        genuinely unique prompts — the prefix-cache
                        yardstick (serve/prefixcache.py): with the
                        cache on, template requests skip straight to
                        incremental tail prefill; TTFT, the
                        prefill-dispatch count and the hit rate tell

Entries may carry ``template`` (an integer template id) +
``template_len``: the target then synthesizes the prompt as that
template's deterministic leading tokens plus a per-request suffix, so
every replay of a catalog entry reproduces the same byte-identical
prefix-sharing structure. Unique entries (``uniq``) mix the request
index into the LEADING tokens so no two requests ever share a full
kv_block page by accident.

Generate entries may carry ``prompt_len`` (tokens; clamped to the
target artifact), ``max_new`` (per-request cap, continuous engines
only) and ``stream`` (consume per-token events; TTFT/TPOT are then
honest first-token numbers instead of completion latency). ``score``
reports ``ttft_p50/p99_ms``, ``tpot_p50_ms``, ``tokens_out`` and
``tok_per_sec`` whenever the results carry them.

Replay (:class:`LoadGen`) schedules arrivals on one pacer thread and
hands each request to a worker pool; ``score()`` turns the outcomes
into the scored fields — p50/p99 latency, SLO attainment
(answered requests inside ``slo_ms``), shed/timeout/error counts, and
the max pacer lag (a nonzero lag means the generator itself fell
behind and the numbers understate the burst).
"""

from __future__ import annotations

import json
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Union

from ..obs import trace as _trace

SCENARIOS = ("steady", "bursty", "mixed_priority", "mixed_kinds",
             "slow_client", "mixed_prompt_len", "shared_prefix")


# ----------------------------------------------------------------------
# trace format

def write_trace(path: str, entries: Sequence[dict]) -> str:
    """Write entries as JSONL, sorted by arrival time."""
    with open(path, "w") as f:
        for e in sorted(entries, key=lambda e: e["t"]):
            f.write(json.dumps(e) + "\n")
    return path


def read_trace(path: str) -> List[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            e = json.loads(line)
            if "t" not in e:
                raise ValueError("trace entry missing 't': %r" % line)
            out.append(e)
    out.sort(key=lambda e: e["t"])
    return out


def trace_from_access_log(records: Sequence[Union[dict, str]]
                          ) -> List[dict]:
    """Convert serve/server.py access-log records (dicts from an
    ``access_log=callable`` sink, or the ``access ...`` JSON lines it
    writes to stderr) into a replayable trace. Only /predict and
    /generate POSTs become entries. The log stamps ``ts`` at response
    COMPLETION, so each request's wall time (``ms``) is subtracted to
    recover its arrival instant — without that a slow request would
    replay later (and possibly reordered) relative to fast requests
    that really arrived after it. Offsets are measured from the first
    recovered arrival. Rows default to 1 — the log records status and
    wall time, not body sizes — so a replay reproduces the arrival
    process and the row mix approximately."""
    entries: List[dict] = []
    for rec in records:
        if isinstance(rec, str):
            line = rec.strip()
            if line.startswith("access "):
                line = line[len("access "):]
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
        path = rec.get("path", "")
        if path not in ("/predict", "/generate"):
            continue
        arrival = float(rec.get("ts", 0.0)) \
            - float(rec.get("ms", 0.0)) / 1000.0
        entries.append({
            "t": arrival,
            "kind": "generate" if path == "/generate" else "predict",
            "rows": int(rec.get("rows", 1)),
            "id": rec.get("request_id"),
        })
    if entries:
        t0 = min(e["t"] for e in entries)
        for e in entries:
            e["t"] = round(e["t"] - t0, 6)
    entries.sort(key=lambda e: e["t"])
    return entries


# ----------------------------------------------------------------------
# scenario catalog

def _lcg(seed: int):
    """Tiny deterministic PRNG (no global random state touched)."""
    state = (seed * 2654435761 + 1) & 0xffffffff

    def rnd() -> float:
        nonlocal state
        state = (state * 1664525 + 1013904223) & 0xffffffff
        return state / 2 ** 32
    return rnd


def make_scenario(name: str, duration_s: float = 4.0,
                  rps: float = 100.0, seed: int = 0,
                  timeout_ms: Optional[float] = None,
                  slow_ms: float = 120.0,
                  burst_period_s: float = 1.0,
                  burst_duty: float = 0.3,
                  short_prompt_len: int = 4,
                  long_prompt_len: int = 48,
                  short_max_new: int = 4,
                  n_templates: int = 4,
                  template_share: float = 0.625,
                  template_len: int = 144,
                  suffix_len: int = 16) -> List[dict]:
    """Synthesize one catalog scenario as a trace (see module doc).
    ``rps`` is the MEAN arrival rate; bursty packs the same volume
    into ``burst_duty`` of each ``burst_period_s``;
    ``short_prompt_len`` / ``long_prompt_len`` shape the
    mixed_prompt_len interleave (2 short : 1 long), whose short
    entries also ask for only ``short_max_new`` completion tokens
    (long entries take the artifact's full max_new).
    ``n_templates`` / ``template_share`` / ``template_len`` /
    ``suffix_len`` shape shared_prefix: a ``template_share`` fraction
    of entries extend one of ``n_templates`` shared
    ``template_len``-token prompt templates with a ``suffix_len``
    per-user suffix (asking for ``short_max_new`` tokens — the
    template-heavy chat shape); the rest are unique
    ``short_prompt_len`` prompts. The mix is deterministic in
    ``seed``, so a catalog entry replays with byte-identical sharing
    structure."""
    if name not in SCENARIOS:
        raise ValueError("unknown scenario %r (know %s)"
                         % (name, ", ".join(SCENARIOS)))
    rnd = _lcg(seed + 1)
    n = max(int(duration_s * rps), 1)
    entries: List[dict] = []
    for i in range(n):
        # uniform-jittered arrivals: mean spacing 1/rps with +-40%
        # jitter (deterministic; Poisson-ish without heavy tails)
        t = (i + 0.8 * (rnd() - 0.5)) / rps
        t = min(max(t, 0.0), duration_s)
        e = {"t": t, "kind": "predict", "rows": 1,
             "priority": "normal"}
        if timeout_ms:
            e["timeout_ms"] = float(timeout_ms)
        if name == "bursty":
            # map the uniform arrival into the ON fraction of its
            # period: same request count, several-x peak rate
            phase = t % burst_period_s
            e["t"] = (t - phase) + phase * burst_duty
        elif name == "mixed_priority":
            if i % 3 == 2:
                e.update(rows=8, priority="batch")
            else:
                e.update(rows=1, priority="high")
        elif name == "mixed_kinds":
            if i % 3 == 2:
                e["kind"] = "generate"
        elif name == "slow_client":
            if i % 4 == 0:
                e["slow_ms"] = float(slow_ms)
        elif name == "mixed_prompt_len":
            e["kind"] = "generate"
            e["stream"] = 1
            if i % 3 == 2:
                e["prompt_len"] = int(long_prompt_len)
            else:
                e["prompt_len"] = int(short_prompt_len)
                e["max_new"] = int(short_max_new)
        elif name == "shared_prefix":
            e["kind"] = "generate"
            e["stream"] = 1
            e["max_new"] = int(short_max_new)
            if rnd() < float(template_share):
                e["template"] = i % int(n_templates)
                e["template_len"] = int(template_len)
                e["prompt_len"] = int(template_len) + int(suffix_len)
            else:
                e["uniq"] = 1
                e["prompt_len"] = int(short_prompt_len)
        entries.append(e)
    entries.sort(key=lambda e: e["t"])
    return entries


# ----------------------------------------------------------------------
# targets

class EngineTarget:
    """Submit entries to in-process engines (ServingEngine or Router —
    anything with ``submit`` / ``submit_tokens``). ``forward`` serves
    "predict" entries over ``data`` (a row pool cycled per request);
    ``decode`` serves "generate" entries over synthesized short
    prompts. ``slow_ms`` is modelled as collecting the answer late —
    the request still completes, its response buffer is just held."""

    def __init__(self, forward=None, decode=None, data=None,
                 prompt_len: int = 4) -> None:
        if forward is None and decode is None:
            raise ValueError("need a forward and/or decode target")
        self.forward = forward
        self.decode = decode
        self.data = data
        self.prompt_len = int(prompt_len)

    def _prompts(self, rows: int, i: int, entry: dict):
        import numpy as np
        c = self.decode.callee
        toks = np.zeros((rows, c.seq_len), np.int32)
        plen = entry.get("prompt_len")
        L = min(int(plen or self.prompt_len), c.max_prompt_len)
        tid = entry.get("template")
        for r in range(rows):
            if tid is not None:
                # shared_prefix: the template's leading tokens are a
                # pure function of its id (byte-identical across
                # requests and replays), the suffix varies per request
                TL = min(int(entry.get("template_len", L)), L)
                toks[r, :TL] = [(int(tid) * 3 + 1 + j * j) % 7 + 1
                                for j in range(TL)]
                toks[r, TL:L] = [(i + r + j) % 7 + 1
                                 for j in range(L - TL)]
            elif entry.get("uniq"):
                # genuinely unique prompts: the request index's base-7
                # digits lead the prompt, so no two requests share a
                # full kv_block page by accident (the legacy pattern
                # below cycles every 7 requests — a dishonest "hit")
                toks[r, :L] = [((i + r) // 7 ** j + j) % 7 + 1
                               for j in range(L)]
            else:
                toks[r, :L] = [(i + r + j) % 7 + 1 for j in range(L)]
        return toks, [L] * rows

    def _generate(self, entry: dict, i: int, rows: int, kw: dict):
        """One generate entry; returns the result-record fields.
        Streaming entries consume the request's event stream so
        ttft_ms is the honest first-token time; non-streaming targets
        (the fixed-shape decoder) only have an answer at completion,
        so their ttft EQUALS their latency — which is exactly the
        comparison the continuous-batching bench draws."""
        toks, lens = self._prompts(rows, i, entry)
        streamable = getattr(self.decode, "supports_stream", False)
        if entry.get("max_new") is not None and streamable:
            kw["max_new"] = int(entry["max_new"])
        t0 = time.perf_counter()
        ttft = None
        ntok = 0
        if entry.get("stream") and streamable:
            req = self.decode.submit_tokens(toks, lens, stream=True,
                                            **kw)
            for ev in req.events(timeout=120.0):
                if "error" in ev:
                    break            # result() below raises it
                if "done" in ev:
                    break
                if ttft is None:
                    ttft = (time.perf_counter() - t0) * 1000.0
                ntok += len(ev.get("tokens") or ())
            req.result(5.0)
        else:
            req = self.decode.submit_tokens(toks, lens, **kw)
            slow = float(entry.get("slow_ms", 0) or 0)
            if slow > 0:
                time.sleep(slow / 1000.0)
            req.result(120.0)
            ttft = (time.perf_counter() - t0) * 1000.0
            # GOODPUT: count the tokens the client asked for. A
            # fixed-shape decoder that cannot honor a per-request
            # max_new still burns its full exported loop — that waste
            # must not inflate its tokens/s
            want = entry.get("max_new")
            art = int(getattr(self.decode.callee, "max_new", 0))
            ntok = rows * (min(int(want), art) if want else art)
        total = (time.perf_counter() - t0) * 1000.0
        rec = {"request_id": getattr(req, "id", None),
               "tokens_out": ntok}
        if ttft is not None:
            rec["ttft_ms"] = round(ttft, 3)
            if ntok > 1:
                rec["tpot_ms"] = round((total - ttft) / (ntok - 1), 3)
        return rec

    def __call__(self, entry: dict, i: int):
        kind = entry.get("kind", "predict")
        rows = int(entry.get("rows", 1))
        kw = {}
        if entry.get("timeout_ms") is not None:
            kw["timeout_ms"] = float(entry["timeout_ms"])
        if entry.get("priority") is not None:
            kw["priority"] = entry["priority"]
        if kind == "generate":
            if self.decode is None:
                raise RuntimeError("scenario has generate entries but "
                                   "no decode target")
            return self._generate(entry, i, rows, kw)
        if self.forward is None:
            raise RuntimeError("scenario has predict entries but "
                               "no forward target")
        n = len(self.data)
        lo = i % n
        d = self.data[lo:lo + rows]
        if len(d) < rows:            # wrap the pool
            import numpy as np
            d = np.concatenate([d, self.data[:rows - len(d)]])
        req = self.forward.submit(d, **kw)
        slow = float(entry.get("slow_ms", 0) or 0)
        if slow > 0:
            time.sleep(slow / 1000.0)
        req.result(120.0)
        return getattr(req, "id", None)


class HTTPTarget:
    """POST entries to a live serve/server.py endpoint. One keep-alive
    connection per worker thread (thread-local). ``slow_ms`` entries
    upload their body in two halves with a stall between — a real
    slow client pinning a handler thread mid-read."""

    def __init__(self, url: str, data=None, prompt_len: int = 4,
                 seq_len: int = 16, timeout_s: float = 120.0) -> None:
        from urllib.parse import urlsplit
        p = urlsplit(url)
        self.host, self.port = p.hostname, p.port
        self.data = data
        self.prompt_len = int(prompt_len)
        self.seq_len = int(seq_len)
        self.timeout_s = float(timeout_s)
        self._local = threading.local()

    def _conn(self):
        import http.client
        c = getattr(self._local, "conn", None)
        if c is None:
            c = http.client.HTTPConnection(self.host, self.port,
                                           timeout=self.timeout_s)
            self._local.conn = c
        return c

    def _body(self, entry: dict, i: int):
        kind = entry.get("kind", "predict")
        rows = int(entry.get("rows", 1))
        if kind == "generate":
            L = int(entry.get("prompt_len") or self.prompt_len)
            tid = entry.get("template")
            if tid is not None:
                TL = min(int(entry.get("template_len", L)), L)
                tmpl = [(int(tid) * 3 + 1 + j * j) % 7 + 1
                        for j in range(TL)]
                prompts = [tmpl + [(i + r + j) % 7 + 1
                                   for j in range(L - TL)]
                           for r in range(rows)]
            elif entry.get("uniq"):
                prompts = [[((i + r) // 7 ** j + j) % 7 + 1
                            for j in range(L)] for r in range(rows)]
            else:
                prompts = [[(i + r + j) % 7 + 1 for j in range(L)]
                           for r in range(rows)]
            obj = {"prompts": prompts}
            if entry.get("stream"):
                obj["stream"] = True
            if entry.get("max_new") is not None:
                obj["max_new"] = int(entry["max_new"])
            path = "/generate"
        else:
            n = len(self.data)
            lo = i % n
            d = list(self.data[lo:lo + rows])
            while len(d) < rows:
                d.append(self.data[(lo + len(d)) % n])
            obj = {"data": [x.tolist() for x in d]}
            path = "/predict"
        if entry.get("timeout_ms") is not None:
            obj["timeout_ms"] = float(entry["timeout_ms"])
        if entry.get("priority") is not None:
            obj["priority"] = entry["priority"]
        return path, json.dumps(obj).encode()

    def _read_stream(self, resp, t0: float):
        """Consume a chunked SSE /generate response; ttft_ms is the
        client-observed arrival of the FIRST token event."""
        ttft = None
        ntok = 0
        rid = None
        while True:
            line = resp.readline()
            if not line:
                raise RuntimeError("SSE stream ended without a "
                                   "terminal event")
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[6:])
            if "error" in ev:
                resp.read()
                raise RuntimeError("stream error: %s" % ev["error"])
            if "done" in ev:
                rid = ev.get("request_id")
                resp.read()       # drain to the terminal chunk
                break
            if ttft is None:
                ttft = (time.perf_counter() - t0) * 1000.0
            ntok += len(ev.get("tokens") or ())
        total = (time.perf_counter() - t0) * 1000.0
        rec = {"request_id": rid, "tokens_out": ntok}
        if ttft is not None:
            rec["ttft_ms"] = round(ttft, 3)
            if ntok > 1:
                rec["tpot_ms"] = round((total - ttft) / (ntok - 1), 3)
        return rec

    def __call__(self, entry: dict, i: int):
        path, body = self._body(entry, i)
        slow = float(entry.get("slow_ms", 0) or 0)
        conn = self._conn()
        t0 = time.perf_counter()
        try:
            if slow > 0 and len(body) > 2:
                half = len(body) // 2
                conn.putrequest("POST", path)
                conn.putheader("Content-Type", "application/json")
                conn.putheader("Content-Length", str(len(body)))
                conn.endheaders()
                conn.send(body[:half])
                time.sleep(slow / 1000.0)   # the slow-client stall
                conn.send(body[half:])
            else:
                conn.request("POST", path, body,
                             {"Content-Type": "application/json"})
            resp = conn.getresponse()
            ctype = resp.getheader("Content-Type", "")
            if resp.status == 200 and ctype.startswith(
                    "text/event-stream"):
                return self._read_stream(resp, t0)
            payload = resp.read()
            st = resp.status
        except Exception:
            try:
                conn.close()
            finally:
                self._local.conn = None
            raise
        if st == 200:
            try:
                return json.loads(payload).get("request_id")
            except ValueError:
                return None
        if st == 429:
            raise _HTTPShed(st)
        if st == 503:
            raise _HTTPUnavailable(st)
        if st == 504:
            raise TimeoutError("HTTP 504")
        raise RuntimeError("HTTP %d: %s" % (st, payload[:200]))


class _HTTPShed(RuntimeError):
    pass


class _HTTPUnavailable(RuntimeError):
    pass


# ----------------------------------------------------------------------
# replay + scoring

def _classify(exc: BaseException) -> str:
    from .engine import DrainError, QueueFullError, RequestExpired
    try:
        from .router import NoReplicaError, ShedError
    except Exception:                    # router never imported
        NoReplicaError = ShedError = ()
    if isinstance(exc, (QueueFullError, ShedError, _HTTPShed)):
        return "shed"
    if isinstance(exc, (DrainError, NoReplicaError, _HTTPUnavailable)):
        return "unavailable"
    if isinstance(exc, (RequestExpired, TimeoutError)):
        return "timeout"
    return "error"


class LoadGen:
    """Replay a trace open-loop: a pacer thread fires each entry at
    ``t0 + entry.t`` into a worker pool; workers run the target and
    record the outcome. The pacer never waits on completions — that is
    the open loop. ``workers`` bounds concurrency; when all workers
    are busy an arrival queues in the pool and its recorded ``lag_ms``
    says by how much the generator itself fell behind."""

    def __init__(self, entries: Sequence[dict],
                 target: Callable[[dict, int], Optional[str]],
                 workers: int = 32) -> None:
        self.entries = sorted(entries, key=lambda e: e["t"])
        self.target = target
        self.workers = int(workers)
        self.results: List[dict] = []
        self.wall_s = 0.0
        self._rlock = threading.Lock()

    def _fire(self, entry: dict, i: int, sched_t: float,
              t0: float) -> None:
        ts = time.perf_counter()
        rec = {"t": sched_t, "kind": entry.get("kind", "predict"),
               "rows": int(entry.get("rows", 1)),
               "priority": entry.get("priority"),
               "lag_ms": round((ts - t0 - sched_t) * 1000.0, 3)}
        try:
            with _trace.span("loadgen.request", "loadgen",
                             {"kind": rec["kind"], "i": i}):
                rid = self.target(entry, i)
            rec["status"] = "ok"
            if isinstance(rid, dict):   # streaming targets return the
                rec.update(rid)         # ttft/tokens fields directly
            else:
                rec["request_id"] = rid
        except Exception as e:
            rec["status"] = _classify(e)
            rec["error"] = "%s: %s" % (type(e).__name__, e)
        rec["latency_ms"] = round(
            (time.perf_counter() - ts) * 1000.0, 3)
        with self._rlock:
            self.results.append(rec)

    def run(self) -> List[dict]:
        from concurrent.futures import ThreadPoolExecutor
        self.results = []
        futures = []
        with ThreadPoolExecutor(self.workers,
                                thread_name_prefix="loadgen") as ex:
            t0 = time.perf_counter()
            for i, e in enumerate(self.entries):
                delay = t0 + float(e["t"]) - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                futures.append(ex.submit(self._fire, e, i,
                                         float(e["t"]), t0))
            for f in futures:
                f.result()
            # first fire to last completion: normalizing throughput by
            # the TRACE duration would credit the drain tail after the
            # last arrival as free capacity (overload windows would
            # all report tok/s == offered)
            self.wall_s = time.perf_counter() - t0
        return self.results


def score(results: Sequence[dict], slo_ms: float,
          duration_s: Optional[float] = None,
          registry=None) -> Dict:
    """Ledger-row fields for one replay: latency percentiles over
    ANSWERED requests, SLO attainment (answered within ``slo_ms``),
    outcome counts, throughput, and the worst pacer lag.

    ``registry`` (the engine's obs registry) adds the server-side
    prefill economics the prefix-cache bench reads:
    ``prefill_dispatches`` (cxxnet_serve_prefills_total) and
    ``prefix_hit_rate`` (cxxnet_prefix_{hits,misses}_total) — absent
    when the series are (hit rate: when the cache is off)."""
    lats = sorted(r["latency_ms"] for r in results
                  if r["status"] == "ok")
    counts: Dict[str, int] = {}
    for r in results:
        counts[r["status"]] = counts.get(r["status"], 0) + 1
    n = len(lats)

    def pct(p: float) -> Optional[float]:
        if not n:
            return None
        return lats[min(int(p * n), n - 1)]
    if duration_s is None:
        duration_s = max((r["t"] for r in results), default=0.0) or 1.0
    within = sum(1 for v in lats if v <= slo_ms)

    def _series(field):
        return sorted(r[field] for r in results
                      if r["status"] == "ok"
                      and r.get(field) is not None)

    def _pctl(vals, q):
        return round(vals[min(int(q * len(vals)), len(vals) - 1)], 3)
    extra = {}
    ttfts = _series("ttft_ms")
    if ttfts:
        # token-streaming targets: first-token latency percentiles —
        # for a non-streaming decode target ttft equals total latency
        # (the first token only exists at completion), which is the
        # honest number for that path
        extra["ttft_p50_ms"] = _pctl(ttfts, 0.50)
        extra["ttft_p99_ms"] = _pctl(ttfts, 0.99)
    tpots = _series("tpot_ms")
    if tpots:
        extra["tpot_p50_ms"] = _pctl(tpots, 0.50)
    toks = sum(r.get("tokens_out", 0) for r in results
               if r["status"] == "ok")
    if toks:
        extra["tokens_out"] = toks
        extra["tok_per_sec"] = round(toks / duration_s, 1)
    if registry is not None:
        pf = registry.get_value("cxxnet_serve_prefills_total")
        if pf is not None:
            extra["prefill_dispatches"] = int(pf)
        hits = registry.get_value("cxxnet_prefix_hits_total")
        miss = registry.get_value("cxxnet_prefix_misses_total")
        if hits is not None and miss is not None and hits + miss > 0:
            extra["prefix_hit_rate"] = round(hits / (hits + miss), 4)
    return dict({
        "requests": len(results),
        "ok": n,
        "shed": counts.get("shed", 0),
        "unavailable": counts.get("unavailable", 0),
        "timeouts": counts.get("timeout", 0),
        "errors": counts.get("error", 0),
        "p50_ms": round(pct(0.50), 3) if n else None,
        "p90_ms": round(pct(0.90), 3) if n else None,
        "p99_ms": round(pct(0.99), 3) if n else None,
        "slo_ms": float(slo_ms),
        "slo_attainment": round(within / n, 4) if n else 0.0,
        "ok_per_sec": round(n / duration_s, 1),
        "max_lag_ms": round(max((r["lag_ms"] for r in results),
                                default=0.0), 3),
    }, **extra)
