"""Request tracing on the serving stack's virtual microsecond clock.

A ``Tracer`` records *spans* (named intervals with explicit ids and parent
links) and *instants* (point events) against the same virtual clock the
runtime/cluster/freshness layers already schedule on — so a trace is a
faithful picture of the simulated deployment, not of the host's wall
clock. Span taxonomy (the contract obs_report and the tests rely on; see
ROADMAP "Architecture invariants"):

  request           root span per sampled request: [arrival, completion],
                    attrs path/session/k/gen/query.
    cache.trivial / cache.hit_exact / cache.hit_session
                    hit-path child covering the whole request interval,
                    attrs carry the cache-miss/hit reason.
    queue.wait      miss-path child: [arrival, dispatch start].
    engine.service  miss-path child: [dispatch start, batch completion].
                    queue.wait + engine.service == the request's recorded
                    end-to-end latency, EXACTLY (same clock arithmetic),
                    which is how obs_report rebuilds p99 from spans alone.
  batch.dispatch    one span per micro-batch (no request id), attrs
                    size/trigger/jit keys/kernel routes actually taken.
  merge.kway        freshness: per-answer delta merge, attrs
                    n_delta/escalations/seq.
  generation.rebuild / generation.swap_stall
                    freshness: background fold-and-build vs the swap stall.
  admission / replica.death / replica.readmit / generation.swap /
  merge.escalate / delta.apply
                    instants (cluster + freshness decision points).

Zero overhead when disabled: layers hold ``tracer = None`` and every
instrumentation site is behind ``if tracer is not None`` (plus per-request
``want(idx)`` sampling — 1/N of requests carry spans, batch spans fire only
when a sampled request is aboard). The acceptance bench
(``bench_qac_obs``) holds online p99 at 1/16 sampling within 10% of
tracing-off.

Export: ``to_jsonl`` (one record per line, ``type`` = span|instant —
what ``scripts/obs_report.py`` consumes) and ``to_chrome`` (Chrome/
Perfetto trace-event JSON: ph="X" duration events + ph="i" instants,
ts/dur in microseconds — load in chrome://tracing or ui.perfetto.dev).

The virtual clock stays for CPU rehearsal and deterministic tests; what
the served path spends on the real clock is recorded beside it by
``stage`` into a ``StageLog`` (always on, ``RuntimeTelemetry.stages``),
in ``time.perf_counter_ns`` — the clock a caller timing the runtime from
outside reads too. Stage taxonomy:

  runtime.lookup    per request, ``submit`` after the deadline sweep: the
                    cache tiers, to the answer (hit) or the enqueue (miss).
                    Kept as ``(idx, ns)`` in ``StageLog.lookups``.
  queue.wait        per miss: its enqueue to the start of the dispatch
                    that takes it, ``(idx, enqueue_ns, start_ns, id)`` in
                    ``StageLog.waits``.
  runtime.dispatch  per dispatch, the whole call; its children tile it:
    runtime.batch   queue pop and input stacking,
    frontend.route  class routing, bucket padding, the list-pad bound,
    engine.<e>.launch  the jitted call until it returns,
    engine.<e>.sync the blocking readback (``bool(all_done)``,
                    ``np.asarray``),
    runtime.scatter slicing the rows out, the caches, the answers.
                    ``<e>`` is ``single``, ``single_full`` (the exact
                    fallback) or ``multi``.

Every stage record carries the id of the dispatch it belongs to; a miss's
``queue.wait`` names the dispatch that served it. While a profiler session
is active each stage (and each ``runtime.lookup``) also enters a
``jax.profiler.TraceAnnotation`` of its name, so it lands on the trace's
host plane on the device ops' clock; otherwise no annotation is made.
``install_gc_clock`` adds the collector's pauses, ``(generation, t0_ns,
t1_ns)``, process-wide (``gc_pauses()``).

The host index build records its stages on the same clock, process-wide,
as ``(name, t0_ns, t1_ns, attrs)`` (``build_stage``, ``build_stages()``).
Inside ``build_clock`` (``build_qac_index`` opens one) they tile the build:

  build.corpus      ``build_corpus``: tokenising, vocabulary, dedup, the
                    dictionary; attrs ``path`` is ``bulk`` (the whole-array
                    pass) or ``loop`` (the per-query loop).
  build.completions ``Completions.build``.
  build.postings    docids and the inverted index's CSR arrays.
  build.pack        the packed postings and their round-trip check.
  build.rmq         the two range-minimum structures.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import gc
import json
import time

from jax.profiler import TraceAnnotation


class Tracer:
    """Span/instant recorder with 1/N per-request sampling (module
    docstring has the taxonomy and the zero-overhead contract)."""

    def __init__(self, *, sample_every: int = 1, capacity: int = 1 << 20):
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, "
                             f"got {sample_every}")
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.sample_every = int(sample_every)
        self.capacity = int(capacity)
        self.clear()

    def clear(self):
        """Drop recorded spans/instants (measured-replay protocol: clear
        after the warm pass so the trace covers only the measured pass).
        Ids keep advancing — parent links can never dangle across clears.
        """
        self.spans: list[dict] = []
        self.instants: list[dict] = []
        self.dropped = 0
        self._next_id = getattr(self, "_next_id", 1)

    def want(self, idx: int) -> bool:
        """Is request ``idx`` sampled? (1/sample_every of the id space.)"""
        return idx % self.sample_every == 0

    def span(self, name: str, t0_us: float, dur_us: float, *,
             cat: str = "serve", req: int | None = None,
             parent: int | None = None, **attrs) -> int | None:
        """Record one interval; returns its span id (parent for children),
        or None once capacity is hit (counted in ``dropped``)."""
        if len(self.spans) >= self.capacity:
            self.dropped += 1
            return None
        sid = self._next_id
        self._next_id += 1
        self.spans.append({
            "id": sid, "parent": parent, "name": name, "cat": cat,
            "req": req, "t0_us": float(t0_us), "dur_us": float(dur_us),
            "attrs": attrs,
        })
        return sid

    def instant(self, name: str, t_us: float, *, cat: str = "serve",
                req: int | None = None, **attrs):
        if len(self.instants) >= self.capacity:
            self.dropped += 1
            return
        self.instants.append({
            "name": name, "cat": cat, "req": req, "t_us": float(t_us),
            "attrs": attrs,
        })

    # -- export ---------------------------------------------------------------
    def to_jsonl(self, path: str) -> str:
        """One JSON record per line: spans (``type: "span"``) then
        instants (``type: "instant"``) — the obs_report input format."""
        with open(path, "w") as f:
            for s in self.spans:
                f.write(json.dumps({"type": "span", **s}) + "\n")
            for e in self.instants:
                f.write(json.dumps({"type": "instant", **e}) + "\n")
        return path

    def to_chrome(self, path: str) -> str:
        """Chrome/Perfetto trace-event JSON. Requests map to tids so each
        sampled request gets its own lane in the viewer; batch/cluster
        events land on lane 0."""
        events = []
        for s in self.spans:
            events.append({
                "name": s["name"], "cat": s["cat"], "ph": "X",
                "ts": s["t0_us"], "dur": s["dur_us"],
                "pid": 0, "tid": s["req"] if s["req"] is not None else 0,
                "args": dict(s["attrs"], span_id=s["id"],
                             parent=s["parent"]),
            })
        for e in self.instants:
            events.append({
                "name": e["name"], "cat": e["cat"], "ph": "i", "s": "t",
                "ts": e["t_us"], "pid": 0,
                "tid": e["req"] if e["req"] is not None else 0,
                "args": dict(e["attrs"]),
            })
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path


def load_jsonl(path: str) -> tuple[list[dict], list[dict]]:
    """Read a ``to_jsonl`` trace back -> (spans, instants)."""
    spans, instants = [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            (spans if rec.get("type") == "span" else instants).append(rec)
    return spans, instants


def span_children(spans: list[dict]) -> dict:
    """parent span id -> list of child spans (None key = roots)."""
    out: dict = {}
    for s in spans:
        out.setdefault(s.get("parent"), []).append(s)
    return out


def request_trees(spans: list[dict]) -> dict:
    """req idx -> (root request span, [child spans]) for every root named
    ``request`` — the obs_report / invariant-test accessor."""
    kids = span_children(spans)
    out = {}
    for root in kids.get(None, []):
        if root["name"] == "request" and root.get("req") is not None:
            out[root["req"]] = (root, kids.get(root["id"], []))
    return out


# -- real-clock stages --------------------------------------------------------
def annotation(name: str):
    """A ``TraceAnnotation`` of ``name``, entered, while a profiler session
    is active (the caller exits it); None otherwise, at the cost of one
    flag read."""
    if not TraceAnnotation.is_enabled():
        return None
    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


class StageLog:
    """Real-clock stage records of one serving replica (module docstring
    has the taxonomy). Times are ``time.perf_counter_ns``."""

    def __init__(self):
        self.spans: list[tuple] = []     # (name, t0_ns, t1_ns, dispatch, attrs)
        self.lookups: list[tuple] = []   # (request idx, ns)
        self.waits: list[tuple] = []     # (idx, enqueue_ns, start_ns, dispatch)
        self.n_dispatches = 0

    def dispatch(self) -> "_Dispatch":
        """The next dispatch's ``runtime.dispatch`` stage: while it is open,
        every ``stage`` records here under its id (``.id``)."""
        return _Dispatch(self)

    def dispatch_ns(self) -> float:
        """Total ``runtime.dispatch`` time."""
        return float(sum(t1 - t0 for name, t0, t1, _, _ in self.spans
                         if name == "runtime.dispatch"))


class stage:
    """``with stage(name, **attrs):`` times the block on the real clock and
    records ``(name, t0_ns, t1_ns, dispatch id, attrs)`` in the log of the
    dispatch in progress (none outside a dispatch); annotates the profiler
    trace while one is taken. ``t0`` (the block's entry) is readable inside
    the block.

    The record runs from where the dispatch's previous stage ended (its
    start, for the first) to the block's exit, so a dispatch's stages tile
    it: the few microseconds of calls and returns between two blocks count
    in the one that follows them. The profiler annotation spans the block
    alone."""

    __slots__ = ("name", "attrs", "t0", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs or None

    def __enter__(self):
        self._ann = annotation(self.name)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        d = _DISPATCH.get()
        if d is not None:
            d.log.spans.append((self.name, d.cursor, t1, d.id, self.attrs))
            d.cursor = t1
        return False


class _Dispatch(stage):
    """``runtime.dispatch``: while open, the dispatch in progress in this
    context, and where its last recorded stage ended (``cursor``)."""

    __slots__ = ("log", "id", "cursor", "_token")

    def __init__(self, log: StageLog):
        super().__init__("runtime.dispatch")
        self.log = log

    def __enter__(self):
        super().__enter__()
        self.id = self.log.n_dispatches
        self.log.n_dispatches += 1
        self.cursor = self.t0
        self._token = _DISPATCH.set(self)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        _DISPATCH.reset(self._token)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.log.spans.append((self.name, self.t0, t1, self.id, None))
        return False


_DISPATCH: contextvars.ContextVar = contextvars.ContextVar("dispatch",
                                                           default=None)
_GC_PAUSES: collections.deque = collections.deque(maxlen=1 << 16)
_gc_t0 = [0]


def _on_gc(phase: str, info: dict):
    if phase == "start":
        _gc_t0[0] = time.perf_counter_ns()
    else:
        _GC_PAUSES.append((info["generation"], _gc_t0[0],
                           time.perf_counter_ns()))


def install_gc_clock():
    """Record every collection's generation and real-clock pause from now
    on, process-wide (the newest 65536 are kept). Idempotent."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_pauses() -> list[tuple]:
    """The recorded collections: ``(generation, t0_ns, t1_ns)``."""
    return list(_GC_PAUSES)


_BUILD_STAGES: collections.deque = collections.deque(maxlen=1 << 12)
_BUILD: contextvars.ContextVar = contextvars.ContextVar("build", default=None)


class build_stage:
    """``with build_stage(name, **attrs):`` one stage of the host index
    build, recorded process-wide as ``(name, t0_ns, t1_ns, attrs)`` (the
    newest 4096 are kept). ``attrs`` may be filled inside the block. Inside
    ``build_clock`` the record runs from where the build's previous stage
    ended, so the stages tile the build; outside it, the block alone."""

    __slots__ = ("name", "attrs", "t0")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        cursor = _BUILD.get()
        t0 = self.t0 if cursor is None else cursor[0]
        _BUILD_STAGES.append((self.name, t0, t1, self.attrs))
        if cursor is not None:
            cursor[0] = t1
        return False


@contextlib.contextmanager
def build_clock():
    """One index build: the ``build_stage`` records made inside it tile it
    from its entry on."""
    token = _BUILD.set([time.perf_counter_ns()])
    try:
        yield
    finally:
        _BUILD.reset(token)


def build_stages() -> list[tuple]:
    """The recorded index build stages: ``(name, t0_ns, t1_ns, attrs)``."""
    return list(_BUILD_STAGES)
