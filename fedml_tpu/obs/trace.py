"""Distributed round tracing: spans whose context rides Message headers.

The reference (and our PR 1 fault layer) had no way to see WHERE a
federated round spends its time: a stalled round could be a dead silo, a
retry storm, or a first-call jit compile.  This tracer stitches one
round into a single cross-process trace — server ``round`` span →
``broadcast`` → per-silo ``recv``/``train``/``upload`` → server
``aggregate`` — by carrying ``(trace_id, span_id)`` in a reserved plain
header key of every `Message` (`CTX_KEY`, mirrored as
``Message.ARG_TRACE``).  Export is Chrome/Perfetto ``trace_event`` JSON
(one file per process; `obs/report.py` merges them), viewable in
``ui.perfetto.dev`` alongside the ``jax.profiler`` XLA traces
``profiler_trace`` already captures.

Cost contract: tracing is a process-global opt-in (`enable()`); when
disabled ``get_tracer()`` is ``None`` and instrumented paths pay exactly
one branch per message, no allocations, no threads.

Clocks: a span's start and duration are ``time.perf_counter_ns()``
readings (monotonic; the clock a harness in the same process stamps its
round edges with).  The tracer takes one ``(time.time_ns(),
perf_counter_ns())`` anchor when it is made, so the exported Chrome
``ts`` stays on the wall clock — which is what stitches the per-process
files of an actor run — while every event keeps the raw ``t0_ns`` /
``dur_ns`` in its ``args``.  A span opened as a context manager also
enters a ``jax.profiler.TraceAnnotation`` of the same name, so whenever
a profiler session is open the program's spans lie on the trace's host
plane beside the device's ops (a flag check when none is).

Compiles: the process's one JAX-monitoring listener (`watch_compiles`,
`compile_totals`) records each stage of a compile as a span (``jit.trace``,
``jit.lower``, ``jit.compile``) under the `TimedSpan` open on the thread
that paid it, and keeps the process's compile totals.  A run that compiles
nothing pays nothing.

Duplicate tolerance: a chaotic wire can deliver one frame twice.  Spans
created with ``deterministic=True`` derive their span id from
``(trace_id, parent_id, name, node)``, and the tracer records the FIRST
span per id — so a duplicated delivery collapses to one span instead of
forking the trace.
"""

from __future__ import annotations

import contextlib
import hashlib
import itertools
import json
import os
import threading
import time
from typing import Optional

# the Message param key trace context travels under (a plain {"t","s"}
# dict, so it rides the JSON header of the binary codec untouched).
# comm/message.py mirrors this as Message.ARG_TRACE — kept literal here
# so this module stays import-cycle-free (stdlib only).
CTX_KEY = "_trace"

# the ONE null context instrumented call sites reuse when tracing is
# disabled: nullcontext is reentrant and stateless, so sharing a single
# instance makes the disabled path literally allocation-free (the
# zero-allocation pin in tests/test_critical_path.py holds it to that)
NULL_CONTEXT = contextlib.nullcontext()

_USE_CURRENT = object()  # start_span default: parent = the active span
_tracer_ids = itertools.count()
# .site: the innermost open `TimedSpan` that has a tracer, on this
# thread (`child` takes its tracer and its recorder from it)
_ambient = threading.local()

# kept spans per tracer: a run of any length holds at most this many (the
# newest; the dropped count is written into the exported file)
MAX_SPANS = 65536


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or None where JAX is absent
    (this module stays importable on the stdlib alone)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class SpanContext:
    """The propagated identity of a span: (trace_id, span_id), plus —
    when extracted from a message — the unique id ``inject()`` stamped on
    that SEND.  The msg_id is what separates "the wire duplicated one
    frame" (same msg_id → recv spans dedupe) from "two messages rode the
    same parent span" (distinct msg_ids → distinct spans)."""
    __slots__ = ("trace_id", "span_id", "msg_id")

    def __init__(self, trace_id: str, span_id: str,
                 msg_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.msg_id = msg_id

    def __repr__(self):
        return f"SpanContext({self.trace_id}, {self.span_id}, {self.msg_id})"


class Span:
    """One timed operation.  ``end()`` records it (idempotent)."""
    __slots__ = ("name", "trace_id", "span_id", "parent_id", "node",
                 "args", "t0", "tid", "_tracer", "_ended", "_annotation")

    def __init__(self, tracer: "SpanTracer", name: str, trace_id: str,
                 span_id: str, parent_id: Optional[str], node, args: dict,
                 t0: int):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.node = node
        self.args = args
        self.t0 = t0
        self.tid = threading.get_ident()
        self._tracer = tracer
        self._ended = False
        self._annotation = None

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def end(self) -> int:
        """Record the span; returns its duration in nanoseconds (0 when
        it had ended already)."""
        if self._ended:
            return 0
        self._ended = True
        dur_ns = self._tracer._clock() - self.t0
        self._tracer._record(self, dur_ns)
        return dur_ns


class SpanTracer:
    """Collects spans; exports Chrome ``trace_event`` JSON.

    ``node`` labels spans that don't pass their own (in-process actors
    pass their node id per span, so one tracer serves a whole local
    federation).  ``clock`` (integer nanoseconds, monotonic) is
    injectable for deterministic tests.
    """

    def __init__(self, node="proc0", clock=time.perf_counter_ns):
        self.node = node
        self._clock = clock
        # wall-clock anchor: exported ``ts`` = wall + (t0 - mono)
        self._anchor = (time.time_ns(), clock())
        self._annotate = _trace_annotation()
        self._lock = threading.Lock()
        self._spans: dict = {}      # span_id -> record, in record order
        self.dropped = 0            # oldest records let go at MAX_SPANS
        self._seq = itertools.count()
        self._local = threading.local()
        # per-tracer nonce keeps generated ids unique across processes
        # (grpc silos) and across tracer instances within one process
        self._nonce = f"{os.getpid():x}.{next(_tracer_ids)}"

    # -- id generation -------------------------------------------------------
    def new_trace_id(self, hint: str = "") -> str:
        return f"{self._nonce}-{hint or next(self._seq)}"

    # -- current-span stack (thread-local) -----------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current_context(self) -> Optional[SpanContext]:
        stack = self._stack()
        return stack[-1].context if stack else None

    # -- span lifecycle ------------------------------------------------------
    def start_span(self, name: str, parent=_USE_CURRENT,
                   trace_id: Optional[str] = None, node=None,
                   span_id: Optional[str] = None, deterministic: bool = False,
                   **args) -> Span:
        """``parent`` accepts a Span, a SpanContext, or None (root); the
        default is the thread's active span.  ``deterministic=True``
        derives the span id from (trace_id, parent, name, node) so a
        duplicated message re-handled on the same node dedupes."""
        if parent is _USE_CURRENT:
            parent = self.current_context()
        elif isinstance(parent, Span):
            parent = parent.context
        if trace_id is None:
            trace_id = parent.trace_id if parent is not None \
                else self.new_trace_id()
        parent_id = parent.span_id if parent is not None else None
        if node is None:
            node = self.node
        if span_id is None:
            if deterministic:
                # include the parent context's message id (present when
                # the parent was extracted off a wire message): dedupes
                # duplicated deliveries of ONE frame without collapsing
                # distinct frames that share a parent span
                msg_id = getattr(parent, "msg_id", None) or ""
                span_id = deterministic_span_id(
                    trace_id, parent_id or "", msg_id, name, str(node))
            else:
                span_id = f"{self._nonce}.{next(self._seq)}"
        return Span(self, name, trace_id, span_id, parent_id, node, args,
                    self._clock())

    def enter(self, name: str, **kw) -> Span:
        """Start a span, make it the thread's current (so sends and spans
        inside it hang under it) and enter the profiler annotation of the
        same name.  Pair with `exit` on the same thread, innermost first
        — `span` and `TimedSpan` are the context managers that do."""
        sp = self.start_span(name, **kw)
        self._stack().append(sp)
        if self._annotate is not None:
            sp._annotation = self._annotate(name)
            sp._annotation.__enter__()
        return sp

    def exit(self, sp: Span) -> int:
        """End what `enter` started; returns the duration in ns."""
        if sp._annotation is not None:
            sp._annotation.__exit__(None, None, None)
            sp._annotation = None
        self._stack().pop()
        return sp.end()

    @contextlib.contextmanager
    def span(self, name: str, **kw):
        """`enter` a span, `exit` it when the block ends."""
        sp = self.enter(name, **kw)
        try:
            yield sp
        finally:
            self.exit(sp)

    def record_span(self, name: str, dur_s: float,
                    t0_ns: Optional[int] = None, parent=None,
                    trace_id: Optional[str] = None, node=None,
                    span_id: Optional[str] = None, **args) -> None:
        """Record an already-finished span retroactively: the hot-path
        form for schedulers that know a phase's duration only after it
        ran (serve queue wait, batch execution, decode steps) — one call
        per event, no context-manager entry on the critical path.
        ``t0_ns`` defaults to ``now - dur_s`` on this tracer's clock;
        pass a Span/SpanContext as ``parent`` to hang it under a
        request, and ``span_id`` where children already name it."""
        if isinstance(parent, Span):
            parent = parent.context
        dur_ns = int(dur_s * 1e9)
        sp = self.start_span(name, parent=parent, trace_id=trace_id,
                             node=node, span_id=span_id, **args)
        sp.t0 = self._clock() - dur_ns if t0_ns is None else t0_ns
        sp._ended = True
        self._record(sp, dur_ns)

    def _record(self, span: Span, dur_ns: int) -> None:
        rec = {"name": span.name, "trace_id": span.trace_id,
               "span_id": span.span_id, "parent_id": span.parent_id,
               "node": span.node, "t0_ns": span.t0, "dur_ns": dur_ns,
               "tid": span.tid, "args": span.args}
        with self._lock:
            if span.span_id not in self._spans:   # dedupe: first wins
                self._spans[span.span_id] = rec
                if len(self._spans) > MAX_SPANS:
                    del self._spans[next(iter(self._spans))]
                    self.dropped += 1

    # -- export --------------------------------------------------------------
    @property
    def spans(self) -> list:
        """Recorded span dicts, in record order (test/report surface)."""
        with self._lock:
            return [dict(rec) for rec in self._spans.values()]

    def to_trace_events(self) -> list:
        """Chrome ``trace_event`` list: one complete ("X") event per span
        plus ``process_name`` metadata naming each node's track."""
        events, nodes = [], {}
        wall_ns, mono_ns = self._anchor
        for rec in self.spans:
            pid = _node_pid(rec["node"])
            nodes.setdefault(pid, rec["node"])
            events.append({
                "name": rec["name"], "cat": "fedml", "ph": "X",
                "ts": (wall_ns + rec["t0_ns"] - mono_ns) // 1000,
                "dur": rec["dur_ns"] // 1000,
                "pid": pid, "tid": rec["tid"] % 1_000_000,
                "args": {"trace_id": rec["trace_id"],
                         "span_id": rec["span_id"],
                         "parent_id": rec["parent_id"],
                         "node": str(rec["node"]),
                         "t0_ns": rec["t0_ns"], "dur_ns": rec["dur_ns"],
                         **rec["args"]}})
        for pid, node in sorted(nodes.items()):
            events.append({"ph": "M", "name": "process_name", "pid": pid,
                           "tid": 0, "args": {"name": f"node {node}"}})
        return events

    def export(self, path: str) -> None:
        """Write ``{"traceEvents": [...]}`` atomically (tmp + replace);
        ``otherData`` says what ``t0_ns`` is read on and how many spans
        the cap let go."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"traceEvents": self.to_trace_events(),
                       "displayTimeUnit": "ms",
                       "otherData": {"clock": "perf_counter_ns",
                                     "anchor_wall_ns": self._anchor[0],
                                     "anchor_mono_ns": self._anchor[1],
                                     "dropped_spans": self.dropped}}, f)
        os.replace(tmp, path)


class TimedSpan:
    """ONE timing site for one boundary: a context manager that reads the
    clock once on entry and once on exit and hands that single interval
    to everything that wants it — the span on ``tracer`` (and, through
    it, the profiler annotation), the ledger phase ``phase`` of ``perf``
    (`obs.perf.PerfRecorder.add_phase`) and the histogram ``hist``.  Any
    of them may be None; with no tracer the site still times (a caller
    that needs the seconds without ``--perf``).  ``wait="device"`` marks
    a span in which the host only waits for the device.  Further
    keywords go to `SpanTracer.start_span` (``parent``, ``trace_id``,
    span args); `set` adds args once the work has produced them."""
    __slots__ = ("_tracer", "_clock", "_name", "_perf", "_phase", "_hist",
                 "_kw", "_outer", "span", "t0_ns", "dur_ns")

    def __init__(self, tracer: Optional[SpanTracer], name: str, perf=None,
                 phase: Optional[str] = None, hist=None,
                 wait: Optional[str] = None, **kw):
        self._tracer = tracer
        self._clock = (tracer._clock if tracer is not None
                       else time.perf_counter_ns)
        self._name = name
        self._perf = perf
        self._phase = phase
        self._hist = hist
        if phase is not None:
            kw["phase"] = phase
        if wait is not None:
            kw["wait"] = wait
        self._kw = kw
        self._outer = None
        self.span: Optional[Span] = None
        self.t0_ns = self.dur_ns = 0

    def __enter__(self) -> "TimedSpan":
        if self._tracer is not None:
            self.span = self._tracer.enter(self._name, **self._kw)
            self.t0_ns = self.span.t0
            self._outer = getattr(_ambient, "site", None)
            _ambient.site = self
        else:
            self.t0_ns = self._clock()
        return self

    def __exit__(self, *exc) -> bool:
        if self.span is not None:
            _ambient.site = self._outer
            self.dur_ns = self._tracer.exit(self.span)
        else:
            self.dur_ns = self._clock() - self.t0_ns
        if self._phase is not None and self._perf is not None:
            self._perf.add_phase(self._phase, self.dur_ns / 1e9)
        if self._hist is not None:
            self._hist.observe(self.dur_ns / 1e9)
        return False

    def set(self, **args) -> None:
        """Counts that ride the span (rows, bytes, slots)."""
        if self.span is not None:
            self.span.args.update(args)

    @property
    def seconds(self) -> float:
        """The measured interval, once the block has ended."""
        return self.dur_ns / 1e9

    def elapsed(self) -> float:
        """Seconds since entry, read inside the block."""
        return (self._clock() - self.t0_ns) / 1e9


def child(name: str, phase: Optional[str] = None, hist=None):
    """A `TimedSpan` under the site that is open on this thread, on that
    site's tracer and ledger.  How library code a spanned caller runs
    opens its own spans without its signature growing a tracer: the
    cohort staging and the aggregator's finalize under the cross-device
    round.  With no site open it times for ``hist`` alone, and is the
    shared null context (one lookup, nothing kept) when there is no
    histogram either."""
    site = getattr(_ambient, "site", None)
    if site is not None:
        return TimedSpan(site._tracer, name, site._perf, phase, hist)
    if hist is not None:
        return TimedSpan(None, name, hist=hist)
    return NULL_CONTEXT


# -- the compiler's time, as spans (ISSUE 41) ----------------------------------

# JAX's monitoring events of the three stages of a compile -> span name
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
# the persistent cache's verdict, seen inside a backend compile -> its arg
_CACHE_VERDICTS = {"/jax/compilation_cache/cache_hits": "cache_hit",
                   "/jax/compilation_cache/cache_misses": "cache_miss"}
_TOTAL_OF = {"jit.trace": "trace_s", "jit.lower": "lower_s",
             "jit.compile": "compile_s"}


class _CompileWatch:
    """The process's one JAX-monitoring listener.  Each stage of a
    compile becomes a span (`COMPILE_SPANS`) under the `TimedSpan` site
    open on the compiling thread, else a root span of the open
    recorder's tracer (`watch_compiles`), else none; the process totals
    count either way.  A stage starts at JAX's start marker (a scalar
    event at entry) and ends at its duration event, both read on
    ``perf_counter_ns`` here; a stage that runs inside another (an inner
    jit traced inside the outer's trace) is that one's child, so a
    thread's leaf spans never overlap.  `jit.compile` says whether the
    persistent cache answered (``cache_hit``) or was written
    (``cache_miss``): both 0 where no cache is on.  Listeners cannot be
    unregistered: one per process, made by `compile_totals` or a
    recorder, whichever comes first."""

    def __init__(self):
        import jax.monitoring as monitoring
        from jax.profiler import TraceAnnotation
        self.annotate = TraceAnnotation
        self.lock = threading.Lock()
        self.totals = {"compiles": 0, "compile_s": 0.0, "trace_s": 0.0,
                       "lower_s": 0.0, "cache_hits": 0, "cache_misses": 0}
        self.tracer: Optional[SpanTracer] = None   # the open recorder's
        self.local = threading.local()   # .open: stages begun, innermost
        #                                  last; .verdict: the cache's
        self.ids = itertools.count()
        self.nonce = f"{os.getpid():x}.jit"
        monitoring.register_scalar_listener(self._on_start)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_end)

    def _open(self) -> list:
        stack = getattr(self.local, "open", None)
        if stack is None:
            stack = self.local.open = []
        return stack

    def _on_start(self, event, value, **_kw) -> None:
        name = COMPILE_SPANS.get(event)
        if name is None:
            return
        # on the profiler's host plane too, as a `TimedSpan` is
        note = self.annotate(name)
        note.__enter__()
        self._open().append({"event": event, "note": note,
                             "t0": time.perf_counter_ns(),
                             "id": f"{self.nonce}.{next(self.ids)}"})
        if name == "jit.compile":
            self.local.verdict = {}

    def _on_event(self, event, **_kw) -> None:
        arg = _CACHE_VERDICTS.get(event)
        if arg is not None:
            with self.lock:     # the event's last part: `cache_hits`
                self.totals[event.rsplit("/", 1)[1]] += 1
            verdict = getattr(self.local, "verdict", None)
            if verdict is None:
                verdict = self.local.verdict = {}
            verdict[arg] = 1

    def _on_end(self, event, secs, fun_name="", **_kw) -> None:
        name = COMPILE_SPANS.get(event)
        if name is None:
            return
        t1 = time.perf_counter_ns()
        stack = self._open()
        if stack and stack[-1]["event"] == event:
            own = stack.pop()
            own["note"].__exit__(None, None, None)
        else:   # no start seen (the listener came mid-compile)
            own = {"t0": t1 - int(secs * 1e9),
                   "id": f"{self.nonce}.{next(self.ids)}"}
        with self.lock:
            self.totals[_TOTAL_OF[name]] += float(secs)
            if name == "jit.compile":
                self.totals["compiles"] += 1
        args = {"fun": fun_name}
        if name == "jit.compile":
            verdict = getattr(self.local, "verdict", None) or {}
            self.local.verdict = {}
            args.update(cache_hit=verdict.get("cache_hit", 0),
                        cache_miss=verdict.get("cache_miss", 0))
        site = getattr(_ambient, "site", None)
        tracer = site._tracer if site is not None else self.tracer
        if tracer is None:
            return
        # one trace id for a nest of stages: the site's, else one the
        # outermost stage keeps for all of them
        root = stack[0] if stack else own
        if site is not None:
            trace_id = site.span.trace_id
        else:
            if "trace" not in root:
                root["trace"] = tracer.new_trace_id()
            trace_id = root["trace"]
        parent = (SpanContext(trace_id, stack[-1]["id"]) if stack
                  else site.span if site is not None else None)
        tracer.record_span(name, (t1 - own["t0"]) / 1e9, t0_ns=own["t0"],
                           parent=parent, trace_id=trace_id,
                           span_id=own["id"], **args)


_compile_watch: Optional[_CompileWatch] = None
_compile_watch_lock = threading.Lock()


def _watch() -> _CompileWatch:
    global _compile_watch
    with _compile_watch_lock:
        if _compile_watch is None:
            _compile_watch = _CompileWatch()
        return _compile_watch


def watch_compiles(tracer: Optional[SpanTracer],
                   since: Optional[SpanTracer] = None) -> None:
    """Make ``tracer`` the one a compile outside any site records its
    spans on (None: no spans), making the listener if there is none yet.
    With ``since``, only while that tracer holds it: a recorder that
    closes hands back its own, never another recorder's."""
    watch = _watch()
    with watch.lock:
        if since is None or watch.tracer is since:
            watch.tracer = tracer


def compile_totals() -> dict:
    """The process's compile account since its listener was made:
    ``compiles`` (backend compiles, a persistent-cache hit among them),
    ``compile_s``, ``trace_s``, ``lower_s``, ``cache_hits`` and
    ``cache_misses`` (entries written).  Running totals: diff two
    around a phase."""
    watch = _watch()
    with watch.lock:
        return dict(watch.totals)


def _node_pid(node) -> int:
    """Stable small integer per node label (Perfetto tracks are per-pid)."""
    try:
        return int(node)
    except (TypeError, ValueError):
        digest = hashlib.blake2s(str(node).encode(), digest_size=2).digest()
        return 1000 + int.from_bytes(digest, "big")


def deterministic_span_id(*parts: str) -> str:
    return hashlib.blake2s("|".join(parts).encode(),
                           digest_size=8).hexdigest()


# -- Message header propagation ---------------------------------------------

_msg_seq = itertools.count()


def inject(msg, ctx: SpanContext) -> None:
    """Attach ``ctx`` to an outgoing message (plain JSON-header param),
    stamping a unique per-send message id: a chaotic wire can deliver
    this one frame twice, and the id is how the receiver's span dedupe
    tells that apart from two genuinely distinct sends."""
    msg.add(CTX_KEY, {"t": ctx.trace_id, "s": ctx.span_id,
                      "m": f"{os.getpid():x}.{next(_msg_seq)}"})


def extract(msg) -> Optional[SpanContext]:
    """Read the propagated context off an inbound message, if any."""
    d = msg.get(CTX_KEY)
    if isinstance(d, dict) and "t" in d and "s" in d:
        return SpanContext(d["t"], d["s"], d.get("m"))
    return None


# -- process-global tracer ---------------------------------------------------

_tracer: Optional[SpanTracer] = None


def get_tracer() -> Optional[SpanTracer]:
    """``None`` unless `enable()` ran — instrumented paths branch on
    exactly this."""
    return _tracer


def enable(node="proc0", clock=time.perf_counter_ns) -> SpanTracer:
    global _tracer
    if _tracer is None:
        _tracer = SpanTracer(node=node, clock=clock)
    return _tracer


def disable() -> None:
    global _tracer
    _tracer = None
