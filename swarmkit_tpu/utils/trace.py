"""Trace plane: in-process span tracer with a crash-dumpable flight
recorder.

SURVEY §5 records the reference's observability gap verbatim: "Tracing /
profiling. No distributed tracer" — SwarmKit ships pprof hooks and
Prometheus gauges only. This build has four asynchronous planes (async
commit, raft group-commit, failpoints/chaos, dispatcher fan-out) whose
latency structure those surfaces cannot see: when a barrier stalls or a
heavy commit eats a wave period, thread stacks say *where* code sits,
never *which* stage of *which* wave took the time. A Dapper-style
in-process tracer closes that: named spans with causal parent links,
propagated across threads (the CommitWorker's heavy half links back to
its originating wave), across RPC calls (context rides a reserved
`_trace_ctx` kwarg in the frame payload) and across raft consensus
(context rides the Entry, so a follower's WAL fsync and apply join the
leader-side proposal's trace).

Cost contract — the same one `utils/failpoints.py` holds and the bench
accepts: DISARMED, every instrumentation site costs one module-global
truthiness test (`trace._REC is None`) and never constructs a Span,
files a record, or builds a closure. `with trace.span(...)`-style sites
at per-WAVE boundaries additionally pay the interpreter's transient
empty-kwargs dict for the call itself; per-ENTRY hot loops (the raft
apply loop, the ready flush, wheel beats) use the guarded
`trace.enabled()` pattern and allocate nothing at all. The conftest
fails any test that leaks an armed tracer, and the disarmed-overhead
guard in tests/test_trace.py pins the no-Span/no-record property on the
tick, dispatcher-flush, and raft ready-loop hot paths. Sites sit at
DECISION boundaries only — never
inside the C segment walk, never in per-entry WAL write loops — and
device syncs get one `tick.device_sync` span per burst (the blocking
value pull), never one per kernel.

Armed, every span reads the calling thread's CPU clock
(`time.thread_time()`) at start and end: the record's `cpu` beside its
wall `dur` tells a stage that computes from one that waits (the GIL, an
fsync, the device pull, a barrier). Span ids are a per-process random
prefix plus a counter, so arming costs no syscall per span.

Armed, a finished span goes two places:

  * the FLIGHT RECORDER — a bounded ring of completed-span records the
    wedge monitor and the chaos harness dump next to CHAOS_SEED, and
    `/debug/trace/recent` serves as JSON span trees;
  * derived STAGE HISTOGRAMS — span names map by prefix onto the
    `tick_stage_seconds{stage=…}` / `raft_commit_path_seconds{stage=…}`
    / `dispatcher_flush_seconds{stage=…}` HistogramFamily-s, feeding the
    existing /metrics exposition (so arming the tracer is also how an
    operator gets per-stage latency percentiles).

Span catalog and parent rules are documented in docs/observability.md.
"""
from __future__ import annotations

import itertools
import os
import threading
from ..analysis.lockgraph import make_lock
import time
from contextlib import contextmanager
from typing import Any, Callable

_REG_LOCK = make_lock('utils.trace.REG_LOCK')
# The armed recorder, or None. Replaced wholesale on arm/disarm so hot
# sites read it without a lock; the disarmed fast path everywhere is
# `if _REC is None: return` / `rec = _REC; if rec is not None: ...`.
_REC: "FlightRecorder | None" = None

_tls = threading.local()          # per-thread implicit-parent span stack
# arm generation: bumped on every arm(). Thread-local stacks are stamped
# with the generation they were built under, so a span left open on SOME
# OTHER thread across a disarm/re-arm (an rpc handler, a CommitWorker
# job) can never become an implicit parent under the NEW recorder —
# disarm() can only clear the CALLING thread's stack.
_GEN = 0

DEFAULT_CAPACITY = 4096

# span-name prefix -> (metrics family, help). The stage label is the
# span name with the prefix stripped. Families are created lazily at
# first armed use, so merely importing this module registers nothing.
_STAGE_FAMILIES: tuple[tuple[str, str, str], ...] = (
    ("tick.", "tick_stage_seconds",
     "Scheduler tick stage latency, derived from trace spans"),
    ("sched.", "tick_stage_seconds",
     "Scheduler tick stage latency, derived from trace spans"),
    ("raft.", "raft_commit_path_seconds",
     "Raft propose->flush->commit->apply stage latency, derived from "
     "trace spans"),
    ("dispatcher.", "dispatcher_flush_seconds",
     "Dispatcher fan-out flush stage latency, derived from trace spans"),
    ("hb.", "dispatcher_flush_seconds",
     "Dispatcher fan-out flush stage latency, derived from trace spans"),
)


# span and trace ids: a random per-process prefix (unique across the
# node processes whose contexts meet over RPC and raft) plus a
# process-wide counter (next() on itertools.count is atomic under the GIL)
_ID_PREFIX = os.urandom(8).hex()
_IDS = itertools.count(1)


def _new_id() -> str:
    return f"{_ID_PREFIX}{next(_IDS):x}"


class Span:
    """One in-flight span. Created ONLY while armed (recorder sites
    guard on `_REC is None` first); `end()` files the completed record
    into the recorder that was armed at start time, so a span that
    straddles a disarm still lands (in the retired recorder) instead of
    crashing its owner thread."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "_rec", "_t0", "_wall", "_cpu0", "_thread", "_on_stack",
                 "_ended")

    def __init__(self, rec: "FlightRecorder", name: str,
                 parent: "tuple[str, str] | Span | None", attrs: dict,
                 on_stack: bool):
        self.name = name
        self.attrs = attrs
        if parent is not None:
            parent = _coerce_ctx(parent)
        if parent is None:
            parent = _current_ctx()
        if parent is not None:
            self.trace_id, self.parent_id = parent
        else:
            self.trace_id, self.parent_id = _new_id(), None
        self.span_id = _new_id()
        self._rec = rec
        self._wall = rec.clock.monotonic() if rec.clock is not None \
            else time.time()
        self._t0 = time.perf_counter()
        self._cpu0 = time.thread_time()
        self._thread = threading.current_thread()
        self._on_stack = on_stack
        self._ended = False
        if on_stack:
            _stack().append(self)

    def ctx(self) -> tuple[str, str]:
        """The propagable context: (trace_id, span_id). Codec-safe (a
        plain tuple of strings) — it rides RPC kwargs and raft entries."""
        return (self.trace_id, self.span_id)

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self, **attrs) -> None:
        if self._ended:
            return
        self._ended = True
        if attrs:
            self.attrs.update(attrs)
        if self._on_stack:
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            else:                      # ended out of order: drop by identity
                try:
                    stack.remove(self)
                except ValueError:
                    pass
        # a span ended on another thread than it started on (raft.propose
        # resolves on the raft worker) has no one thread's CPU time; it is
        # filed under the thread that opened it
        cpu = (time.thread_time() - self._cpu0
               if threading.current_thread() is self._thread else None)
        self._rec.record(self.name, self._wall,
                         time.perf_counter() - self._t0,
                         self.trace_id, self.span_id, self.parent_id,
                         self.attrs, cpu, self._thread.name)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.attrs.setdefault("error", repr(exc))
        self.end()


class _NoopSpan:
    """Singleton returned by span() when disarmed: no allocation, every
    method a no-op."""

    __slots__ = ()

    def ctx(self):
        return None

    def set(self, **attrs):
        return self

    def end(self, **attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


NOOP = _NoopSpan()


class FlightRecorder:
    """Bounded ring of completed-span records.

    A record is a plain dict (codec/JSON-safe):
      {name, t0, dur, cpu, trace, span, parent, thread, attrs}
    `thread` is the name of the thread that opened the span.
    `t0` is wall-clock seconds (`time.time()`, the clock a profiler
    trace's `profile_start_time` is on; or the injected clock's monotonic
    time — tests pin expiry logic with FakeClock), `dur` is perf_counter
    seconds, `cpu` the thread CPU seconds the span took (None for a
    stage `rec()` filed pre-measured and for a span that ended on another
    thread). The ring is `capacity` records deep; old spans fall off —
    exactly the crash-forensics shape: the TAIL near the wedge/failure
    is what matters.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY, clock=None):
        self.capacity = max(16, int(capacity))
        self.clock = clock
        self._lock = make_lock('utils.trace.recorder')
        self._ring: list[dict] = []
        self.spans_recorded = 0      # records filed, dropped ones included
        self.dropped = 0             # records that fell off the ring

    # ------------------------------------------------------------- writing
    def record(self, name: str, t0: float, dur: float, trace_id: str,
               span_id: str, parent_id: str | None, attrs: dict,
               cpu: float | None = None, thread: str | None = None) -> None:
        rec = {"name": name, "t0": t0, "dur": dur, "cpu": cpu,
               "trace": trace_id, "span": span_id, "parent": parent_id,
               "thread": thread or threading.current_thread().name,
               "attrs": attrs}
        with self._lock:
            self.spans_recorded += 1
            self._ring.append(rec)
            if len(self._ring) > self.capacity:
                # trim in chunks: one del per capacity/8 appends, not one
                # memmove per record
                cut = max(1, self.capacity // 8)
                del self._ring[:cut]
                self.dropped += cut
        if dur > 0.0 and _REC is self:
            # a zero-duration record (a stage that measured nothing) is
            # not a latency sample — it must not flood the derived stage
            # histograms with 0s; and a
            # span ending into a RETIRED recorder (it straddled a
            # disarm) keeps its forensics record but must not grow the
            # histograms — those populate only while armed (CLAUDE.md)
            _observe_stage(name, dur)

    # ------------------------------------------------------------- reading
    def snapshot(self, seconds: float | None = None) -> list[dict]:
        """Completed records, oldest first; `seconds` keeps spans that
        RETIRED within the trailing window — keyed on end time, not
        start, so a span LONGER than the window (the slow stage an
        operator is hunting) still shows up in the capture."""
        with self._lock:
            out = list(self._ring)
        if seconds is not None:
            now = self.clock.monotonic() if self.clock is not None \
                else time.time()
            out = [r for r in out if now - (r["t0"] + r["dur"]) <= seconds]
        return out

    def tail(self, n: int = 64) -> list[dict]:
        with self._lock:
            return self._ring[-n:]

    def trees(self, seconds: float | None = None) -> list[dict]:
        """Group records into trace trees: one root per trace whose
        parent is absent from the window (JSON-ready for /debug/trace)."""
        recs = self.snapshot(seconds)
        by_span = {r["span"]: dict(r, children=[]) for r in recs}
        roots = []
        for r in by_span.values():
            parent = by_span.get(r["parent"]) if r["parent"] else None
            if parent is not None:
                parent["children"].append(r)
            else:
                roots.append(r)
        for r in by_span.values():
            r["children"].sort(key=lambda c: c["t0"])
        roots.sort(key=lambda c: c["t0"])
        return roots

    def tail_text(self, n: int = 64) -> str:
        """The crash-forensics dump: the recorder tail, one span per
        line, newest last (wedge monitor / chaos-failure output)."""
        lines = []
        for r in self.tail(n):
            # ids end in their per-process counter: the tail tells them
            # apart
            parent = f" <{r['parent'][-8:]}" if r["parent"] else ""
            attrs = "".join(f" {k}={v}" for k, v in r["attrs"].items())
            lines.append(
                f"[{r['t0']:.6f} +{r['dur'] * 1e3:8.3f}ms] "
                f"{r['name']} trace={r['trace'][-8:]} "
                f"span={r['span'][-8:]}{parent}"
                f" thread={r['thread']}{attrs}")
        return "\n".join(lines)


def _stack() -> list:
    if getattr(_tls, "gen", -1) != _GEN:
        # stale stack from a previous arm window: spans still on it end
        # fine (they hold their recorder; end() tolerates a missing
        # stack entry) but must not parent this window's spans
        _tls.gen = _GEN
        _tls.stack = []
    return _tls.stack


def _current_stack() -> list | None:
    if getattr(_tls, "gen", -1) != _GEN:
        return None
    return getattr(_tls, "stack", None)


def _current_ctx() -> tuple[str, str] | None:
    s = _current_stack()
    if s:
        return s[-1].ctx()
    return None


def _coerce_ctx(parent) -> tuple[str, str] | None:
    """Normalize a parent that may have arrived OFF THE WIRE (an
    Entry.trace field, the RPC `_trace_ctx` kwarg): anything that is
    not a 2-sequence of strings is treated as absent — a version-skewed
    or buggy peer's garbage ctx must never raise inside the consumer's
    apply loop (it would wedge commit application on that node)."""
    if parent is None:
        return None
    if isinstance(parent, Span):
        return parent.ctx()
    if isinstance(parent, (tuple, list)) and len(parent) == 2 \
            and isinstance(parent[0], str) and isinstance(parent[1], str):
        return (parent[0], parent[1])
    return None


# span name -> (resolved HistogramFamily, stage label), or None for a
# name no family takes: filled at the name's first armed record (the
# prefix scan and registry lookup per record were measurable armed
# overhead)
_STAGE_OF_NAME: dict[str, Any] = {}


def _observe_stage(name: str, dur: float) -> None:
    try:
        hit = _STAGE_OF_NAME[name]
    except KeyError:
        hit = None
        for prefix, family, help_ in _STAGE_FAMILIES:
            if name.startswith(prefix):
                from . import metrics

                hit = (metrics.histogram_family(family, help_, ("stage",)),
                       (name[len(prefix):] or name.rstrip("."),))
                break
        _STAGE_OF_NAME[name] = hit
    if hit is not None:
        hit[0].observe(hit[1], dur)


# ------------------------------------------------------------------ sites
def enabled() -> bool:
    return _REC is not None


def span(name: str, parent=None, **attrs):
    """Open a span (context manager). Disarmed: returns the NOOP
    singleton — nothing allocated. The span parents to `parent` (a ctx
    tuple or Span) or, implicitly, to the calling thread's innermost
    open span."""
    rec = _REC
    if rec is None:
        return NOOP
    return Span(rec, name, parent, attrs, on_stack=True)


def start(name: str, parent=None, **attrs):
    """Open a span WITHOUT installing it as the thread's implicit
    parent (cross-thread spans: the owner ends it from wherever the
    work completes). Returns None when disarmed — callers guard."""
    rec = _REC
    if rec is None:
        return None
    return Span(rec, name, parent, attrs, on_stack=False)


def ctx() -> tuple[str, str] | None:
    """The current propagable context, None when disarmed or no span is
    open. What RPC calls and raft proposals carry across boundaries."""
    if _REC is None:
        return None
    return _current_ctx()


def rec(name: str, seconds: float, parent=None, **attrs) -> None:
    """Record an already-measured stage as a completed span (the
    instrumented hot paths already time their stages into dicts — this
    files those measurements without restructuring them into `with`
    blocks). Disarmed: one truthiness test, nothing else."""
    r = _REC
    if r is None:
        return
    parent = _coerce_ctx(parent)
    if parent is None:
        parent = _current_ctx()
    if parent is not None:
        trace_id, parent_id = parent
    else:
        trace_id, parent_id = _new_id(), None
    wall = (r.clock.monotonic() if r.clock is not None else time.time())
    r.record(name, wall - seconds, seconds, trace_id, _new_id(),
             parent_id, attrs)


def annotate(**attrs) -> None:
    """Add attrs to the calling thread's innermost open span (a callee
    stamping what it launched onto its caller's span). Armed only:
    sites guard with `if trace.enabled():`, so a disarmed site builds no
    kwargs dict."""
    if _REC is None:
        return
    s = _current_stack()
    if s:
        s[-1].attrs.update(attrs)


def wrap(name: str, fn: Callable[[], Any], parent=None, **attrs):
    """Wrap a thunk so it runs under a span parented to `parent` —
    the cross-thread link for CommitWorker jobs (the heavy commit half
    joins its originating wave's trace). Disarmed: returns `fn`
    unchanged, no closure allocated beyond this call."""
    if _REC is None:
        return fn
    if isinstance(parent, Span):
        parent = parent.ctx()
    if parent is None:
        parent = _current_ctx()

    def run():
        # ON-stack on the worker thread: spans the job opens inside
        # (tick.commit.materialize/writeback, a raft.propose from the
        # store write-back) nest under this one instead of becoming
        # orphan roots — the whole point of the cross-thread link
        with span(name, parent=parent, **attrs):
            return fn()

    return run


# ----------------------------------------------------------------- arming
def arm(capacity: int = DEFAULT_CAPACITY, clock=None) -> FlightRecorder:
    """Arm the tracer (idempotent re-arm replaces the recorder)."""
    global _REC, _GEN
    r = FlightRecorder(capacity=capacity, clock=clock)
    with _REG_LOCK:
        _GEN += 1
        _REC = r
    return r


def disarm() -> None:
    global _REC, _RETIRED
    with _REG_LOCK:
        if _REC is not None:
            # keep the recorder readable across the disarm: report hooks
            # (the chaos makereport section) and a window's readers run
            # AFTER the owner disarmed
            _RETIRED = _REC
        _REC = None
    # a disarm must not leave implicit parents behind for the next arm
    s = getattr(_tls, "stack", None)
    if s:
        del s[:]


def active() -> bool:
    return _REC is not None


def recorder() -> FlightRecorder | None:
    return _REC


def retired() -> FlightRecorder | None:
    """The recorder the last disarm() retired, until clear_retired_tail():
    a traced window's records stay readable after the window closed."""
    return _RETIRED


@contextmanager
def armed(capacity: int = DEFAULT_CAPACITY, clock=None):
    """`with trace.armed() as rec: ...` — the per-test arming surface;
    always disarms on exit (the conftest guard fails leaks)."""
    r = arm(capacity=capacity, clock=clock)
    try:
        yield r
    finally:
        disarm()


def tail_text(n: int = 64) -> str:
    """Crash-forensics helper: the armed recorder's tail, or "" when
    disarmed — callers (wedge monitor, chaos harness) print it next to
    their stack dump / CHAOS_SEED without caring whether tracing is on."""
    r = _REC
    return r.tail_text(n) if r is not None else ""


# the recorder the most recent disarm() retired — lets a post-teardown
# report hook still show what it held
_RETIRED: FlightRecorder | None = None


def last_tail_text(n: int = 64) -> str:
    """The armed tail, falling back to the tail of the recorder the last
    disarm retired — for hooks that run after the owning harness already
    disarmed (the conftest chaos report section). Clear the retired
    recorder with `clear_retired_tail()` before each scope that must not
    see a stale predecessor's spans."""
    r = _REC if _REC is not None else _RETIRED
    return r.tail_text(n) if r is not None else ""


def clear_retired_tail() -> None:
    global _RETIRED
    _RETIRED = None


# ---------------------------------------------------------------- env var
# SWARMKIT_TPU_TRACE arms the tracer in subprocesses (multi-process
# swarmd tests, operator debugging): "1" or a ring capacity.
_ENV_VAR = "SWARMKIT_TPU_TRACE"

_env_val = os.environ.get(_ENV_VAR, "").strip().lower()
if _env_val and _env_val not in ("0", "false", "off", "no"):
    try:
        _cap = int(_env_val)
    except ValueError:
        _cap = DEFAULT_CAPACITY
    arm(capacity=_cap if _cap > 1 else DEFAULT_CAPACITY)
