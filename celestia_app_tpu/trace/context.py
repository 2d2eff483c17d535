"""Request/block-scoped trace context: one trace_id from RPC submission
to DAH root.

PR 2 made the device pipeline legible; this layer makes everything above
it attributable: a `TraceContext` is issued at request entry (the three
serving planes' BroadcastTx handlers, or locally by `TestNode.broadcast`)
and threaded EXPLICITLY through the layers — mempool entries store the
submitting request's context, the block built from a reap adopts the
first reaped tx's trace_id, and every span below (square build, device
dispatch, consensus round, commit) joins that trace.  The contextvar here
is an in-thread convenience so deep call stacks (square.build inside
App.prepare_proposal) pick up the active context without threading a
parameter through every signature; across threads the context object
itself is passed (mempool entry -> proposer thread), never the
thread-local.

`trace_span` is the measurement primitive: it opens a child context,
makes it current for the body, and on exit exports the span THREE ways —

  * a row in the per-name event table (same shape tracer.span wrote, plus
    trace_id/span_id/parent_span_id columns), keeping the existing
    `celestia_<name>_seconds` histogram families alive;
  * an OTLP-shaped row in the `spans` table (trace/spans.py), pulled via
    GET /trace_tables/spans or mirrored to $CELESTIA_SPANS_OUT JSONL —
    the whole-block lifecycle tree reconstructs from this one table;
  * optionally one observation on the end-to-end phase histogram
    `celestia_e2e_seconds{phase=...}` (the `e2e=` argument).

Every span also opens through `SpanClock`, the one place the package
enters `jax.profiler.TraceAnnotation`: the bare span name lands on the
device trace as a host event (recorded only while a profiler session
runs — `$CELESTIA_PROFILE_BLOCKS`, or any `jax.profiler.start_trace`),
and the span's row carries `start_ns`/`end_ns` on the clock the profiler
stamps host events with (`time.time_ns`, CLOCK_REALTIME; a trace's
events are offsets from its `profile_start_time` on that clock; the
duration itself is timed on the monotonic clock) plus, while a profiler
session records, `cpu_ms`: the thread CPU time over the body — busy time
apart from waiting on the GIL or the device.

A span opened with `root=False` outside any trace joins none: it mints
no root context and writes no OTLP row, only its table row, histogram
and annotation.  The serving path's per-sample steps open so: outside a
request's trace they have no tree to join, and they run once a sample.

$CELESTIA_TRACE=off mutes every export (the annotation stays: one C++
call that records nothing without a profiler session); context
PROPAGATION still runs so explicit threading (mempool-entry contexts,
block adoption) never breaks when tracing is muted.  No device syncs
anywhere: spans time host calls the layers already make.

Cross-NODE propagation (the fleet era): `serialize_context` renders the
active identity as the `x-celestia-trace` header value
(`<32-hex trace_id>-<16-hex span_id>`), and `adopt_context` /
`adopt_or_new` rebuild it on the receiving process — SAME trace_id, fresh
span_id, the sender's span as parent — so a request crossing the wire
stays one trace.  Every root/adopted context stamps a `node_id` baggage
entry (a stable per-process identity, `$CELESTIA_NODE_ID` override) so
merged spans tables attribute each row to its emitting process.
"""

from __future__ import annotations

import os
import re
import socket
import time
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from random import Random

from celestia_app_tpu.trace import spans
from celestia_app_tpu.trace.metrics import registry
from celestia_app_tpu.trace.tracer import (
    SPAN_LABEL_ATTRS,
    trace_enabled,
    traced,
)

#: The one header name every inter-node hop uses (HTTP header, gRPC
#: metadata key, and the gossip envelope's "trace" field all carry the
#: same serialized value).
TRACE_HEADER = "x-celestia-trace"

_NODE_ID: str | None = None

# Trace and span ids come from a private Mersenne Twister seeded from the
# OS (and reseeded in a forked child), as OpenTelemetry's generator does:
# spans open on serving hot paths, where an os.urandom syscall per id
# costs more than the rest of the span on a host with slow syscalls.  Its
# own instance, so a caller seeding the global `random` cannot make two
# processes mint the same ids.
_IDS = Random()
os.register_at_fork(after_in_child=_IDS.seed)
getrandbits = _IDS.getrandbits
_HEADER_RE = re.compile(r"^([0-9a-f]{32})-([0-9a-f]{16})$")


def node_id() -> str:
    """Stable per-process node identity: `$CELESTIA_NODE_ID` when set,
    else `<hostname>-<pid>` — computed once so every span, flight bundle,
    and fleet row a process emits carries the same value.  Sanitized to
    `[A-Za-z0-9._-]` (it lands in filenames and header values)."""
    global _NODE_ID
    if _NODE_ID is None:
        raw = os.environ.get("CELESTIA_NODE_ID") or (
            f"{socket.gethostname()}-{os.getpid()}"
        )
        _NODE_ID = re.sub(r"[^A-Za-z0-9._-]", "_", raw) or "node"
    return _NODE_ID


def _reset_node_id_for_tests() -> None:
    global _NODE_ID
    _NODE_ID = None


@dataclass(frozen=True)
class TraceContext:
    """Identity + baggage of one request or block trace.

    `trace_id` is stable for the whole tree; each span gets its own
    `span_id` with `parent_id` linking it to its creator.  `baggage`
    carries low-volume attribution (height, round, k, source) copied onto
    every descendant span's attributes.  `start_unix_ns` is the wall
    clock at trace issue — the anchor the e2e `total` phase measures
    from.
    """

    trace_id: str
    span_id: str
    parent_id: str | None = None
    baggage: dict = field(default_factory=dict)
    start_unix_ns: int = 0

    def child(self, **baggage) -> "TraceContext":
        """A child context: same trace, fresh span id, merged baggage."""
        return TraceContext(
            trace_id=self.trace_id,
            span_id=_new_span_id(),
            parent_id=self.span_id,
            baggage={**self.baggage, **baggage},
            start_unix_ns=self.start_unix_ns,
        )


def _new_span_id() -> str:
    return f"{getrandbits(64):016x}"


def new_context(**baggage) -> TraceContext:
    """Issue a fresh root context (a new trace_id) — request entry.  The
    issuing process's `node_id` rides the baggage (explicit baggage wins,
    so a per-server identity can override the process default)."""
    return TraceContext(
        trace_id=f"{getrandbits(128):032x}",
        span_id=_new_span_id(),
        baggage={"node_id": node_id(), **baggage},
        start_unix_ns=time.time_ns(),
    )


def serialize_context(ctx: TraceContext | None = None) -> str | None:
    """The wire form of `ctx` (default: the current context) for the
    `x-celestia-trace` header / gRPC metadata / gossip `trace` field:
    `<trace_id>-<span_id>`, or None outside a trace (the hop then carries
    no header and the receiver mints its own root)."""
    ctx = ctx if ctx is not None else current_context()
    if ctx is None:
        return None
    return f"{ctx.trace_id}-{ctx.span_id}"


def adopt_context(header: str | None, **baggage) -> TraceContext | None:
    """Rebuild an incoming wire context: SAME trace_id, fresh span_id,
    the sender's span as parent — the receiving process JOINS the trace
    instead of re-minting it, which is what stitches a multi-node drill
    under one trace_id.  Returns None on an absent or malformed header
    (a bad header must never fail the request — the caller falls back to
    `new_context`).  This process's `node_id` is stamped into baggage
    (explicit baggage wins, for per-server identities in one process)."""
    if not header:
        return None
    m = _HEADER_RE.match(header.strip().lower())
    if m is None:
        return None
    trace_id, parent_span = m.group(1), m.group(2)
    return TraceContext(
        trace_id=trace_id,
        span_id=_new_span_id(),
        parent_id=parent_span,
        baggage={"node_id": node_id(), **baggage},
        start_unix_ns=time.time_ns(),
    )


def adopt_or_new(header: str | None, **baggage) -> TraceContext:
    """Request entry on a serving plane: adopt the peer's context when the
    hop carried one, else issue a fresh root — the ONE pattern every rpc/
    ingress threads (trace_lint rule 7 pins this)."""
    return adopt_context(header, **baggage) or new_context(**baggage)


_CURRENT: ContextVar[TraceContext | None] = ContextVar(
    "celestia_trace_context", default=None
)


def current_context() -> TraceContext | None:
    """The context active on THIS thread/task, or None outside a trace."""
    return _CURRENT.get()


@contextmanager
def use_context(ctx: TraceContext | None):
    """Make `ctx` current for the body — the explicit hand-off point when
    a context crosses a thread boundary (block production adopting a
    mempool entry's context)."""
    token = _CURRENT.set(ctx)
    try:
        yield ctx
    finally:
        _CURRENT.reset(token)


#: Baggage keys copied onto every span's event-table row (when the span
#: sets no attribute of that name): the block a span worked for and which
#: side of it (prepare / process), so a height's rows select without a
#: join against the spans table.
ROW_BAGGAGE = ("height", "phase")

_TRACE_ANNOTATION = None
# Span histograms by span name: registry families are never dropped, so
# the lookup is paid once per name, not once per span.
_HISTOGRAMS: dict = {}


def _annotation(name: str):
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name)


class SpanClock:
    """The one way the package opens a span: a profiler annotation under
    the span's bare name, and — when `timed` — the body's wall interval
    (`start_ns` on `time.time_ns`, the profiler's host clock; `end_ns` is
    `start_ns` plus the interval timed on the monotonic clock, so a clock
    step cannot bend a duration) and, while a profiler session records,
    its thread CPU time (`cpu_ns`).
    `trace_span`, `Tracer.span` and the manual spans (mempool reap) all
    time through it.

    The thread CPU clock is a system call (about 6 µs alone and 15 µs
    inside a span on a TPU v5e host, against 0.1 µs for the wall clock),
    and a serving thread opens three spans per sample, so it is read only
    while a profiler records: the window in which busy time is told from
    waiting on the GIL or the device."""

    __slots__ = ("_annotation", "_timed", "_t0", "_cpu0", "start_ns",
                 "end_ns", "cpu_ns")

    def __init__(self, name: str, timed: bool = True):
        self._annotation = _annotation(name)
        self._timed = timed
        self.start_ns = self.end_ns = self._t0 = 0
        self.cpu_ns = self._cpu0 = None

    def __enter__(self) -> "SpanClock":
        self._annotation.__enter__()
        if self._timed:
            # The CPU interval nests inside the wall one: cpu <= wall.
            self.start_ns = time.time_ns()
            self._t0 = time.perf_counter_ns()
            if _TRACE_ANNOTATION.is_enabled():
                self._cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if self._timed:
            if self._cpu0 is not None:
                self.cpu_ns = time.thread_time_ns() - self._cpu0
            self.end_ns = self.start_ns + time.perf_counter_ns() - self._t0
        self._annotation.__exit__(*exc)
        return False

    @property
    def elapsed_ns(self) -> int:
        return self.end_ns - self.start_ns

    def row_fields(self) -> dict:
        """The timing columns every span row carries (`cpu_ms` while a
        profiler records)."""
        fields = {
            "duration_ms": self.elapsed_ns / 1e6,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
        }
        if self.cpu_ns is not None:
            fields["cpu_ms"] = self.cpu_ns / 1e6
        return fields


class _Span:
    """One open `trace_span` (a class, not a generator: the span opens on
    serving hot paths, where the generator protocol's cost shows)."""

    __slots__ = ("_name", "_ctx", "_e2e", "_buckets", "_baggage", "_root",
                 "_attrs", "_child", "_token", "_clock")

    def __init__(self, name, ctx, e2e, buckets, baggage, root, attrs):
        self._name, self._ctx, self._e2e = name, ctx, e2e
        self._buckets, self._baggage, self._root = buckets, baggage, root
        self._attrs = attrs

    def __enter__(self) -> dict:
        parent = self._ctx if self._ctx is not None else current_context()
        if parent is None and not self._root:
            self._child = self._token = None
        else:
            bag = self._baggage or {}
            child = self._child = (
                parent.child(**bag) if parent is not None
                else new_context(**bag)
            )
            self._token = _CURRENT.set(child)
        self._clock = SpanClock(self._name, timed=trace_enabled())
        self._clock.__enter__()
        return self._attrs

    def __exit__(self, *exc) -> bool:
        clock = self._clock
        clock.__exit__(*exc)
        if self._token is not None:
            _CURRENT.reset(self._token)
        if clock._timed:
            attrs = self._attrs
            export_span(self._name, self._child, clock, attrs,
                        buckets=self._buckets, e2e=self._e2e)
            attrs["duration_ms"] = clock.elapsed_ns / 1e6
        return False


def trace_span(
    name: str,
    ctx: TraceContext | None = None,
    e2e: str | None = None,
    buckets: tuple[float, ...] | None = None,
    baggage: dict | None = None,
    root: bool = True,
    **attrs,
) -> _Span:
    """Measure one span of trace `ctx` (explicit, else the current one,
    else a fresh root — unless `root` is False: then the span joins no
    trace and writes no OTLP row): `with trace_span(...) as sp:`.  `sp`
    is a mutable attr dict so results discovered inside the body (square
    size, vote power) land on the span; after the body it also holds the
    span's `duration_ms` (absent when tracing is muted).  `e2e` names the
    celestia_e2e_seconds phase this span feeds, if any; `baggage` is
    merged into the span's context, so every span opened inside the body
    carries it too.
    """
    return _Span(name, ctx, e2e, buckets, baggage, root, attrs)


def export_span(name, ctx, clock: SpanClock, attrs, buckets=None,
                e2e=None) -> None:
    """The span's three exports (event table + histogram + OTLP row) plus
    the optional e2e phase — all off the timed region.  Public for call
    sites that must pick the span's context AFTER the measured work (the
    mempool reap learns which trace it belongs to by doing the reap);
    the caller has checked `trace_enabled()`.  A span outside any trace
    (`ctx` None) writes no ids and no OTLP row."""
    elapsed_ns = clock.elapsed_ns
    row = clock.row_fields()
    if ctx is not None:
        row["trace_id"] = ctx.trace_id
        row["span_id"] = ctx.span_id
        row["parent_span_id"] = ctx.parent_id
        for k in ROW_BAGGAGE:
            if k in ctx.baggage:
                row[k] = ctx.baggage[k]
    row.update(attrs)
    traced().append(name, row)
    labels = {a: str(attrs[a]) for a in SPAN_LABEL_ATTRS if a in attrs}
    hist = _HISTOGRAMS.get(name)
    if hist is None:
        hist = _HISTOGRAMS[name] = registry().histogram(
            f"celestia_{name}_seconds", f"wall time of {name}",
            **({"buckets": buckets} if buckets else {}),
        )
    hist.observe(elapsed_ns / 1e9, **labels)
    if ctx is None:
        return
    spans.record_span(
        name, ctx, clock.start_ns, clock.end_ns, {**ctx.baggage, **attrs}
    )
    if e2e is not None:
        spans.observe_e2e(
            e2e, elapsed_ns / 1e9, namespace=ctx.baggage.get("namespace")
        )
