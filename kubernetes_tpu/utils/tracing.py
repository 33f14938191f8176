"""OTel-style request tracing (SURVEY §5.1 component-base/tracing) and
the host's self-time ledger.

A lightweight in-process tracer: spans with trace/span ids, parentage
via contextvars (so nested awaits auto-parent), W3C `traceparent`
propagation for cross-component HTTP hops, and export to the Chrome
trace-event JSON that Perfetto (and chrome://tracing) loads — the same
timeline family the jax profiler emits, so a control-plane trace and a
device trace can sit side by side.

A span is a WALL interval: it stays open across every `await` inside
it, so on a loop with hundreds of tasks in flight "which span is open"
says nothing about what the thread is running. The ledger says that.
Per thread it keeps the name executing now and since when, and at every
transition — an event-loop callback starts or ends, a span is entered
or left on that thread, the collector starts or stops — it charges the
elapsed stretch to the name that was executing. Inside a callback that
name is the innermost open span of the callback's own context (a task
resumed inside `scheduler.bind` charges `scheduler.bind` for the
microseconds it runs and nothing for the milliseconds it awaits), else
the context's ambient name (`ambient`: what a long-lived task such as
a reflector says its loose time is), else `loop.other`. Between
callbacks the thread is in `loop.idle` (the selector wait and the
loop's own bookkeeping); inside the collector in `host.gc`. Per span
name the ledger also keeps how often it closed and its summed wall.

What comes out:
- six counter families (`register_into`), growing only while tracing
  is on: `ktpu_host_self_seconds_total{layer,span,thread}`,
  `ktpu_span_wall_seconds_total{layer,span}`,
  `ktpu_span_total{layer,span}`, `ktpu_loop_wall_seconds_total`,
  `ktpu_loop_busy_seconds_total`, `ktpu_trace_spans_dropped_total`;
- leaf records in `Tracer.spans`, beside the spans (same `name` /
  `start` / `end` shape, the thread in `attrs`): one per stretch a
  loop thread ran with no span of that callback's own open — a
  callback's loose time, the selector wait, the collector — stretched
  over the spans entered and left inside it. They tile each loop
  thread's time, and a reader that gives an instant to the
  latest-started record still open then names what the thread ran:
  a span entered in this very callback, else the leaf, never a span
  that some other task left open across an `await`.

A span's layer comes from `LAYERS` below and nowhere else.

Where spans come from:
- APIServer: one span per request (verb/resource/user/status), child
  spans for store ops and admission;
- Scheduler: a span per scheduling attempt and per binding cycle,
  attributed with the pod key;
- anything else via `TRACER.span(...)`.

The pod's journey (create → schedule → bind) crosses async boundaries
the context can't follow (informer → queue → cycle), so spans carry a
`pod` attribute and `trace_for(pod_key)` assembles the cross-component
story — the reference's kube-apiserver + kube-scheduler traces joined
on object identity.

Disabled by default, and off means off: `span()` returns one shared
no-op context manager, no loop hook and no `gc` callback are installed,
no counter grows (utiltrace remains the always-on threshold logger).
"""

from __future__ import annotations

import asyncio.events
import contextlib
import contextvars
import gc
import itertools
import json
import logging
import threading
import time
import zlib
from collections import deque
from typing import Any

from kubernetes_tpu.metrics.registry import Counter, Registry
from kubernetes_tpu.utils import flags

logger = logging.getLogger(__name__)

_ids = itertools.count(1)
_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "ktpu_current_span", default=None)
_ambient: contextvars.ContextVar["str | None"] = contextvars.ContextVar(
    "ktpu_ambient_span", default=None)

#: pod-annotation key carrying the creating request's traceparent across
#: the informer/queue async boundary (the context can't follow a pod from
#: the apiserver handler to the scheduling cycle; the object can).
TRACEPARENT_ANNOTATION = "ktpu.io/traceparent"

#: the ledger's own names: between callbacks, in a callback that no span
#: and no ambient name claims, inside the collector.
IDLE = "loop.idle"
OTHER = "loop.other"
GC = "host.gc"
_OWN_NAMES = frozenset((IDLE, OTHER, GC))

#: span-name prefix → layer, first match wins (PERF.md §3 uses the same
#: layer words). Work done for the `events` resource is the `events`
#: layer whichever component does it (`wire.create.events`,
#: `store.commit.events`); a name no row claims is filed under its own
#: first component.
LAYERS: tuple[tuple[str, str], ...] = (
    (IDLE, "idle"),
    (OTHER, "other"),
    (GC, "gc"),
    ("events.", "events"),
    ("wire.", "wire"),
    ("apiserver.", "wire"),
    ("grpc.", "wire"),
    ("admission.", "wire"),
    ("store.", "store"),
    ("informer.", "informer"),
    ("scheduler.queue", "queue"),
    ("scheduler.loop", "queue"),
    ("scheduler.bind", "bind"),
    ("framework.PreBind", "bind"),
    ("framework.Bind", "bind"),
    ("framework.PostBind", "bind"),
    ("scheduler.", "attempt"),
    ("framework.", "attempt"),
    ("solver.", "attempt"),
)
_layer_of: dict[str, str] = {}


def layer_of(name: str) -> str:
    """The layer a span name belongs to, resolved once per name."""
    layer = _layer_of.get(name)
    if layer is None:
        if name.endswith(".events"):
            layer = "events"
        else:
            layer = next((lay for prefix, lay in LAYERS
                          if name.startswith(prefix)),
                         name.split(".", 1)[0])
        _layer_of[name] = layer
    return layer


def current_span() -> "Span | None":
    """The span the calling context is inside, if any (shared across all
    Tracer instances — parentage is a property of the call stack, not of
    the collector)."""
    return _current.get()


class ambient:
    """`with ambient(name):` names the loose time of the calling context:
    what its callbacks are charged to while no span is open in them. For
    long-lived loops (a reflector, a watch pump, the scheduling loop)
    that start before tracing does and so can never sit inside a span,
    and around the creation of a transport, whose I/O callbacks keep the
    context they were registered in. Tasks and callbacks started inside
    inherit it. Two contextvar writes, tracing on or off."""

    __slots__ = ("name", "_token")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> None:
        self._token = _ambient.set(self.name)

    def __exit__(self, *exc) -> None:
        _ambient.reset(self._token)


def stamp_traceparent(obj: dict) -> None:
    """Stamp the current span's traceparent into `obj`'s annotations so a
    later consumer in another task (the scheduler's attempt span) can
    parent to the request that created the object. No-op outside a span,
    so call sites need no enabled-check of their own."""
    sp = _current.get()
    if sp is None:
        return
    meta = obj.setdefault("metadata", {})
    ann = meta.get("annotations")
    if ann is None:
        ann = meta["annotations"] = {}
    ann.setdefault(TRACEPARENT_ANNOTATION,
                   format_traceparent(sp.trace_id, sp.span_id))


def traceparent_of(obj: dict | None) -> str | None:
    """Read a stamped traceparent back off an object (see
    stamp_traceparent)."""
    if not obj:
        return None
    ann = (obj.get("metadata") or {}).get("annotations")
    if not ann:
        return None
    return ann.get(TRACEPARENT_ANNOTATION)


class Span:
    """One wall interval; its own context manager (`with tracer.span(..)
    as sp`), sync or across awaits."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "end", "attrs", "_tracer", "_token", "_seq")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: str | None, attrs: dict, tracer=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = 0.0            # set when entered (or by record())
        self.end: float | None = None
        self.attrs = attrs
        self._tracer = tracer
        self._token = None
        self._seq = -1

    @property
    def duration_ms(self) -> float:
        return 1000.0 * ((self.end or time.monotonic()) - self.start)

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        self._tracer._entered(self, False)
        return self

    def __exit__(self, *exc) -> None:
        _current.reset(self._token)
        self._tracer._left(self, False)


class _Leaf:
    """One executed stretch of one thread, in the spans' ring: the shape
    a reader of `Tracer.spans` expects of a span, no ids."""

    __slots__ = ("name", "start", "end", "attrs")
    trace_id = span_id = ""
    parent_id = None

    def __init__(self, name: str, start: float, end: float, attrs: dict):
        self.name = name
        self.start = start
        self.end = end
        self.attrs = attrs


class _Section:
    """`with tracer.section(name):` — a span for hot synchronous code.
    Counted and charged like one (closes, wall, self-time under its
    name), with no ids, no attributes, no parent and no record in the
    ring. Not to be held across an `await`."""

    __slots__ = ("name", "start", "_tracer")

    def __init__(self, name: str, tracer: "Tracer"):
        self.name = name
        self.start = 0.0
        self._tracer = tracer

    def __enter__(self) -> None:
        self._tracer._entered(self, True)

    def __exit__(self, *exc) -> None:
        self._tracer._left(self, True)


class _ThreadLedger:
    """One thread's side of the ledger. Only its own thread writes
    `name`/`since`/`totals`, so the hot path takes no lock."""

    __slots__ = ("label", "attrs", "epoch", "name", "since", "t0", "wall",
                 "leaf", "cut", "stack", "seq", "busy", "gc_at", "handle",
                 "totals", "others", "dropped")

    def __init__(self, ident: int):
        self.label = "worker"
        self.attrs = {"thread": "worker", "tid": ident}
        self.epoch = 0
        self.name: str | None = None     # executing now; None = untracked
        self.since = 0.0
        self.t0: float | None = None     # when this epoch's wall began
        self.wall = 0.0                  # settled seconds the ledger was on
        #: the leaf record being stretched, and whether the next loose
        #: stretch must start a new one (a record that is still open
        #: began after this leaf did, and would beat it)
        self.leaf: _Leaf | None = None
        self.cut = False
        #: the names interrupted by what was entered in the running
        #: callback (off the loop: on this thread) and is still open;
        #: empty = the thread runs loose, under no span of its own
        self.stack: list = []
        self.seq = 0                     # callbacks run; spans remember it
        self.busy = False                # inside _switch (gc re-entrancy)
        self.gc_at: float | None = None  # the collection being charged
        self.handle = None               # the loop callback being run
        #: name -> [closes, wall seconds, self seconds]
        self.totals: dict[str, list] = {}
        #: loop.other by the callback's qualified name
        self.others: dict[str, float] = {}
        self.dropped = 0

    def to_loop(self) -> None:
        self.label = "loop"
        self.attrs = {"thread": "loop", "tid": self.attrs["tid"]}
        # the ledger's own names are series from the first callback on,
        # a stretch without a collection reads 0 and not "no such series"
        for name in (IDLE, OTHER, GC):
            self.totals.setdefault(name, [0, 0.0, 0.0])


_ORIG_HANDLE_RUN = asyncio.events.Handle._run
#: a loose stretch shorter than this gets no leaf record of its own: the
#: leaf before it on that thread is stretched over it. That keeps the
#: loop's bookkeeping between two callbacks from splitting a run of one
#: name, and the ring within what a pure-Python reader can sort. The
#: counters stay exact; only the records are this coarse.
_FOLD_S = 10e-6


def _callback_name(handle) -> str:
    """Qualified name of what a loop handle runs: the coroutine of a
    task's step, else the callable."""
    cb = handle._callback
    owner = getattr(cb, "__self__", None)
    get_coro = getattr(owner, "get_coro", None)
    if get_coro is not None:
        cb = get_coro()
    return getattr(cb, "__qualname__", None) or type(cb).__qualname__


class _LedgerCounter(Counter):
    """A counter family whose values the ledger computes when read."""

    def __init__(self, name: str, help_: str, labels, collect):
        super().__init__(name, help_, labels)
        self._collect = collect

    def _sync(self) -> None:
        values = self._collect()
        with self._lock:
            self._values.clear()
            self._values.update(values)

    def value(self, **labels: str) -> float:
        self._sync()
        return super().value(**labels)

    def render(self) -> str:
        self._sync()
        return super().render()


class Tracer:
    """Span collector and self-time ledger. Bounded ring (oldest records
    drop, counted) so an always-on tracer can't grow without limit.

    `enabled` is a plain attribute to callers; setting it installs or
    removes the loop hook and the collector callback. One tracer at a
    time sees the event loop's callbacks: the one enabled last.

    `threshold_ms` is the utiltrace-semantics dump: when a ROOT span (no
    parent — e.g. a request arriving with no traceparent) closes slower
    than the threshold, its whole subtree logs as an indented breakdown;
    fast roots stay silent. Defaults from KTPU_TRACE_THRESHOLD_MS
    (unset = no tree dumps; the always-on per-attempt threshold logger
    remains utils/trace.Trace)."""

    def __init__(self, enabled: bool = False, max_spans: int = 65536,
                 threshold_ms: float | None = None):
        if threshold_ms is None:
            threshold_ms = flags.get("KTPU_TRACE_THRESHOLD_MS")
        self.threshold_ms = threshold_ms
        # deque(maxlen): O(1) ring-buffer appends — a full list ring
        # would memmove 64k entries per span on the hot path.
        self.spans: "deque[Span | _Leaf]" = deque(maxlen=max_spans)
        self._enabled = False
        self._epoch = 0
        self._off_at = 0.0
        self._local = threading.local()
        self._ledgers: list[_ThreadLedger] = []
        self._ledgers_lock = threading.Lock()
        self._hook = self._make_hook()
        fam = self._families
        self._counters = tuple(
            _LedgerCounter(name, help_, labels,
                           lambda name=name: fam()[name])
            for name, help_, labels in (
                ("ktpu_host_self_seconds_total",
                 "Seconds a thread executed under a span name while "
                 "tracing was on (children and waits excluded)",
                 ("layer", "span", "thread")),
                ("ktpu_span_wall_seconds_total",
                 "Summed wall of closed spans (waits included)",
                 ("layer", "span")),
                ("ktpu_span_total", "Spans closed", ("layer", "span")),
                ("ktpu_loop_wall_seconds_total",
                 "Seconds the ledger was on, on event-loop threads", ()),
                ("ktpu_loop_busy_seconds_total",
                 "ktpu_loop_wall_seconds_total less loop.idle", ()),
                ("ktpu_trace_spans_dropped_total",
                 "Records the span ring dropped because it was full", ()),
            ))
        self.enabled = enabled

    # -- on / off ----------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    @enabled.setter
    def enabled(self, on: bool) -> None:
        on = bool(on)
        if on == self._enabled:
            return
        now = time.monotonic()
        if on:
            self._epoch += 1
            self._enabled = True
            asyncio.events.Handle._run = self._hook
            gc.callbacks.append(self._on_gc)
            if asyncio.events._get_running_loop() is not None:
                # switched on from inside a callback: the rest of it is
                # this thread's first stretch
                led = self._ledger(now)
                led.to_loop()
                self._switch(led, now, self._context_name(_current.get(),
                                                          _ambient.get()))
            return
        mine = getattr(self._local, "ledger", None)
        if mine is not None and mine.epoch == self._epoch:
            # this thread's open stretch ends here (its leaf with it)
            self._switch(mine, now, None)
            del mine.stack[:]
        self._enabled = False
        self._off_at = now
        if asyncio.events.Handle._run is self._hook:
            asyncio.events.Handle._run = _ORIG_HANDLE_RUN
        with contextlib.suppress(ValueError):
            gc.callbacks.remove(self._on_gc)
        with self._ledgers_lock:
            ledgers = list(self._ledgers)
        for led in ledgers:
            # another thread's open stretch is that thread's to settle
            # (up to _off_at, at its next transition); its wall is not
            if led.t0 is not None:
                led.wall += now - led.t0
                led.t0 = None
        # whoever reads the ring now reads one that nothing is appended
        # to: a thread caught between its check of `enabled` and its
        # append holds the old ring
        self.spans = deque(self.spans, maxlen=self.spans.maxlen)

    def _make_hook(self):
        tracer = self
        orig = _ORIG_HANDLE_RUN
        monotonic = time.monotonic

        def _run(handle):
            """asyncio.events.Handle._run with the ledger's callback
            boundary around it."""
            now = monotonic()
            led = tracer._ledger(now)
            if led.label != "loop":
                led.to_loop()
            ctx = handle._context
            if led.stack:
                # left over from a callback this hook did not see the
                # end of (tracing was switched on inside it)
                del led.stack[:]
                led.cut = True
            led.seq += 1
            led.handle = handle
            tracer._switch(led, now, tracer._context_name(
                ctx.get(_current), ctx.get(_ambient)))
            try:
                return orig(handle)
            finally:
                now = monotonic()
                tracer._switch(led, now, IDLE)
                led.handle = None
                if led.stack:
                    # spans entered in this callback stay open across
                    # an await: they began after the leaf did, so the
                    # leaf ends with the callback and is not reused
                    del led.stack[:]
                    led.cut = True
                    if led.leaf is not None and tracer._enabled:
                        led.leaf.end = now

        return _run

    @staticmethod
    def _context_name(sp: "Span | None", ambient: str | None) -> str:
        if sp is not None and sp.end is None:
            return sp.name
        return ambient or OTHER

    # -- the ledger --------------------------------------------------------

    def _ledger(self, now: float) -> _ThreadLedger:
        """The calling thread's ledger, woken into the current epoch."""
        try:
            led = self._local.ledger
        except AttributeError:
            led = self._local.ledger = _ThreadLedger(threading.get_ident())
            with self._ledgers_lock:
                self._ledgers.append(led)
        if led.epoch != self._epoch:
            # whatever was open belongs to a stretch that has ended
            led.epoch = self._epoch
            led.name = led.leaf = led.gc_at = None
            led.cut = False
            led.seq = 0
            del led.stack[:]
        if led.t0 is None and self._enabled:
            led.t0 = now
        return led

    def _switch(self, led: _ThreadLedger, now: float,
                name: str | None) -> None:
        """Charge the open stretch to the name that ran it; `name` runs
        from `now` on. A loose stretch of a loop thread (no span of this
        callback's own open) is also covered by a leaf record."""
        prev = led.name
        since = led.since
        if now < since:
            # `now` was read before a collection that has since been
            # charged: the clock of a thread's ledger never runs back
            now = since
        led.since = now
        led.name = name
        if prev is None:
            return
        led.busy = True
        dt = now - since
        ent = led.totals.get(prev)
        if ent is None:
            ent = led.totals[prev] = [0, 0.0, 0.0]
        ent[2] += dt
        if prev is OTHER and led.handle is not None:
            key = _callback_name(led.handle)
            led.others[key] = led.others.get(key, 0.0) + dt
        if not led.stack and led.label == "loop":
            leaf = led.leaf
            spans = self.spans
            if leaf is not None and not led.cut and (
                    leaf.name == prev or dt < _FOLD_S):
                leaf.end = now
            elif self._enabled:
                led.leaf = leaf = _Leaf(prev, since, now, led.attrs)
                led.cut = False
                if len(spans) == spans.maxlen:
                    led.dropped += 1
                spans.append(leaf)
        led.busy = False

    def _entered(self, scope, section: bool) -> None:
        """A span (its own record in the ring) or a section (none)
        begins on this thread."""
        # a span starts where its first stretch does, so nothing that is
        # open began between the two
        now = scope.start = time.monotonic()
        led = self._ledger(now)
        spans = self.spans
        if not self._enabled:
            return
        if not section:
            scope._seq = led.seq
            if len(spans) == spans.maxlen:
                led.dropped += 1
            spans.append(scope)
        prev = led.name
        self._switch(led, now, scope.name)
        led.stack.append(prev)

    def _left(self, scope, section: bool) -> None:
        now = time.monotonic()
        if not section:
            scope.end = now
        led = self._ledger(now)
        ent = led.totals.get(scope.name)
        if ent is None:
            ent = led.totals[scope.name] = [0, 0.0, 0.0]
        ent[0] += 1
        ent[1] += now - scope.start
        if self._enabled:
            stack = led.stack
            if stack and (section or scope._seq == led.seq):
                # left in the callback (off the loop: on the thread) it
                # was entered in: back to what it interrupted
                self._switch(led, now, stack[-1])
                stack.pop()
            else:
                # a span left in a later callback than it was entered
                # in: the context knows what encloses it; off the loop
                # nothing does
                self._switch(led, now, self._context_name(
                    _current.get(), _ambient.get())
                    if led.handle is not None else None)
        elif led.name is not None:
            # tracing went off under it, on another thread's say
            self._switch(led, min(now, self._off_at), None)
        if not section and self.threshold_ms is not None \
                and scope.parent_id is None \
                and scope.duration_ms >= self.threshold_ms:
            self._log_tree(scope)

    def _on_gc(self, phase: str, info: dict) -> None:
        """`gc.callbacks`: the collector's time is its own name, on the
        thread it interrupted, and nests there like a section — with a
        record of its own, which no span stands for it."""
        now = time.monotonic()
        led = self._ledger(now)
        if phase == "start":
            if led.busy or not self._enabled:
                return  # interrupted the ledger itself: left to `prev`
            prev = led.name
            self._switch(led, now, GC)
            led.stack.append(prev)
            led.gc_at = led.since
        elif led.gc_at is not None:
            start, led.gc_at = led.gc_at, None
            stack = led.stack
            self._switch(led, now, stack[-1] if stack else None)
            if stack:
                stack.pop()
            # the loose leaf it interrupted ended where it began
            led.cut = led.cut or not stack
            spans = self.spans
            if self._enabled:
                if len(spans) == spans.maxlen:
                    led.dropped += 1
                spans.append(_Leaf(GC, start, led.since, led.attrs))

    def cut(self) -> None:
        """A record with a caller-held start begins now — the queue
        stamps an entry that the attempt will `record` once it is popped.
        End the thread's loose leaf here, so that the next one starts
        after that record and not before it (a reader that prefers the
        latest-started open record would else prefer the wait)."""
        if not self._enabled:
            return
        now = time.monotonic()
        led = self._ledger(now)
        if not led.stack:
            self._switch(led, now, led.name)
        led.cut = True

    # -- export of the ledger ----------------------------------------------

    def _families(self) -> dict[str, dict[tuple, float]]:
        """The counter families' values, from every thread's ledger."""
        with self._ledgers_lock:
            ledgers = list(self._ledgers)
        now = time.monotonic()
        self_s: dict[tuple, float] = {}
        wall_s: dict[tuple, float] = {}
        closes: dict[tuple, float] = {}
        loop_wall = loop_idle = 0.0
        dropped = 0
        for led in ledgers:
            dropped += led.dropped
            if led.label == "loop":
                loop_wall += led.wall
                if led.t0 is not None:
                    loop_wall += now - led.t0
            for name, (n, wall, self_) in list(led.totals.items()):
                layer = layer_of(name)
                if self_ or name in _OWN_NAMES:
                    key = (layer, name, led.label)
                    self_s[key] = self_s.get(key, 0.0) + self_
                    if name is IDLE and led.label == "loop":
                        loop_idle += self_
                if n:
                    key = (layer, name)
                    closes[key] = closes.get(key, 0.0) + n
                    wall_s[key] = wall_s.get(key, 0.0) + wall
        return {
            "ktpu_host_self_seconds_total": self_s,
            "ktpu_span_wall_seconds_total": wall_s,
            "ktpu_span_total": closes,
            "ktpu_loop_wall_seconds_total": {(): loop_wall},
            "ktpu_loop_busy_seconds_total": {(): loop_wall - loop_idle},
            "ktpu_trace_spans_dropped_total": {(): float(dropped)},
        }

    def register_into(self, registry: Registry) -> None:
        """Serve the ledger's families through another registry's render
        (the WatchMetrics pattern: same objects, one truth)."""
        for c in self._counters:
            registry._metrics.setdefault(c.name, c)

    def unattributed(self, top: int = 5) -> list[tuple[str, float]]:
        """`loop.other` seconds by the callback's qualified name, largest
        first: where the next span or ambient name belongs."""
        with self._ledgers_lock:
            ledgers = list(self._ledgers)
        out: dict[str, float] = {}
        for led in ledgers:
            for key, s in list(led.others.items()):
                out[key] = out.get(key, 0.0) + s
        return sorted(out.items(), key=lambda kv: -kv[1])[:top]

    # -- span creation -----------------------------------------------------

    def span(self, name: str, *, traceparent: str | None = None,
             **attrs: Any):
        """Context manager for one span, sync or across awaits; the
        shared no-op when the tracer is off."""
        if not self._enabled:
            return _NOOP
        parent = _current.get()
        if traceparent:
            trace_id, parent_id = _parse_traceparent(traceparent)
        elif parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, parent_id = f"t{next(_ids):016x}", None
        return Span(name, trace_id, f"s{next(_ids):08x}", parent_id, attrs,
                    self)

    def section(self, name: str):
        """Context manager for hot synchronous code: counted and charged
        to the ledger under `name` like a span, but with no ids, no
        attributes and no record in the ring (see _Section). The shared
        no-op when the tracer is off."""
        if not self._enabled:
            return _NOOP
        return _Section(name, self)

    def annotate(self, **attrs: Any) -> None:
        """Attach attributes to the CURRENT span (e.g. the pod key a
        create request turns out to be about, known only after the body
        parses)."""
        if not self._enabled:
            return
        sp = _current.get()
        if sp is not None:
            sp.attrs.update(attrs)

    def record(self, name: str, start: float, end: float | None = None,
               **attrs: Any) -> "Span | None":
        """Retroactively record a COMPLETED span from caller-held
        timestamps (time.monotonic clock), parented to the current span —
        e.g. the scheduler's queue wait, which elapses across tasks no
        context can follow but whose endpoints the queue stamped. Wall
        and count only: no code ran under it."""
        if not self._enabled:
            return None
        parent = _current.get()
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, parent_id = f"t{next(_ids):016x}", None
        sp = Span(name, trace_id, f"s{next(_ids):08x}", parent_id, attrs)
        sp.start = start
        sp.end = end if end is not None else time.monotonic()
        led = self._ledger(time.monotonic())
        ent = led.totals.get(name)
        if ent is None:
            ent = led.totals[name] = [0, 0.0, 0.0]
        ent[0] += 1
        ent[1] += sp.end - start
        spans = self.spans
        if self._enabled:
            spans.append(sp)
        return sp

    def current_traceparent(self) -> str | None:
        sp = _current.get()
        if sp is None:
            return None
        return format_traceparent(sp.trace_id, sp.span_id)

    # -- threshold tree dump (utiltrace semantics for span trees) ----------

    def _log_tree(self, root: Span) -> None:
        by_parent: dict[str, list[Span]] = {}
        # a copy: the ledger appends leaf records while this runs
        for s in list(self.spans):
            if s.trace_id == root.trace_id and s.parent_id:
                by_parent.setdefault(s.parent_id, []).append(s)
        attrs = ",".join(f"{k}={v}" for k, v in root.attrs.items())
        lines = [f"Span[{root.name}{{{attrs}}}]: "
                 f"total {root.duration_ms:.1f}ms" if attrs else
                 f"Span[{root.name}]: total {root.duration_ms:.1f}ms"]

        def walk(sp: Span, depth: int) -> None:
            for child in sorted(by_parent.get(sp.span_id, ()),
                                key=lambda s: s.start):
                a = ",".join(f"{k}={v}" for k, v in child.attrs.items())
                lines.append(f'{"  " * depth}{child.name}'
                             f'{"{" + a + "}" if a else ""} '
                             f"{child.duration_ms:.1f}ms")
                walk(child, depth + 1)

        walk(root, 1)
        logger.info("\n".join(lines))

    # -- queries + export --------------------------------------------------

    def trace_for(self, pod_key: str) -> list[Span]:
        """Every span attributed to one pod, time-ordered — the
        cross-component create→schedule→bind story."""
        return sorted((s for s in list(self.spans)
                       if s.attrs.get("pod") == pod_key),
                      key=lambda s: s.start)

    def to_perfetto(self) -> str:
        """Chrome trace-event JSON (Perfetto/chrome://tracing/the jax
        profiler's timeline family). Complete ('X') events in µs: spans
        under pid 1, one track per trace (a checksum of the trace id, so
        the same trace lands on the same track in every process); leaf
        records under pid 2, one track per real thread."""
        events = []
        for s in list(self.spans):
            if s.end is None:
                continue
            leaf = type(s) is _Leaf
            args = {k: str(v) for k, v in s.attrs.items()}
            if not leaf:
                args.update(trace_id=s.trace_id, span_id=s.span_id)
                if s.parent_id:
                    args["parent_id"] = s.parent_id
            events.append({
                "name": s.name, "ph": "X", "pid": 2 if leaf else 1,
                "tid": s.attrs["tid"] if leaf
                else zlib.crc32(s.trace_id.encode()) % 100_000,
                "ts": round(s.start * 1e6, 3),
                "dur": round((s.end - s.start) * 1e6, 3),
                "args": args,
            })
        return json.dumps({"traceEvents": events}, separators=(",", ":"))

    def clear(self) -> None:
        self.spans.clear()


#: what a disabled tracer's span() returns: stateless, safe to re-enter.
_NOOP = contextlib.nullcontext()


def format_traceparent(trace_id: str, span_id: str) -> str:
    # W3C shape (version-trace-parent-flags); ids are our own tokens.
    return f"00-{trace_id}-{span_id}-01"


def _parse_traceparent(header: str) -> tuple[str, str | None]:
    # Tolerate garbage (wrong type, malformed): propagation input comes
    # off the wires, and a bad header must degrade to a fresh trace, not
    # crash the serving path.
    parts = header.split("-") if isinstance(header, str) else ()
    if len(parts) >= 3:
        return parts[1], parts[2]
    return f"t{next(_ids):016x}", None


#: process-wide default; enable with DEFAULT_TRACER.enabled = True.
DEFAULT_TRACER = Tracer(enabled=False)
