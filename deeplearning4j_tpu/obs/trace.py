"""Structured span tracer: the always-available, default-off timeline.

The reference threads one observability spine through every training
loop — the ``IterationListener`` chain invoked per optimizer iteration
(deeplearning4j-core/.../optimize/api/IterationListener.java, fired from
StochasticGradientDescent.java:66-67) feeding the UI/stats plane
(deeplearning4j-ui-parent). Our reproduction grew five disjoint ledgers
instead; this module is the correlation layer those ledgers lack: a
Dapper-style span tracer (PAPERS.md — always-on, low-overhead tracing
built in before the production story needs it) over the hot seams the
repo already owns:

  dispatch.<jit>   train-step dispatch (trace vs cache-hit vs execute)
                   — ops/dispatch.instrumented_jit
  etl.wait/stage   input-pipeline staging waits — etl/pipeline.py
  ckpt.*           checkpoint snapshot/write/commit — resilience/
  fleet.round/split, membership epochs — parallel/fleet.py
  serve.request/batch  request -> coalesced batch -> jit dispatch, with
                   a request id threading through the batcher
  serve.queue/admit/tick.*/idle  one streamed /generate from the engine
                   to the paged decoder's tick, every span of a request
                   carrying its ``rid`` — serving/paged.py

Spans are HOST-SIDE events only: a span around a jit call measures the
(async) dispatch, never a device sync — the same bulk-readback rule the
listener chain follows (a per-step ``block_until_ready`` would serialize
the pipeline this tracer exists to observe). Timing uses the monotonic
clock (``time.perf_counter``); ids are process-local integers.

A span that a ``with`` block holds is also a
``jax.profiler.TraceAnnotation`` of the same name (where the process has
jax loaded; nothing is imported for it), so a profiler session finds the
program's spans in its host plane, on the clock of the device's
operations. Outside a session that costs a flag test. Spans recorded
after the fact (:func:`record_span`) and spans that cross threads
(:func:`open_span`) are waits, not host work, and carry no annotation.

Gate: ``DL4J_TPU_OBS`` (default OFF). Disabled, :func:`span` returns a
shared null context — one env lookup and one branch per call site, no
allocation of Span objects, no annotation, no ring writes — and training
is bit-exact vs a build without the tracer (tests/test_obs.py proves it).
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from deeplearning4j_tpu.ops import env as envknob

ENV_OBS = "DL4J_TPU_OBS"
ENV_SPANS = "DL4J_TPU_OBS_SPANS"

_ON = ("1", "on", "true", "yes")

# programmatic override (tests and the benchmark's traced run toggle
# without relying on env mutation ordering): None = defer to the env
_forced: Optional[bool] = None


def obs_enabled() -> bool:
    """The observability gate, read at CALL time (per span) so a single
    process can switch tracing on for one stretch of its run."""
    if _forced is not None:
        return _forced
    return envknob.raw(ENV_OBS, "").strip().lower() in _ON


def set_enabled(value: Optional[bool]) -> None:
    """Force the gate on/off programmatically; ``None`` restores the env
    decision."""
    global _forced
    _forced = value


class Span:
    """One timed operation: name, id, parent id, monotonic start/end,
    free-form attributes. Mutable only through :meth:`set_attr` while
    open; finished spans live in the tracer ring as plain dicts."""

    __slots__ = ("name", "span_id", "parent_id", "start", "end", "attrs",
                 "wall", "keep")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 attrs: Dict[str, Any]):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs
        self.wall = time.time()  # correlation with external logs only
        self.start = time.perf_counter()
        self.end: Optional[float] = None
        self.keep = True

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def set_parent(self, parent) -> None:
        """Name the span (or span id) that caused this one, where that
        is known only after the span opened (the decoder's admission
        learns its request when the pick returns)."""
        self.parent_id = _span_id(parent)

    def discard(self) -> None:
        """Have the ``with`` block that holds this span end without
        recording it: for a block that finds it had nothing to do (an
        admission pass with no request to pick)."""
        self.keep = False

    @property
    def duration_s(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def record(self) -> tuple:
        """The span as the ring and the journal keep it: ONE flat tuple
        of plain values, the attributes' keys and values following the
        six fixed fields. The garbage collector stops tracking such a
        tuple at its first pass (a nested tuple or a dict it would not),
        so a kept span is never promoted to the oldest generation; kept
        as dicts, a serving window's spans brought a full collection of
        the heap (50 ms, PERF.md PR 26) into the window."""
        rec = [self.name, self.span_id, self.parent_id,
               round(self.wall, 6), round(self.start, 6),
               None if self.end is None
               else round(self.end - self.start, 6)]
        for kv in self.attrs.items():
            rec += kv
        return tuple(rec)


_FIELDS = ("name", "span_id", "parent_id", "t_wall", "t_mono", "duration_s")


def record_dict(rec: tuple) -> Dict[str, Any]:
    """A :meth:`Span.record` as the dict every reader gets."""
    d: Dict[str, Any] = dict(zip(_FIELDS, rec))
    d["attrs"] = dict(zip(rec[6::2], rec[7::2]))
    return d


def _span_id(parent) -> Optional[int]:
    """A parent given as a Span, the null span, an id or None."""
    return getattr(parent, "span_id", parent)


class _NullSpan:
    """The disabled-path span: every mutator is a no-op so call sites
    keep ONE code path (``with span(...) as sp: ... sp.set_attr(...)``)
    whether obs is on or off."""

    __slots__ = ()
    name = None
    span_id = None
    parent_id = None

    def set_attr(self, key, value):
        pass

    def set_parent(self, parent):
        pass

    def discard(self):
        pass


NULL_SPAN = _NullSpan()


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return NULL_SPAN

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()

# jax.profiler.TraceAnnotation once a span finds jax loaded; False where
# this jax has none. Never imported for the tracer's sake: a process that
# has not loaded jax has no profiler session to annotate.
_ANNOTATION: Any = None


def _annotation(name: str):
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:
            _ANNOTATION = False
    return _ANNOTATION(name) if _ANNOTATION else None


class _SpanCtx:
    """Context manager for one live span; pushes/pops the thread-local
    parent stack so nested spans parent automatically (a serving batch
    span opened in the batcher worker thread becomes the parent of the
    jit dispatch span the model call opens on that same thread)."""

    __slots__ = ("_tracer", "_span", "_ann")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._ann = None

    def __enter__(self) -> Span:
        self._tracer._stack().append(self._span)
        self._ann = _annotation(self._span.name)
        if self._ann is not None:
            self._ann.__enter__()
        return self._span

    def __exit__(self, exc_type, exc, tb):
        sp = self._span
        sp.end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            sp.attrs["error"] = exc_type.__name__
        stack = self._tracer._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        if sp.keep:
            self._tracer._finish(sp)
        return False


class Tracer:
    """Span factory + bounded ring of finished spans.

    Finished spans fan out to the flight-recorder journal (obs/journal)
    and a duration histogram in the metrics registry (obs/registry) —
    one instrumentation point, three read surfaces (ring for tests/
    debugging, journal for post-mortem timelines, histogram for export).
    """

    def __init__(self, capacity: Optional[int] = None, *,
                 registry=None, journal=None):
        self._lock = threading.Lock()
        self._ring: deque = deque(
            maxlen=capacity if capacity is not None
            else envknob.get_int(ENV_SPANS) or 65536)
        # finished spans the full ring pushed out since the last clear():
        # a reader that wants every span of a window checks it is 0
        self.dropped = 0
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._registry = registry
        self._journal = journal

    # -- wiring (lazy: obs/__init__ connects the default singletons) ------
    def attach(self, *, registry=None, journal=None) -> None:
        if registry is not None:
            self._registry = registry
        if journal is not None:
            self._journal = journal

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = []
            self._local.stack = stack
        return stack

    # -- recording --------------------------------------------------------
    def _new(self, name: str, parent, attrs: Dict[str, Any]) -> Span:
        """`parent` names the causing span across threads; without one
        the innermost span open on THIS thread is the parent, a
        discarded one left out (it will not be in the ring to be found)."""
        pid = _span_id(parent)
        if pid is None:
            pid = next((s.span_id for s in reversed(self._stack())
                        if s.keep), None)
        return Span(name, next(self._ids), pid, attrs)

    def span(self, name: str, parent=None, **attrs) -> _SpanCtx:
        return _SpanCtx(self, self._new(name, parent, attrs))

    def open_span(self, name: str, parent=None, **attrs) -> Span:
        """A span that :meth:`close_span` ends, on this thread or any
        other (a streamed request opens where the engine admits it and
        closes where its stream ends). It joins no thread's parent
        stack: its children name it through ``parent=``."""
        return self._new(name, parent, attrs)

    def close_span(self, sp: Span) -> None:
        if sp.end is None:
            sp.end = time.perf_counter()
            self._finish(sp)

    def record_span(self, name: str, seconds: float, parent=None,
                    ago: float = 0.0, **attrs) -> None:
        """A completed span recorded after the fact — for waits measured
        inline (the ETL consumer stall, a request's time in the decode
        queue) where wrapping the wait in a context manager would
        restructure the hot loop. It lasted ``seconds`` and ended ``ago``
        seconds before this call."""
        sp = Span(name, next(self._ids), _span_id(parent), attrs)
        sp.start -= float(seconds) + float(ago)
        sp.wall -= float(seconds) + float(ago)
        sp.end = sp.start + float(seconds)
        self._finish(sp)

    def current_span(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    def _finish(self, sp: Span) -> None:
        rec = sp.record()
        with self._lock:
            full = len(self._ring) == self._ring.maxlen
            if full:
                self.dropped += 1
            self._ring.append(rec)
        registry = self._registry
        if full and registry is not None:
            registry.counter("dl4j_spans_dropped")
        journal = self._journal
        if journal is not None:
            # light-path append: the record is already timestamped, and
            # record_dict is all the journal knows of its layout
            journal.append_span(rec, record_dict)
        if registry is not None and sp.end is not None:
            registry.histogram("dl4j_span_seconds", sp.end - sp.start,
                               span=sp.name)

    # -- reading ----------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self._ring)
        return [record_dict(r) for r in out if name is None or r[0] == name]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0


_TRACER: Optional[Tracer] = None
_TRACER_LOCK = threading.Lock()


def tracer() -> Tracer:
    """The process-wide tracer, wired to the default registry/journal on
    first use (lazy so importing the instrumented modules never pays for
    the whole obs plane)."""
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                from deeplearning4j_tpu.obs import journal as journal_mod
                from deeplearning4j_tpu.obs import registry as registry_mod

                _TRACER = Tracer(
                    registry=registry_mod.default_registry(),
                    journal=journal_mod.default_journal())
    return _TRACER


def span(name: str, parent=None, **attrs):
    """THE instrumentation entry point: a context manager yielding a Span
    when obs is enabled, the shared null context otherwise. The disabled
    path is one env read + one branch — cheap enough for the per-dispatch
    hot path this plane instruments. Attributes that cost anything to
    compute go through ``sp.set_attr`` inside the block, which the null
    span ignores."""
    if not obs_enabled():
        return _NULL_CTX
    return tracer().span(name, parent, **attrs)


def open_span(name: str, parent=None, **attrs):
    """Gated :meth:`Tracer.open_span`: a Span to hand to
    :func:`close_span` later, from any thread; the null span when obs is
    off."""
    if not obs_enabled():
        return NULL_SPAN
    return tracer().open_span(name, parent, **attrs)


def close_span(sp) -> None:
    """End a span :func:`open_span` gave; the null span is ignored, and
    a span opened while obs was on is recorded even if it has since been
    switched off (half a request's tree is worse than the whole)."""
    if sp is not NULL_SPAN:
        tracer().close_span(sp)


def record_span(name: str, seconds: float, parent=None, ago: float = 0.0,
                **attrs) -> None:
    """Gated after-the-fact span recording (see Tracer.record_span)."""
    if obs_enabled():
        tracer().record_span(name, seconds, parent, ago, **attrs)
