"""Host-side span tracer: wall-clock timelines as Chrome trace events.

The fourth monitor pillar. The existing three answer "what did the
step compute" (`Metrics`), "what does the stream look like over time"
(`MetricsLogger`), and "what does the program move" (`audit`) — but
every claim about TIME so far is an aggregate: the serving engine
reports TTFT percentiles with no way to see why ONE request was slow,
and the PR-3 ring-overlap story is asserted statically, never shown on
a timeline. `Tracer` is the instrument:

* ``tracer.span("prefill", tokens=n)`` — a context manager recording a
  wall-clock span into a thread-safe ring buffer (bounded memory: a
  long serving run keeps the last ``capacity`` events, oldest dropped);
* every span is also a `phase(name, **counts)`: a
  `jax.profiler.TraceAnnotation` named ``apex/<name>`` whose counts
  ride as annotation metadata (`step_span` a `StepTraceAnnotation`),
  so when a device capture (`profiler.trace`) is live, the host spans
  land on the SAME captured timeline as the XLA ops, under the names
  the Chrome export carries — host scheduling gaps and device ring
  hops line up in one Perfetto view. `phase` is the ONE span
  primitive of the program: the serving engine's tick opens its
  ``engine.*`` phases through `Tracer.phase`, which always enters
  the annotation (under a microsecond and nothing formatted while no
  capture is live) and records in the ring only when the tracer is
  enabled;
* ``export_chrome_trace(path)`` writes the standard Chrome trace-event
  JSON (``ph: "X"`` complete events over named tracks), loadable in
  Perfetto / ``chrome://tracing`` with no converter;
* retrospective ``add_span(name, begin, end)`` records a span from
  timestamps the caller already holds — the serving engine's
  per-request timelines are built this way from the SAME
  ``perf_counter`` readings that feed ``stats()``, so trace-span
  boundaries reproduce the reported TTFT/queue-wait numbers exactly.

The DISABLED path is the default and must cost nothing: module-level
``NULL_TRACER`` is a shared singleton whose ``span()`` returns one
preallocated no-op context manager — call sites pay an attribute check
(``tracer.enabled``), never an allocation, and the engine's compiled
programs and host↔device fetch pattern are untouched (pinned by
tests/L0/test_trace.py).

**The collector as a span (ISSUE 37).** `install_gc_hook()` puts one
callback on ``gc.callbacks``: every collection of Python's cyclic
collector is a ``phase("host.gc", generation=)`` from its start to
its stop, and `gc_pauses()` returns the process's totals
(collections, seconds, the longest). The serving engine asks for the
hook and reads the totals once a tick, so a pause shows on the tick it
delayed (``gc_n``/``gc_us``) and, in a capture, as a host event where
it stopped the thread.

**Fleet-causal tracing (ISSUE 19).** A fleet shatters one request's
timeline across tracers: the router records `dispatch`, replica A the
prefill, replica B (after a failover or a page-shipping handoff) the
decode and the `finish`. Three pieces re-join them:

* `mint_trace_id()` — the router stamps one process-unique trace id on
  every admitted request; it rides every hop (migration records,
  `resume_request` payloads, failover resubmission) and every
  per-request tracer event carries it as an ``args`` field, so the
  lifeline survives request-id reuse and engine boundaries;
* `merge_traces([...])` — folds N tracers into ONE Chrome trace-event
  body with a distinct ``pid`` (and ``process_name`` metadata) per
  tracer and all timestamps renormalized onto a single clock zero
  (every tracer reads the same ``perf_counter``), so Perfetto renders
  a migrated request as one causally-ordered lifeline across replica
  processes; `export_merged_trace(path, ...)` writes it;
* exactly-once delivery becomes visually checkable: one ``finish``
  event per trace id in the merged body (asserted by
  `trace_lifelines`, the test/bench helper).

**Runtime retrace sentinel (ISSUE 19).** Every serving PR swears "the
mixed step traces once", but only graphlint checks it, statically. The
`RetraceSentinel` subscribes to jax's own compilation events
(`jax.monitoring`: the ``/jax/core/compile/*`` phase durations plus
the ``/jax/compilation_cache/*`` events tests/conftest.py already
counts), folds them into ``xla_compiles_total{phase=}`` registry
counters, and — once `arm()`-ed at the warmup boundary — counts every
post-warmup compile (`tripped`); with ``policy="raise"`` the owning
engine/router raises `RetraceError` at the next tick. Compilation
events are process-global, so one armed sentinel guards the whole
fleet.
"""

import gc
import itertools
import json
import os
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

__all__ = [
    "PROGRAM_PREFIX",
    "phase",
    "install_gc_hook",
    "gc_pauses",
    "Tracer",
    "NULL_TRACER",
    "mint_trace_id",
    "merge_traces",
    "export_merged_trace",
    "trace_lifelines",
    "RetraceSentinel",
    "RetraceError",
    "COMPILE_EVENT_PHASES",
]


# guards the once-a-process installs: the collector's hook and the
# retrace sentinel's listeners
_INSTALL_LOCK = threading.Lock()

#: every span the program itself opens in a profiler capture starts
#: with this (the benchmark's own spans start with ``bench/``)
PROGRAM_PREFIX = "apex/"


def phase(name: str, *, step_num: Optional[int] = None, **counts):
    """The program's one span primitive: a
    `jax.profiler.TraceAnnotation` named ``apex/<name>`` (a
    `StepTraceAnnotation` when ``step_num`` is given) with ``counts``
    as annotation metadata — never formatted into the name; counts
    known only later in the span are added with ``.set_metadata``.
    In a capture it is a host event on the clock the device planes
    share, with the counts as its stats; with no capture live,
    entering and leaving it costs well under a microsecond and the
    counts are not formatted, so call sites enter it always. A
    string count must hold no comma (the profiler's encoding splits
    on it): join lists with spaces."""
    if step_num is not None:
        return jax.profiler.StepTraceAnnotation(
            PROGRAM_PREFIX + name, step_num=step_num, **counts
        )
    return jax.profiler.TraceAnnotation(PROGRAM_PREFIX + name, **counts)


# ---------------------------------------------------------------------
# the collector as a span (ISSUE 37)
# ---------------------------------------------------------------------

# Process-wide, like the collector: [collections, seconds in them, the
# longest one's seconds] since the hook went in. One collection runs at
# a time and its two callbacks run on the thread that set it off, so
# the open span needs no lock.
_GC_TOTALS = [0, 0.0, 0.0]
_GC_OPEN: List[Any] = []  # [the open ``host.gc`` annotation, its start]


def _on_gc(when: str, info: Dict[str, int]) -> None:
    if when == "start":
        span = phase("host.gc", generation=info["generation"])
        span.__enter__()
        _GC_OPEN[:] = span, time.perf_counter()
    elif _GC_OPEN:  # a stop whose start came before the hook: skipped
        span, t0 = _GC_OPEN
        seconds = time.perf_counter() - t0
        del _GC_OPEN[:]
        span.__exit__(None, None, None)
        _GC_TOTALS[0] += 1
        _GC_TOTALS[1] += seconds
        if seconds > _GC_TOTALS[2]:
            _GC_TOTALS[2] = seconds


def install_gc_hook() -> None:
    """Make every collection of Python's cyclic collector a
    `phase("host.gc", generation=)` from its start to its stop, and
    keep the process's totals for `gc_pauses`. Idempotent;
    like `_install_listeners` below it goes in once a process and
    stays (`InferenceEngine` asks for it and has no ``close()``). In a
    capture a pause is a host event on the capture's clock, on the
    thread it stopped; out of one it costs a collection two clock
    reads and an annotation that formats nothing."""
    with _INSTALL_LOCK:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def gc_pauses() -> Tuple[int, float, float]:
    """(collections, seconds spent in them, the longest one's seconds)
    in this process since `install_gc_hook`."""
    return tuple(_GC_TOTALS)


class _NullSpan:
    """Shared no-op context manager for the disabled path (one
    module-level instance; entering it allocates nothing)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Live span handle: records on exit, annotates the device
    timeline while open."""

    __slots__ = ("_tracer", "name", "track", "args", "_t0", "_ann")

    def __init__(self, tracer, name, track, args, annotation):
        self._tracer = tracer
        self.name = name
        self.track = track
        self.args = args
        self._ann = annotation
        self._t0 = 0.0

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = self._tracer.clock()
        return self

    def is_enabled(self):
        """Whether anything keeps this span's counts: always, because
        the tracer's ring records it (the annotation's own method: a
        bare `phase` says whether a profiler capture is live, so a
        caller asks before it builds counts nobody would read)."""
        return True

    def set_metadata(self, **counts):
        """Counts known only once the span is open (the annotation's
        own method, so a bare `phase` and a recorded one read alike)."""
        self.args.update(counts)
        if self._ann is not None:
            self._ann.set_metadata(**counts)

    def __exit__(self, *exc):
        end = self._tracer.clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer.add_span(
            self.name, self._t0, end, track=self.track, **self.args
        )
        return False


class Tracer:
    """Thread-safe wall-clock span recorder with Chrome-JSON export.

    ``capacity`` bounds the ring buffer (oldest events drop — a
    serving run can trace forever in constant memory);
    ``annotate_device=True`` (default) additionally wraps every live
    `span` in a `jax.profiler.TraceAnnotation` so a concurrent
    `profiler.trace` capture shows the host spans against the device
    ops. All timestamps are ``time.perf_counter`` seconds relative to
    the tracer's creation (one clock — the engine's ``stats()``
    latencies and the exported spans can be compared directly).

    Construct with ``enabled=False`` (or use the shared
    ``NULL_TRACER``) for the free disabled path: ``span`` returns a
    shared no-op context manager and every ``add_*`` returns
    immediately.
    """

    def __init__(
        self,
        enabled: bool = True,
        capacity: int = 65536,
        annotate_device: bool = True,
        registry=None,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.enabled = bool(enabled)
        self.annotate_device = annotate_device
        self.clock = time.perf_counter
        self._t0 = self.clock()
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=capacity)
        # ring-wrap visibility: a full ring drops the OLDEST event per
        # append — count the drops (they used to be silent) and, when
        # a telemetry registry is attached, export them as a counter
        # alongside the serving metrics
        self._dropped = 0
        self._drop_counter = (
            registry.counter(
                "tracer_dropped_events_total",
                "Trace events evicted by ring-buffer wrap "
                "(raise Tracer(capacity=...) if nonzero).",
            )
            if registry is not None else None
        )
        # track name -> tid, in registration order (Perfetto sorts by
        # the sort_index metadata we export, so registration order IS
        # display order: engine track first, then requests as admitted)
        self._tracks: Dict[str, int] = {}

    # -- recording ------------------------------------------------------

    def span(self, name: str, track: Optional[str] = None, **args):
        """Context manager timing a live region (one ring-buffer event
        on exit; a `TraceAnnotation` scope while open)."""
        if not self.enabled:
            return _NULL_SPAN
        ann = phase(name, **args) if self.annotate_device else None
        return _Span(self, name, track, args, ann)

    def phase(self, name: str, track: Optional[str] = None, **counts):
        """`phase(name, **counts)`, entered whether or not the tracer
        is enabled; an enabled tracer also records it in the ring,
        from one pair of clock reads around the annotation. The
        serving engine's tick is built from these (`span` stays the
        free no-op on a disabled tracer, so it cannot be)."""
        ann = phase(name, **counts)
        if not self.enabled:
            return ann
        return _Span(self, name, track, counts, ann)

    def step_span(self, step: int, name: str = "train_step"):
        """`StepTraceAnnotation`-aligned span for one train step: the
        profiler groups the device ops under the step number, and the
        host-side span records wall time for the same tick."""
        if not self.enabled:
            return _NULL_SPAN
        ann = (
            phase(name, step_num=int(step))
            if self.annotate_device else None
        )
        return _Span(self, name, None, {"step": int(step)}, ann)

    def add_span(
        self,
        name: str,
        begin: float,
        end: float,
        track: Optional[str] = None,
        **args,
    ) -> None:
        """Record a completed span from caller-held ``perf_counter``
        timestamps (the engine's retrospective per-request spans)."""
        if not self.enabled:
            return
        with self._lock:
            self._note_wrap_locked()
            self._events.append(
                ("X", name, self._tid_locked(track), begin, end - begin, args)
            )

    def instant(
        self, name: str, ts: Optional[float] = None,
        track: Optional[str] = None, **args,
    ) -> None:
        """Record a zero-duration marker (request enqueue/finish)."""
        if not self.enabled:
            return
        if ts is None:
            ts = self.clock()
        with self._lock:
            self._note_wrap_locked()
            self._events.append(
                ("i", name, self._tid_locked(track), ts, 0.0, args)
            )

    def _note_wrap_locked(self) -> None:
        """Called before an append: a full ring is about to evict its
        oldest event — account the drop instead of losing it silently."""
        if len(self._events) == self._events.maxlen:
            self._dropped += 1
            if self._drop_counter is not None:
                self._drop_counter.inc()

    @property
    def dropped(self) -> int:
        """Events evicted by ring wrap since creation (`clear` does
        not reset it — the count is about the tracer's lifetime)."""
        return self._dropped

    def _tid_locked(self, track: Optional[str]) -> int:
        if track is None:
            track = "main"
        tid = self._tracks.get(track)
        if tid is None:
            tid = len(self._tracks)
            self._tracks[track] = tid
        return tid

    # -- access / export ------------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        """Chrome trace-event dicts (host pid 1, ts/dur in µs since
        tracer creation) — the body `export_chrome_trace` writes."""
        with self._lock:
            snap = list(self._events)
            tracks = dict(self._tracks)
        out: List[Dict[str, Any]] = []
        for track, tid in tracks.items():
            out.append({
                "ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
                "args": {"name": track},
            })
            out.append({
                "ph": "M", "name": "thread_sort_index", "pid": 1,
                "tid": tid, "args": {"sort_index": tid},
            })
        for ph, name, tid, ts, dur, args in snap:
            ev: Dict[str, Any] = {
                "ph": ph, "name": name, "pid": 1, "tid": tid,
                "ts": round((ts - self._t0) * 1e6, 3),
            }
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 3)
            else:
                ev["s"] = "t"  # instant scope: thread
            if args:
                ev["args"] = args
            out.append(ev)
        return out

    def export_chrome_trace(self, path: str) -> int:
        """Write the Perfetto-loadable JSON; returns the event count
        (metadata included)."""
        events = self.events()
        other: Dict[str, Any] = {
            "producer": "rocm_apex_tpu.monitor.trace",
            "process_name": "host",
            "dropped_events": self._dropped,
        }
        if self._dropped:
            other["warning"] = (
                f"{self._dropped} events dropped by ring-buffer wrap "
                f"(capacity {self._events.maxlen}); the timeline is "
                f"incomplete — raise Tracer(capacity=...)"
            )
        with open(path, "w") as f:
            json.dump(
                {
                    "traceEvents": events,
                    "displayTimeUnit": "ms",
                    "otherData": other,
                },
                f,
            )
        return len(events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._tracks.clear()


# The free default: share one disabled tracer so every call site can
# hold a tracer unconditionally and pay only `tracer.enabled` checks.
NULL_TRACER = Tracer(enabled=False, capacity=1)


# ---------------------------------------------------------------------
# fleet-causal trace context (ISSUE 19)
# ---------------------------------------------------------------------

_TRACE_SEQ = itertools.count()


def mint_trace_id(prefix: str = "t") -> str:
    """One process-unique trace id: ``<prefix><pid hex>-<seq hex>``.
    The router mints one per ADMITTED request (not per attempt), so a
    request that migrates, fails over, or hands off keeps the same id
    across every replica that touches it — the join key
    `merge_traces` timelines group on. Monotonic within a process;
    the pid component keeps multi-process fleets collision-free."""
    return f"{prefix}{os.getpid():x}-{next(_TRACE_SEQ):x}"


def merge_traces(
    tracers: Sequence[Tracer],
    labels: Optional[Sequence[str]] = None,
) -> Dict[str, Any]:
    """Fold N tracers into ONE Chrome trace-event body: tracer ``i``
    becomes process ``pid=i+1`` (named ``labels[i]``, default
    ``tracer<i>``), its tracks keep their per-process thread ids
    (namespaced by the pid — Perfetto scopes tids per process), and
    every timestamp is renormalized onto a single clock zero (the
    earliest tracer's creation time; all tracers read the same
    ``time.perf_counter``, so absolute event times are directly
    comparable). A request that hopped replicas renders as one
    left-to-right causal lifeline: ``dispatch`` on the router process,
    ``resume``/spans on each replica process it visited, exactly one
    ``finish`` — grouped by the ``trace_id`` event arg.

    Returns the loadable JSON body (``traceEvents`` +
    ``displayTimeUnit`` + ``otherData``); `export_merged_trace`
    writes it to disk."""
    tracers = list(tracers)
    if not tracers:
        raise ValueError("merge_traces needs at least one tracer")
    if labels is None:
        labels = [f"tracer{i}" for i in range(len(tracers))]
    labels = [str(x) for x in labels]
    if len(labels) != len(tracers):
        raise ValueError(
            f"{len(labels)} labels for {len(tracers)} tracers"
        )
    t0 = min(tr._t0 for tr in tracers)
    events: List[Dict[str, Any]] = []
    dropped = 0
    for i, (tr, label) in enumerate(zip(tracers, labels)):
        pid = i + 1
        with tr._lock:
            snap = list(tr._events)
            tracks = dict(tr._tracks)
        dropped += tr._dropped
        events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": label},
        })
        events.append({
            "ph": "M", "name": "process_sort_index", "pid": pid,
            "tid": 0, "args": {"sort_index": i},
        })
        for track, tid in tracks.items():
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid,
                "tid": tid, "args": {"name": track},
            })
            events.append({
                "ph": "M", "name": "thread_sort_index", "pid": pid,
                "tid": tid, "args": {"sort_index": tid},
            })
        for ph, name, tid, ts, dur, args in snap:
            ev: Dict[str, Any] = {
                "ph": ph, "name": name, "pid": pid, "tid": tid,
                "ts": round((ts - t0) * 1e6, 3),
            }
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 3)
            else:
                ev["s"] = "t"
            if args:
                ev["args"] = args
            events.append(ev)
    other: Dict[str, Any] = {
        "producer": "rocm_apex_tpu.monitor.trace.merge_traces",
        "processes": {
            str(i + 1): label for i, label in enumerate(labels)
        },
        "dropped_events": dropped,
    }
    if dropped:
        other["warning"] = (
            f"{dropped} events dropped by ring-buffer wrap across the "
            f"merged tracers; some lifelines are incomplete"
        )
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": other,
    }


def export_merged_trace(
    path: str,
    tracers: Sequence[Tracer],
    labels: Optional[Sequence[str]] = None,
) -> int:
    """`merge_traces` to disk (Perfetto-loadable); returns the event
    count, metadata included."""
    body = merge_traces(tracers, labels)
    with open(path, "w") as f:
        json.dump(body, f)
    return len(body["traceEvents"])


def trace_lifelines(
    body: Dict[str, Any],
) -> Dict[str, Dict[str, Any]]:
    """Group a merged (or single-tracer) trace body by ``trace_id``:
    ``{trace_id: {"pids": sorted pids touched, "events": count,
    "finishes": count of finish events, "names": sorted event
    names}}``. The exactly-once acceptance reads directly off it —
    every lifeline must show ``finishes == 1``, and a migrated
    request's ``pids`` spans more than one process."""
    lifelines: Dict[str, Dict[str, Any]] = {}
    for ev in body.get("traceEvents", ()):
        tid_ = (ev.get("args") or {}).get("trace_id")
        if not tid_:
            continue
        line = lifelines.setdefault(
            tid_, {"pids": set(), "events": 0, "finishes": 0,
                   "names": set()},
        )
        line["pids"].add(ev.get("pid", 1))
        line["events"] += 1
        line["names"].add(ev["name"])
        if ev["name"] == "finish":
            line["finishes"] += 1
    for line in lifelines.values():
        line["pids"] = sorted(line["pids"])
        line["names"] = sorted(line["names"])
    return lifelines


# ---------------------------------------------------------------------
# runtime retrace sentinel (ISSUE 19)
# ---------------------------------------------------------------------

#: jax.monitoring event -> the compile phase it witnesses. The
#: ``/jax/core/compile/*`` durations fire on EVERY jit trace/lower/
#: backend-compile regardless of cache configuration; the
#: ``/jax/compilation_cache/*`` events additionally fire when the
#: persistent compilation cache is enabled (the same substrate
#: tests/conftest.py counts hit ratios from).
COMPILE_EVENT_PHASES: Dict[str, str] = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/compile_requests_use_cache":
        "cache_request",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}


class RetraceError(RuntimeError):
    """A compile landed after the warmup boundary on a sentinel with
    ``policy="raise"`` — some input shape, dtype, or closure drifted
    and XLA re-traced (the latency cliff the one-compiled-trace
    invariant exists to prevent)."""


# One process-wide pair of jax.monitoring listeners fanning out to the
# live sentinels. jax has no public unregister, so registering per
# sentinel would grow the dispatch list forever; the WeakSet lets
# short-lived sentinels (tests, benches) vanish with their owners.
_SENTINELS: "weakref.WeakSet" = weakref.WeakSet()
_LISTENERS_INSTALLED = False


def _dispatch_compile_event(event: str, **kwargs) -> None:
    phase = COMPILE_EVENT_PHASES.get(event)
    if phase is None:
        return
    for sentinel in list(_SENTINELS):
        sentinel._note(phase)


def _dispatch_compile_duration(
    event: str, duration: float, **kwargs
) -> None:
    _dispatch_compile_event(event)


def _install_listeners() -> None:
    global _LISTENERS_INSTALLED
    with _INSTALL_LOCK:
        if _LISTENERS_INSTALLED:
            return
        import jax.monitoring as jax_monitoring

        jax_monitoring.register_event_listener(_dispatch_compile_event)
        jax_monitoring.register_event_duration_secs_listener(
            _dispatch_compile_duration
        )
        _LISTENERS_INSTALLED = True


class RetraceSentinel:
    """Continuous enforcement of "the fleet compiles once".

    Counts every jax compilation event by phase (`counts`; into
    ``xla_compiles_total{phase=}`` when a registry is attached). After
    `arm()` — the warmup boundary; `InferenceEngine.reset_stats()`
    arms its sentinel because that IS the bench contract's
    warmed-up-now marker — post-warmup events additionally land in
    `post_warmup` (and ``xla_compiles_post_warmup_total{phase=}``),
    and phases in ``trip_phases`` (default: a fresh jaxpr trace or a
    backend compile — cache hits don't trip; re-checking the
    persistent cache is cheap, re-tracing is the cliff) accumulate
    into `tripped` and emit a ``retrace`` tracer instant.

    ``policy="count"`` observes; ``policy="raise"`` makes `check()` —
    called by the owning engine/router once per tick, NOT from inside
    the jax callback where an exception would surface mid-compile —
    raise `RetraceError`. Events are process-global: any compile
    anywhere in the process counts, which is exactly the property
    that lets one router-held sentinel guard N replicas."""

    def __init__(
        self,
        registry=None,
        *,
        policy: str = "count",
        tracer: Optional[Tracer] = None,
        trip_phases: Sequence[str] = ("trace", "compile"),
    ):
        if policy not in ("count", "raise"):
            raise ValueError(
                f"retrace policy must be 'count' or 'raise', "
                f"got {policy!r}"
            )
        self.policy = policy
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.trip_phases = frozenset(str(p) for p in trip_phases)
        unknown = self.trip_phases - set(COMPILE_EVENT_PHASES.values())
        if unknown:
            raise ValueError(
                f"unknown trip phases {sorted(unknown)}; phases are "
                f"{sorted(set(COMPILE_EVENT_PHASES.values()))}"
            )
        self._lock = threading.Lock()
        self.counts: Dict[str, int] = {}
        self.post_warmup: Dict[str, int] = {}
        self.armed = False
        self._counter = None
        self._post_counter = None
        if registry is not None and registry.enabled:
            self._counter = registry.counter(
                "xla_compiles_total",
                "jax compilation events by phase (trace/lower/compile "
                "+ the persistent-cache request/hit/miss events).",
                labelnames=("phase",),
            )
            self._post_counter = registry.counter(
                "xla_compiles_post_warmup_total",
                "Compilation events AFTER the sentinel was armed — "
                "nonzero means something re-traced in the serving "
                "window.",
                labelnames=("phase",),
            )
        _install_listeners()
        _SENTINELS.add(self)

    # invoked from the module-level jax.monitoring fan-out
    def _note(self, phase: str) -> None:
        with self._lock:
            self.counts[phase] = self.counts.get(phase, 0) + 1
            if self._counter is not None:
                self._counter.inc(phase=phase)
            if not self.armed:
                return
            self.post_warmup[phase] = (
                self.post_warmup.get(phase, 0) + 1
            )
            if self._post_counter is not None:
                self._post_counter.inc(phase=phase)
        if self.tracer.enabled and phase in self.trip_phases:
            self.tracer.instant(
                "retrace", track="sentinel", phase=phase,
            )

    def arm(self) -> None:
        """Mark the warmup boundary: compiles from here on are
        retraces."""
        self.armed = True

    def disarm(self) -> None:
        self.armed = False

    @property
    def tripped(self) -> int:
        """Post-warmup events in the trip phases (0 = the invariant
        held)."""
        with self._lock:
            return sum(
                n for p, n in self.post_warmup.items()
                if p in self.trip_phases
            )

    def check(self) -> int:
        """Tick-boundary enforcement point: returns `tripped`, raising
        `RetraceError` under ``policy="raise"`` when nonzero."""
        n = self.tripped
        if n and self.policy == "raise":
            with self._lock:
                detail = dict(self.post_warmup)
            raise RetraceError(
                f"{n} compilation event(s) landed after warmup "
                f"(post-warmup by phase: {detail}) — the "
                f"one-compiled-trace invariant broke at runtime"
            )
        return n

    def close(self) -> None:
        """Drop out of the process-wide dispatch (also implicit on
        GC)."""
        _SENTINELS.discard(self)

    def status(self) -> Dict[str, Any]:
        """JSON-ready dump for ``/varz``."""
        with self._lock:
            return {
                "policy": self.policy,
                "armed": self.armed,
                "tripped": sum(
                    n for p, n in self.post_warmup.items()
                    if p in self.trip_phases
                ),
                "counts": dict(self.counts),
                "post_warmup": dict(self.post_warmup),
            }
