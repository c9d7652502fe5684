"""The span recorder — the program's one tracing mechanism.

Every `tracer().span(name, **attrs)` is a real span at every level.
What a level adds:

1. `off` (the default) records each closed span into a bounded
   in-memory **ring** — one tuple `(seq, parent_seq, name, t0, t1,
   attrs, depth)` on `time.perf_counter` — and, while a profiler
   session is live, enters a `jax.profiler.TraceAnnotation
   ("ss:<name>")`, which puts the span into the profiler's own
   `/host:CPU` plane beside the device's `XLA Ops`, on one clock (with
   no session it asks `TraceMe.is_enabled()`, 0.04 us, and makes no
   annotation). Nothing else: no dict, no file, no subscriber and —
   critically — **no device fence**: the async dispatch pipeline the
   engines are built around is untouched (pinned by
   tests/test_telemetry.py's no-fence test). About 2 us a span in a
   tight loop on this sandbox's CPU and 5-8 us between real work (cold
   caches); engines open a dozen a step (the VM one per instruction).
2. `steps` also streams every span as a dict to `spans.jsonl` and to
   the subscribers. `Span.fence()` is still a no-op, so queued device
   work is never drained — timestamps measure *dispatch*, and only
   log-point spans (which the drivers already synchronize) measure
   compute.
3. `spans` adds a `jax.block_until_ready` on the arrays handed to
   `Span.fence()` at span exit, so a span's duration brackets the
   DEVICE time of the work dispatched inside it. This serializes
   dispatch at every phase boundary — the honest cost of attributable
   time; the README documents it as the measurement mode.

`seq` numbers spans in the order they OPEN, `parent_seq` is the
innermost span open on the same thread at that moment (None at the
top), and the ring is ordered by CLOSE, so a parent follows its
children. `depth` is the nesting depth, the span's Chrome track.

Time that something else measured lands in the same ring, as entries
recorded after the fact (`_record`) under whatever span the calling
thread had open. Once a process has JAX the watch registers
`jax.monitoring` listeners: every Python tracing of a jitted function
becomes a `trace` entry, every conversion of a jaxpr to MLIR (a Pallas
kernel's own lowering included) a `lower` entry, every backend compile
a `compile` entry (which step recompiled), each with the function's
name in `fun`, and every program the persistent cache did not hold a
zero-length `cache_miss` entry. A jitted function traced inside
another's trace reports inside its caller's interval, so a reader
takes the UNION of the `trace` intervals, never their sum. The watch
hooks `gc.callbacks` too: a collection that took `GC_SPAN_MIN_S` or
more becomes a `gc` entry with its `generation` and what it
`collected` (a shorter one leaves nothing), and while a profiler
session is live it stands in the trace as `ss:gc` like any span, so an
idle gap of the device under it is named for it.

`watch_compiles()`, which a driver calls before its first compile
(`runtime.enable_compile_cache()`), also records `startup`, once a
process and at the top level whatever span is open: from the process's
start as the kernel has it (`process_start()`: `/proc/self/stat` and
`/proc/uptime`; where `/proc` is absent, from this module's import,
which leaves out the interpreter and whatever was imported before the
package) to that call, which is the interpreter and the imports of JAX
and of the package. A tracer that `configure()` installs later holds
that one entry again, as its first: it is a fact of the process, as
the listeners serve whichever tracer is global.

Export: one `spans.jsonl` line per closed span (append-streamed, so a
killed run keeps its trace) and a Chrome-trace `trace.json`
(`ph: "X"` complete events, microsecond timebase) written by `close()`
— loadable in Perfetto / chrome://tracing with zero TPU tooling.
Track names and the request lifecycle's `complete()` phases stream to
the file and the subscribers only; the ring holds the spans that code
opened and the entries recorded after the fact.

This module imports nothing but the standard library: a process that
never imports JAX (the `--validate` pre-commit hook, a launch-only
router) annotates nothing, watches no compiles and no collections.
"""

from __future__ import annotations

import functools
import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from pathlib import Path

LEVELS = ("off", "steps", "spans")

# ring bound: the VM emits one span per pipeline instruction, so a long
# run would otherwise grow without limit. The longest same-process
# reader is the benchmark's: set-up's compiles and every span of a 10 s
# ramp and a 30 s window, 10.5 spans a serving step, which is 9,300
# spans at today's 45 ms step and 105,000 at the 4 ms the roofline
# allows. A full ring holds 32-52 MB (244 B a span, 396 B with an
# attribute). spans.jsonl streams EVERY event and is the source of
# truth for trace.json.
RING_CAP = 131_072

# prefix of the program's spans in a profiler trace (the benchmark's
# own are `bench:<name>`)
ANNOTATION_PREFIX = "ss:"

# spans that mark one step of the program's outer loop become
# `StepTraceAnnotation`s, numbered by this attribute
_STEP_ATTR = {"engine.step": "tick", "step": "step"}

# named tracks (`Tracer.track`) take Chrome tids from here up; span
# nesting depths stay single digits, so the two can never collide
_FIRST_TRACK_TID = 1000

# phase hooks (telemetry/profiler.py): while a sampling profiler runs
# it installs a (push, pop) pair here and every span enter/exit feeds
# its NAME into the profiler's cross-thread phase registry — so samples
# landing inside a `step` or `decode.prep` span are attributable
# without the engines knowing. None when no profiler runs: the cost on
# the hot path is one module-global read per span.
PHASE_HOOKS = None

# a collection shorter than this leaves no `gc` entry: the young
# generations' take tens of microseconds and would be most of the ring
GC_SPAN_MIN_S = 1e-3

# (TraceAnnotation, StepTraceAnnotation) once this process has JAX
_NOTES = None
_WATCHING = False


def _resolve_notes():
    """The profiler's annotation classes, once JAX is in the process
    (never imported from here: see the module docstring). The first
    resolution also starts the watch (`_watch`)."""
    global _NOTES
    if "jax" not in sys.modules:
        return None
    try:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
    except ImportError:     # JAX is mid-import on another thread
        return None
    _NOTES = (TraceAnnotation, StepTraceAnnotation,
              TraceAnnotation.is_enabled)
    _watch()
    return _NOTES


class Span:
    """One timed region. Duration covers enter -> exit; `fence(arrs)`
    marks arrays whose device completion the exit waits on (at the
    `spans` level only)."""

    __slots__ = ("_tr", "name", "attrs", "seq", "_parent", "_stack",
                 "_t0", "_fences", "_note")

    def __init__(self, tr: "Tracer", name: str, attrs: dict):
        self._tr = tr
        self.name = name
        self.attrs = attrs
        self._fences: tuple = ()
        self._note = None

    def fence(self, *arrays) -> None:
        """Block the span exit on these arrays' device completion
        (`spans` level; no-op at `off` and `steps`). Call with the
        step's outputs so the span measures compute, not dispatch."""
        if self._tr.level == "spans":
            self._fences += arrays

    def set(self, **attrs) -> None:
        """Attributes learned inside the span (`n_admitted`, ...)."""
        self.attrs.update(attrs)
        if self._note is not None:
            self._note.set_metadata(**attrs)

    def __enter__(self):
        tr = self._tr
        stack = self._stack = tr._thread_stack()
        self._parent = stack[-1].seq if stack else None
        self.seq = next(tr._ids)
        stack.append(self)
        if PHASE_HOOKS is not None:
            PHASE_HOOKS[0](self.name)
        notes = _NOTES or _resolve_notes()
        # a session is live: an annotation made now is recorded; one
        # made while none is live would be dropped by the profiler
        # itself, at four times the price of asking
        if notes is not None and notes[2]():
            name, attrs = self.name, self.attrs
            key = _STEP_ATTR.get(name)
            if key is not None and key in attrs:
                note = notes[1](ANNOTATION_PREFIX + name,
                                step_num=attrs[key], **attrs)
            else:
                note = notes[0](ANNOTATION_PREFIX + name, **attrs)
            note.__enter__()
            self._note = note
        self._t0 = tr._clock()
        return self

    def __exit__(self, *exc):
        tr = self._tr
        if self._fences and tr.level == "spans":
            _block(self._fences)
        t1 = tr._clock()
        if self._note is not None:
            self._note.__exit__(None, None, None)
        if PHASE_HOOKS is not None:
            PHASE_HOOKS[1](self.name)
        stack = self._stack
        assert stack and stack[-1] is self, (
            "span nesting violated: exiting a span that is not the "
            "innermost open span")
        stack.pop()
        tr._close((self.seq, self._parent, self.name, self._t0, t1,
                   self.attrs, len(stack)))
        return False


def _block(arrays):
    import jax

    for a in arrays:
        jax.block_until_ready(a)


class Tracer:
    """Span recorder: bounded ring, streaming JSONL + Chrome export.

    The engines dispatch from one Python thread; background threads
    (prefetch, async save, whoever compiles) may emit spans too, each
    with its own nesting stack. The lock guards the ring's counter, the
    JSONL append and the subscribers; it is re-entrant because a
    collection can start between two bytecodes of a thread that holds
    it, and its `gc` entry is recorded from that thread.
    """

    def __init__(self, trace_dir=None, level: str = "off",
                 clock=time.perf_counter):
        assert level in LEVELS, f"level {level!r} not in {LEVELS}"
        self.level = level
        self.dir = Path(trace_dir) if trace_dir else None
        self._clock = clock
        self._epoch = clock()
        self._local = threading.local()  # per-thread span stacks
        self._ring: deque = deque(maxlen=RING_CAP)
        self._ids = itertools.count()    # span ids, in order of opening
        self._n_closed = 0               # spans ever put into the ring
        # named tracks (round 13): one per serving request
        self._next_tid = _FIRST_TRACK_TID
        self._lock = threading.RLock()
        self._jsonl = None
        # span-event subscribers (round 12): the live monitor's
        # flight recorder rides here so the incident ring holds the
        # phase spans next to the metrics lines. Called under the
        # emit lock — keep them O(ring append) cheap. Fed at `steps`
        # and `spans` only.
        self.subscribers: list = []
        if self.dir is not None and level != "off":
            self.dir.mkdir(parents=True, exist_ok=True)
            # "w", not "a": each run owns its trace dir (appending a
            # second run would mix two ts epochs into one garbled
            # Perfetto timeline); per-line flushes still mean a killed
            # run keeps everything it emitted
            self._jsonl = (self.dir / "spans.jsonl").open("w")

    def _thread_stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    # ------------------------------------------------------------ spans

    def span(self, name: str, **attrs) -> Span:
        """Open a span; use as a context manager. Real at every level
        (see the module docstring for what `off` does with it)."""
        return Span(self, name, attrs)

    def now(self) -> float:
        """This tracer's clock (perf_counter by default) — callers that
        record phase boundaries host-side and export them later via
        `complete` must stamp on THIS clock, not time.time."""
        return self._clock()

    def track(self, name: str) -> int:
        """Allocate a named Chrome-trace track and return its tid —
        one per serving request, so each request renders as its own
        named row in Perfetto next to the engine tick spans. Emits the
        thread_name metadata line; returns 0 (the shared depth track)
        when tracing is off."""
        if self.level == "off":
            return 0
        with self._lock:
            tid = self._next_tid
            self._next_tid += 1
        self._emit({"name": "thread_name", "ph": "M", "ts": 0.0,
                    "tid": tid, "args": {"name": name}})
        return tid

    def complete(self, name: str, t0: float, t1: float,
                 tid: int | None = None, **attrs) -> None:
        """Record an already-closed span directly — the lifecycle
        path, where phase boundaries are recorded host-side as they
        happen and exported when the phase ENDS. `t0`/`t1` are on this
        tracer's clock (`now()`); `tid` targets a named track from
        `track()`. To the file and the subscribers only (a request's
        `prefill` phase is no scheduler `prefill` span, and the ring's
        readers look spans up by name); nothing at `off`."""
        if self.level == "off":
            return
        ev = {"name": name, "ph": "X",
              "ts": round((t0 - self._epoch) * 1e6, 1),
              "dur": round(max(0.0, t1 - t0) * 1e6, 1), "args": attrs}
        if tid is not None:
            ev["tid"] = tid
        self._emit(ev)

    def _close(self, entry: tuple) -> None:
        with self._lock:
            self._ring.append(entry)
            self._n_closed += 1
        if self.level != "off":
            self._emit(self._as_dict(entry))

    def _as_dict(self, entry: tuple) -> dict:
        _seq, _parent, name, t0, t1, attrs, depth = entry
        return {"name": name, "ph": "X",
                "ts": round((t0 - self._epoch) * 1e6, 1),
                "dur": round((t1 - t0) * 1e6, 1),
                "depth": depth, "args": attrs}

    def _emit(self, ev: dict) -> None:
        with self._lock:
            if self._jsonl is not None:
                self._jsonl.write(json.dumps(ev) + "\n")
                self._jsonl.flush()
            for fn in self.subscribers:
                try:
                    fn(ev)
                except Exception:
                    pass  # a monitor bug must not kill the traced run

    # ------------------------------------------------------------- ring

    def ring(self) -> list[tuple]:
        """The buffered spans, oldest close first, as the tuples the
        module docstring describes (the most recent `RING_CAP`; the
        ring has dropped its oldest once `event_count` exceeds its
        length)."""
        with self._lock:
            return list(self._ring)

    @property
    def event_count(self) -> int:
        """Spans closed so far (monotonic; survives ring eviction —
        pair with `events_since` to read a window)."""
        return self._n_closed

    @property
    def events(self) -> list[dict]:
        """The buffered spans as dicts in the export's shape (the full
        stream, track names and lifecycle phases included, lives in
        spans.jsonl)."""
        return [self._as_dict(e) for e in self.ring()]

    def events_since(self, seq: int) -> list[dict]:
        """Spans closed at or after count `seq` (from `event_count`)
        that are still buffered — the O(window) way to read e.g. one
        batch's spans without rescanning the run."""
        with self._lock:
            n = max(0, self._n_closed - seq)
            tail = list(itertools.islice(reversed(self._ring), n))
        return [self._as_dict(e) for e in reversed(tail)]

    def spans_named(self, name: str) -> list[dict]:
        return [self._as_dict(e) for e in self.ring() if e[2] == name]

    # ----------------------------------------------------------- export

    @staticmethod
    def _chrome_event(e: dict) -> dict:
        # explicit tid (a named lifecycle track) wins over the span
        # nesting depth
        ev = {"name": e["name"], "ph": e["ph"], "ts": e["ts"],
              "pid": 0, "tid": e.get("tid", e.get("depth", 0)),
              "args": e.get("args", {})}
        if e["ph"] == "X":
            ev["dur"] = e["dur"]
        return ev

    def chrome_trace(self) -> dict:
        """The trace in Chrome format (Perfetto-loadable). Sourced from
        the streamed spans.jsonl when a trace dir is configured (the
        COMPLETE stream — the ring is bounded and holds spans only),
        else from the ring. Span depth maps to tid so nesting renders
        as the usual flame layout; attrs ride in `args`."""
        src: list = self.events
        if self.dir is not None:
            path = self.dir / "spans.jsonl"
            if path.exists():
                with self._lock:
                    if self._jsonl is not None:
                        self._jsonl.flush()
                src = [json.loads(line)
                       for line in path.read_text().splitlines()
                       if line.strip()]
        return {"traceEvents": [self._chrome_event(e) for e in src],
                "displayTimeUnit": "ms"}

    def close(self) -> None:
        """Flush the JSONL stream and write `trace.json` (Chrome)."""
        trace = (self.chrome_trace()
                 if self.dir is not None and self.level != "off"
                 else None)
        if self._jsonl is not None:
            with self._lock:
                self._jsonl.close()
                self._jsonl = None
        if trace is not None:
            (self.dir / "trace.json").write_text(json.dumps(trace))


# ------------------------------------------------------- global tracer

_TRACER = Tracer(level="off")


def configure(trace_dir=None, level: str = "off") -> Tracer:
    """Install (and return) the process-global tracer the engines emit
    into. Drivers call this once from the CLI flags; tests swap it
    freely (the previous tracer is closed). Its first entry is the
    process's `startup`, once the watch below has recorded one."""
    global _TRACER
    _TRACER.close()
    _TRACER = Tracer(trace_dir=trace_dir, level=level)
    if _READY_AT is not None:
        _record_startup()
    return _TRACER


def tracer() -> Tracer:
    """The active process-global tracer (default: level 'off')."""
    return _TRACER


def spanned(name: str, **attrs):
    """Decorator: the whole call is one span of the global tracer (an
    engine's constructor is its `build`)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inside(*args, **kwargs):
            with _TRACER.span(name, **attrs):
                return fn(*args, **kwargs)
        return inside
    return wrap


# ------------------------------- what something else timed: the watch

_IMPORTED_AT = time.perf_counter()
_PROCESS_START = None
_READY_AT = None        # end of `startup`: the first `watch_compiles()`
_SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def process_start() -> float:
    """When this process started, on `time.perf_counter` (the global
    tracer's clock): the kernel's record of it, or this module's import
    where there is no `/proc`. The start of `startup`, and what a
    time-to-ready is counted from."""
    global _PROCESS_START
    if _PROCESS_START is None:
        try:
            with open("/proc/self/stat") as f:
                ticks = int(f.read().rsplit(")", 1)[1].split()[19])
            with open("/proc/uptime") as f:
                age = float(f.read().split()[0]) \
                    - ticks / os.sysconf("SC_CLK_TCK")
            _PROCESS_START = time.perf_counter() - age
        except (OSError, ValueError, IndexError):
            _PROCESS_START = _IMPORTED_AT
    return _PROCESS_START


def _record(name: str, t0: float, t1: float, attrs: dict) -> None:
    """A span that something else timed, into the global tracer's ring
    under the calling thread's innermost open span."""
    tr = _TRACER
    stack = tr._thread_stack()
    tr._close((next(tr._ids), stack[-1].seq if stack else None, name,
               t0, t1, attrs, len(stack)))


def _record_startup() -> None:
    tr = _TRACER
    tr._close((next(tr._ids), None, "startup", process_start(), _READY_AT,
               {}, 0))


def _on_duration(event: str, seconds: float, **kw) -> None:
    name = _SPAN_OF_EVENT.get(event)
    if name is not None:
        t1 = _TRACER._clock()
        _record(name, t1 - seconds, t1, {"fun": kw.get("fun_name", "")})


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_MISS_EVENT:
        t = _TRACER._clock()
        _record("cache_miss", t, t, {})


_GC_T0 = 0.0
_GC_NOTE = None


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks`: collections do not nest, so one stamp serves."""
    global _GC_T0, _GC_NOTE
    if phase == "start":
        if _NOTES is not None and _NOTES[2]():
            _GC_NOTE = _NOTES[0](ANNOTATION_PREFIX + "gc",
                                 generation=info["generation"])
            _GC_NOTE.__enter__()
        _GC_T0 = _TRACER._clock()
        return
    t1 = _TRACER._clock()
    if _GC_NOTE is not None:
        _GC_NOTE.__exit__(None, None, None)
        _GC_NOTE = None
    if t1 - _GC_T0 >= GC_SPAN_MIN_S:
        _record("gc", _GC_T0, t1, {"generation": info["generation"],
                                   "collected": info["collected"]})


def _watch() -> None:
    """Register the `jax.monitoring` listeners and hook `gc.callbacks`,
    once a process (JAX offers no way to take a listener back, so they
    serve whichever tracer is global at the time)."""
    global _WATCHING
    if _WATCHING:
        return
    _WATCHING = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    gc.callbacks.append(_on_gc)


def watch_compiles() -> None:
    """Start the watch and, the first time, record `startup`: the call
    is where a process says it is ready to compile. Called by
    `runtime.enable_compile_cache()`, before a driver's first compile.
    The first span opened after JAX was imported starts the watch too
    (`_resolve_notes`), but that is no moment a process chose: it
    records no `startup`."""
    global _READY_AT
    _watch()
    if _READY_AT is None:
        _READY_AT = time.perf_counter()
        _record_startup()
