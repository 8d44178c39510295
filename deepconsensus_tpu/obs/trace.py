"""Structured trace spans: Chrome-trace-event JSONL, fleet-safe.

Every ticket/pack in the pipeline gets spans — featurize, pack-wait,
H2D transfer, device compute, finalize drain, stitch — plus one
request-level span per tier (route / featurize / serve_request), all
stamped with a trace id minted at the outermost tier (the router for
fleet traffic, the CLI for batch runs) and carried across processes in
the ``X-Dctpu-Trace-Id`` protocol header. Load the file straight into
Perfetto / chrome://tracing, or summarize it with ``dctpu trace``.

Two kinds of span. A *stage* (``cat: "stage"``) is work on a thread,
opened lexically with ``obs.stage(registry, name)``: its ``args.span``
is an id unique in the process and ``args.parent`` the id of the stage
that encloses it on the same thread (absent at top level), so a reader
can take a stage's self time: its duration minus what its children
cover. A *wait* (``cat: "wait"``: ``pack_wait``, ``device_compute``) is
an interval stamped after the fact between two events; it has no parent
and is no one's child, and ``dctpu trace`` lists it apart.

File format. Chrome's JSON trace format tolerates a missing closing
``]`` and a trailing comma, so the file is written as a ``[`` header
line followed by one complete-event object per line, each line ending
``,``. Events are kept in memory and written out as whole lines, one
O_APPEND write per flush: at ``close()`` / ``configure(None)``, at
interpreter exit and whenever ``FLUSH_EVENTS`` events are buffered (so
a resident server stays bounded). N fleet processes share ONE trace
file with no coordination: the header is written only by the process
that wins the O_CREAT|O_EXCL race, every other writer just appends
whole lines. pid distinguishes tiers (a process_name metadata event
labels each). A forked child starts with an empty buffer; a child that
leaves through ``os._exit`` (a multiprocessing worker) calls
``flush()`` itself if it has traced anything.

Overhead when off. Tracing is enabled by ``DCTPU_TRACE=<path>`` (or
``configure(path)``); when unset, ``enabled()`` is a module-global
``is None`` check, ``span()`` yields a no-op context and a stage costs
its two clock reads, its histogram observation and an append and a pop
on the thread's stack of open stages (kept with tracing off too, so a
compile can say which stage it arrived under): no id is taken and no
event is built. The one exception is the start-up record below.

Start-up record. A program builds its runner, and compiles, before it
knows where to trace to. While no writer is configured, the events whose
name is in ``STARTUP_SPANS`` (the start-up stages and JAX's trace, lower
and compile events, obs/compiles.py: a handful a process, seconds each)
are built and kept in a list of at most ``EARLY_EVENTS``; the oldest
stay, and what does not fit is counted. ``configure(path)`` writes them
into the new file first, each as it was stamped, with the count of the
dropped ones if there were any. They carry ``args.under`` (the name of
the stage they arrived under) but no ids, which are taken only with
tracing on. ``clear_early()`` empties the list, and so does a fork in
the child.

Profiler bridge. While tracing is on and ``jax`` is already imported, a
stage also enters ``jax.profiler.TraceAnnotation(name)``, so a profiler
capture of the process shows the program's stages on the host plane
beside the device's operations, on the profiler's clock. This module
never imports jax itself (the router has none).

Timestamps are wall-clock microseconds (``time.time()``): the one
clock every fleet process shares, so cross-tier spans land on one
timeline. Within a process, launch-before-finalize ordering (what the
span-derived overlap fraction reads) is preserved because both stamps
come from the same clock in the same thread.
"""
from __future__ import annotations

import atexit
import contextlib
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

ENV_TRACE = 'DCTPU_TRACE'

# Span categories (docs/observability.md#span-model): 'stage' for work
# on a thread, 'wait' for intervals stamped after the fact, 'request'
# for per-request tier spans.
CAT_STAGE = 'stage'
CAT_WAIT = 'wait'

STAGE_FEATURIZE = 'featurize'
STAGE_SUBMIT = 'submit'
STAGE_FLUSH = 'flush'
STAGE_STACK = 'stack_windows'
STAGE_FORMAT = 'format_rows'
STAGE_PACK_CUT = 'pack_cut'
STAGE_DISPATCH = 'dispatch'
STAGE_PACK_CAST = 'pack_cast'
STAGE_LAUNCH = 'forward_launch'
STAGE_H2D = 'h2d_transfer'
STAGE_FINALIZE = 'finalize_drain'
STAGE_DELIVER = 'deliver'
STAGE_STITCH = 'stitch'
STAGE_PACK_WAIT = 'pack_wait'
STAGE_DEVICE_COMPUTE = 'device_compute'
WAITS = (STAGE_PACK_WAIT, STAGE_DEVICE_COMPUTE)
# Start-up (docs/observability.md#start-up-and-compiles): the stages of
# a runner's construction, and JAX's own trace, lower and compile events
# (obs/compiles.py) under whichever stage caused them.
STAGE_IMPORT_RUNNER = 'import_runner'
STAGE_CHECKPOINT_LOAD = 'checkpoint_load'
STAGE_RUNNER_INIT = 'runner_init'
STAGE_WEIGHTS_PREPARE = 'weights_prepare'
STAGE_WEIGHTS_PLACE = 'weights_place'
STAGE_JIT_TRACE = 'jit_trace'
STAGE_JIT_LOWER = 'jit_lower'
STAGE_XLA_COMPILE = 'xla_compile'
COMPILE_SPANS = (STAGE_JIT_TRACE, STAGE_JIT_LOWER, STAGE_XLA_COMPILE)
# Kept while no writer is configured, and written first by configure().
STARTUP_SPANS = frozenset((
    STAGE_IMPORT_RUNNER, STAGE_CHECKPOINT_LOAD, STAGE_RUNNER_INIT,
    STAGE_WEIGHTS_PREPARE, STAGE_WEIGHTS_PLACE) + COMPILE_SPANS)
EARLY_EVENTS = 1024
EARLY_DROPPED_EVENT = 'early_events_dropped'

# Events buffered before a flush: a resident server's memory bound.
FLUSH_EVENTS = 4096


def _json_default(value: Any) -> Any:
  """Numpy scalars (a count taken from a shape or nbytes) as plain
  numbers; anything else by its text, so a flush never raises."""
  item = getattr(value, 'item', None)
  return item() if callable(item) else str(value)


def _write_lines(fd: int, events: List[Dict[str, Any]]) -> None:
  """One os.write of complete lines: writers that share a file
  interleave by whole flushes, never inside a line."""
  if fd < 0 or not events:
    return
  data = ''.join(
      json.dumps(e, separators=(',', ':'), default=_json_default) + ',\n'
      for e in events).encode()
  while data:  # a short write continues where it stopped
    data = data[os.write(fd, data):]


def _complete(name: str, cat: str, ts_s: float, dur_s: float,
              args: Optional[Dict[str, Any]], pid: int) -> Dict[str, Any]:
  """One 'X' (complete) event of the calling thread."""
  return {
      'name': name, 'cat': cat, 'ph': 'X',
      'ts': ts_s * 1e6, 'dur': max(0.0, dur_s) * 1e6,
      'pid': pid, 'tid': threading.get_ident() & 0xffffffff,
      'args': args or {},
  }


class TraceWriter:
  """Buffers Chrome trace events and appends them, whole lines at a
  time, to one (possibly shared) file."""

  def __init__(self, path: str, tier: str = ''):
    self.path = path
    self.tier = tier
    self._lock = threading.Lock()
    self._pid = os.getpid()
    try:
      # Exactly one process wins the create and owns the `[` header;
      # everyone else appends events only.
      fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
      try:
        os.write(fd, b'[\n')
      finally:
        os.close(fd)
    except FileExistsError:
      pass
    self._fd = os.open(path, os.O_WRONLY | os.O_APPEND)  # guarded by: self._lock
    self._events: List[Dict[str, Any]] = []  # guarded by: self._lock
    if tier:
      self._emit_raw({
          'name': 'process_name', 'ph': 'M', 'pid': self._pid, 'tid': 0,
          'args': {'name': f'dctpu-{tier}'},
      })

  def _emit_raw(self, event: Dict[str, Any]) -> None:
    with self._lock:
      self._events.append(event)
      full = len(self._events) >= FLUSH_EVENTS
    if full:
      self.flush()

  def complete_event(self, name: str, cat: str, ts_s: float, dur_s: float,
                     args: Optional[Dict[str, Any]] = None) -> None:
    """One 'X' (complete) event; ts/dur in seconds of time.time()."""
    self._emit_raw(_complete(name, cat, ts_s, dur_s, args, self._pid))

  def flush(self) -> None:
    """Writes out what is buffered."""
    with self._lock:
      events, self._events = self._events, []
      _write_lines(self._fd, events)

  def close(self) -> None:
    with self._lock:
      events, self._events = self._events, []
      _write_lines(self._fd, events)
      if self._fd >= 0:
        os.close(self._fd)
        self._fd = -1

  def reset_after_fork(self) -> None:
    """In a forked child: the parent's buffered events are the parent's
    to write, and the lock may have been held by a thread that does not
    exist here."""
    self._lock = threading.Lock()
    self._pid = os.getpid()
    with self._lock:
      self._events = []


# Module state: one writer per process. `_writer is None` is the
# tracing-off fast path read on every span() call.
# dclint: lock-free (configure() runs at process startup before worker
# threads exist; after that the cell is read-only)
_writer: Optional[TraceWriter] = None
_local = threading.local()
# Stage ids, unique in the process (next() on a count is atomic).
_span_ids = itertools.count(1)
# The start-up record: STARTUP_SPANS events stamped while no writer is
# configured, at most EARLY_EVENTS of them, and how many did not fit.
_early_lock = threading.Lock()
_early: List[Dict[str, Any]] = []  # guarded by: _early_lock
early_events_dropped = 0  # guarded by: _early_lock


def _keep_early(event: Dict[str, Any]) -> None:
  global early_events_dropped
  with _early_lock:
    if len(_early) < EARLY_EVENTS:
      _early.append(event)
    else:
      early_events_dropped += 1


def clear_early() -> None:
  """Empties the start-up record (a test that builds a runner and
  configures late starts from here, whatever other tests compiled)."""
  global early_events_dropped
  with _early_lock:
    del _early[:]
    early_events_dropped = 0


def configure(path: Optional[str], tier: str = '') -> Optional[TraceWriter]:
  """Enables tracing to `path` (None/'' disables, and writes out what
  the last writer still held). A new writer starts with the start-up
  record. Returns the writer."""
  global _writer, early_events_dropped
  if _writer is not None:
    _writer.close()
    _writer = None
  if path:
    _writer = writer = TraceWriter(path, tier=tier)
    with _early_lock:
      early, dropped = list(_early), early_events_dropped
      del _early[:]
      early_events_dropped = 0
    for event in early:
      writer._emit_raw(event)
    if dropped:
      writer._emit_raw({
          'name': EARLY_DROPPED_EVENT, 'ph': 'M', 'pid': writer._pid,
          'tid': 0, 'args': {'count': dropped}})
  return _writer


def configure_from_env(tier: str = '') -> Optional[TraceWriter]:
  """Enables tracing when DCTPU_TRACE names a path (fleet processes
  inherit the env var from their spawner — that is how soak_e2e points
  every tier at one shared trace file)."""
  return configure(os.environ.get(ENV_TRACE) or None, tier=tier)


def enabled() -> bool:
  return _writer is not None


def writer() -> Optional[TraceWriter]:
  return _writer


def flush() -> None:
  """Writes out the buffered events (also runs at interpreter exit)."""
  w = _writer
  if w is not None:
    w.flush()


def _after_fork_in_child() -> None:
  global _early_lock
  w = _writer
  if w is not None:
    w.reset_after_fork()
  # The parent's start-up record is the parent's to write.
  _early_lock = threading.Lock()
  clear_early()


atexit.register(flush)
os.register_at_fork(after_in_child=_after_fork_in_child)


def mint_trace_id() -> str:
  """16-hex-char trace id (half a UUID; collision-safe at fleet scale)."""
  return os.urandom(8).hex()


def set_trace_id(trace_id: Optional[str]) -> None:
  """Binds `trace_id` to the current thread; span() stamps it into
  every event's args until cleared."""
  _local.trace_id = trace_id


def get_trace_id() -> Optional[str]:
  return getattr(_local, 'trace_id', None)


def complete_event(name: str, cat: str, t0: float, t1: float,
                   args: Optional[Dict[str, Any]] = None) -> None:
  """After-the-fact span from two time.time() stamps. With tracing off
  a no-op, so instrumentation sites call it unconditionally, except for
  the STARTUP_SPANS, which are kept for the writer to come."""
  w = _writer
  if w is None and name not in STARTUP_SPANS:
    return
  args = dict(args or {})
  trace_id = get_trace_id()
  if trace_id and 'trace_id' not in args:
    args['trace_id'] = trace_id
  if w is None:
    _keep_early(_complete(name, cat, t0, t1 - t0, args, os.getpid()))
  else:
    w.complete_event(name, cat, t0, t1 - t0, args)


def caused_event(name: str, t0: float, t1: float,
                 args: Dict[str, Any]) -> None:
  """A stage stamped after the fact that belongs to whatever stage is
  open on this thread (a compile belongs to the call that caused it):
  `args.under` is that stage's name, so a reader needs no join,
  `args.pack` its pack where it has one and, with tracing on,
  `args.parent` its id and `args.span` an id of this event's own."""
  stack = getattr(_local, 'stack', None)
  if stack:
    cause = stack[-1]
    args['under'] = cause.name
    if 'pack' in cause.args:
      args['pack'] = cause.args['pack']
    if cause._span:
      args['parent'] = cause._span
  if _writer is not None:
    args['span'] = next(_span_ids)
  complete_event(name, CAT_STAGE, t0, t1, args)


@contextlib.contextmanager
def span(name: str, cat: str = 'stage',
         **args: Any) -> Iterator[None]:
  """Context-managed span with no id and no histogram (request-level
  spans; pipeline stages use obs.stage). The tracing-off path is one
  global read and an empty yield."""
  if _writer is None:
    yield
    return
  t0 = time.time()
  try:
    yield
  finally:
    complete_event(name, cat, t0, time.time(), args)


def _profiler_annotation(name: str):
  """jax.profiler.TraceAnnotation(name) where jax is already imported
  (and far enough along to have its profiler), else None."""
  jax = sys.modules.get('jax')
  cls = getattr(getattr(jax, 'profiler', None), 'TraceAnnotation', None)
  return cls(name) if cls is not None else None


class Stage:
  """One lexically scoped stage: `with obs.stage(registry, name, **args)`.

  Feeds the same interval to the `stage_<name>_s` histogram and to the
  span, and stands on the thread's stack of open stages while it is open
  (with tracing off too: caused_event reads the top). With tracing on it
  takes a process-unique id and records the enclosing stage of this
  thread as its parent. `set()` adds counts that are only known inside
  the block (bytes of a result). Once per submit or per pack, never per
  window: it is a few microseconds, not free.
  """

  __slots__ = ('_registry', 'name', 'args', 't0', '_span', '_parent',
               '_annotation')

  def __init__(self, registry, name: str, args: Dict[str, Any]):
    self._registry = registry
    self.name = name
    self.args = args
    self.t0 = 0.0
    self._span = 0
    self._parent = 0
    self._annotation = None

  def set(self, **args: Any) -> None:
    self.args.update(args)

  def __enter__(self) -> 'Stage':
    stack = getattr(_local, 'stack', None)
    if stack is None:
      stack = _local.stack = []
    if _writer is not None:
      self._span = next(_span_ids)
      self._parent = stack[-1]._span if stack else 0
      self._annotation = _profiler_annotation(self.name)
      if self._annotation is not None:
        self._annotation.__enter__()
    stack.append(self)
    self.t0 = time.time()
    return self

  def __exit__(self, exc_type, exc, tb) -> None:
    t1 = time.time()
    if self._registry is not None:
      self._registry.observe(f'stage_{self.name}_s', t1 - self.t0)
    # (A thread that opened no stage has no stack: a generator may be
    # closed by another thread than the one that ran it.)
    stack = getattr(_local, 'stack', ())
    if stack and stack[-1] is self:
      stack.pop()
    elif self in stack:
      # A generator abandoned mid-stage left its stages above this one.
      del stack[stack.index(self):]
    if not self._span:
      # Opened with tracing off: no event, but for the start-up stages,
      # which name the stage above them in place of the id it lacks.
      if self.name in STARTUP_SPANS:
        if stack:
          self.args['under'] = stack[-1].name
        complete_event(self.name, CAT_STAGE, self.t0, t1, self.args)
      return
    if self._annotation is not None:
      self._annotation.__exit__(exc_type, exc, tb)
    args = self.args
    args['span'] = self._span
    if self._parent:
      args['parent'] = self._parent
    complete_event(self.name, CAT_STAGE, self.t0, t1, args)
