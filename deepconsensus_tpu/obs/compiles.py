"""JAX's trace, lower and compile events as spans and counters.

`jax.monitoring` publishes what a jitted call costs the first time it
meets a shape: `/jax/core/compile/jaxpr_trace_duration` (tracing the
Python function to a jaxpr), `.../jaxpr_to_mlir_module_duration`
(lowering the jaxpr to an MLIR module) and `.../backend_compile_duration`
(XLA, or the read of the executable out of the persistent cache), each
with `time.time()` stamps (the span clock) and JAX's `fun_name`,
synchronously on the thread that made the call. So the stage open on
that thread is the cause, and the event becomes a span under it
(`trace.caused_event`): `jit_trace`, `jit_lower`, `xla_compile`.

A forward's trace raises hundreds of sub-millisecond traces of `jnp`
helpers: events shorter than `MIN_SPAN_S` are counted and observed, not
written. Inner traces end before the outer one and carry the same open
stage, so a reader that wants seconds takes the union of the intervals.
The persistent cache's `cache_hits` and `cache_retrieval_time_sec` arrive
inside the backend-compile interval on the same thread; they are held
until that interval closes and become its `cache_hit` and
`cache_retrieval_s`.

Counters (`jit_traces_total`, `xla_compiles_total`,
`xla_cache_hits_total`), the three stage histograms (every event's
interval, so their sums count a nested trace twice) and the gauges
`jit_trace_seconds`, `jit_lower_seconds`, `xla_compile_seconds` (seconds
since the registry was bound, nested events not counted twice: what
`startup_split` reads) go to the registry bound last (`install(registry)`:
a `ModelRunner` binds its own), always on: a compile is rare and costs
seconds. Nothing fires in a call that does not compile.

Imported where jax already is (inference/runner.py): `obs/__init__` never
imports jax (the router has none).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from jax import monitoring

from deepconsensus_tpu.obs import trace
from deepconsensus_tpu.obs.metrics import MetricsRegistry

SPAN_OF_EVENT = {
    '/jax/core/compile/jaxpr_trace_duration': trace.STAGE_JIT_TRACE,
    '/jax/core/compile/jaxpr_to_mlir_module_duration': trace.STAGE_JIT_LOWER,
    '/jax/core/compile/backend_compile_duration': trace.STAGE_XLA_COMPILE,
}
CACHE_HIT_EVENT = '/jax/compilation_cache/cache_hits'
CACHE_RETRIEVAL_EVENT = '/jax/compilation_cache/cache_retrieval_time_sec'
COUNTER_OF_SPAN = {
    trace.STAGE_JIT_TRACE: 'jit_traces_total',
    trace.STAGE_XLA_COMPILE: 'xla_compiles_total',
}
CACHE_HITS_COUNTER = 'xla_cache_hits_total'
SECONDS_GAUGE_OF_SPAN = {
    trace.STAGE_JIT_TRACE: 'jit_trace_seconds',
    trace.STAGE_JIT_LOWER: 'jit_lower_seconds',
    trace.STAGE_XLA_COMPILE: 'xla_compile_seconds',
}
# Shorter events are counted and observed, not written.
MIN_SPAN_S = 1e-3
# Ended events a thread remembers of each kind, to find those a later
# one encloses.
MAX_ENDED = 256

_lock = threading.Lock()
_registry: Optional[MetricsRegistry] = None  # guarded by: _lock
_seconds: Dict[str, float] = {}  # guarded by: _lock
_installed = False  # guarded by: _lock
# Per thread: what the cache said inside the backend-compile interval
# that is open, and the ended events of each kind, (start, seconds).
_local = threading.local()


def _on_event(event: str, **_kw: Any) -> None:
  if event == CACHE_HIT_EVENT:
    _local.cache_hit = True


def _on_duration(event: str, seconds: float, **_kw: Any) -> None:
  if event == CACHE_RETRIEVAL_EVENT:
    _local.cache_retrieval_s = seconds


def _on_time_span(event: str, start: float, end: float,
                  fun_name: str = '', **_kw: Any) -> None:
  name = SPAN_OF_EVENT.get(event)
  if name is None:
    return
  args = {'fun': fun_name}
  # Events of a thread arrive inner before outer: those this one
  # encloses are the last ended ones that began after it did.
  ended = _local.__dict__.setdefault(name, [])
  enclosed_s = 0.0
  while ended and ended[-1][0] >= start:
    enclosed_s += ended.pop()[1]
  ended.append((start, end - start))
  del ended[:-MAX_ENDED]
  with _lock:
    registry = _registry
    seconds = _seconds[name] = (
        _seconds.get(name, 0.0) + end - start - enclosed_s)
  if registry is not None:
    registry.observe(f'stage_{name}_s', end - start)
    registry.set_gauge(SECONDS_GAUGE_OF_SPAN[name], seconds)
    if name in COUNTER_OF_SPAN:
      registry.inc(COUNTER_OF_SPAN[name])
  if name == trace.STAGE_XLA_COMPILE:
    args['cache_hit'] = bool(_local.__dict__.pop('cache_hit', False))
    retrieval_s = _local.__dict__.pop('cache_retrieval_s', None)
    if retrieval_s is not None:
      args['cache_retrieval_s'] = retrieval_s
    if args['cache_hit'] and registry is not None:
      registry.inc(CACHE_HITS_COUNTER)
  if end - start >= MIN_SPAN_S:
    trace.caused_event(name, start, end, args)


def install(registry: Optional[MetricsRegistry] = None) -> None:
  """Registers the three listeners, once a process, and binds
  `registry` (where given) as the one the counters go to."""
  global _registry, _installed
  with _lock:
    if registry is not None:
      _registry = registry
      _seconds.clear()
    if _installed:
      return
    _installed = True
  monitoring.register_event_listener(_on_event)
  monitoring.register_event_duration_secs_listener(_on_duration)
  monitoring.register_event_time_span_listener(_on_time_span)


def startup_split(registry: MetricsRegistry) -> Dict[str, Any]:
  """Where a runner's start-up went, in seconds, from its registry (so
  with tracing off too): the start-up stages' histogram sums, the three
  seconds gauges, and how many of the compiles the persistent cache
  answered."""
  snap = registry.snapshot()

  def stage_s(*stages: str) -> float:
    return round(sum(
        snap['histograms'].get(f'stage_{stage}_s', {}).get('sum', 0.0)
        for stage in stages), 3)

  split = {
      'import_s': stage_s(trace.STAGE_IMPORT_RUNNER),
      'checkpoint_s': stage_s(trace.STAGE_CHECKPOINT_LOAD),
      'weights_s': stage_s(trace.STAGE_WEIGHTS_PREPARE,
                           trace.STAGE_WEIGHTS_PLACE),
  }
  for name, gauge in SECONDS_GAUGE_OF_SPAN.items():
    split[f'{name}_s'] = round(snap['gauges'].get(gauge, 0.0), 3)
  split['n_xla_compiles'] = snap['counters'].get(
      COUNTER_OF_SPAN[trace.STAGE_XLA_COMPILE], 0)
  split['n_xla_cache_hits'] = snap['counters'].get(CACHE_HITS_COUNTER, 0)
  return split


def format_startup(split: Dict[str, Any]) -> str:
  """`startup_split` as the one line `dctpu run` and `dctpu serve` log."""
  return (
      f'start-up: import {split["import_s"]:.1f} s, '
      f'checkpoint {split["checkpoint_s"]:.1f} s, '
      f'weights {split["weights_s"]:.1f} s, '
      f'jit trace {split["jit_trace_s"]:.1f} s, '
      f'lower {split["jit_lower_s"]:.1f} s, '
      f'compile {split["xla_compile_s"]:.1f} s '
      f'({split["n_xla_cache_hits"]} of {split["n_xla_compiles"]} '
      'from the cache)')
