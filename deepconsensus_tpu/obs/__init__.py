"""Unified observability plane: metrics registry, trace spans,
profiler capture, trace summarization.

``stage`` (work on a thread, lexically scoped, with a span id and a
parent) and ``record_stage`` (an interval stamped after the fact) are
the two helpers every pipeline instrumentation site calls: each feeds
the SAME measured interval to both the stage histogram
(``stage_<name>_s`` on the tier's MetricsRegistry) and the trace span,
which is what makes span-derived per-stage totals reconcile with
/metricz histogram sums by construction.
"""
from __future__ import annotations

from typing import Any, Optional

from deepconsensus_tpu.obs import metrics
from deepconsensus_tpu.obs import profiler
from deepconsensus_tpu.obs import summarize
from deepconsensus_tpu.obs import trace
from deepconsensus_tpu.obs.metrics import (DEFAULT_LATENCY_BUCKETS,
                                           MetricsRegistry)


def stage_histogram_name(stage: str) -> str:
  return f'stage_{stage}_s'


def stage(registry: Optional[MetricsRegistry], name: str,
          **args: Any) -> trace.Stage:
  """`with obs.stage(registry, name, **args) as st:` times the block as
  one pipeline stage: a histogram observation and, with tracing on, a
  span that knows its parent (see trace.Stage). `st.set(...)` adds
  counts known only inside the block."""
  return trace.Stage(registry, name, args)


def record_stage(registry: Optional[MetricsRegistry], stage: str,
                 t0: float, t1: float, cat: str = trace.CAT_STAGE,
                 **args: Any) -> None:
  """Records one interval [t0, t1] (time.time() stamps) stamped after
  the fact, as both a histogram observation and a trace span with no
  parent. `cat=trace.CAT_WAIT` for an interval between two events that
  is no thread's work (pack_wait, device_compute)."""
  if registry is not None:
    registry.observe(stage_histogram_name(stage), t1 - t0)
  trace.complete_event(stage, cat, t0, t1, args)


__all__ = [
    'DEFAULT_LATENCY_BUCKETS',
    'MetricsRegistry',
    'metrics',
    'profiler',
    'record_stage',
    'stage',
    'stage_histogram_name',
    'summarize',
    'trace',
]
