"""Reduction of a JAX profiler trace (.xplane.pb) to the numbers read here.

Two halves. `load` reads the file with `jax.profiler.ProfileData` into
plain Python: {plane: {line: [(name, start_ns, duration_ns), ...]}}, with
each line's own time base applied, so that all planes share one clock.
Everything else works on those lists, so it is checked on a recorded
trace kept as JSON under benchmark/tests/.

On a TPU v5e (seen by hand in the first chip trace of PR 24): the device
plane is `/device:TPU:0`; its line `XLA Modules` has one event per
executed program, named `jit_<function>(<fingerprint>)`: the forward that
`ModelRunner` jits is `jit_forward(...)`; its line `XLA Ops` has one
event per HLO operation. Host threads are lines of `/host:CPU`, and a
`jax.profiler.TraceAnnotation` is an event on the line of its thread.
"""
from __future__ import annotations

import glob
import os
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
Planes = Dict[str, Dict[str, List[Event]]]

DEVICE_PLANE_PREFIX = '/device:TPU:'
HOST_PLANE = '/host:CPU'
MODULE_LINE = 'XLA Modules'
OP_LINE = 'XLA Ops'


def find_trace(trace_dir: str) -> str:
  found = sorted(glob.glob(
      os.path.join(trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
  if not found:
    raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
  return found[-1]


def load(path: str, host_names: Iterable[str] = ()) -> Planes:
  """Device planes whole; of the host plane only events named in
  `host_names` (the benchmark's annotations), which keeps it small."""
  import jax

  keep = set(host_names)
  planes: Planes = {}
  for plane in jax.profiler.ProfileData.from_file(path).planes:
    device = plane.name.startswith(DEVICE_PLANE_PREFIX)
    if not device and plane.name != HOST_PLANE:
      continue
    lines = planes.setdefault(plane.name, {})
    for line in plane.lines:
      events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
                if device or ev.name in keep]
      if events:
        lines.setdefault(line.name, []).extend(events)
  return planes


def device_planes(planes: Planes) -> List[str]:
  return sorted(p for p in planes if p.startswith(DEVICE_PLANE_PREFIX))


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
  """Length of the union of [start, end) intervals given in ns."""
  total, cur_lo, cur_hi = 0.0, None, None
  for lo, hi in sorted(intervals):
    if cur_hi is None or lo > cur_hi:
      if cur_hi is not None:
        total += cur_hi - cur_lo
      cur_lo, cur_hi = lo, hi
    else:
      cur_hi = max(cur_hi, hi)
  if cur_hi is not None:
    total += cur_hi - cur_lo
  return total / 1e9


def _clip(events: List[Event], lo: float, hi: float):
  for _name, start, dur in events:
    a, b = max(start, lo), min(start + dur, hi)
    if b > a:
      yield a, b


def window_of(planes: Planes, name: str) -> Tuple[float, float]:
  """[start, end) in ns of the host annotation `name` (the measured
  window, which the harness wraps in one annotation)."""
  for events in planes.get(HOST_PLANE, {}).values():
    for ev_name, start, dur in events:
      if ev_name == name:
        return start, start + dur
  raise KeyError(f'annotation {name!r} not in the trace')


def busy_seconds(planes: Planes, lo: float, hi: float) -> float:
  """Seconds in [lo, hi) in which an operation ran on the device,
  averaged over the device planes."""
  per_device = []
  for plane in device_planes(planes):
    lines = planes[plane]
    events = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
    per_device.append(union_seconds(_clip(events, lo, hi)))
  return sum(per_device) / len(per_device) if per_device else 0.0


def module_durations(planes: Planes, prefix: str, lo: float, hi: float,
                     plane: Optional[str] = None) -> List[float]:
  """Device durations (s) of the executed programs whose name starts with
  `prefix` and that started inside [lo, hi), on one device plane."""
  names = device_planes(planes)
  if not names:
    return []
  events = planes[plane or names[0]].get(MODULE_LINE, [])
  return [dur / 1e9 for name, start, dur in events
          if name.startswith(prefix) and lo <= start < hi]


def short_op_name(name: str) -> str:
  """`%fusion.2 = bf16[...] fusion(...), kind=kCustom, ...` ->
  `%fusion.2 bf16[16384000,8] kCustom`: the trace names an operation by
  its whole HLO line."""
  head, sep, rest = name.partition(' = ')
  if not sep:
    return name[:96]
  out_shape = rest.split('{', 1)[0].split(' ', 1)[0]
  kind = rest.rsplit('kind=', 1)[1].split(',', 1)[0] if 'kind=' in rest else ''
  return ' '.join(x for x in (head, out_shape, kind) if x)[:96]


FORWARD_MODULE_PREFIX = 'jit_forward'  # the forward that ModelRunner jits


def forward_median_seconds(planes: Planes, lo: float,
                           hi: float) -> Optional[float]:
  """Median device duration of the forward program's runs in [lo, hi)."""
  return median(module_durations(planes, FORWARD_MODULE_PREFIX, lo, hi))


def top_ops(planes: Planes, lo: float, hi: float, k: int = 10):
  """[[name, seconds], ...]: device operations by total time in [lo, hi)."""
  names = device_planes(planes)
  if not names:
    return []
  totals: Dict[str, float] = {}
  for name, start, dur in planes[names[0]].get(OP_LINE, []):
    a, b = max(start, lo), min(start + dur, hi)
    if b > a:
      totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
  ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
  return [[short_op_name(name), seconds] for name, seconds in ranked]


def idle_gaps(planes: Planes, lo: float, hi: float, host_names: Iterable[str],
              k: int = 10):
  """[[label, seconds], ...]: idle time of the first device in [lo, hi),
  summed by what the host was doing (the innermost of the benchmark's
  annotations that covers the gap's middle, else 'host_other')."""
  names = device_planes(planes)
  if not names:
    return []
  lines = planes[names[0]]
  busy = sorted(_clip(lines.get(OP_LINE) or lines.get(MODULE_LINE) or [],
                      lo, hi))
  gaps, cursor = [], lo
  for a, b in busy:
    if a > cursor:
      gaps.append((cursor, a))
    cursor = max(cursor, b)
  if hi > cursor:
    gaps.append((cursor, hi))
  keep = set(host_names)
  host = [(n, s, s + d) for evs in planes.get(HOST_PLANE, {}).values()
          for n, s, d in evs if n in keep]
  totals: Dict[str, float] = {}
  for a, b in gaps:
    mid = (a + b) / 2
    cover = [(e - s, n) for n, s, e in host if s <= mid < e]
    label = min(cover)[1] if cover else 'host_other'
    totals[label] = totals.get(label, 0.0) + (b - a) / 1e9
  ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
  return [[name, seconds] for name, seconds in ranked]


def median(values: List[float]) -> Optional[float]:
  return statistics.median(values) if values else None


def describe(planes: Planes) -> List[str]:
  """One line per plane and line, for a look by hand."""
  out = []
  for plane, lines in sorted(planes.items()):
    for line, events in sorted(lines.items()):
      sample = sorted({name for name, _s, _d in events})[:4]
      out.append(f'{plane} | {line} | {len(events)} events | {sample}')
  return out
