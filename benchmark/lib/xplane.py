"""Reduction of a JAX profiler trace (.xplane.pb) to the numbers read here.

Two halves. `load` reads the file with `jax.profiler.ProfileData` into
plain Python: {plane: {line: [(name, start_ns, duration_ns), ...]}}, with
each line's own time base applied, so that all planes share one clock.
Everything else works on those lists, so it is checked on a recorded
trace kept as JSON under benchmark/tests/.

Beside the events `load` keeps a side table, `planes.scopes`: for each
device plane the source scope of every event of its `XLA Ops` line, in the
line's order (an event stays a 3-tuple). `scope_seconds` reads it, so that a
metric file can tell which operations are a given layer's.

On a TPU v5e (seen by hand in the first chip trace of PR 24): the device
plane is `/device:TPU:0`; its line `XLA Modules` has one event per
executed program, named `jit_<function>(<fingerprint>)`: the forward that
`ModelRunner` jits is `jit_forward(...)`; its line `XLA Ops` has one
event per HLO operation. Host threads are lines of `/host:CPU`, and a
`jax.profiler.TraceAnnotation` is an event on the line of its thread.

Where the scope is (seen by hand in a chip trace of `teacher_polish`, PR
27): NOT among the stats of the event. An `XLA Ops` event carries only
`device_offset_ps`, `device_duration_ps` and `Time Scale Multiplier`, and
`ProfileData` shows no others. The scope is a stat of the event's
*metadata* (the file's `XPlane.event_metadata`, one entry per HLO
operation, which `ProfileData` does not expose): `tf_op`, the JAX op-name
path with every `jax.named_scope` and Flax module on it, such as
`jit(forward)/DeepConsensusModel/apply_with_intermediates/
DeepConsensusModel._embed_rows/ip_embedding/jit(_take)/gather:` for
`%fusion.2`. A fusion has the path of its root operation. Beside it lie
`source` (file:line), `source_stack`, `hlo_category`, `flops`,
`bytes_accessed` and `shape_with_layout`, which nothing reads yet. An
event's name is its metadata's `name` (the whole HLO line, unique in a
program), so `op_scopes` reads the metadata straight from the file's
protobuf wire format and `load` joins the two by name.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE_PREFIX = '/device:TPU:'
HOST_PLANE = '/host:CPU'
MODULE_LINE = 'XLA Modules'
OP_LINE = 'XLA Ops'
SCOPE_STAT = 'tf_op'


class Planes(dict):
  """{plane: {line: [Event, ...]}}, and beside it `scopes`: {device plane:
  [scope of the i-th event of its `XLA Ops` line, ...]}. A plain dict, as
  the tests make by hand, is a trace without that side table."""

  def __init__(self, *args, **kwargs):
    super().__init__(*args, **kwargs)
    self.scopes: Dict[str, List[str]] = {}


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
  planes = Planes()
  scopes = op_scopes(path)
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
        if device and line.name == OP_LINE:
          by_name = scopes.get(plane.name, {})
          planes.scopes.setdefault(plane.name, []).extend(
              by_name.get(name, '') for name, _s, _d in events)
  return planes


def _fields(buf) -> Iterator[Tuple[int, object]]:
  """(field number, value) of one protobuf message: an int for a varint,
  a memoryview for a length-delimited field, None for a fixed one."""
  i, n = 0, len(buf)

  def varint():
    nonlocal i
    value = shift = 0
    while True:
      byte = buf[i]
      i += 1
      value |= (byte & 0x7F) << shift
      if byte < 0x80:
        return value
      shift += 7

  while i < n:
    key = varint()
    number, wire = key >> 3, key & 7
    if wire == 0:
      yield number, varint()
    elif wire == 2:
      size = varint()
      yield number, buf[i:i + size]
      i += size
    elif wire in (1, 5):
      i += 8 if wire == 1 else 4
      yield number, None
    else:
      raise ValueError(f'protobuf wire type {wire} in an .xplane.pb')


def _text(view) -> str:
  return bytes(view).decode('utf-8', 'replace')


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
  """{device plane: {event name: scope}} from the file's event metadata
  (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
  .stat_metadata = 5, maps whose entries hold the message under 2;
  XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .str_value =
  5, .ref_value = 7, the id of a stat metadata whose name is the string;
  XStatMetadata.id = 1, .name = 2). Lines and events are skipped whole."""
  out: Dict[str, Dict[str, str]] = {}
  with open(path, 'rb') as f:
    space = memoryview(f.read())
  for number, plane in _fields(space):
    if number != 1:
      continue
    name, event_entries, stat_names = '', [], {}
    for field, value in _fields(plane):
      if field == 2:
        name = _text(value)
      elif field == 4:
        event_entries.append(value)
      elif field == 5:
        meta = dict(_fields(dict(_fields(value))[2]))
        stat_names[meta.get(1, 0)] = _text(meta.get(2, b''))
    if not name.startswith(DEVICE_PLANE_PREFIX):
      continue
    scope_ids = {i for i, n in stat_names.items() if n == SCOPE_STAT}
    by_name = out.setdefault(name, {})
    for entry in event_entries:
      event_name, scope = '', ''
      for field, value in _fields(dict(_fields(entry))[2]):
        if field == 2:
          event_name = _text(value)
        elif field == 5:
          stat = dict(_fields(value))
          if stat.get(1) in scope_ids:
            scope = (_text(stat[5]) if 5 in stat
                     else stat_names.get(stat.get(7), ''))
      by_name[event_name] = scope.rstrip(':')
  return out


def scopes_of(planes) -> Dict[str, List[str]]:
  """The side table; empty for a plain dict of planes."""
  return getattr(planes, 'scopes', {})


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


def scope_seconds(planes: Planes, lo: float, hi: float,
                  pattern: str) -> float:
  """Seconds in [lo, hi) in which an operation ran on the device whose
  scope the regular expression `pattern` finds (`re.search`; '' finds
  every operation): the union of their intervals, averaged over the device
  planes as `busy_seconds` reckons, so that an operation nested in another
  is not counted twice. 0.0 where the planes carry no scopes."""
  table = scopes_of(planes)
  search = re.compile(pattern).search
  found: Dict[str, bool] = {}
  per_device = []
  for plane in device_planes(planes):
    hits = []
    for event, scope in zip(planes[plane].get(OP_LINE, []),
                            table.get(plane, [])):
      if scope not in found:
        found[scope] = search(scope) is not None
      if found[scope]:
        hits.append(event)
    per_device.append(union_seconds(_clip(hits, lo, hi)))
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
  for plane, scopes in sorted(scopes_of(planes).items()):
    distinct = sorted(set(scopes))
    out.append(f'{plane} | scopes | {len(distinct)} distinct | {distinct[:4]}')
  return out


def to_recording(planes: Planes) -> dict:
  """What the reduction reads, as JSON can hold it: the device planes'
  `XLA Modules` and `XLA Ops` lines and the host events, operation names
  cut to their short form, and the side table as each plane's distinct
  scopes with one index per `XLA Ops` event."""
  keep, scopes = {}, {}
  for plane, lines in planes.items():
    for line, events in lines.items():
      if plane == HOST_PLANE or line in (MODULE_LINE, OP_LINE):
        keep.setdefault(plane, {})[line] = [
            [short_op_name(n) if line == OP_LINE else n, s, d]
            for n, s, d in events]
  for plane, column in scopes_of(planes).items():
    names = sorted(set(column))
    index = {name: i for i, name in enumerate(names)}
    scopes[plane] = {'names': names, 'index': [index[s] for s in column]}
  return {'planes': keep, 'scopes': scopes}


def from_recording(recording: dict) -> Planes:
  """The planes of a recording; one made before the side table has none."""
  planes = Planes(
      (plane, {line: [tuple(e) for e in events]
               for line, events in lines.items()})
      for plane, lines in recording['planes'].items())
  for plane, column in recording.get('scopes', {}).items():
    planes.scopes[plane] = [column['names'][i] for i in column['index']]
  return planes
