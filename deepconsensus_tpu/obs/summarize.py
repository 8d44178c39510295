"""Trace-file summarization: the analysis half of ``dctpu trace``.

Reads a Chrome-trace-event file written by obs.trace (possibly by many
fleet processes appending to one file) and derives:

* per-stage time breakdown — total span duration and count per
  pipeline stage, and each stage's SELF time: its duration minus what
  its child stages cover, children found through `args.parent` ->
  `args.span` within one process (obs.stage writes both). A stage with
  no id (stamped after the fact: featurize, stitch) has no children, so
  its self time is its duration;
* waits apart — `pack_wait` and `device_compute` (`cat: "wait"`) are
  intervals between two events, not work on a thread: consecutive
  `pack_wait`s tile the timeline by construction and a `device_compute`
  runs from a forward's launch to the end of its drain on the host's
  clock (about dispatch_depth pack periods). They have totals and
  counts, no self time, and never enter the critical path;
* critical-path attribution — stages ordered by self time, each as a
  fraction of the end-to-end wall interval: the stage where a thread
  actually spent the time tops the list (with one thread the fractions
  sum to at most 1.0);
* straggler packs — the slowest decile of device_compute spans with
  their bucket / dp / row-count context;
* what the forward held and computed — from the `forward_launch`
  spans' args: block kind, layer pattern, the share of the experts held,
  positions launched, resident weight bytes;
* start-up and compiles — the start-up stages in order; JAX's trace,
  lower and compile seconds (`jit_trace`, `jit_lower`, `xla_compile`:
  nested events of a thread counted once) by the stage they arrived
  under; the functions that took the most trace time; and every compile
  after the first `submit`, with the pack of the launch that caused it;
* a span-derived transfer-overlap fraction that must agree with the
  counter-derived ``transfer_overlap_fraction``: a pack's forward
  launch (the device_compute span start) happening strictly BEFORE its
  own finalize_drain span start means a later dispatch launched it —
  the overlapped double-buffer path — while a direct launch happens
  inside finalize. Same pipeline property, measured through a second
  mechanism; disagreement means the instrumentation (or the double
  buffer) broke.

The per-stage totals here and the ``stage_*_s`` histogram sums in
/metricz come from the same measured intervals (obs.record_stage), so
they reconcile within float rounding (tests/test_obs.py).
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu.obs import trace as trace_lib


def load_trace(path: str) -> List[Dict[str, Any]]:
  """Parses an obs.trace file into a list of event dicts."""
  events: List[Dict[str, Any]] = []
  try:
    with open(path, 'r', encoding='utf-8') as f:
      lines = f.readlines()
  except OSError as e:
    raise faults_lib.CorruptInputError(
        f'cannot read trace file {path}: {e}') from e
  for i, line in enumerate(lines, start=1):
    text = line.strip()
    if not text or text in ('[', ']'):
      continue
    if text.endswith(','):
      text = text[:-1]
    try:
      event = json.loads(text)
    except ValueError as e:
      raise faults_lib.CorruptInputError(
          f'{path}:{i}: undecodable trace event: {e}') from e
    if not isinstance(event, dict):
      raise faults_lib.CorruptInputError(
          f'{path}:{i}: trace event is not an object')
    events.append(event)
  return events


def _complete_spans(events: List[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
  return [e for e in events if e.get('ph') == 'X']


def tier_names(events: List[Dict[str, Any]]) -> Dict[int, str]:
  """pid -> tier label from process_name metadata events."""
  out: Dict[int, str] = {}
  for e in events:
    if e.get('ph') == 'M' and e.get('name') == 'process_name':
      out[int(e.get('pid', 0))] = str(
          (e.get('args') or {}).get('name', ''))
  return out


def trace_groups(events: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
  """trace_id -> {'pids': sorted pids, 'names': span names, 'n_spans'}.

  The fleet-soak connectivity check: a delivered request's id must
  group spans from every tier it crossed (router -> featurize worker
  -> replica for the bam/1 leg) into ONE connected trace.
  """
  groups: Dict[str, Dict[str, Any]] = {}
  for e in _complete_spans(events):
    trace_id = (e.get('args') or {}).get('trace_id')
    if not trace_id:
      continue
    g = groups.setdefault(str(trace_id),
                          {'pids': set(), 'names': set(), 'n_spans': 0})
    g['pids'].add(int(e.get('pid', 0)))
    g['names'].add(str(e.get('name', '')))
    g['n_spans'] += 1
  return {
      tid: {'pids': sorted(g['pids']), 'names': sorted(g['names']),
            'n_spans': g['n_spans']}
      for tid, g in groups.items()
  }


def span_overlap(events: List[Dict[str, Any]]) -> Dict[str, Any]:
  """Span-derived transfer/compute overlap: per (pid, pack), the
  device_compute span starting strictly before its finalize_drain span
  means the launch was overlapped by a later dispatch."""
  compute_ts: Dict[Tuple[int, Any], float] = {}
  finalize_ts: Dict[Tuple[int, Any], float] = {}
  for e in _complete_spans(events):
    args = e.get('args') or {}
    if 'pack' not in args:
      continue
    key = (int(e.get('pid', 0)), args['pack'])
    if e.get('name') == trace_lib.STAGE_DEVICE_COMPUTE:
      compute_ts[key] = float(e['ts'])
    elif e.get('name') == trace_lib.STAGE_FINALIZE:
      finalize_ts[key] = float(e['ts'])
  n_overlapped = 0
  n_direct = 0
  for key, ts in compute_ts.items():
    fin = finalize_ts.get(key)
    if fin is None:
      # Drain-free pack: a fully device-resident run batches its drain
      # at end-of-input, so the pack has a device_compute span but no
      # finalize_drain span of its own. Its launch was necessarily
      # overlapped — a direct launch only ever happens INSIDE finalize
      # (runner._finalize_sync), which would have emitted the span.
      # Dropping these from the sample (the old behavior) skewed the
      # span-derived fraction low on exactly the runs that overlap
      # best.
      n_overlapped += 1
      continue
    if ts < fin:
      n_overlapped += 1
    else:
      n_direct += 1
  launches = n_overlapped + n_direct
  return {
      'n_packs': launches,
      'n_overlapped': n_overlapped,
      'n_direct': n_direct,
      'span_overlap_fraction': (
          round(n_overlapped / launches, 4) if launches else 0.0),
  }


def _is_wait(event: Dict[str, Any]) -> bool:
  """cat 'wait', or one of the wait names in a trace written before the
  category existed."""
  return (event.get('cat') == trace_lib.CAT_WAIT
          or event.get('name') in trace_lib.WAITS)


def _thread(event: Dict[str, Any]) -> Tuple[int, int]:
  return int(event.get('pid', 0)), int(event.get('tid', 0))


def _outermost(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
  """The events no other of `events` on the same thread encloses. JAX's
  compile events nest (a function traced inside another's trace) and
  all name the same open stage: their seconds are those of the
  outermost."""
  out: List[Dict[str, Any]] = []
  end_of: Dict[Tuple[int, int], float] = {}
  for e in sorted(events, key=lambda e: (float(e['ts']),
                                         -float(e.get('dur', 0.0)))):
    end = float(e['ts']) + float(e.get('dur', 0.0))
    if end <= end_of.get(_thread(e), float('-inf')):
      continue
    end_of[_thread(e)] = end
    out.append(e)
  return out


def _is_compile(event: Dict[str, Any]) -> bool:
  return event.get('name') in trace_lib.COMPILE_SPANS


def self_times(events: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
  """stage name -> {'total_s', 'self_s', 'count', 'under'}: over the
  work stages (cat 'stage', waits left out), a stage's self time is its
  duration minus the durations of the stages whose `args.parent` is its
  `args.span` in the same process (children of one parent run one after
  the other on its thread, so they do not overlap; of JAX's compile
  events, which nest, only the outermost count). 'under' lists the
  names of the stages it ran under ('' at top level)."""
  stages = [e for e in _complete_spans(events)
            if e.get('cat') == trace_lib.CAT_STAGE and not _is_wait(e)]
  stages = ([e for e in stages if not _is_compile(e)]
            + _outermost([e for e in stages if _is_compile(e)]))
  name_of: Dict[Tuple[int, Any], str] = {}
  child_us: Dict[Tuple[int, Any], float] = {}
  for e in stages:
    args = e.get('args') or {}
    pid = int(e.get('pid', 0))
    if 'span' in args:
      name_of[(pid, args['span'])] = str(e.get('name', ''))
    if 'parent' in args:
      key = (pid, args['parent'])
      child_us[key] = child_us.get(key, 0.0) + float(e.get('dur', 0.0))
  out: Dict[str, Dict[str, Any]] = {}
  for e in stages:
    args = e.get('args') or {}
    pid = int(e.get('pid', 0))
    dur = float(e.get('dur', 0.0))
    own = dur - child_us.get((pid, args.get('span')), 0.0)
    row = out.setdefault(str(e.get('name', '')), {
        'total_s': 0.0, 'self_s': 0.0, 'count': 0, 'under': set()})
    row['total_s'] += dur / 1e6
    row['self_s'] += max(0.0, own) / 1e6
    row['count'] += 1
    row['under'].add(name_of.get((pid, args.get('parent')),
                                 str(args.get('under', ''))))
  return {
      name: {'total_s': round(row['total_s'], 6),
             'self_s': round(row['self_s'], 6),
             'count': row['count'], 'under': sorted(row['under'])}
      for name, row in sorted(out.items())}


def startup(events: List[Dict[str, Any]], top: int = 5) -> Dict[str, Any]:
  """The start-up stages in order, and what JAX traced, lowered and
  compiled: seconds of each kind by the stage the events arrived under
  (`args.under`; nested events of a thread counted once), the `top`
  functions by trace seconds, and the compiles that began after the
  first `submit` of their process ("which step recompiled")."""
  spans = _complete_spans(events)
  stages = sorted(
      (e for e in spans if e.get('name') in trace_lib.STARTUP_SPANS
       and not _is_compile(e)), key=lambda e: float(e['ts']))
  # The stage above one: by name where it was stamped with tracing off,
  # through its parent's id otherwise.
  name_of = {(int(e.get('pid', 0)), e['args']['span']): str(e['name'])
             for e in spans if 'span' in (e.get('args') or {})}

  def under(e: Dict[str, Any]):
    args = e.get('args') or {}
    return args.get('under') or name_of.get(
        (int(e.get('pid', 0)), args.get('parent')))

  def seconds(group: List[Dict[str, Any]]) -> float:
    return round(
        sum(float(e.get('dur', 0.0)) for e in _outermost(group)) / 1e6, 6)

  def grouped(group: List[Dict[str, Any]], key) -> Dict[str, float]:
    by_key: Dict[str, List[Dict[str, Any]]] = {}
    for e in group:
      by_key.setdefault(key(e.get('args') or {}), []).append(e)
    return {k: seconds(v) for k, v in sorted(by_key.items())}

  by_kind = {kind: [e for e in spans if e.get('name') == kind]
             for kind in trace_lib.COMPILE_SPANS}
  kinds = {
      kind: {'total_s': seconds(group), 'count': len(group),
             'under': grouped(group, lambda a: str(a.get('under', '')))}
      for kind, group in by_kind.items()}
  compiles = by_kind[trace_lib.STAGE_XLA_COMPILE]
  kinds[trace_lib.STAGE_XLA_COMPILE]['cache_hits'] = sum(
      1 for e in compiles if (e.get('args') or {}).get('cache_hit'))
  by_fun = grouped(by_kind[trace_lib.STAGE_JIT_TRACE],
                   lambda a: str(a.get('fun', '')))
  first_submit: Dict[int, float] = {}
  for e in spans:
    if e.get('name') == trace_lib.STAGE_SUBMIT:
      pid = int(e.get('pid', 0))
      first_submit[pid] = min(first_submit.get(pid, float('inf')),
                              float(e['ts']))
  late = []
  for e in sorted(compiles, key=lambda e: float(e['ts'])):
    if float(e['ts']) < first_submit.get(int(e.get('pid', 0)),
                                         float('inf')):
      continue
    args = e.get('args') or {}
    late.append({
        'fun': args.get('fun'), 'under': args.get('under'),
        'pack': args.get('pack'),
        'dur_s': round(float(e.get('dur', 0.0)) / 1e6, 6),
        'cache_hit': bool(args.get('cache_hit'))})
  return {
      'stages': [
          {'stage': str(e['name']),
           'dur_s': round(float(e.get('dur', 0.0)) / 1e6, 6),
           'under': under(e)}
          for e in stages],
      'compile_kinds': kinds,
      'top_traced': [
          {'fun': fun, 'trace_s': s} for fun, s in sorted(
              by_fun.items(), key=lambda item: -item[1])[:top]],
      'compiles_after_first_submit': late,
      'early_events_dropped': sum(
          int((e.get('args') or {}).get('count', 0)) for e in events
          if e.get('ph') == 'M'
          and e.get('name') == trace_lib.EARLY_DROPPED_EVENT),
  }


# What `dctpu trace` collects of the `forward_launch` spans: a field and
# how each of its values is read. A stack's rotations are one string, 'W
# default, F yarn×16'.
_LAUNCH_FIELDS = (
    ('block_kind', str),
    ('attention_path', str),
    ('delta_rule_path', str),
    ('latent_attention_path', str),
    ('grouped_attention_path', str),
    ('grouped_product_path', str),
    ('combine_path', str),
    ('moe_turns', int),
    ('block_form', str),
    ('layer_pattern', str),
    ('attention_window', int),
    ('shared_experts', int),
    ('rope', lambda rope: ', '.join(
        f'{letter} {how}' for letter, how in dict(rope).items())),
    ('ffn_pattern', str),
    ('router_scoring', str),
)


def summarize(events: List[Dict[str, Any]],
              straggler_decile: float = 0.9) -> Dict[str, Any]:
  """Full trace summary (the ``dctpu trace`` payload)."""
  spans = _complete_spans(events)
  if not spans:
    raise faults_lib.CorruptInputError(
        'trace contains no complete (ph=X) spans')
  t_min = min(float(e['ts']) for e in spans)
  t_max = max(float(e['ts']) + float(e.get('dur', 0.0)) for e in spans)
  wall_s = (t_max - t_min) / 1e6

  stage_totals: Dict[str, float] = {}
  stage_counts: Dict[str, int] = {}
  waits: Dict[str, Dict[str, Any]] = {}
  for e in spans:
    if e.get('cat') not in (trace_lib.CAT_STAGE, trace_lib.CAT_WAIT):
      continue
    name = str(e.get('name', ''))
    dur_s = float(e.get('dur', 0.0)) / 1e6
    stage_totals[name] = stage_totals.get(name, 0.0) + dur_s
    stage_counts[name] = stage_counts.get(name, 0) + 1
    if _is_wait(e):
      row = waits.setdefault(name, {'total_s': 0.0, 'count': 0})
      row['total_s'] += dur_s
      row['count'] += 1

  self_time = self_times(events)
  critical_path = sorted(
      ({'stage': name,
        'self_s': row['self_s'],
        'fraction_of_wall': (round(row['self_s'] / wall_s, 4)
                             if wall_s else 0.0)}
       for name, row in self_time.items()),
      key=lambda row: -row['self_s'])

  compute_spans = sorted(
      (e for e in spans
       if e.get('name') == trace_lib.STAGE_DEVICE_COMPUTE),
      key=lambda e: float(e.get('dur', 0.0)))
  stragglers = []
  if compute_spans:
    cut = int(len(compute_spans) * straggler_decile)
    for e in compute_spans[cut:]:
      args = e.get('args') or {}
      stragglers.append({
          'pack': args.get('pack'),
          'dur_s': round(float(e.get('dur', 0.0)) / 1e6, 6),
          'bucket': args.get('bucket'),
          'dp': args.get('dp'),
          'n_rows': args.get('n_rows'),
          'pid': e.get('pid'),
      })
    stragglers.sort(key=lambda row: -row['dur_s'])

  launches = [e.get('args') or {} for e in spans
              if e.get('name') == trace_lib.STAGE_LAUNCH]
  forward = {'n_launches': len(launches)}
  # Each field's values over the launches, under its name made plural.
  for field, cast in _LAUNCH_FIELDS:
    forward[field if field.endswith('s') else field + 's'] = sorted(
        {cast(a[field]) for a in launches if a.get(field)})
  forward.update(
      experts_held=sorted(
          {(*a['experts_held'], a.get('experts_published'))
           for a in launches if a.get('experts_held')}),
      n_positions=sum(int(a.get('n_positions') or 0) for a in launches),
      weight_bytes=max(
          (int(a.get('weight_bytes') or 0) for a in launches), default=0))

  return {
      'n_events': len(events),
      'n_spans': len(spans),
      'wall_s': round(wall_s, 6),
      'tiers': tier_names(events),
      'stage_totals_s': {k: round(v, 6)
                         for k, v in sorted(stage_totals.items())},
      'stage_counts': dict(sorted(stage_counts.items())),
      'self_time': self_time,
      'waits': {name: {'total_s': round(row['total_s'], 6),
                       'count': row['count']}
                for name, row in sorted(waits.items())},
      'critical_path': critical_path,
      'stragglers': stragglers,
      'forward': forward,
      'startup': startup(events),
      'overlap': span_overlap(events),
      'n_traces': len(trace_groups(events)),
  }


def _format_startup(startup: Dict[str, Any]) -> List[str]:
  kinds = {kind: row for kind, row in (
      startup.get('compile_kinds') or {}).items() if row['count']}
  if not startup.get('stages') and not kinds:
    return []
  lines = ['start-up and compiles:']
  for row in startup.get('stages', ()):
    under = f'  under: {row["under"]}' if row.get('under') else ''
    lines.append(f'  {row["stage"]:<16} {row["dur_s"]:>10.4f}s{under}')
  for kind, row in kinds.items():
    under = ', '.join(f'{name or "-"} {s:.4f}s'
                      for name, s in row['under'].items())
    hits = (f'  cache hits {row["cache_hits"]}/{row["count"]}'
            if 'cache_hits' in row else '')
    lines.append(f'  {kind:<16} {row["total_s"]:>10.4f}s  n={row["count"]}'
                 f'{hits}  under: {under}')
  if startup.get('top_traced'):
    lines.append('  most trace time: ' + ', '.join(
        f'{row["fun"]} {row["trace_s"]:.4f}s'
        for row in startup['top_traced']))
  late = startup.get('compiles_after_first_submit') or ()
  lines.append(f'  compiles after the first submit: {len(late)}')
  for row in late:
    lines.append(
        f'    {row["fun"]} {row["dur_s"]:.4f}s under {row["under"] or "-"} '
        f'pack={row["pack"]}'
        + (' (cache hit)' if row['cache_hit'] else ''))
  if startup.get('early_events_dropped'):
    lines.append(f'  start-up events dropped before tracing was '
                 f'configured: {startup["early_events_dropped"]}')
  return lines


def format_summary(summary: Dict[str, Any]) -> str:
  """Human-readable rendering for the CLI."""
  lines = [
      f'trace: {summary["n_spans"]} spans over '
      f'{summary["wall_s"]:.3f}s wall',
  ]
  if summary.get('tiers'):
    tiers = ', '.join(f'{pid}={name}'
                      for pid, name in sorted(summary['tiers'].items()))
    lines.append(f'tiers: {tiers}')
  lines.append('self time per stage (critical-path order):')
  self_time = summary['self_time']
  for row in summary['critical_path']:
    stage = row['stage']
    st = self_time[stage]
    under = ', '.join(u or '-' for u in st['under'])
    lines.append(
        f'  {stage:<16} self {st["self_s"]:>10.4f}s '
        f'({100 * row["fraction_of_wall"]:5.1f}% of wall)  '
        f'total {st["total_s"]:>10.4f}s  n={st["count"]}  '
        f'under: {under}')
  if summary.get('waits'):
    lines.append('waits (intervals between two events, not work; '
                 'no self time):')
    for name, row in summary['waits'].items():
      lines.append(f'  {name:<16} total {row["total_s"]:>10.4f}s  '
                   f'n={row["count"]}')
  forward = summary.get('forward') or {}
  if forward.get('n_launches'):
    lines.append(
        f'forward: {forward["n_launches"]} launches of '
        f'{", ".join(forward["block_kinds"]) or "?"} '
        f'(attention: {", ".join(forward["attention_paths"]) or "?"}), '
        f'{forward["n_positions"]} positions, '
        f'{forward["weight_bytes"] / 2**30:.3f} GiB of weights resident')
    if forward.get('layer_patterns'):
      delta_rule = ', '.join(forward.get('delta_rule_paths', ()))
      latent = ', '.join(forward.get('latent_attention_paths', ()))
      grouped_heads = ', '.join(forward.get('grouped_attention_paths', ()))
      ffn =', '.join(forward.get('ffn_patterns', ()))
      scoring = ', '.join(forward.get('router_scorings', ()))
      grouped = ', '.join(forward.get('grouped_product_paths', ()))
      combine = ', '.join(forward.get('combine_paths', ()))
      turns = ', '.join(str(n) for n in forward.get('moe_turns', ()))
      shared = ', '.join(
          f'{n} averaged' for n in forward.get('shared_experts', ()))
      experts = '; '.join(
          f'{what}: {said}' for what, said in (
              ('router', scoring), ('grouped products', grouped),
              ('combine', combine), ('turns a pack', turns),
              ('shared experts', shared)) if said)
      # A sequential block is what every kind but one has: only the other
      # form is said.
      forms = ', '.join(form for form in forward.get('block_forms', ())
                        if form != 'sequential')
      windows = ', '.join(
          str(w) for w in forward.get('attention_windows', ()))
      ropes = '; '.join(forward.get('ropes', ()))
      lines.append(
          f'  layers: {", ".join(forward["layer_patterns"])}'
          + (f' ({forms} block)' if forms else '')
          + (f' (window: {windows})' if windows else '')
          + (f' (rope: {ropes})' if ropes else '')
          + (f' (delta rule: {delta_rule})' if delta_rule else '')
          + (f' (latent attention: {latent})' if latent else '')
          + (f' (grouped-head attention: {grouped_heads})'
             if grouped_heads else '') + ''.join(
              f'; experts {lo}-{hi - 1} of {published} held'
              for lo, hi, published in forward.get('experts_held', ()))
          + (f' ({experts})' if experts else '')
          + (f'; feed-forward: {ffn}' if ffn else ''))
  lines.extend(_format_startup(summary.get('startup') or {}))
  overlap = summary['overlap']
  lines.append(
      f'transfer overlap (span-derived): '
      f'{overlap["n_overlapped"]}/{overlap["n_packs"]} packs '
      f'(fraction {overlap["span_overlap_fraction"]})')
  if summary['stragglers']:
    lines.append('straggler packs (slowest decile of device compute):')
    for row in summary['stragglers'][:10]:
      lines.append(
          f'  pack {row["pack"]} {row["dur_s"]:.4f}s '
          f'bucket={row["bucket"]} dp={row["dp"]} '
          f'n_rows={row["n_rows"]} pid={row["pid"]}')
  if summary.get('n_traces'):
    lines.append(f'distinct request traces: {summary["n_traces"]}')
  return '\n'.join(lines)
