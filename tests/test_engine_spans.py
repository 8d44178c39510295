"""The span chain of the engine and the runner (obs.stage sites).

A weightless ModelRunner whose jitted forward is replaced by a stub that
echoes each window's draft-CCS row, so `dispatch`, `pack_cast`,
`h2d_transfer`, `forward_launch`, `finalize_drain` and `deliver` all run
for real on the CPU. What is held here: which spans a submit emits and
under which parent, that children lie inside their parents and siblings
do not overlap, that the number of events depends on submits and packs
and never on windows, and that with tracing off nothing is built and
nothing changes but the histograms.
"""
import collections
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.inference import engine as engine_lib
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.obs import summarize as summarize_lib
from deepconsensus_tpu.obs import trace as trace_lib

BATCH = 8
STUB_QUAL = 40
EPS_US = 2.0  # float rounding of ts + dur at 1.7e15 us


@pytest.fixture(autouse=True)
def _reset_trace():
  trace_lib.configure(None)
  yield
  trace_lib.configure(None)


@pytest.fixture(scope='module')
def params():
  p = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(p, is_training=False)
  return p


def _engine(params, batch_size=BATCH, ragged=False, buckets=None):
  """(engine, delivered): the real runner with a stub forward."""
  options = runner_lib.InferenceOptions(
      batch_size=batch_size, use_ragged_kernel=ragged,
      window_buckets=buckets)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  runner = runner_lib.ModelRunner(params, {}, options)
  ccs_row = 4 * params.max_passes

  def forward(_variables, main_u8, _sn, *_lengths):
    ids = main_u8[:, ccs_row, :, 0]
    return ids, jnp.full(ids.shape, STUB_QUAL, jnp.uint8)

  runner._forward = forward
  runner._ragged_forward = forward
  delivered = {}
  engine = engine_lib.ConsensusEngine(
      runner, options,
      deliver=lambda t, ids, quals: delivered.__setitem__(
          t, (ids.copy(), quals.copy())))
  return engine, delivered


def _raw(params, n, seed=0, width=None):
  rng = np.random.default_rng(seed)
  shape = (n, params.total_rows, width or params.max_length, 1)
  return rng.integers(0, 5, size=shape).astype(np.float32)


def _traced(tmp_path, fn):
  """Runs fn() with tracing on; returns the complete events. The runner
  was built, and other tests compiled, with tracing off: the start-up
  record they left is not this run's."""
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.clear_early()
  trace_lib.configure(path, tier='test')
  try:
    fn()
  finally:
    trace_lib.configure(None)
  # Without what the stub's eager jnp calls compiled at first use under
  # `forward_launch` (obs/compiles.py): whether they did depends on what
  # ran before in the process.
  return [e for e in summarize_lib.load_trace(path) if e.get('ph') == 'X'
          and e['name'] not in trace_lib.COMPILE_SPANS]


def _by_name(events):
  out = collections.defaultdict(list)
  for e in events:
    out[e['name']].append(e)
  return out


def _parent_name(events, event):
  ids = {e['args']['span']: e['name'] for e in events if 'span' in e['args']}
  return ids.get(event['args'].get('parent'))


# ----------------------------------------------------------------------
# What one submit emits


def test_list_submit_cutting_two_packs_emits_the_chain(tmp_path, params):
  engine, delivered = _engine(params)
  windows = list(_raw(params, 2 * BATCH + 3))
  events = _traced(
      tmp_path, lambda: engine.submit(windows, list(range(len(windows)))))
  spans = _by_name(events)
  assert len(spans['submit']) == 1
  assert spans['submit'][0]['args']['n_windows'] == 2 * BATCH + 3
  assert spans['submit'][0]['args']['formatted'] == 0
  assert 'parent' not in spans['submit'][0]['args']
  # The grouping by width; then one fill per pack buffer written to (two
  # full packs and the three windows that begin the third).
  assert len(spans['stack_windows']) == 1
  assert len(spans['format_rows']) == 3
  assert len(spans['pack_cut']) == 2
  assert len(spans['dispatch']) == 2
  for name in ('stack_windows', 'format_rows', 'pack_cut', 'dispatch'):
    for e in spans[name]:
      assert _parent_name(events, e) == 'submit', name
  for name in ('pack_cast', 'h2d_transfer'):
    assert len(spans[name]) == 2
    parents = [e['args']['parent'] for e in spans[name]]
    assert sorted(parents) == sorted(
        d['args']['span'] for d in spans['dispatch']), name
  # Pack 1's forward is launched by pack 2's dispatch (double buffer).
  assert len(spans['forward_launch']) == 1
  assert _parent_name(events, spans['forward_launch'][0]) == 'dispatch'
  # Nothing drained yet at depth 8; the waits carry no ids.
  assert 'finalize_drain' not in spans and 'deliver' not in spans
  assert len(spans['pack_wait']) == 2
  for e in spans['pack_wait']:
    assert e['cat'] == 'wait'
    assert 'span' not in e['args'] and 'parent' not in e['args']
  # Counts in args.
  grouped = spans['stack_windows'][0]['args']
  assert grouped['n_rows'] == 2 * BATCH + 3 and grouped['bytes'] == 0
  fills = [e['args'] for e in spans['format_rows']]
  assert [a['n_rows'] for a in fills] == [BATCH, BATCH, 3]
  # A window is read once as float32 and written once as uint8 + 4 SN.
  row_u8 = (params.total_rows - 4) * params.max_length + 4 * 4
  assert [a['bytes'] for a in fills] == [
      BATCH * row_u8, BATCH * row_u8, 3 * row_u8]
  assert [a['bytes_read'] for a in fills] == [
      windows[0].nbytes * k for k in (BATCH, BATCH, 3)]
  assert [e['args']['n_rows'] for e in spans['pack_cut']] == [BATCH, BATCH]
  assert [e['args']['bytes_concatenated'] for e in spans['pack_cut']] == [0, 0]
  # A full pack leaves the engine compact: nothing to cast or pad.
  cast = spans['pack_cast'][0]['args']
  assert cast['bytes_in'] == cast['bytes_out'] == BATCH * row_u8
  assert [e['args']['pack'] for e in spans['dispatch']] == [1, 2]
  assert spans['h2d_transfer'][0]['args']['bytes'] == cast['bytes_out']
  assert not delivered


def test_second_submit_fills_the_begun_pack_and_flush_drains(
    tmp_path, params):
  engine, delivered = _engine(params)
  n = BATCH + 3

  def run():
    engine.submit(list(_raw(params, n)), list(range(n)))
    engine.submit(list(_raw(params, n, seed=1)), list(range(n, 2 * n)))
    engine.flush()

  events = _traced(tmp_path, run)
  spans = _by_name(events)
  assert len(spans['submit']) == 2 and len(spans['flush']) == 1
  cuts = spans['pack_cut']
  assert [_parent_name(events, e) for e in cuts] == [
      'submit', 'submit', 'flush']
  # The second submit goes on in the buffer the first one began: no cut
  # copies anything, whatever tail it found.
  assert [e['args']['bytes_concatenated'] for e in cuts] == [0, 0, 0]
  assert [e['args']['n_rows'] for e in cuts] == [BATCH, BATCH, 2 * n - 2 * BATCH]
  assert [e['args']['n_rows'] for e in spans['format_rows']] == [
      BATCH, 3, BATCH - 3, n - (BATCH - 3)]
  # Only the flushed pack came short; it is padded in its own buffer, so
  # the runner's `pack_cast` has nothing left to do on any pack.
  for e in spans['pack_cast']:
    assert e['args']['bytes_in'] == e['args']['bytes_out']
  assert engine.stats()['n_pack_buffers_allocated'] == 3
  # flush drains all three packs: drain and deliver under `flush`, and the
  # last pack's forward is launched directly, inside its drain.
  assert len(spans['finalize_drain']) == len(spans['deliver']) == 3
  for name in ('finalize_drain', 'deliver'):
    assert {_parent_name(events, e) for e in spans[name]} == {'flush'}
  assert spans['finalize_drain'][0]['args']['bytes'] > 0
  launch_parents = sorted(
      _parent_name(events, e) for e in spans['forward_launch'])
  assert launch_parents == ['dispatch', 'dispatch', 'finalize_drain']
  assert len(spans['device_compute']) == 3
  assert {e['cat'] for e in spans['device_compute']} == {'wait'}
  assert sum(e['args']['n_rows'] for e in spans['deliver']) == 2 * n
  assert len(delivered) == 2 * n


def test_children_lie_inside_parents_and_siblings_do_not_overlap(
    tmp_path, params):
  engine, _ = _engine(params, batch_size=4)

  def run():
    for step in range(3):
      n = 11
      engine.submit(list(_raw(params, n, seed=step)),
                    list(range(step * n, (step + 1) * n)))
    engine.flush()

  events = [e for e in _traced(tmp_path, run) if e['cat'] == 'stage']
  by_id = {e['args']['span']: e for e in events}
  assert len(by_id) == len(events)  # ids are unique
  children = collections.defaultdict(list)
  for e in events:
    parent = e['args'].get('parent')
    if parent is not None:
      p = by_id[parent]
      assert p['tid'] == e['tid']
      assert e['ts'] >= p['ts'] - EPS_US
      assert e['ts'] + e['dur'] <= p['ts'] + p['dur'] + EPS_US
      children[parent].append(e)
  assert children
  for siblings in children.values():
    siblings.sort(key=lambda e: e['ts'])
    for a, b in zip(siblings, siblings[1:]):
      assert a['ts'] + a['dur'] <= b['ts'] + EPS_US
  # Every stage but submit and flush has a parent.
  assert {e['name'] for e in events if 'parent' not in e['args']} == {
      'submit', 'flush'}


def test_event_count_does_not_depend_on_the_number_of_windows(
    tmp_path, params):
  """No stage site sits inside a per-window loop: one submit that cuts
  one pack emits the same events at 100 windows as at 10,000."""
  counts = {}
  for n in (100, 10_000):
    engine, delivered = _engine(params, batch_size=n)
    (tmp_path / str(n)).mkdir()
    events = _traced(
        tmp_path / str(n),
        lambda: (engine.submit(list(_raw(params, n)), list(range(n))),
                 engine.flush()))
    counts[n] = collections.Counter(e['name'] for e in events)
    assert len(delivered) == n
  assert counts[100] == counts[10_000]
  assert counts[100]['submit'] == 1 and counts[100]['dispatch'] == 1
  assert sum(counts[100].values()) < 20


def test_submit_of_an_array_skips_the_stack_and_formatted_rows_take_the_fill(
    tmp_path, params):
  engine, delivered = _engine(params)
  raw = _raw(params, BATCH)
  events = _traced(
      tmp_path, lambda: engine.submit(raw, list(range(BATCH))))
  names = collections.Counter(e['name'] for e in events)
  assert names['stack_windows'] == 0 and names['format_rows'] == 1

  from deepconsensus_tpu.models import data as data_lib
  rows = data_lib.format_rows_batch(raw, params)
  (tmp_path / 'formatted').mkdir()
  events = _traced(
      tmp_path / 'formatted',
      lambda: (engine.submit_formatted(rows, list(range(BATCH, 2 * BATCH))),
               engine.flush()))
  spans = _by_name(events)
  # Formatted rows are written into the pack by the same fill.
  assert 'stack_windows' not in spans and len(spans['format_rows']) == 1
  assert spans['submit'][0]['args']['formatted'] == 1
  assert len(spans['dispatch']) == 1
  assert len(delivered) == 2 * BATCH


def test_leaves_and_h2d_cover_submit_and_flush_but_for_the_remainder(
    tmp_path, params):
  """The four stage sites survive the in-place fill, each under the
  parent the table in docs/observability.md names, and the leaves still
  account for `submit` + `flush` up to the self time of `submit`,
  `flush` and `dispatch` (host_unattributed_ms_per_pack)."""
  engine, delivered = _engine(params, batch_size=4)
  n = 11

  def run():
    for step in range(3):
      engine.submit(list(_raw(params, n, seed=step)),
                    list(range(step * n, (step + 1) * n)))
    engine.flush()

  events = [e for e in _traced(tmp_path, run) if e['cat'] == 'stage']
  spans = _by_name(events)
  assert len(delivered) == 3 * n
  for name, parents in (
      ('stack_windows', {'submit'}), ('format_rows', {'submit'}),
      ('pack_cut', {'submit', 'flush'}), ('dispatch', {'submit', 'flush'}),
      ('pack_cast', {'dispatch'}), ('h2d_transfer', {'dispatch'})):
    assert spans[name], name
    assert {_parent_name(events, e) for e in spans[name]} == parents, name
  assert len(spans['pack_cut']) == len(spans['pack_cast']) == engine.n_packs
  assert {e['args']['bytes_concatenated'] for e in spans['pack_cut']} == {0}
  owners = ('submit', 'flush', 'dispatch')
  covered = collections.defaultdict(float)
  for e in events:
    if 'parent' in e['args']:
      covered[e['args']['parent']] += e['dur']
  remainder = sum(e['dur'] - covered[e['args']['span']]
                  for name in owners for e in spans[name])
  # A leaf is a stage with no child of its own but for a drain's direct
  # launch, which lies inside it.
  leaves = sum(
      e['dur'] for e in events
      if e['name'] not in owners
      and _parent_name(events, e) != 'finalize_drain')
  top = sum(e['dur'] for name in ('submit', 'flush') for e in spans[name])
  assert remainder >= 0
  assert leaves + remainder == pytest.approx(top, abs=10 * EPS_US)


def test_ragged_packer_speaks_the_same_vocabulary(tmp_path, params):
  buckets = (params.max_length, 2 * params.max_length)
  engine, delivered = _engine(params, batch_size=4, ragged=True,
                              buckets=buckets)
  from deepconsensus_tpu.models import data as data_lib
  narrow = data_lib.format_rows_batch(_raw(params, 6), params)
  wide = data_lib.format_rows_batch(
      _raw(params, 3, seed=1, width=buckets[1]), params,
      window_buckets=buckets)
  rows = list(narrow) + list(wide)

  def run():
    engine.submit_formatted(rows, list(range(len(rows))))
    engine.flush()

  events = _traced(tmp_path, run)
  spans = _by_name(events)
  n_packs = engine.n_packs
  assert n_packs >= 2
  assert len(spans['dispatch']) == len(spans['pack_cast']) == n_packs
  assert len(spans['h2d_transfer']) == len(spans['deliver']) == n_packs
  # Two widths: the grouping and two stacks; never formatted.
  assert len(spans['stack_windows']) == 3 and 'format_rows' not in spans
  # A cut that finds nothing cuttable is a pack_cut with n_rows 0.
  cut_rows = [e['args']['n_rows'] for e in spans['pack_cut']]
  assert sum(1 for r in cut_rows if r) == n_packs
  assert sum(cut_rows) == len(rows)
  assert {_parent_name(events, e) for e in spans['pack_cast']} == {'dispatch'}
  assert {_parent_name(events, e) for e in spans['pack_cut']} <= {
      'submit', 'flush'}
  assert len(delivered) == len(rows)


# ----------------------------------------------------------------------
# Off is off


def test_tracing_off_builds_no_event_and_changes_no_byte(
    tmp_path, params, monkeypatch):
  windows = list(_raw(params, 2 * BATCH + 3, seed=3))
  tickets = list(range(len(windows)))

  def run(engine):
    engine.submit(windows, tickets)
    engine.flush()

  traced_engine, traced = _engine(params)
  _traced(tmp_path, lambda: run(traced_engine))

  built = []
  monkeypatch.setattr(trace_lib.TraceWriter, 'complete_event',
                      lambda self, *a, **k: built.append(a))
  monkeypatch.setattr(trace_lib.TraceWriter, '__init__',
                      lambda self, *a, **k: built.append('opened'))
  monkeypatch.delenv(trace_lib.ENV_TRACE, raising=False)
  engine, plain = _engine(params)
  run(engine)
  assert not built and not trace_lib.enabled()
  assert not getattr(trace_lib._local, 'stack', None)
  assert sorted(plain) == sorted(traced) == tickets
  for t in tickets:
    assert plain[t][0].tobytes() == traced[t][0].tobytes()
    assert plain[t][1].tobytes() == traced[t][1].tobytes()
  histograms = engine.runner.obs.snapshot()['histograms']
  for name, count in (
      ('submit', 1), ('flush', 1), ('stack_windows', 1), ('format_rows', 3),
      ('pack_cut', 3), ('dispatch', 3), ('pack_cast', 3),
      ('forward_launch', 3), ('h2d_transfer', 3), ('finalize_drain', 3),
      ('deliver', 3), ('pack_wait', 3), ('device_compute', 3)):
    assert histograms[f'stage_{name}_s']['count'] == count, name


def test_stage_histograms_reconcile_with_span_totals(tmp_path, params):
  engine, _ = _engine(params)
  windows = list(_raw(params, 3 * BATCH))
  events = _traced(
      tmp_path,
      lambda: (engine.submit(windows, list(range(len(windows)))),
               engine.flush()))
  histograms = engine.runner.obs.snapshot()['histograms']
  totals = collections.defaultdict(float)
  for e in events:
    totals[e['name']] += e['dur'] / 1e6
  assert set(totals) >= {'submit', 'pack_cut', 'dispatch', 'deliver'}
  for name, total in totals.items():
    assert histograms[f'stage_{name}_s']['sum'] == pytest.approx(
        total, rel=1e-6, abs=2e-6), name


def test_dctpu_trace_prints_self_time_and_no_gap_accounting(
    tmp_path, params, capsys):
  from deepconsensus_tpu import cli

  engine, _ = _engine(params)
  windows = list(_raw(params, 2 * BATCH + 3))
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.clear_early()  # the runner's start-up is not under a submit
  trace_lib.configure(path, tier='run')
  try:
    engine.submit(windows, list(range(len(windows))))
    engine.flush()
  finally:
    trace_lib.configure(None)
  assert cli.main(['trace', path]) == 0
  text = capsys.readouterr().out
  assert 'self time per stage' in text and 'waits' in text
  assert 'gaps' not in text
  assert cli.main(['trace', path, '--json']) == 0
  payload = json.loads(capsys.readouterr().out)
  assert not [key for key in payload if 'gaps' in key]
  self_time = payload['self_time']
  assert self_time['submit']['under'] == ['']
  assert self_time['format_rows']['under'] == ['submit']
  assert set(self_time['forward_launch']['under']) == {
      'dispatch', 'finalize_drain'}
  # One thread: self times add up to the top-level stages' totals.
  top = self_time['submit']['total_s'] + self_time['flush']['total_s']
  assert sum(r['self_s'] for r in self_time.values()) == pytest.approx(
      top, rel=1e-3)
  assert set(payload['waits']) == {'pack_wait', 'device_compute'}
  assert not {'pack_wait', 'device_compute'} & {
      r['stage'] for r in payload['critical_path']}


def _retention_params():
  """The second block kind at a tiny size."""
  p = config_lib.get_config('transformer_learn_values_retention+custom')
  with p.unlocked():
    p.max_passes = 5
    p.transformer_input_size = 64
    p.num_heads, p.num_kv_heads, p.head_dim = 4, 2, 8
    p.filter_size = 96
    p.num_hidden_layers = 2
    p.dtype = p.inference_dtype = 'float32'
  config_lib.finalize_params(p, is_training=False)
  return p


def _weighted_engine(p, weights):
  """_engine() with a parameter tree resident: the stub forward reads
  none of it, the runner counts all of it."""
  options = runner_lib.InferenceOptions(batch_size=BATCH)
  options.max_passes = p.max_passes
  options.max_length = p.max_length
  options.use_ccs_bq = p.use_ccs_bq
  runner = runner_lib.ModelRunner(p, {'params': weights}, options)
  ccs_row = 4 * p.max_passes

  def forward(_variables, main_u8, _sn):
    ids = main_u8[:, ccs_row, :, 0]
    return ids, jnp.full(ids.shape, STUB_QUAL, jnp.uint8)

  runner._forward = forward
  engine = engine_lib.ConsensusEngine(
      runner, options, deliver=lambda t, ids, quals: None)
  return engine, runner, options


def _test_params():
  p = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(p, is_training=False)
  return p


KINDS = {
    config_lib.BLOCK_BANDED_SOFTMAX: _test_params,
    config_lib.BLOCK_POWER_RETENTION: _retention_params,
}


@pytest.mark.parametrize('kind', sorted(KINDS))
def test_forward_launch_says_what_the_forward_holds_and_computes(
    tmp_path, kind, capsys):
  from deepconsensus_tpu import cli

  p = KINDS[kind]()
  weights = {'a': jnp.ones((7, 3), jnp.bfloat16),
             'b': {'c': jnp.ones((5,), jnp.float32)}}
  engine, runner, _ = _weighted_engine(p, weights)
  windows = list(_raw(p, 2 * BATCH + 3))
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.configure(path, tier='run')
  try:
    engine.submit(windows, list(range(len(windows))))
    engine.flush()
  finally:
    trace_lib.configure(None)
  events = [e for e in summarize_lib.load_trace(path) if e.get('ph') == 'X']
  launches = _by_name(events)['forward_launch']
  assert len(launches) == 3
  for e in launches:
    assert e['args']['block_kind'] == kind
    # The CPU takes no kernel on its own (model_lib.kernel_paths).
    assert e['args']['attention_path'] == 'xla'
    # The compiled pack's rows x width, the tail pack's padding included.
    assert e['args']['n_positions'] == BATCH * p.max_length
    assert e['args']['weight_bytes'] == 7 * 3 * 2 + 5 * 4
    # One letter a layer; only sparse experts state a held share.
    assert e['args']['layer_pattern'] == (
        {config_lib.BLOCK_BANDED_SOFTMAX: 'B',
         config_lib.BLOCK_POWER_RETENTION: 'R'}[kind] * p.num_hidden_layers)
    assert e['args']['ffn_pattern'] == 'D' * p.num_hidden_layers
    assert e['args']['block_form'] == 'sequential'
    assert 'attention_window' not in e['args']
    assert 'experts_held' not in e['args']
    assert 'shared_experts' not in e['args']
    assert 'rope' not in e['args']
    assert 'router_scoring' not in e['args']
    # Nor a delta rule: these kinds have no Gated DeltaNet mixer.
    assert 'delta_rule_path' not in e['args']
    # Nor a latent attention layer.
    assert 'latent_attention_path' not in e['args']
    # Nor a grouped-head softmax layer.
    assert 'grouped_attention_path' not in e['args']
    # Nor grouped products: no sparse experts.
    assert 'grouped_product_path' not in e['args']
    assert 'combine_path' not in e['args']
    assert 'moe_turns' not in e['args']
  stats = engine.stats()
  assert stats['block_kind'] == kind
  assert stats['model_weight_bytes'] == 62
  assert stats['n_forward_positions'] == 3 * BATCH * p.max_length
  snapshot = runner.obs.snapshot()
  assert snapshot['gauges']['model_weight_bytes'] == 62
  assert snapshot['counters']['n_forward_positions'] == (
      3 * BATCH * p.max_length)
  # And `dctpu trace` shows it.
  assert cli.main(['trace', path, '--json']) == 0
  forward = json.loads(capsys.readouterr().out)['forward']
  assert forward == {'n_launches': 3, 'block_kinds': [kind],
                     'attention_paths': ['xla'], 'delta_rule_paths': [],
                     'latent_attention_paths': [],
                     'grouped_attention_paths': [],
                     'grouped_product_paths': [],
                     'combine_paths': [],
                     'moe_turns': [],
                     'block_forms': ['sequential'],
                     'layer_patterns': [config_lib.layer_pattern(p)],
                     'attention_windows': [],
                     'ffn_patterns': [config_lib.ffn_pattern(p)],
                     'router_scorings': [], 'shared_experts': [], 'ropes': [],
                     'experts_held': [],
                     'n_positions': 3 * BATCH * p.max_length,
                     'weight_bytes': 62}
  assert cli.main(['trace', path]) == 0
  assert (f'forward: 3 launches of {kind} (attention: xla)'
          in capsys.readouterr().out)


@pytest.mark.parametrize('kind', sorted(KINDS))
def test_metricz_shows_weight_bytes_and_forward_positions(kind):
  from deepconsensus_tpu.serve.service import ConsensusService, ServeOptions

  p = KINDS[kind]()
  _, runner, options = _weighted_engine(
      p, {'w': jnp.ones((11, 2), jnp.bfloat16)})
  service = ConsensusService(runner, options, ServeOptions())
  before = service.stats()['counters']
  assert before['model_weight_bytes'] == 44
  assert before['n_forward_positions'] == 0
  service.warmup()  # one pack through the runner
  stats = service.stats()
  assert stats['counters']['model_weight_bytes'] == 44
  assert stats['counters']['n_forward_positions'] == BATCH * p.max_length
  assert stats['block_kind'] == kind
  prom = service.prom_text()
  assert 'dctpu_model_weight_bytes{tier="serve"} 44' in prom
  assert ('dctpu_n_forward_positions{tier="serve"} '
          f'{BATCH * p.max_length}') in prom


def test_profiler_capture_shows_the_stages_on_the_host_plane(
    tmp_path, params):
  """The profiler bridge: with tracing on (and jax imported), a stage is
  also a jax.profiler.TraceAnnotation, so a capture of the process has
  the program's spans beside the device's operations."""
  engine, _ = _engine(params)
  windows = list(_raw(params, BATCH))
  trace_lib.configure(str(tmp_path / 'spans.jsonl'), tier='run')
  profile_dir = str(tmp_path / 'profile')
  options = jax.profiler.ProfileOptions()
  options.python_tracer_level = 0
  jax.profiler.start_trace(profile_dir, profiler_options=options)
  try:
    engine.submit(windows, list(range(BATCH)))
    engine.flush()
  finally:
    jax.profiler.stop_trace()
    trace_lib.configure(None)
  found = list((tmp_path / 'profile').rglob('*.xplane.pb'))
  assert found
  data = jax.profiler.ProfileData.from_file(str(found[0]))
  host = [p for p in data.planes if p.name == '/host:CPU']
  assert host
  names = {ev.name for line in host[0].lines for ev in line.events}
  assert {'submit', 'format_rows', 'pack_cut', 'dispatch'} <= names
