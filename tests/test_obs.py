"""Observability plane suite (deepconsensus_tpu/obs/).

Covers the four obs subsystems in isolation plus their contracts:

  * metrics registry — typed counters/gauges, fixed-bucket histograms
    with nearest-rank percentiles (the deque-index under-report at
    small n is the regression test), unified snapshot, Prometheus text
    exposition;
  * trace spans — Chrome-trace JSONL framing (one `[` header however
    many writers share the file, atomic one-line appends), the
    tracing-off fast path, thread-local trace-id stamping, and the
    record_stage / stage contract that feeds the SAME measured interval
    to both the histogram and the span (the reconciliation
    guarantee), the per-thread stage stack behind
    `args.span` / `args.parent`, and the buffered writer (nothing
    before the threshold, everything at close, whole lines, an empty
    buffer after a fork);
  * start-up and compiles — the start-up record that waits for
    `configure()` (bounded, first in the file, empty after a fork) and
    JAX's trace, lower and compile events as spans under the stage that
    caused them, with their counters and histograms (obs/compiles.py);
  * summarize — per-stage totals, self time through `args.parent`,
    waits apart, critical-path ordering by self time, straggler
    extraction, span-derived overlap (launch-before-finalize ordering),
    trace-group connectivity, corrupt-file typing;
  * profiler — guarded on-demand capture status dicts;

plus the `dctpu trace` CLI and dead-letter trace-id stamping.
"""
import json
import os
import threading

import pytest

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu import obs as obs_lib
from deepconsensus_tpu.obs import metrics as metrics_lib
from deepconsensus_tpu.obs import profiler as profiler_lib
from deepconsensus_tpu.obs import summarize as summarize_lib
from deepconsensus_tpu.obs import trace as trace_lib


@pytest.fixture(autouse=True)
def _reset_trace():
  """Each test starts and ends with tracing off and no trace id, and
  starts with an empty start-up record (other tests compile too)."""
  trace_lib.configure(None)
  trace_lib.set_trace_id(None)
  trace_lib.clear_early()
  yield
  trace_lib.configure(None)
  trace_lib.set_trace_id(None)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:

  def test_counter_and_gauge(self):
    reg = metrics_lib.MetricsRegistry(tier='test')
    reg.inc('n_requests')
    reg.inc('n_requests', 4)
    reg.set_gauge('outstanding', 3.5)
    assert reg.counter_values()['n_requests'] == 5
    snap = reg.snapshot()
    assert snap['counters']['n_requests'] == 5
    assert snap['gauges']['outstanding'] == 3.5

  def test_histogram_observe_and_snapshot(self):
    reg = metrics_lib.MetricsRegistry()
    h = reg.histogram('latency_s', bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
      h.observe(v)
    snap = h.snapshot()
    assert snap['count'] == 5
    assert snap['sum'] == pytest.approx(56.05)
    assert snap['buckets'] == [[0.1, 1], [1.0, 2], [10.0, 1], ['inf', 1]]

  def test_nearest_rank_percentiles_small_n(self):
    # The old deque implementation indexed int(0.99 * n), which at
    # n=10 reads the 9th of 10 sorted samples — under-reporting p99.
    # Nearest-rank picks ceil(0.99 * 10) = the 10th sample's bucket.
    h = metrics_lib.Histogram('x', threading.Lock(),
                              bounds=(0.01, 0.1, 1.0, 10.0))
    for _ in range(9):
      h.observe(0.005)
    h.observe(5.0)  # the single slow outlier
    assert h.percentile(0.99) == 10.0
    assert h.percentile(0.50) == 0.01

  def test_percentiles_canonical_keys_only(self):
    h = metrics_lib.Histogram('x', threading.Lock(), bounds=(1.0,))
    assert h.percentiles()['p50'] is None
    h.observe(0.5)
    p = h.percentiles()
    assert p['p50'] == 1.0
    assert p['count'] == 1
    # The one-release p50_s/p99_s/n aliases are removed.
    assert set(p) == {'p50', 'p99', 'count'}

  def test_empty_histogram_rejected(self):
    with pytest.raises(ValueError):
      metrics_lib.Histogram('x', threading.Lock(), bounds=())

  def test_prom_text(self):
    reg = metrics_lib.MetricsRegistry(tier='serve')
    reg.inc('n_requests', 7)
    reg.set_gauge('outstanding', 2)
    reg.histogram('latency_s', bounds=(0.1, 1.0)).observe(0.5)
    text = reg.to_prom()
    assert 'dctpu_n_requests{tier="serve"} 7' in text
    assert 'dctpu_outstanding{tier="serve"} 2' in text
    # Cumulative le buckets plus +Inf, _sum and _count.
    assert 'dctpu_latency_s_bucket{tier="serve",le="0.1"} 0' in text
    assert 'dctpu_latency_s_bucket{tier="serve",le="1.0"} 1' in text
    assert 'dctpu_latency_s_bucket{tier="serve",le="+Inf"} 1' in text
    assert 'dctpu_latency_s_count{tier="serve"} 1' in text

  def test_prom_counters_text_skips_non_numeric(self):
    text = metrics_lib.prom_counters_text(
        {'n_ok': 3, 'inference_dtype': 'float32', 'flag': True},
        tier='serve')
    assert 'dctpu_n_ok{tier="serve"} 3' in text
    assert 'inference_dtype' not in text
    assert 'flag' not in text

  def test_concurrent_inc(self):
    reg = metrics_lib.MetricsRegistry()
    threads = [threading.Thread(
        target=lambda: [reg.inc('n') for _ in range(1000)])
        for _ in range(8)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    assert reg.counter_values()['n'] == 8000


# ---------------------------------------------------------------------------
# Trace spans
# ---------------------------------------------------------------------------


class TestTraceSpans:

  def test_off_by_default(self):
    assert not trace_lib.enabled()
    # No-ops, no file writes.
    trace_lib.complete_event('x', 'stage', 0.0, 1.0)
    with trace_lib.span('x'):
      pass

  def test_writes_loadable_chrome_trace(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    trace_lib.complete_event('featurize', 'stage', 10.0, 10.5,
                             {'n_zmws': 3})
    with trace_lib.span('stitch', n_zmws=3):
      pass
    trace_lib.configure(None)
    raw = open(path).read()
    assert raw.startswith('[\n')
    events = summarize_lib.load_trace(path)
    names = [e['name'] for e in events]
    assert 'process_name' in names          # tier metadata
    assert 'featurize' in names and 'stitch' in names
    feat = next(e for e in events if e['name'] == 'featurize')
    assert feat['ph'] == 'X'
    assert feat['ts'] == pytest.approx(10.0 * 1e6)
    assert feat['dur'] == pytest.approx(0.5 * 1e6)
    assert feat['args']['n_zmws'] == 3

  def test_single_header_with_multiple_writers(self, tmp_path):
    # N fleet processes share one file: only the O_CREAT|O_EXCL winner
    # writes `[`; everyone appends whole-line events.
    path = str(tmp_path / 'shared.jsonl')
    w1 = trace_lib.TraceWriter(path, tier='router')
    w2 = trace_lib.TraceWriter(path, tier='serve')
    w1.complete_event('route', 'request', 1.0, 0.1)
    w2.complete_event('serve_request', 'request', 1.05, 0.2)
    w1.close()
    w2.close()
    lines = open(path).read().splitlines()
    assert lines.count('[') == 1 and lines[0] == '['
    events = summarize_lib.load_trace(path)
    names = [e['name'] for e in events]
    assert 'route' in names and 'serve_request' in names
    # Both writers announced their tier (in a real fleet each is its
    # own pid; in-process they collide on pid, so count the events).
    labels = sorted(e['args']['name'] for e in events
                    if e['name'] == 'process_name')
    assert labels == ['dctpu-router', 'dctpu-serve']

  def test_thread_local_trace_id_stamping(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    trace_lib.set_trace_id('aabbccdd00112233')
    trace_lib.complete_event('stitch', 'stage', 0.0, 1.0)
    # Explicit arg wins over the thread-local binding.
    trace_lib.complete_event('stitch', 'stage', 0.0, 1.0,
                             {'trace_id': 'other'})
    seen = {}

    def other_thread():
      trace_lib.complete_event('featurize', 'stage', 0.0, 1.0)
      seen['done'] = True

    t = threading.Thread(target=other_thread)
    t.start()
    t.join()
    trace_lib.configure(None)
    events = [e for e in summarize_lib.load_trace(path)
              if e['ph'] == 'X']
    ids = [e['args'].get('trace_id') for e in events]
    assert ids == ['aabbccdd00112233', 'other', None]
    assert seen['done']

  def test_mint_trace_id(self):
    a, b = trace_lib.mint_trace_id(), trace_lib.mint_trace_id()
    assert len(a) == 16 and a != b
    int(a, 16)  # hex

  def test_configure_from_env(self, tmp_path, monkeypatch):
    path = str(tmp_path / 'env.jsonl')
    monkeypatch.setenv(trace_lib.ENV_TRACE, path)
    assert trace_lib.configure_from_env(tier='serve') is not None
    assert trace_lib.enabled()
    monkeypatch.delenv(trace_lib.ENV_TRACE)
    assert trace_lib.configure_from_env() is None
    assert not trace_lib.enabled()


class TestStage:

  def _events(self, path):
    return [e for e in summarize_lib.load_trace(path) if e['ph'] == 'X']

  def test_nesting_gives_span_and_parent(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    reg = metrics_lib.MetricsRegistry()
    with obs_lib.stage(reg, 'submit', n_windows=3) as outer:
      with obs_lib.stage(reg, 'format_rows') as st:
        st.set(n_rows=3, bytes=12)
      with obs_lib.stage(reg, 'dispatch'):
        with obs_lib.stage(reg, 'pack_cast'):
          pass
    with obs_lib.stage(reg, 'flush'):
      pass
    trace_lib.configure(None)
    events = {e['name']: e for e in self._events(path)}
    assert set(events) == {'submit', 'format_rows', 'dispatch',
                           'pack_cast', 'flush'}
    ids = {name: e['args']['span'] for name, e in events.items()}
    assert len(set(ids.values())) == 5
    assert 'parent' not in events['submit']['args']
    assert 'parent' not in events['flush']['args']
    assert events['format_rows']['args']['parent'] == ids['submit']
    assert events['dispatch']['args']['parent'] == ids['submit']
    assert events['pack_cast']['args']['parent'] == ids['dispatch']
    assert events['format_rows']['args']['bytes'] == 12
    assert events['submit']['args']['n_windows'] == 3
    assert all(e['cat'] == 'stage' for e in events.values())
    assert outer.name == 'submit'
    assert reg.histogram('stage_pack_cast_s').snapshot()['count'] == 1

  def test_threads_do_not_see_each_others_stack(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    inside = threading.Event()
    release = threading.Event()

    def other():
      with obs_lib.stage(None, 'featurize'):
        with obs_lib.stage(None, 'decode'):
          inside.set()
          assert release.wait(10)

    t = threading.Thread(target=other)
    with obs_lib.stage(None, 'submit'):
      t.start()
      assert inside.wait(10)
      # The other thread is two stages deep right now.
      with obs_lib.stage(None, 'dispatch'):
        pass
      release.set()
      t.join(10)
    assert not t.is_alive()
    trace_lib.configure(None)
    events = {e['name']: e for e in self._events(path)}
    assert events['dispatch']['args']['parent'] == (
        events['submit']['args']['span'])
    assert 'parent' not in events['featurize']['args']
    assert events['decode']['args']['parent'] == (
        events['featurize']['args']['span'])
    assert events['decode']['tid'] != events['dispatch']['tid']

  def test_stage_records_on_error_and_unwinds_the_stack(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    with pytest.raises(RuntimeError):
      with obs_lib.stage(None, 'submit'):
        with obs_lib.stage(None, 'dispatch'):
          raise RuntimeError('boom')
    with obs_lib.stage(None, 'flush'):
      pass
    trace_lib.configure(None)
    events = {e['name']: e for e in self._events(path)}
    assert set(events) == {'submit', 'dispatch', 'flush'}
    assert 'parent' not in events['flush']['args']

  def test_off_opens_no_file_and_builds_no_event(self, tmp_path,
                                                 monkeypatch):
    monkeypatch.delenv(trace_lib.ENV_TRACE, raising=False)
    assert trace_lib.configure_from_env() is None
    opened = []
    monkeypatch.setattr(trace_lib.os, 'open',
                        lambda *a, **k: opened.append(a))
    monkeypatch.setattr(trace_lib, 'complete_event',
                        lambda *a, **k: opened.append(a))
    reg = metrics_lib.MetricsRegistry()
    with obs_lib.stage(reg, 'submit', n_windows=1) as st:
      st.set(bytes=4)
      with obs_lib.stage(reg, 'dispatch'):
        pass
    assert not opened
    assert not getattr(trace_lib._local, 'stack', None)
    assert reg.histogram('stage_submit_s').snapshot()['count'] == 1
    assert reg.histogram('stage_dispatch_s').snapshot()['count'] == 1

  def test_record_stage_wait_category_has_no_ids(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    with obs_lib.stage(None, 'submit'):
      obs_lib.record_stage(None, trace_lib.STAGE_PACK_WAIT, 1.0, 2.0,
                           cat=trace_lib.CAT_WAIT, bucket=100)
    trace_lib.configure(None)
    wait = next(e for e in self._events(path) if e['name'] == 'pack_wait')
    assert wait['cat'] == 'wait'
    assert wait['args'] == {'bucket': 100}


class TestBufferedWriter:

  def _lines(self, path):
    return open(path).read().splitlines()

  def test_nothing_before_the_threshold_everything_at_close(
      self, tmp_path, monkeypatch):
    monkeypatch.setattr(trace_lib, 'FLUSH_EVENTS', 8)
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='serve')  # one metadata event
    for i in range(6):
      trace_lib.complete_event('stitch', 'stage', float(i), i + 0.5)
    assert self._lines(path) == ['[']
    trace_lib.complete_event('stitch', 'stage', 6.0, 6.5)  # the eighth
    assert len(self._lines(path)) == 1 + 8
    for i in range(3):
      trace_lib.complete_event('stitch', 'stage', float(i), i + 0.5)
    assert len(self._lines(path)) == 1 + 8
    trace_lib.configure(None)
    lines = self._lines(path)
    assert len(lines) == 1 + 11
    assert all(line.endswith('},') for line in lines[1:])
    assert len(summarize_lib.load_trace(path)) == 11

  def test_flush_writes_out_and_keeps_tracing(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path)
    trace_lib.complete_event('stitch', 'stage', 0.0, 1.0)
    assert self._lines(path) == ['[']
    trace_lib.flush()
    assert len(self._lines(path)) == 2
    trace_lib.flush()  # nothing twice
    assert len(self._lines(path)) == 2
    assert trace_lib.enabled()

  def test_one_write_per_flush_of_whole_lines(self, tmp_path, monkeypatch):
    path = str(tmp_path / 'trace.jsonl')
    writer = trace_lib.TraceWriter(path)
    for i in range(5):
      writer.complete_event('stitch', 'stage', float(i), 0.5, {'i': i})
    writes = []
    real_write = os.write
    monkeypatch.setattr(
        trace_lib.os, 'write',
        lambda fd, data: writes.append(data) or real_write(fd, data))
    writer.close()
    assert len(writes) == 1
    assert writes[0].endswith(b',\n') and writes[0].count(b'\n') == 5

  def test_two_writers_interleave_whole_lines(self, tmp_path, monkeypatch):
    monkeypatch.setattr(trace_lib, 'FLUSH_EVENTS', 16)
    path = str(tmp_path / 'shared.jsonl')
    writers = [trace_lib.TraceWriter(path, tier=t) for t in ('a', 'b')]
    n = 200

    def emit(w, tag):
      for i in range(n):
        w.complete_event(tag, 'stage', float(i), 0.25,
                         {'i': i, 'pad': 'x' * 200})

    threads = [threading.Thread(target=emit, args=(w, t))
               for w, t in zip(writers, ('route', 'serve_request'))]
    for t in threads:
      t.start()
    for t in threads:
      t.join(30)
    assert not any(t.is_alive() for t in threads)
    for w in writers:
      w.close()
    lines = self._lines(path)
    assert lines.count('[') == 1 and lines[0] == '['
    events = summarize_lib.load_trace(path)  # every line parses whole
    for tag in ('route', 'serve_request'):
      assert [e['args']['i'] for e in events if e['name'] == tag] == (
          list(range(n)))

  def test_numpy_counts_in_args_do_not_break_the_flush(self, tmp_path):
    import numpy as np

    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path)
    trace_lib.complete_event('pack_cut', 'stage', 0.0, 1.0,
                             {'n_rows': np.int64(7), 'what': object})
    trace_lib.configure(None)
    event = summarize_lib.load_trace(path)[0]
    assert event['args']['n_rows'] == 7
    assert isinstance(event['args']['what'], str)

  @pytest.mark.skipif(not hasattr(os, 'fork'), reason='needs fork')
  def test_forked_child_starts_with_an_empty_buffer(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path)
    trace_lib.complete_event('parent_before', 'stage', 0.0, 1.0)
    pid = os.fork()
    if pid == 0:  # the child: one event of its own, written by hand
      try:
        trace_lib.complete_event('child', 'stage', 1.0, 2.0)
        trace_lib.flush()
      finally:
        os._exit(0)
    _, status = os.waitpid(pid, 0)
    assert status == 0
    trace_lib.complete_event('parent_after', 'stage', 2.0, 3.0)
    trace_lib.configure(None)
    events = summarize_lib.load_trace(path)
    names = sorted(e['name'] for e in events)
    assert names == ['child', 'parent_after', 'parent_before']
    by_name = {e['name']: e for e in events}
    assert by_name['child']['pid'] == pid
    assert by_name['parent_before']['pid'] == os.getpid()


class TestRecordStage:

  def test_feeds_histogram_and_span_same_interval(self, tmp_path):
    # The reconciliation guarantee: span totals == histogram sums
    # because both read the same (t0, t1).
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    reg = metrics_lib.MetricsRegistry()
    intervals = [(1.0, 1.5), (2.0, 2.25), (3.0, 3.75)]
    for t0, t1 in intervals:
      obs_lib.record_stage(reg, trace_lib.STAGE_STITCH, t0, t1, pack=1)
    trace_lib.configure(None)
    hist_sum = reg.histogram(
        obs_lib.stage_histogram_name(trace_lib.STAGE_STITCH)
    ).snapshot()['sum']
    events = summarize_lib.load_trace(path)
    span_sum = sum(e['dur'] for e in events if e.get('ph') == 'X') / 1e6
    assert hist_sum == pytest.approx(1.5)
    assert span_sum == pytest.approx(hist_sum, rel=1e-6)

  def test_none_registry_still_emits_span(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    obs_lib.record_stage(None, trace_lib.STAGE_FEATURIZE, 0.0, 0.5)
    trace_lib.configure(None)
    events = summarize_lib.load_trace(path)
    assert any(e.get('name') == 'featurize' for e in events)

  def test_tracing_off_records_histogram_only(self):
    reg = metrics_lib.MetricsRegistry()
    obs_lib.record_stage(reg, trace_lib.STAGE_H2D, 0.0, 0.5)
    snap = reg.histogram(
        obs_lib.stage_histogram_name(trace_lib.STAGE_H2D)).snapshot()
    assert snap['count'] == 1


# ---------------------------------------------------------------------------
# Summarize
# ---------------------------------------------------------------------------


def _span(name, ts_s, dur_s, pid=1, cat='stage', **args):
  return {'name': name, 'cat': cat, 'ph': 'X', 'ts': ts_s * 1e6,
          'dur': dur_s * 1e6, 'pid': pid, 'tid': 1, 'args': args}


class TestSummarize:

  def _pipeline_events(self):
    ev = [{'name': 'process_name', 'ph': 'M', 'pid': 1, 'tid': 0,
           'args': {'name': 'dctpu-run'}}]
    # Two packs: pack 0 launched directly (inside finalize), pack 1
    # overlapped (launched before its finalize started).
    ev += [
        _span('featurize', 0.0, 1.0, n_zmws=10, trace_id='t1'),
        _span('pack_wait', 1.0, 0.2, bucket=100),
        _span('h2d_transfer', 1.2, 0.1, pack=0, bucket=100),
        # pack 0: compute starts AT its finalize start (direct).
        _span('finalize_drain', 1.3, 0.5, pack=0),
        _span('device_compute', 1.3, 0.5, pack=0, bucket=100, dp=1,
              n_rows=64),
        # pack 1: compute started 1.5, finalize started 1.9 (overlap).
        _span('h2d_transfer', 1.4, 0.1, pack=1, bucket=100),
        _span('device_compute', 1.5, 2.0, pack=1, bucket=100, dp=1,
              n_rows=64),
        _span('finalize_drain', 1.9, 1.6, pack=1),
        _span('stitch', 3.5, 0.5, n_zmws=10, trace_id='t1'),
    ]
    return ev

  def test_stage_totals_and_counts(self):
    s = summarize_lib.summarize(self._pipeline_events())
    assert s['stage_totals_s']['device_compute'] == pytest.approx(2.5)
    assert s['stage_counts']['device_compute'] == 2
    assert s['stage_totals_s']['featurize'] == pytest.approx(1.0)
    assert s['wall_s'] == pytest.approx(4.0)
    assert s['tiers'] == {1: 'dctpu-run'}

  def test_critical_path_orders_by_self_time_and_leaves_waits_out(self):
    s = summarize_lib.summarize(self._pipeline_events())
    # finalize_drain did 0.5 + 1.6 s of work on its thread; the waits
    # (device_compute 2.5 s, pack_wait) are longer and never enter.
    top = s['critical_path'][0]
    assert top['stage'] == 'finalize_drain'
    assert top['self_s'] == pytest.approx(2.1)
    assert top['fraction_of_wall'] == pytest.approx(2.1 / 4.0, abs=1e-3)
    names = [row['stage'] for row in s['critical_path']]
    assert 'device_compute' not in names and 'pack_wait' not in names
    assert s['waits']['device_compute'] == {'total_s': 2.5, 'count': 2}
    assert s['waits']['pack_wait']['count'] == 1

  def test_span_overlap_rule(self):
    overlap = summarize_lib.span_overlap(self._pipeline_events())
    # pack 0: compute ts == finalize ts -> direct; pack 1: compute ts
    # strictly before finalize ts -> overlapped.
    assert overlap['n_packs'] == 2
    assert overlap['n_overlapped'] == 1
    assert overlap['n_direct'] == 1
    assert overlap['span_overlap_fraction'] == 0.5

  def test_overlap_counts_drain_free_pack_as_overlapped(self):
    """Regression: a device_compute span with no finalize_drain span
    (a drain-free pack — device-resident runs batch their drain at
    end-of-input) used to be dropped from the sample, skewing the
    span-derived fraction LOW on exactly the best-overlapped runs. A
    direct launch only ever happens inside finalize, which would have
    emitted the span — so drain-free means overlapped."""
    events = [_span('device_compute', 0.0, 1.0, pack=9)]
    overlap = summarize_lib.span_overlap(events)
    assert overlap['n_packs'] == 1
    assert overlap['n_overlapped'] == 1
    assert overlap['n_direct'] == 0
    assert overlap['span_overlap_fraction'] == 1.0

  def test_overlap_mixed_drained_and_drain_free(self):
    events = self._pipeline_events() + [
        _span('device_compute', 4.0, 0.5, pack=2, bucket=100, dp=1,
              n_rows=64),
    ]
    overlap = summarize_lib.span_overlap(events)
    assert overlap['n_packs'] == 3
    assert overlap['n_overlapped'] == 2  # pack 1 (early launch) + pack 2
    assert overlap['n_direct'] == 1

  def _nested_events(self):
    """One submit of 10 s: stack 2 s, format 3 s, a dispatch of 2 s that
    holds a cast of 1.5 s, a drain of 1 s; 2 s are the submit's own. A
    pack_wait of 10 s and a device_compute of 30 s lie over it all."""
    return [
        _span('submit', 0.0, 10.0, span=1),
        _span('stack_windows', 0.0, 2.0, span=2, parent=1),
        _span('format_rows', 2.0, 3.0, span=3, parent=1),
        _span('dispatch', 6.0, 2.0, span=4, parent=1),
        _span('pack_cast', 6.0, 1.5, span=5, parent=4),
        _span('finalize_drain', 8.5, 1.0, span=6, parent=1),
        _span('pack_wait', 0.0, 10.0, cat='wait', bucket=100),
        _span('device_compute', 0.0, 30.0, cat='wait', pack=1),
        # Same ids in another process: never this submit's children.
        _span('submit', 0.0, 4.0, pid=2, span=1),
        _span('format_rows', 1.0, 1.0, pid=2, span=2, parent=1),
        # Stamped after the fact: no id, its self time is its duration.
        _span('featurize', 0.0, 5.0),
    ]

  def test_self_time_through_parent_ids(self):
    st = summarize_lib.self_times(self._nested_events())
    assert st['submit'] == {'total_s': 14.0, 'self_s': 2.0 + 3.0,
                            'count': 2, 'under': ['']}
    assert st['dispatch']['self_s'] == pytest.approx(0.5)
    assert st['dispatch']['under'] == ['submit']
    assert st['pack_cast'] == {'total_s': 1.5, 'self_s': 1.5, 'count': 1,
                               'under': ['dispatch']}
    assert st['format_rows']['total_s'] == pytest.approx(4.0)
    assert st['format_rows']['self_s'] == pytest.approx(4.0)
    assert st['featurize']['self_s'] == pytest.approx(5.0)
    assert 'pack_wait' not in st and 'device_compute' not in st
    # One thread per process: self times add up to the top-level totals.
    assert sum(r['self_s'] for r in st.values()) == pytest.approx(
        10.0 + 4.0 + 5.0)

  def test_summary_lists_waits_apart_and_orders_by_self_time(self):
    s = summarize_lib.summarize(self._nested_events())
    assert s['waits'] == {'device_compute': {'total_s': 30.0, 'count': 1},
                          'pack_wait': {'total_s': 10.0, 'count': 1}}
    order = [row['stage'] for row in s['critical_path']]
    assert order[:3] == ['featurize', 'submit', 'format_rows']
    assert 'pack_wait' not in order and 'device_compute' not in order
    # Totals still cover the waits (they reconcile with the
    # histograms); stragglers and overlap still read device_compute.
    assert s['stage_totals_s']['device_compute'] == pytest.approx(30.0)
    assert s['stragglers'][0]['pack'] == 1
    text = summarize_lib.format_summary(s)
    assert 'self time per stage' in text
    assert 'waits (intervals between two events' in text
    assert 'gaps' not in text and 'coverage' not in text
    # No gap accounting from host spans, no coverage: the keys are these.
    assert set(s) == {
        'n_events', 'n_spans', 'wall_s', 'tiers', 'stage_totals_s',
        'stage_counts', 'self_time', 'waits', 'critical_path', 'stragglers',
        'forward', 'startup', 'overlap', 'n_traces'}
    # No forward_launch span in this trace: the block says so and the
    # text leaves its line out.
    assert s['forward'] == {'n_launches': 0, 'block_kinds': [],
                            'attention_paths': [], 'delta_rule_paths': [],
                            'latent_attention_paths': [],
                            'grouped_attention_paths': [],
                            'grouped_product_paths': [],
                            'combine_paths': [], 'moe_turns': [],
                            'block_forms': [],
                            'layer_patterns': [], 'attention_windows': [],
                            'ffn_patterns': [], 'router_scorings': [],
                            'shared_experts': [], 'ropes': [],
                            'experts_held': [],
                            'n_positions': 0, 'weight_bytes': 0}
    assert 'forward:' not in text

  def test_layers_line_says_the_experts_turns_a_pack(self):
    """`moe_turns` of the launches of a sparse-expert stack: listed once
    each, and on the `layers:` line beside the grouped products and the
    combine; a launch without experts says nothing of turns."""
    launch = dict(block_kind='window_moe', attention_path='xla',
                  layer_pattern='WWWF', router_scoring='softmax',
                  grouped_product_path='group_kernel',
                  combine_path='token_tile_kernel', experts_held=[0, 64],
                  experts_published=64, n_positions=51_200)
    s = summarize_lib.summarize([
        _span('forward_launch', 0.0, 0.1, pack=0, moe_turns=4, **launch),
        _span('forward_launch', 1.0, 0.1, pack=1, moe_turns=4, **launch),
        _span('forward_launch', 2.0, 0.1, pack=2, moe_turns=2, **launch),
        _span('forward_launch', 3.0, 0.1, pack=3, block_kind='banded',
              attention_path='xla', layer_pattern='BB', n_positions=100)])
    assert s['forward']['moe_turns'] == [2, 4]
    text = summarize_lib.format_summary(s)
    assert ('experts 0-63 of 64 held (router: softmax; grouped products: '
            'group_kernel; combine: token_tile_kernel; turns a pack: 2, 4)'
            in text)
    s = summarize_lib.summarize([
        _span('forward_launch', 3.0, 0.1, pack=3, block_kind='banded',
              attention_path='xla', layer_pattern='BB', n_positions=100)])
    assert s['forward']['moe_turns'] == []
    assert 'turns' not in summarize_lib.format_summary(s)

  def test_stragglers_slowest_decile(self):
    events = [
        _span('device_compute', float(i), 0.1 + (0.9 if i == 7 else 0),
              pack=i, bucket=200, dp=2, n_rows=32)
        for i in range(10)
    ]
    s = summarize_lib.summarize(events)
    assert len(s['stragglers']) == 1
    row = s['stragglers'][0]
    assert row['pack'] == 7 and row['bucket'] == 200 and row['dp'] == 2

  def test_trace_groups_connectivity(self):
    events = [
        _span('route', 0.0, 1.0, pid=1, cat='request', trace_id='abc'),
        _span('featurize', 0.1, 0.5, pid=2, trace_id='abc'),
        _span('serve_request', 0.6, 0.4, pid=3, cat='request',
              trace_id='abc'),
        _span('serve_request', 0.0, 0.1, pid=3, cat='request',
              trace_id='other'),
    ]
    groups = summarize_lib.trace_groups(events)
    assert groups['abc']['pids'] == [1, 2, 3]
    assert groups['abc']['n_spans'] == 3
    assert groups['other']['pids'] == [3]

  def test_empty_trace_is_corrupt(self):
    with pytest.raises(faults_lib.CorruptInputError):
      summarize_lib.summarize([])

  def test_corrupt_file_typed(self, tmp_path):
    p = tmp_path / 'bad.jsonl'
    p.write_text('[\n{"name": "x", not json}\n')
    with pytest.raises(faults_lib.CorruptInputError):
      summarize_lib.load_trace(str(p))
    with pytest.raises(faults_lib.CorruptInputError):
      summarize_lib.load_trace(str(tmp_path / 'missing.jsonl'))

  def test_format_summary_renders(self):
    s = summarize_lib.summarize(self._pipeline_events())
    text = summarize_lib.format_summary(s)
    assert 'device_compute' in text
    assert 'transfer overlap (span-derived)' in text
    assert 'straggler' in text


# ---------------------------------------------------------------------------
# Profiler
# ---------------------------------------------------------------------------


class TestProfiler:

  def test_capture_returns_status_dict(self, tmp_path):
    result = profiler_lib.capture_profile(str(tmp_path / 'prof'), 0.1)
    # On a jax-enabled box the capture succeeds; either way the call
    # must return a status dict, never raise.
    assert isinstance(result, dict) and 'ok' in result
    if result['ok']:
      assert result['out_dir'] == str(tmp_path / 'prof')

  def test_concurrent_capture_refused(self, tmp_path):
    assert profiler_lib._capture_lock.acquire(blocking=False)
    try:
      result = profiler_lib.capture_profile(str(tmp_path / 'p'), 0.1)
    finally:
      profiler_lib._capture_lock.release()
    assert result['ok'] is False
    assert 'already running' in result['error']

  def test_install_sigusr2_off_main_thread(self, tmp_path):
    out = {}

    def worker():
      out['installed'] = profiler_lib.install_sigusr2(str(tmp_path))

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert out['installed'] is False


# ---------------------------------------------------------------------------
# Dead-letter trace stamping + CLI
# ---------------------------------------------------------------------------


class TestDeadLetterTraceId:

  def test_record_stamps_thread_local_trace_id(self, tmp_path):
    path = str(tmp_path / 'failed.jsonl')
    writer = faults_lib.DeadLetterWriter(path)
    trace_lib.set_trace_id('feedfacefeedface')
    writer.record('zmw/1', 'featurize', 'ValueError', 'boom', 'dropped')
    trace_lib.set_trace_id(None)
    writer.record('zmw/2', 'featurize', 'ValueError', 'boom', 'dropped')
    writer.close()
    entries = [json.loads(l) for l in open(path)]
    assert entries[0]['trace_id'] == 'feedfacefeedface'
    assert 'trace_id' not in entries[1]


class TestTraceCli:

  def _write_trace(self, tmp_path):
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    obs_lib.record_stage(None, trace_lib.STAGE_FEATURIZE, 0.0, 1.0)
    obs_lib.record_stage(None, trace_lib.STAGE_DEVICE_COMPUTE,
                         1.0, 2.0, pack=0)
    obs_lib.record_stage(None, trace_lib.STAGE_FINALIZE, 1.0, 2.1,
                         pack=0)
    trace_lib.configure(None)
    return path

  def test_cli_text_and_json(self, tmp_path, capsys):
    from deepconsensus_tpu import cli

    path = self._write_trace(tmp_path)
    assert cli.main(['trace', path]) == 0
    out = capsys.readouterr().out
    assert 'featurize' in out and 'device_compute' in out
    assert cli.main(['trace', path, '--json']) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload['stage_counts']['featurize'] == 1
    assert payload['overlap']['n_packs'] == 1

  def test_cli_corrupt_exits_2(self, tmp_path, capsys):
    from deepconsensus_tpu import cli

    bad = tmp_path / 'bad.jsonl'
    bad.write_text('{nope\n')
    assert cli.main(['trace', str(bad)]) == 2
    assert 'dctpu:' in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Start-up record and compile events
# ---------------------------------------------------------------------------


class TestStartupRecord:

  def _events(self, path):
    return [e for e in summarize_lib.load_trace(path) if e['ph'] == 'X']

  def test_startup_spans_wait_for_configure_and_come_first(self, tmp_path):
    reg = metrics_lib.MetricsRegistry()
    with obs_lib.stage(reg, trace_lib.STAGE_RUNNER_INIT) as st:
      with obs_lib.stage(reg, trace_lib.STAGE_WEIGHTS_PLACE):
        pass
      st.set(weight_bytes=12)
    obs_lib.record_stage(reg, trace_lib.STAGE_CHECKPOINT_LOAD, 1.0, 2.5,
                         bytes=7)
    # In-window names raised before configure() are not kept.
    with obs_lib.stage(reg, 'submit'):
      with obs_lib.stage(reg, 'dispatch'):
        pass
    obs_lib.record_stage(reg, trace_lib.STAGE_FEATURIZE, 3.0, 4.0)
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    with obs_lib.stage(reg, 'flush'):
      pass
    trace_lib.configure(None)
    events = self._events(path)
    assert [e['name'] for e in events] == [
        'weights_place', 'runner_init', 'checkpoint_load', 'flush']
    place, init, load, flush = events
    # Stamped with tracing off: the stage above by name, no ids.
    assert place['args'] == {'under': 'runner_init'}
    assert init['args'] == {'weight_bytes': 12}
    assert load['args'] == {'bytes': 7}
    assert load['ts'] == pytest.approx(1.0 * 1e6)
    assert load['dur'] == pytest.approx(1.5 * 1e6)
    assert init['ts'] <= place['ts']
    assert place['ts'] + place['dur'] <= init['ts'] + init['dur']
    assert 'span' in flush['args']
    assert reg.histogram('stage_runner_init_s').snapshot()['count'] == 1
    # Written once: a second file starts empty.
    second = str(tmp_path / 'second.jsonl')
    trace_lib.configure(second)
    trace_lib.configure(None)
    assert self._events(second) == []

  def test_the_list_stops_at_its_bound_and_counts_the_rest(
      self, tmp_path, monkeypatch):
    monkeypatch.setattr(trace_lib, 'EARLY_EVENTS', 4)
    for i in range(7):
      obs_lib.record_stage(None, trace_lib.STAGE_JIT_TRACE, float(i),
                           i + 0.5, fun=f'f{i}')
    assert trace_lib.early_events_dropped == 3
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path)
    trace_lib.configure(None)
    events = summarize_lib.load_trace(path)
    # The oldest stay.
    assert [e['args']['fun'] for e in events if e['ph'] == 'X'] == [
        'f0', 'f1', 'f2', 'f3']
    dropped = [e for e in events
               if e['name'] == trace_lib.EARLY_DROPPED_EVENT]
    assert [e['args']['count'] for e in dropped] == [3]
    assert summarize_lib.startup(events)['early_events_dropped'] == 3
    assert trace_lib.early_events_dropped == 0

  def test_clear_early_empties_the_record(self, tmp_path):
    obs_lib.record_stage(None, trace_lib.STAGE_IMPORT_RUNNER, 0.0, 1.0)
    trace_lib.clear_early()
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path)
    trace_lib.configure(None)
    assert self._events(path) == []

  @pytest.mark.skipif(not hasattr(os, 'fork'), reason='needs fork')
  def test_forked_child_starts_with_no_startup_record(self, tmp_path):
    obs_lib.record_stage(None, trace_lib.STAGE_IMPORT_RUNNER, 0.0, 1.0)
    path = str(tmp_path / 'child.jsonl')
    pid = os.fork()
    if pid == 0:
      try:
        trace_lib.configure(path)
        trace_lib.complete_event('child', 'stage', 1.0, 2.0)
        trace_lib.configure(None)
      finally:
        os._exit(0)
    _, status = os.waitpid(pid, 0)
    assert status == 0
    assert [e['name'] for e in self._events(path)] == ['child']
    # The parent still holds its own.
    mine = str(tmp_path / 'parent.jsonl')
    trace_lib.configure(mine)
    trace_lib.configure(None)
    assert [e['name'] for e in self._events(mine)] == ['import_runner']

  def test_stack_of_open_stages_is_kept_with_tracing_off(self):
    assert not trace_lib.enabled()
    with obs_lib.stage(None, 'dispatch', pack=3):
      with obs_lib.stage(None, 'forward_launch', pack=3) as launch:
        assert trace_lib._local.stack[-1] is launch
        trace_lib.caused_event(trace_lib.STAGE_XLA_COMPILE, 1.0, 2.0,
                               {'fun': 'jit(f)'})
    assert trace_lib._local.stack == []
    (event,) = trace_lib._early
    assert event['args'] == {'fun': 'jit(f)', 'under': 'forward_launch',
                             'pack': 3}


def test_obs_imports_no_jax():
  """The router has no jax: the listeners of obs/compiles.py are
  registered where jax already is (inference/runner.py)."""
  import subprocess
  import sys

  done = subprocess.run(
      [sys.executable, '-c',
       'import sys; import deepconsensus_tpu.obs; '
       'import deepconsensus_tpu.obs.trace; '
       'sys.exit(int("jax" in sys.modules))'],
      cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
      timeout=60)
  assert done.returncode == 0


class TestCompileEvents:

  COMPILE = ('jit_trace', 'jit_lower', 'xla_compile')

  @pytest.fixture
  def compiles_lib(self):
    """The module; the registry a test binds is let go of afterwards."""
    from deepconsensus_tpu.obs import compiles

    yield compiles
    compiles.install(metrics_lib.MetricsRegistry())

  @staticmethod
  def _jitted():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
      return jnp.sin(x) * 2.0

    def forward(x):
      for _ in range(3):
        x = inner(x) + jnp.tanh(x)
      return x

    return jax.jit(forward), jnp.ones((8, 16))

  def test_a_jit_under_a_stage_yields_its_three_spans(
      self, tmp_path, monkeypatch, compiles_lib):
    monkeypatch.setattr(compiles_lib, 'MIN_SPAN_S', 0.0)
    forward, x = self._jitted()
    x.block_until_ready()  # the array's own programs compile out here,
    trace_lib.clear_early()  # and are not this test's
    reg = metrics_lib.MetricsRegistry()
    compiles_lib.install(reg)
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    with obs_lib.stage(reg, trace_lib.STAGE_LAUNCH, pack=5):
      forward(x)
    with obs_lib.stage(reg, trace_lib.STAGE_LAUNCH, pack=6):
      forward(x)  # the same shape: nothing fires
    trace_lib.configure(None)
    events = [e for e in summarize_lib.load_trace(path) if e['ph'] == 'X']
    first, second = [e for e in events if e['name'] == 'forward_launch']
    caused = [e for e in events if e['name'] in self.COMPILE]
    assert {e['name'] for e in caused} == set(self.COMPILE)
    for e in caused:
      assert e['cat'] == 'stage'
      assert e['args']['under'] == 'forward_launch'
      assert e['args']['parent'] == first['args']['span']
      assert e['args']['pack'] == 5
      assert e['args']['span'] not in (first['args']['span'],
                                       second['args']['span'])
      assert first['ts'] <= e['ts']
      assert e['ts'] + e['dur'] <= first['ts'] + first['dur'] + 1.0
    by_fun = {(e['name'], e['args']['fun']) for e in caused}
    assert ('jit_trace', 'forward') in by_fun
    assert ('jit_trace', 'inner') in by_fun
    assert ('jit_lower', 'jit(forward)') in by_fun
    (compiled,) = [e for e in caused if e['name'] == 'xla_compile']
    assert compiled['args']['fun'] == 'jit(forward)'
    assert compiled['args']['cache_hit'] in (True, False)
    # Counters and histograms hold the same intervals.
    after = reg.counter_values()
    n = {name: sum(1 for e in caused if e['name'] == name)
         for name in self.COMPILE}
    assert after['xla_compiles_total'] == n['xla_compile'] == 1
    assert after['jit_traces_total'] == n['jit_trace'] >= 2
    snap = reg.snapshot()
    for name in self.COMPILE:
      hist = snap['histograms'][f'stage_{name}_s']
      assert hist['count'] == n[name]
      assert hist['sum'] == pytest.approx(
          sum(e['dur'] for e in caused if e['name'] == name) / 1e6,
          abs=1e-5)
    # The seconds gauges count a nested trace once: the outermost's own.
    outer = max((e for e in caused if e['name'] == 'jit_trace'),
                key=lambda e: e['dur'])
    assert outer['args']['fun'] == 'forward'
    assert snap['gauges']['jit_trace_seconds'] == pytest.approx(
        outer['dur'] / 1e6, abs=1e-5)
    assert snap['gauges']['jit_trace_seconds'] < (
        snap['histograms']['stage_jit_trace_s']['sum'])
    split = compiles_lib.startup_split(reg)
    assert split['n_xla_compiles'] == 1
    assert split['xla_compile_s'] == pytest.approx(
        compiled['dur'] / 1e6, abs=1e-3)
    assert compiles_lib.format_startup(split).startswith('start-up: import')
    # dctpu trace: seconds by the stage above, nested traces once.
    startup = summarize_lib.summarize(
        summarize_lib.load_trace(path))['startup']
    kinds = startup['compile_kinds']
    assert kinds['jit_trace']['count'] == n['jit_trace']
    assert kinds['jit_trace']['total_s'] == pytest.approx(
        outer['dur'] / 1e6, abs=1e-5)
    assert list(kinds['xla_compile']['under']) == ['forward_launch']
    assert startup['top_traced'][0]['fun'] == 'forward'
    assert startup['compiles_after_first_submit'] == []

  def test_short_events_are_counted_and_not_written(
      self, tmp_path, monkeypatch, compiles_lib):
    monkeypatch.setattr(compiles_lib, 'MIN_SPAN_S', 1e9)
    forward, x = self._jitted()
    reg = metrics_lib.MetricsRegistry()
    compiles_lib.install(reg)
    path = str(tmp_path / 'trace.jsonl')
    trace_lib.configure(path, tier='run')
    with obs_lib.stage(reg, trace_lib.STAGE_LAUNCH, pack=1):
      forward(x)
    trace_lib.configure(None)
    names = [e['name'] for e in summarize_lib.load_trace(path)
             if e['ph'] == 'X']
    assert names == ['forward_launch']
    assert reg.counter_values()['jit_traces_total'] >= 2
    assert reg.counter_values()['xla_compiles_total'] >= 1
    assert reg.histogram('stage_jit_lower_s').snapshot()['count'] >= 1

  def test_a_compile_after_the_first_submit_names_its_pack(self):
    events = [
        _span('submit', 10.0, 5.0, span=1),
        _span('xla_compile', 2.0, 1.0, fun='jit(forward)',
              under='forward_launch', pack=1, cache_hit=True),
        _span('forward_launch', 12.0, 2.5, span=2, parent=1, pack=9),
        _span('jit_trace', 12.0, 0.5, fun='forward', under='forward_launch',
              pack=9, parent=2, span=3),
        _span('jit_trace', 12.1, 0.2, fun='inner', under='forward_launch',
              pack=9, parent=2, span=4),
        _span('xla_compile', 12.5, 2.0, fun='jit(forward)',
              under='forward_launch', pack=9, parent=2, span=5,
              cache_hit=False),
    ]
    summary = summarize_lib.summarize(events)
    assert summary['startup']['compiles_after_first_submit'] == [
        {'fun': 'jit(forward)', 'under': 'forward_launch', 'pack': 9,
         'dur_s': 2.0, 'cache_hit': False}]
    assert summary['startup']['compile_kinds']['xla_compile'][
        'cache_hits'] == 1
    # The launch's self time: its 2.5 s less the trace (nested one once)
    # and the compile.
    assert summary['self_time']['forward_launch']['self_s'] == (
        pytest.approx(0.0))
    assert summary['self_time']['jit_trace']['total_s'] == pytest.approx(0.5)
    text = summarize_lib.format_summary(summary)
    assert 'start-up and compiles:' in text
    assert 'compiles after the first submit: 1' in text
    assert 'jit(forward) 2.0000s under forward_launch pack=9' in text
