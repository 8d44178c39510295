"""`dctpu serve` resilience suite.

In-process server on a stubbed (weightless) model for the fast tier:
admission control, deadlines, client fault modes, pack-failure
isolation, quarantine attribution, drain semantics, and serve-vs-batch
byte identity. The real-subprocess SIGTERM-under-load acceptance demo
(jit compile + signal delivery) is marked slow and runs with the
resilience suite (`scripts/run_resilience.sh --serve`).
"""
import collections
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deepconsensus_tpu import faults as shared_faults
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.serve import client as client_lib
from deepconsensus_tpu.serve import server as server_lib
from deepconsensus_tpu.serve.client import ServeClient, ServeClientError
from deepconsensus_tpu.serve.service import ConsensusService, ServeOptions

pytestmark = pytest.mark.resilience

BATCH = 8
STUB_QUAL = 40


@pytest.fixture(scope='module')
def params():
  p = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(p, is_training=False)
  return p


class _StubControl:
  """Mutable knobs for the stubbed forward (per-test behavior)."""

  def __init__(self):
    self.dispatch_delay = 0.0


def _stub_runner(params, control=None):
  options = runner_lib.InferenceOptions(batch_size=BATCH)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  runner = runner_lib.ModelRunner(params, {}, options)
  mp = params.max_passes
  control = control or _StubControl()

  def dispatch_pack(main_u8, sn, n_rows=None, batch_size=None):
    if control.dispatch_delay:
      time.sleep(control.dispatch_delay)
    return main_u8[:n_rows]

  def finalize(rows):
    ids = rows[:, 4 * mp, :, 0].astype(np.int32)
    return ids, np.full(ids.shape, STUB_QUAL, np.int32)

  runner.dispatch_pack = dispatch_pack
  runner.finalize = finalize
  return runner, options, control


class _Ctx:
  def __init__(self, service, httpd, port, control):
    self.service = service
    self.httpd = httpd
    self.port = port
    self.control = control
    self.client = ServeClient(port=port, timeout=30)


@pytest.fixture()
def serve_ctx(params, tmp_path):
  """One in-process server per test: fresh counters, fresh dead-letter
  sidecar, stub model (no weights, no jit)."""
  made = []

  def make(**overrides):
    runner, options, control = _stub_runner(params)
    buckets = overrides.pop('window_buckets', None)
    if buckets:
      options.window_buckets = buckets
    so_kw = dict(
        io_timeout_s=2.0,
        default_deadline_s=20.0,
        dead_letter_path=str(tmp_path / 'serve.failed.jsonl'),
    )
    so_kw.update(overrides)
    service = ConsensusService(runner, options, ServeOptions(**so_kw))
    service.warmup()
    service.start()
    httpd = server_lib.build_server(service, '127.0.0.1', 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    ctx = _Ctx(service, httpd, httpd.server_address[1], control)
    made.append(ctx)
    return ctx

  yield make
  for ctx in made:
    ctx.service.begin_drain()
    ctx.httpd.shutdown()
    ctx.httpd.server_close()
    ctx.service.drain(timeout=10)


def _mol(params, name, n=4, seed=0, width=None):
  width = width or params.max_length
  rng = np.random.default_rng(seed)
  return dict(
      name=name,
      subreads=rng.integers(
          0, 5, size=(n, params.total_rows, width, 1)
      ).astype(np.float32),
      window_pos=np.arange(n, dtype=np.int64) * width,
      ccs_bq=np.full((n, width), 30, dtype=np.int32),
      overflow=np.zeros(n, dtype=np.uint8),
  )


def test_polish_roundtrip_and_metrics(serve_ctx, params):
  ctx = serve_ctx()
  assert ctx.client.wait_ready(10)
  resp = ctx.client.polish(**_mol(params, 'm/1/ccs'))
  assert resp['status'] == 'ok'
  assert len(resp['seq']) > 0
  assert len(resp['quals']) == len(resp['seq'])
  assert resp['counters']['n_windows_to_model'] == 4
  m = ctx.client.metricz()
  assert m['counters']['n_requests'] == 1
  assert m['latency']['count'] == 1
  assert m['latency']['p50'] is not None
  assert m['counters']['n_rejected_backpressure'] == 0
  assert m['counters']['n_deadline_cancelled'] == 0
  assert m['counters']['n_quarantined_by_request'] == 0


def test_concurrent_clients_byte_identical_to_solo(serve_ctx, params):
  """Continuous batching packs many clients' windows into shared
  fixed-shape packs; every client still gets exactly its solo result
  (zero cross-request state leaks)."""
  ctx = serve_ctx()
  mols = [_mol(params, f'm/{i}/ccs', n=3 + i % 4, seed=i)
          for i in range(10)]
  solo = [ctx.client.polish(**m) for m in mols]
  results = [None] * len(mols)
  errors = []

  def worker(i):
    try:
      results[i] = ServeClient(port=ctx.port, timeout=30).polish(**mols[i])
    except Exception as e:
      errors.append(e)

  threads = [threading.Thread(target=worker, args=(i,))
             for i in range(len(mols))]
  for t in threads:
    t.start()
  for t in threads:
    t.join(30)
  assert not errors
  for i, (s, r) in enumerate(zip(solo, results)):
    assert r['status'] == 'ok', i
    assert r['seq'] == s['seq'], i
    np.testing.assert_array_equal(r['quals'], s['quals'])
  stats = ctx.service.stats()
  # Shared packs actually happened: fewer packs than requests' windows
  # would need unbatched.
  assert stats['n_model_packs'] < sum(3 + i % 4 for i in range(10))


def test_mixed_width_clients_share_per_bucket_packs(serve_ctx, params):
  """Clients sending L=100 and L=200 requests concurrently each get
  their solo bytes back; the engine packs each width into its own
  bucket's shared packs and reports per-bucket counters in /metricz."""
  ctx = serve_ctx(window_buckets=(100, 200))
  assert ctx.client.wait_ready(10)
  mols = [_mol(params, f'm/{i}/ccs', n=3 + i % 3, seed=i,
               width=200 if i % 2 else 100)
          for i in range(10)]
  solo = [ctx.client.polish(**m) for m in mols]
  results = [None] * len(mols)
  errors = []

  def worker(i):
    try:
      results[i] = ServeClient(port=ctx.port, timeout=30).polish(**mols[i])
    except Exception as e:
      errors.append(e)

  threads = [threading.Thread(target=worker, args=(i,))
             for i in range(len(mols))]
  for t in threads:
    t.start()
  for t in threads:
    t.join(30)
  assert not errors
  for i, (s, r) in enumerate(zip(solo, results)):
    assert r['status'] == 'ok', i
    assert r['seq'] == s['seq'], i
    np.testing.assert_array_equal(r['quals'], s['quals'])
  m = ctx.client.metricz()
  counters = m['counters']
  assert set(map(int, counters['n_packs_by_bucket'])) == {100, 200}
  assert counters['padding_fraction'] > 0
  # Starvation accounting reaches /metricz (values depend on request
  # interleaving; the math is pinned at the engine boundary).
  assert counters['n_starvation_flushes'] >= 0
  assert 0.0 <= counters['flush_padding_fraction'] <= 1.0
  assert counters['use_ragged_kernel'] == 0
  assert m['window_buckets'] == [100, 200]
  # A width outside the buckets is a 400, not an engine fault.
  with pytest.raises(ServeClientError, match='400'):
    ctx.client.polish(**_mol(params, 'm/bad/ccs', width=150))


def test_metricz_hammer_during_soak_exact_counters(serve_ctx, params):
  """Regression for the metrics/model-loop race: /metricz used to
  sort the latency deque while _finish appended to it ("deque mutated
  during iteration"). N reader threads hammer /metricz through a full
  soak batch; every read must succeed and the final counters must be
  exact — no torn reads, no lost increments."""
  ctx = serve_ctx()
  assert ctx.client.wait_ready(10)
  ctx.control.dispatch_delay = 0.002  # keep latencies flowing
  n_requests = 24
  stop = threading.Event()
  reader_errors = []
  n_reads = [0]

  def hammer():
    client = ServeClient(port=ctx.port, timeout=30)
    while not stop.is_set():
      try:
        m = client.metricz()
        # Counters must always be internally coherent mid-soak.
        assert 0 <= m['counters']['n_requests'] <= n_requests
        assert 0 <= m['latency']['count'] <= n_requests
        n_reads[0] += 1
      except Exception as e:  # noqa: BLE001 - reported via the assert
        reader_errors.append(e)
        return

  readers = [threading.Thread(target=hammer) for _ in range(4)]
  for t in readers:
    t.start()

  submit_errors = []

  def submit(base):
    client = ServeClient(port=ctx.port, timeout=30)
    for i in range(n_requests // 4):
      try:
        resp = client.polish(**_mol(params, f'm/{base}_{i}/ccs'))
        assert resp['status'] == 'ok'
      except Exception as e:  # noqa: BLE001
        submit_errors.append(e)

  submitters = [threading.Thread(target=submit, args=(w,))
                for w in range(4)]
  for t in submitters:
    t.start()
  for t in submitters:
    t.join(60)
  stop.set()
  for t in readers:
    t.join(30)

  assert not submit_errors, submit_errors[:3]
  assert not reader_errors, reader_errors[:3]
  assert n_reads[0] > 0
  m = ctx.client.metricz()
  assert m['counters']['n_requests'] == n_requests
  assert m['latency']['count'] == n_requests
  assert m['counters']['n_quarantined_by_request'] == 0
  assert m['counters']['n_deadline_cancelled'] == 0


def test_garbage_body_rejected_400(serve_ctx, params):
  ctx = serve_ctx()
  status = client_lib.send_garbage('127.0.0.1', ctx.port)
  assert status == 400
  # Service unharmed: a well-formed request still completes.
  assert ctx.client.polish(**_mol(params, 'm/2/ccs'))['status'] == 'ok'


def test_oversized_rejected_on_header_413(serve_ctx, params):
  ctx = serve_ctx()
  status = client_lib.send_oversized('127.0.0.1', ctx.port,
                                     claimed_bytes=1 << 40)
  assert status == 413
  assert ctx.client.polish(**_mol(params, 'm/3/ccs'))['status'] == 'ok'


def test_window_cap_rejected_413(serve_ctx, params):
  ctx = serve_ctx(max_windows_per_request=2)
  with pytest.raises(ServeClientError) as exc:
    ctx.client.polish(**_mol(params, 'm/4/ccs', n=5))
  assert exc.value.status == 413


def test_mid_request_disconnect_harmless(serve_ctx, params):
  ctx = serve_ctx()
  from deepconsensus_tpu.serve import protocol
  body = protocol.encode_request(**_mol(params, 'm/5/ccs'))
  for _ in range(3):
    client_lib.send_disconnect('127.0.0.1', ctx.port, body)
  assert ctx.client.healthz()['_status'] == 200
  assert ctx.client.polish(**_mol(params, 'm/6/ccs'))['status'] == 'ok'
  # Disconnected uploads never reached admission.
  assert ctx.client.metricz()['counters']['n_requests'] == 1


def test_slowloris_cut_by_io_timeout(serve_ctx, params):
  """A drip-feed connection is cut at io_timeout_s (2s here), long
  before the requested 20s, and the model loop never notices."""
  ctx = serve_ctx()
  survived = client_lib.send_slowloris('127.0.0.1', ctx.port,
                                       duration_s=20.0, interval_s=0.5)
  assert survived < 10.0
  assert ctx.client.polish(**_mol(params, 'm/7/ccs'))['status'] == 'ok'


def test_backpressure_429(serve_ctx, params):
  """max_pending=1 with a slow model: while one request occupies the
  loop, the next is shed with a typed 429 classifying transient."""
  ctx = serve_ctx(max_pending=1)
  ctx.control.dispatch_delay = 3.0
  first = {}

  def slow_one():
    first['resp'] = ctx.client.polish(**_mol(params, 'm/8/ccs'))

  t = threading.Thread(target=slow_one)
  t.start()
  time.sleep(0.5)  # the slow request is admitted and in flight
  rejected = None
  deadline = time.monotonic() + 2.0  # well inside the 3s dispatch
  while time.monotonic() < deadline and rejected is None:
    try:
      ServeClient(port=ctx.port, timeout=10).polish(
          **_mol(params, 'm/9/ccs'))
    except ServeClientError as e:
      rejected = e
    time.sleep(0.05)
  t.join(20)
  assert rejected is not None, 'never saw backpressure'
  assert rejected.status == 429
  assert rejected.kind == shared_faults.FaultKind.TRANSIENT
  assert first['resp']['status'] == 'ok'  # admitted work unaffected
  assert ctx.client.metricz()['counters']['n_rejected_backpressure'] >= 1


def test_deadline_cancelled_504(serve_ctx, params):
  ctx = serve_ctx()
  ctx.control.dispatch_delay = 2.0
  with pytest.raises(ServeClientError) as exc:
    ctx.client.polish(**_mol(params, 'm/10/ccs'), deadline_s=0.3)
  assert exc.value.status == 504
  assert exc.value.kind == shared_faults.FaultKind.TRANSIENT
  ctx.control.dispatch_delay = 0.0
  # The loop sheds the cancelled work and keeps serving.
  assert ctx.client.polish(**_mol(params, 'm/11/ccs'))['status'] == 'ok'
  assert ctx.client.metricz()['counters']['n_deadline_cancelled'] == 1


def test_poison_quarantined_with_attribution_others_clean(
    serve_ctx, params, monkeypatch, tmp_path):
  """The acceptance core: a poison request sharing packs with clean
  requests fails its shared pack, fails its isolation retry, and is
  quarantined + dead-lettered with request attribution — while the
  clean requests complete byte-identical to their solo runs."""
  ctx = serve_ctx(on_request_error='ccs-fallback')
  clean = [_mol(params, f'm/{20 + i}/ccs', n=3, seed=i) for i in range(4)]
  solo = [ctx.client.polish(**m) for m in clean]
  poison_mol = _mol(params, 'm/666/ccs', n=3, seed=99)

  monkeypatch.setenv(shared_faults.ENV_POISON_WINDOW, 'm/666/')
  results = [None] * len(clean)
  poison_result = {}

  def clean_worker(i):
    results[i] = ServeClient(port=ctx.port, timeout=30).polish(**clean[i])

  def poison_worker():
    poison_result['resp'] = ServeClient(
        port=ctx.port, timeout=30).polish(**poison_mol)

  threads = [threading.Thread(target=clean_worker, args=(i,))
             for i in range(len(clean))] + [
      threading.Thread(target=poison_worker)]
  for t in threads:
    t.start()
  for t in threads:
    t.join(30)
  monkeypatch.delenv(shared_faults.ENV_POISON_WINDOW)

  # Clean clients: byte-identical to solo despite sharing packs with
  # the poison payload.
  for i, (s, r) in enumerate(zip(solo, results)):
    assert r is not None and r['status'] == 'ok', i
    assert r['seq'] == s['seq'], i
  # Poison client: degraded per policy (draft-CCS fallback), not a
  # service crash.
  resp = poison_result['resp']
  assert resp['status'] == 'fallback'
  assert 'poison' in resp['error']
  assert ctx.service.healthy
  m = ctx.client.metricz()
  assert m['counters']['n_quarantined_by_request'] == 1
  assert m['counters']['n_isolation_retries'] >= 1
  # Dead-letter carries request attribution.
  entries = [json.loads(line)
             for line in open(tmp_path / 'serve.failed.jsonl')]
  mine = [e for e in entries if e['zmw'] == 'm/666/ccs']
  assert len(mine) == 1
  assert mine[0]['stage'] == 'model'
  assert mine[0]['action'] == 'ccs-fallback'
  assert mine[0]['request_id'] > 0
  assert 'client' in mine[0] and 'model_pack' in mine[0]


def test_quarantine_skip_policy(serve_ctx, params, monkeypatch):
  ctx = serve_ctx(on_request_error='skip')
  monkeypatch.setenv(shared_faults.ENV_POISON_WINDOW, 'm/667/')
  resp = ctx.client.polish(**_mol(params, 'm/667/ccs', seed=1))
  assert resp['status'] == 'quarantined'
  assert resp['seq'] == b''


def test_draining_rejects_new_admissions(serve_ctx, params):
  ctx = serve_ctx()
  assert ctx.client.polish(**_mol(params, 'm/30/ccs'))['status'] == 'ok'
  ctx.service.begin_drain()
  assert ctx.client.readyz()['_status'] == 503
  assert ctx.client.healthz()['_status'] == 200  # alive, just draining
  with pytest.raises(ServeClientError) as exc:
    ctx.client.polish(**_mol(params, 'm/31/ccs'))
  assert exc.value.status == 503
  assert exc.value.kind == shared_faults.FaultKind.TRANSIENT
  assert ctx.service.drain(timeout=10)


def test_client_sabotage_env_hooks(serve_ctx, params, monkeypatch):
  """DCTPU_FAULT_SERVE_CLIENT turns a well-behaved ServeClient into
  the adversarial one, scoped by ZMW substring."""
  ctx = serve_ctx()
  monkeypatch.setenv(shared_faults.ENV_SERVE_CLIENT_FAULT, 'garbage')
  monkeypatch.setenv(shared_faults.ENV_SERVE_CLIENT_FAULT_ZMW, '/40/')
  sabotaged = ctx.client.polish(**_mol(params, 'm/40/ccs'))
  assert sabotaged['status'] == 'client-fault'
  assert sabotaged['mode'] == 'garbage'
  # Out-of-scope names are untouched.
  assert ctx.client.polish(**_mol(params, 'm/41/ccs'))['status'] == 'ok'


# ----------------------------------------------------------------------
# Observability plane: unified /metricz schema, Prometheus exposition,
# on-demand profiler capture, request trace spans (ISSUE 15)


def _http_get(port, path):
  import urllib.request
  req = urllib.request.urlopen(
      f'http://127.0.0.1:{port}{path}', timeout=15)
  with req as r:
    return r.status, r.headers.get('Content-Type', ''), r.read()


def test_metricz_unified_schema(serve_ctx, params):
  """Every tier's /metricz leads with the same top-level keys; the
  one-release legacy aliases (serve `faults` block, `p50_s`/`p99_s`/`n`
  percentile keys) are gone."""
  ctx = serve_ctx()
  assert ctx.client.wait_ready(10)
  ctx.client.polish(**_mol(params, 'm/70/ccs'))
  m = ctx.client.metricz()
  for key in ('tier', 'ready', 'draining', 'outstanding', 'counters',
              'latency', 'histograms'):
    assert key in m, key
  assert m['tier'] == 'serve'
  assert m['counters']['n_requests'] == 1
  assert 'serve_request_latency_s' in m['histograms']
  # Nearest-rank percentiles under the canonical keys ONLY: the
  # p50_s/p99_s/n aliases kept for one release are removed.
  lat = m['latency']
  assert lat['p50'] is not None and lat['p99'] is not None
  assert lat['count'] == 1
  assert not {'p50_s', 'p99_s', 'n'} & set(lat)
  # The legacy serve-only faults split is removed with them.
  assert 'faults' not in m


def test_metricz_prom_format(serve_ctx, params):
  ctx = serve_ctx()
  assert ctx.client.wait_ready(10)
  ctx.client.polish(**_mol(params, 'm/71/ccs'))
  status, ctype, body = _http_get(ctx.port, '/metricz?format=prom')
  assert status == 200
  assert ctype.startswith('text/plain')
  text = body.decode()
  assert 'dctpu_n_requests{tier="serve"} 1' in text
  assert 'dctpu_serve_request_latency_s_bucket{tier="serve",' in text
  assert 'dctpu_serve_request_latency_s_count{tier="serve"} 1' in text


def test_debugz_profile_capture(serve_ctx, params, tmp_path):
  """/debugz/profile?seconds=N runs a bounded jax.profiler capture in
  the handler thread and reports a status dict either way."""
  ctx = serve_ctx()
  assert ctx.client.wait_ready(10)
  out_dir = str(tmp_path / 'prof')
  status, _, body = _http_get(
      ctx.port, f'/debugz/profile?seconds=0.2&out={out_dir}')
  result = json.loads(body)
  assert status in (200, 503)
  assert 'ok' in result
  if result['ok']:
    assert result['out_dir'] == out_dir
    assert os.path.isdir(out_dir)
  # Bad seconds param is a 400, not a crash.
  import urllib.error
  with pytest.raises(urllib.error.HTTPError) as exc:
    _http_get(ctx.port, '/debugz/profile?seconds=banana')
  assert exc.value.code == 400


def test_request_trace_spans_and_header_propagation(
    serve_ctx, params, tmp_path):
  """A traced replica emits the request's span tree stamped with the
  trace id minted upstream (carried in the polish protocol header)."""
  from deepconsensus_tpu import obs as obs_lib

  trace_path = str(tmp_path / 'serve_trace.jsonl')
  obs_lib.trace.configure(trace_path, tier='serve')
  try:
    ctx = serve_ctx()
    assert ctx.client.wait_ready(10)
    resp = ctx.client.polish(**_mol(params, 'm/72/ccs'),
                             trace_id='0123456789abcdef')
    assert resp['status'] == 'ok'
  finally:
    obs_lib.trace.configure(None)
  from deepconsensus_tpu.obs import summarize as summarize_lib
  events = summarize_lib.load_trace(trace_path)
  spans = [e for e in events if e.get('ph') == 'X']
  req = [e for e in spans if e['name'] == 'serve_request']
  assert len(req) == 1
  assert req[0]['args']['trace_id'] == '0123456789abcdef'
  assert req[0]['args']['zmw'] == 'm/72/ccs'
  # The stitch leg of the same request carries the same id.
  stitch = [e for e in spans if e['name'] == 'stitch'
            and e['args'].get('trace_id') == '0123456789abcdef']
  assert stitch


def test_quarantine_record_carries_trace_id(serve_ctx, params,
                                            monkeypatch, tmp_path):
  """Dead-lettered / quarantined requests are joinable to their trace:
  the failure record carries the request's trace id."""
  ctx = serve_ctx(on_request_error='ccs-fallback')
  monkeypatch.setenv(shared_faults.ENV_POISON_WINDOW, 'm/73/')
  resp = ctx.client.polish(**_mol(params, 'm/73/ccs'),
                           trace_id='feedfeedfeedfeed')
  assert resp['status'] == 'fallback'
  entries = [json.loads(line)
             for line in open(tmp_path / 'serve.failed.jsonl')]
  mine = [e for e in entries if e['zmw'] == 'm/73/ccs']
  assert len(mine) == 1
  assert mine[0]['trace_id'] == 'feedfeedfeedfeed'


# ----------------------------------------------------------------------
# Data-parallel serving: mesh-backed service vs single-device service


@pytest.mark.multichip
def test_serve_with_mesh_byte_identical_to_single_device(params):
  """A dp=8 mesh behind the service must be invisible to clients:
  every response byte-matches the single-device service, while
  /metricz's faults split reports the sharded-dispatch counters."""
  import jax
  import jax.numpy as jnp

  from deepconsensus_tpu.models import model as model_lib
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  variables = model_lib.get_model(params).init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))
  mols = [_mol(params, f'm/{i}/ccs', n=3 + i % 4, seed=i)
          for i in range(6)]

  def serve_all(mesh):
    options = runner_lib.InferenceOptions(
        batch_size=BATCH, min_quality=0, min_length=0)
    options.max_passes = params.max_passes
    options.max_length = params.max_length
    options.use_ccs_bq = params.use_ccs_bq
    runner = runner_lib.ModelRunner(params, variables, options,
                                    mesh=mesh)
    service = ConsensusService(runner, options,
                               ServeOptions(io_timeout_s=2.0))
    service.warmup()
    service.start()
    httpd = server_lib.build_server(service, '127.0.0.1', 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
      client = ServeClient(port=httpd.server_address[1], timeout=30)
      assert client.wait_ready(10)
      responses = [client.polish(**m) for m in mols]
      metrics = client.metricz()
    finally:
      service.begin_drain()
      httpd.shutdown()
      httpd.server_close()
      service.drain(timeout=10)
    return responses, metrics

  single, metrics_single = serve_all(None)
  mesh = mesh_lib.make_mesh(dp=8, tp=1, devices=jax.devices()[:8])
  sharded, metrics_sharded = serve_all(mesh)

  for i, (s, m) in enumerate(zip(single, sharded)):
    assert m['status'] == s['status'], i
    assert m['seq'] == s['seq'], i
    np.testing.assert_array_equal(m['quals'], s['quals'])
  assert metrics_single['counters']['n_packs_dispatched_sharded'] == 0
  counters = metrics_sharded['counters']
  assert counters['n_packs_dispatched_sharded'] > 0
  assert (counters['n_transfer_overlapped']
          + counters['n_transfer_direct']) >= counters[
              'n_packs_dispatched_sharded']


# ----------------------------------------------------------------------
# Subprocess acceptance demo: SIGTERM drain under load, clean exit


@pytest.mark.slow
def test_sigterm_drains_under_load_subprocess(params, tmp_path):
  """Real `dctpu serve` process (random-init weights, real jit):
  SIGTERM mid-load must stop admissions, finish every admitted
  request (zero accepted-then-lost), and exit 0."""
  env = dict(os.environ, JAX_PLATFORMS='cpu')
  proc = subprocess.Popen(
      [sys.executable, '-m', 'deepconsensus_tpu.cli', 'serve',
       '--random_init', '--port', '0', '--min_quality', '0',
       '--dead_letter', str(tmp_path / 'dl.jsonl')],
      stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
  try:
    ready = json.loads(proc.stdout.readline())
    assert ready['event'] == 'ready'
    port = ready['port']
    client = ServeClient(port=port, timeout=60)
    assert client.wait_ready(60)

    outcomes = collections.Counter()
    lock = threading.Lock()
    stop_clients = threading.Event()

    def worker(wid):
      i = 0
      while not stop_clients.is_set():
        i += 1
        try:
          resp = ServeClient(port=port, timeout=60).polish(
              **_mol(params, f'm/{wid}_{i}/ccs', n=2, seed=wid * 100 + i))
          with lock:
            outcomes[resp['status']] += 1
        except ServeClientError as e:
          with lock:
            # 503 draining is the only acceptable rejection here.
            outcomes[f'http_{e.status}'] += 1
        except (ConnectionError, OSError):
          with lock:
            outcomes['conn_refused'] += 1
          return

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(4)]
    for t in threads:
      t.start()
    time.sleep(2.0)  # load flowing
    proc.send_signal(signal.SIGTERM)
    proc.wait(timeout=120)
    stop_clients.set()
    for t in threads:
      t.join(30)

    assert proc.returncode == 0, proc.stderr.read()[-2000:]
    tail = [json.loads(line) for line in proc.stdout.read().splitlines()
            if line.startswith('{')]
    drained = [d for d in tail if d.get('event') == 'drained']
    assert drained and drained[0]['drained'] is True
    # Zero accepted-then-lost: every request either completed ('ok',
    # or 'filtered' when random weights polish below the length floor)
    # or was rejected with a typed drain/backpressure code before
    # admission. No deadline cancels, no quarantines, no hangs.
    assert outcomes['ok'] + outcomes['filtered'] >= 1
    unexpected = {k: v for k, v in outcomes.items()
                  if k not in ('ok', 'filtered', 'http_503', 'http_429',
                               'conn_refused')}
    assert not unexpected, outcomes
    assert drained[0]['counters']['n_deadline_cancelled'] == 0
  finally:
    if proc.poll() is None:
      proc.kill()
      proc.wait()
