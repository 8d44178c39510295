"""Fleet tier suite: `dctpu route` + disaggregated featurize workers.

In-process router fronting stubbed (weightless) model replicas, so the
balancing/retry/drain semantics run in milliseconds:

  * protocol version negotiation — the features/1 compact frame and
    the bam/1 raw frame, old-client/new-server and new-client/
    old-server behavior, lossless-packing guards;
  * registry health gating and the balancer's weighted least-loaded
    pick with bounded in-flight;
  * the ack-boundary retry semantics: send-phase failures and explicit
    429/503 refusals move to another replica, post-send failures
    surface as typed ReplicaLostError and are never placed twice;
  * multi-replica byte identity vs a solo replica, and the
    disaggregated bam/1 -> featurize worker -> model replica path vs
    monolithic client-side featurize;
  * runtime /v1/register joins and the rolling-restart drain flow;
  * probe hysteresis: a flapping replica never re-enters the candidate
    set until it earns ready_after consecutive healthy probes;
  * multi-tenant QoS: weighted-fair admission (a saturating bulk
    stream cannot starve an interactive trickle), per-client quotas
    as typed 429s, class-aware shed accounting;
  * the preemption notice -> drain -> exit path on the replica, and
    the autoscaler control law (scale out on SLO breach, scale in
    cold, replace preempted capacity) against scripted signals.

The real-subprocess acceptance demo — autoscaler holding the SLO
through a load ramp plus a forced preemption drill — lives in
scripts/soak_e2e.py --fleet (scripts/run_resilience.sh --fleet).
"""
import json
import socket
import struct
import threading
import time

import numpy as np
import pytest

from deepconsensus_tpu import faults as shared_faults
from deepconsensus_tpu.fleet import registry as registry_lib
from deepconsensus_tpu.fleet import router as router_lib
from deepconsensus_tpu.fleet.autoscaler import Autoscaler, AutoscalerOptions
from deepconsensus_tpu.fleet.balancer import LeastLoadedBalancer
from deepconsensus_tpu.fleet.featurize_worker import (
    FeaturizeService,
    FeaturizeWorkerOptions,
    worker_main,
)
from deepconsensus_tpu.fleet.registry import (
    FEATURIZE_TIER,
    MODEL_TIER,
    ReplicaRegistry,
    ReplicaState,
)
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.preprocess import (
    FeatureLayout,
    create_proc_feeder,
    reads_to_pileup,
)
from deepconsensus_tpu.preprocess.pileup import row_indices
from deepconsensus_tpu.serve import protocol
from deepconsensus_tpu.serve import server as server_lib
from deepconsensus_tpu.serve.client import ServeClient, ServeClientError
from deepconsensus_tpu.serve.service import ConsensusService, ServeOptions

pytestmark = [pytest.mark.fleet, pytest.mark.resilience]

BATCH = 8
STUB_QUAL = 40


@pytest.fixture(scope='module')
def params():
  p = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(p, is_training=False)
  return p


def _stub_runner(params):
  options = runner_lib.InferenceOptions(batch_size=BATCH)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  runner = runner_lib.ModelRunner(params, {}, options)
  mp = params.max_passes

  def finalize(rows):
    ids = rows[:, 4 * mp, :, 0].astype(np.int32)
    return ids, np.full(ids.shape, STUB_QUAL, np.int32)

  runner.dispatch_pack = (
      lambda main_u8, sn, n_rows=None, batch_size=None: main_u8[:n_rows])
  runner.finalize = finalize
  return runner, options


def _mol(params, name, n=4, seed=0):
  rng = np.random.default_rng(seed)
  return dict(
      name=name,
      subreads=rng.integers(
          0, 5, size=(n, params.total_rows, params.max_length, 1)
      ).astype(np.float32),
      window_pos=np.arange(n, dtype=np.int64) * params.max_length,
      ccs_bq=np.full((n, params.max_length), 30, dtype=np.int32),
      overflow=np.zeros(n, dtype=np.uint8),
  )


def _features(params, name, n=4, seed=0):
  """_mol as per-window preprocess feature dicts (polish_features
  input)."""
  mol = _mol(params, name, n=n, seed=seed)
  return [
      dict(
          name=name,
          subreads=mol['subreads'][i],
          window_pos=int(mol['window_pos'][i]),
          ccs_base_quality_scores=mol['ccs_bq'][i],
          overflow=bool(mol['overflow'][i]),
      )
      for i in range(n)
  ]


class _Fleet:
  """One router + its replicas, all in-process."""

  def __init__(self):
    self.replicas = []      # (service, httpd, port)
    self.workers = []       # (stop_event, thread, port)
    self.router_stop = threading.Event()
    self.router_thread = None
    self.router_stats = {}
    self.port = None

  def client(self, timeout=30):
    return ServeClient(port=self.port, timeout=timeout)


@pytest.fixture()
def fleet(params):
  """Factory: fleet(n_replicas, n_workers, **router_options) builds an
  in-process fleet and returns a _Fleet handle. Everything is torn
  down at test end."""
  made = []

  def make_replica():
    runner, options = _stub_runner(params)
    service = ConsensusService(
        runner, options, ServeOptions(io_timeout_s=5.0))
    service.warmup()
    service.start()
    httpd = server_lib.build_server(service, '127.0.0.1', 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return service, httpd, httpd.server_address[1]

  def make_worker():
    stop = threading.Event()
    ready = {}
    opts = FeaturizeWorkerOptions(
        max_passes=params.max_passes, max_length=params.max_length)
    t = threading.Thread(
        target=lambda: worker_main(
            opts, port=0, ready_fn=ready.update, stop_event=stop),
        daemon=True)
    t.start()
    while 'port' not in ready:
      time.sleep(0.01)
    return stop, t, ready['port']

  def make(n_replicas=2, n_workers=0, **router_overrides):
    f = _Fleet()
    for _ in range(n_replicas):
      f.replicas.append(make_replica())
    for _ in range(n_workers):
      f.workers.append(make_worker())
    opts = router_lib.RouterOptions(
        probe_interval_s=0.1, probe_timeout_s=2.0, io_timeout_s=5.0,
        **router_overrides)
    ready = {}
    f.router_thread = threading.Thread(
        target=lambda: f.router_stats.update(router_lib.route_main(
            [f'127.0.0.1:{p}' for _, _, p in f.replicas],
            [f'127.0.0.1:{p}' for _, _, p in f.workers],
            options=opts, port=0, ready_fn=ready.update,
            stop_event=f.router_stop)),
        daemon=True)
    f.router_thread.start()
    while 'port' not in ready:
      time.sleep(0.01)
    f.port = ready['port']
    made.append(f)
    return f

  yield make
  for f in made:
    f.router_stop.set()
    f.router_thread.join(timeout=15)
    for stop, t, _ in f.workers:
      stop.set()
      t.join(timeout=10)
    for service, httpd, _ in f.replicas:
      service.begin_drain()
      httpd.shutdown()
      httpd.server_close()
      service.drain(timeout=10)


# ----------------------------------------------------------------------
# Protocol version negotiation (features/1, bam/1, legacy)


def _decode_kwargs(params):
  return dict(total_rows=params.total_rows,
              max_length=params.max_length, max_windows=512)


def test_features_frame_roundtrips_byte_identical(params):
  """A features/1 compact pack decodes to the exact arrays the legacy
  float frame carries — the model replica cannot tell them apart."""
  feats = _features(params, 'm/7/ccs', n=3, seed=7)
  for fd in feats:
    # Real pileups carry per-window-constant SN rows; the random _mol
    # tensor doesn't, so pin them to make the pack eligible.
    fd['subreads'][-4:] = np.arange(4, dtype=np.float32)[:, None, None]
  legacy = protocol.request_from_features(feats)
  compact = protocol.features_pack_from_features(feats)
  assert compact is not None
  assert len(compact) < len(legacy) // 2  # the point of the frame
  ref = protocol.decode_request(legacy, **_decode_kwargs(params))
  got = protocol.decode_request(compact, **_decode_kwargs(params))
  assert got['name'] == ref['name']
  for key in ('subreads', 'window_pos', 'ccs_bq', 'overflow'):
    np.testing.assert_array_equal(got[key], ref[key], err_msg=key)


@pytest.mark.parametrize('max_passes,use_ccs_bq', [
    (2, False), (2, True), (20, False), (20, True), (5, True),
])
def test_bq_row_derivation_matches_layout(max_passes, use_ccs_bq):
  """Both frame codecs derive the ccs_bq row from total_rows alone;
  that derivation must match the canonical row layout for every
  (max_passes, use_ccs_bq)."""
  *_, ccs_bq_range, sn_range = row_indices(max_passes, use_ccs_bq)
  total_rows = sn_range[1]
  derived = protocol._bq_row_for_total_rows(total_rows)
  if use_ccs_bq:
    assert derived == ccs_bq_range[0]
  else:
    assert derived is None


def test_lossless_guard_falls_back_to_legacy_frame(params):
  """Values that don't pack losslessly into uint8 (pw > 255, or SN
  rows that vary inside a window) make the compact encoder bow out
  with None — the caller then ships the exact legacy float frame."""
  feats = _features(params, 'm/8/ccs', n=2, seed=8)
  mp = params.max_passes
  feats[0]['subreads'][mp, 0, 0] = 300.0  # pre-clip pw overflows uint8
  assert protocol.features_pack_from_features(feats) is None

  feats = _features(params, 'm/9/ccs', n=2, seed=9)
  feats[0]['subreads'][-1, 0, 0] = 1.0    # sn no longer constant
  feats[0]['subreads'][-1, 1, 0] = 2.0
  assert protocol.features_pack_from_features(feats) is None

  feats = _features(params, 'm/10/ccs', n=2, seed=10)
  feats[0]['subreads'][0, 0, 0] = 0.5     # non-integral value
  assert protocol.features_pack_from_features(feats) is None


def test_unknown_frame_is_typed_400_not_parse_crash(params):
  """A client speaking a future frame version gets a typed 400 naming
  the known frames, never an unhandled parse error."""
  import io as _io
  buf = _io.BytesIO()
  np.savez(buf, frame=np.array('features/99'), payload=np.zeros(3))
  with pytest.raises(shared_faults.BadRequestError) as e:
    protocol.decode_request(buf.getvalue(), **_decode_kwargs(params))
  for frame in protocol.KNOWN_FRAMES:
    assert frame in str(e.value)


def test_bam_frame_to_model_replica_is_typed_400(params):
  """An old-topology deployment (client with a new frame, no router in
  front) answers with a typed 400 pointing at the route tier."""
  body = protocol.encode_bam_request(b'x' * 10, b'y' * 10, name='z/1')
  with pytest.raises(shared_faults.BadRequestError, match='dctpu route'):
    protocol.decode_request(body, **_decode_kwargs(params))


def test_bam_frame_roundtrip_and_malformed_variants():
  body = protocol.encode_bam_request(b'SUB', b'CCS', name='m/1/ccs')
  assert protocol.sniff_frame(body) == protocol.FRAME_BAM
  req = protocol.decode_bam_request(body)
  assert req['subreads_bam'] == b'SUB'
  assert req['ccs_bam'] == b'CCS'
  assert req['name'] == 'm/1/ccs'

  with pytest.raises(shared_faults.BadRequestError):
    protocol.decode_bam_request(b'not an npz at all')
  with pytest.raises(shared_faults.BadRequestError, match='empty'):
    protocol.decode_bam_request(
        protocol.encode_bam_request(b'', b'CCS'))
  # A features/1 body is the wrong frame for a featurize worker.
  feats_body = protocol.encode_request(
      'm/1', np.zeros((1, 4, 8, 1), np.float32),
      np.zeros(1, np.int64), np.zeros((1, 8), np.int32),
      np.zeros(1, np.uint8))
  with pytest.raises(shared_faults.BadRequestError):
    protocol.decode_bam_request(feats_body)


def test_legacy_frame_still_decodes(params):
  """Old clients keep working against new servers: the frameless
  legacy body is untouched by the version negotiation."""
  feats = _features(params, 'm/11/ccs', n=2, seed=11)
  legacy = protocol.request_from_features(feats)
  assert protocol.sniff_frame(legacy) is None
  out = protocol.decode_request(legacy, **_decode_kwargs(params))
  assert out['name'] == 'm/11/ccs'


# ----------------------------------------------------------------------
# Registry + balancer semantics (no HTTP)


def _ready_replica(reg, url, tier=MODEL_TIER, **attrs):
  reg.add(url, tier=tier)
  with reg.lock:
    r = reg._replicas[url]
    r.state = ReplicaState.READY
    for k, v in attrs.items():
      setattr(r, k, v)
  return url


def test_registry_health_gates_new_replicas():
  """add() never yields a routable replica until a probe has seen
  /readyz pass: JOINING replicas are invisible to the balancer."""
  reg = ReplicaRegistry()
  reg.add('127.0.0.1:1', tier=MODEL_TIER)
  assert reg.snapshot()[0].state == ReplicaState.JOINING
  balancer = LeastLoadedBalancer(reg)
  with pytest.raises(shared_faults.FleetRejection, match='not.*ready|no model replica is ready'):
    balancer.acquire(MODEL_TIER)


def test_registry_rejects_unknown_tier():
  reg = ReplicaRegistry()
  with pytest.raises(ValueError, match='tier'):
    reg.add('127.0.0.1:1', tier='gpu')


def test_balancer_prefers_least_loaded_and_degraded_half_weight():
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1', queue_depth=6)
  _ready_replica(reg, 'b:1', queue_depth=0)
  balancer = LeastLoadedBalancer(reg)
  assert balancer.acquire(MODEL_TIER).url == 'b:1'
  # b now carries 1 in-flight; a degraded replica with the same load
  # scores twice as busy, so the pick still avoids it.
  _ready_replica(reg, 'c:1', queue_depth=0, degraded=True)
  picks = [balancer.acquire(MODEL_TIER).url for _ in range(2)]
  assert picks.count('c:1') <= 1  # healthy replicas absorb more


def test_balancer_bounded_inflight_saturates_with_typed_503():
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1')
  balancer = LeastLoadedBalancer(reg, max_inflight=2)
  balancer.acquire(MODEL_TIER)
  balancer.acquire(MODEL_TIER)
  with pytest.raises(shared_faults.FleetRejection,
                     match='in-flight bound') as e:
    balancer.acquire(MODEL_TIER)
  assert e.value.http_status == 503
  assert e.value.kind == shared_faults.FaultKind.TRANSIENT
  balancer.release('a:1', 'ok')
  assert balancer.acquire(MODEL_TIER).url == 'a:1'


def test_balancer_scales_bound_by_mesh_dp():
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1', mesh_dp=4)
  balancer = LeastLoadedBalancer(reg, max_inflight=2)
  for _ in range(8):  # 2 * mesh_dp
    balancer.acquire(MODEL_TIER)
  with pytest.raises(shared_faults.FleetRejection):
    balancer.acquire(MODEL_TIER)


def test_draining_replica_gets_no_new_work():
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1')
  _ready_replica(reg, 'b:1')
  reg.mark_draining('a:1')
  balancer = LeastLoadedBalancer(reg)
  assert all(
      balancer.acquire(MODEL_TIER, exclude=()).url == 'b:1'
      for _ in range(3))


def test_registry_aggregates_replica_counters():
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1', counters={'n_requests': 3, 'x_fraction': 0.5})
  _ready_replica(reg, 'b:1', counters={'n_requests': 4, 'x_fraction': 1.0})
  agg = reg.aggregate_counters()
  assert agg['n_requests'] == 7
  assert agg['x_fraction'] == pytest.approx(0.75)  # fractions average


def test_flapping_replica_needs_consecutive_healthy_probes(monkeypatch):
  """Probe hysteresis regression: a replica flapping alive/dead never
  re-enters the balancer's candidate set on a single good probe — READY
  after DEAD requires ready_after CONSECUTIVE healthy probes, and an
  explicit re-register (operator intent) clears the debt."""
  script = ['ok']

  class FakeProbeClient:
    def __init__(self, host=None, port=None, timeout=None):
      del host, port, timeout

    def readyz(self):
      if script[0] == 'down':
        raise OSError('connection refused')
      return {'ready': True, 'mesh_dp': 1}

    def metricz(self):
      return {'outstanding': 0, 'counters': {}}

  monkeypatch.setattr(registry_lib, 'ServeClient', FakeProbeClient)
  reg = ReplicaRegistry(dead_after=1, ready_after=2)
  reg.add('127.0.0.1:9', tier=MODEL_TIER)
  balancer = LeastLoadedBalancer(reg)

  def probe(outcome):
    script[0] = outcome
    reg.probe_all()
    return reg.snapshot()[0].state

  # A fresh join has no hysteresis debt: one healthy probe suffices.
  assert probe('ok') == ReplicaState.READY
  assert probe('down') == ReplicaState.DEAD
  # One good probe mid-flap is noise: health-gated, no traffic.
  assert probe('ok') == ReplicaState.JOINING
  with pytest.raises(shared_faults.FleetRejection):
    balancer.acquire(MODEL_TIER)
  # The next miss resets the streak; healing starts over.
  assert probe('down') == ReplicaState.DEAD
  assert probe('ok') == ReplicaState.JOINING
  # The second CONSECUTIVE healthy probe earns READY back.
  assert probe('ok') == ReplicaState.READY
  assert balancer.acquire(MODEL_TIER).url == '127.0.0.1:9'
  balancer.release('127.0.0.1:9', 'ok')
  # Explicit re-registration (rolling-restart rejoin) clears the debt:
  # one healthy probe promotes again.
  assert probe('down') == ReplicaState.DEAD
  reg.add('127.0.0.1:9', tier=MODEL_TIER)
  assert probe('ok') == ReplicaState.READY


# ----------------------------------------------------------------------
# Multi-tenant QoS: weighted-fair admission, quotas, class shed


def test_wfq_interactive_trickle_beats_queued_bulk_backlog():
  """Starvation regression: with the only slot held and a bulk backlog
  already queued, a later-arriving interactive waiter (weight 4) gets
  the first freed slot — its virtual finish time lands ahead of the
  weight-1 backlog."""
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1')
  bal = LeastLoadedBalancer(reg, max_inflight=1, queue_wait_s=20.0,
                            max_queued_per_class=8)
  bal.acquire(MODEL_TIER, klass='bulk', client='hog')  # hold the slot
  order = []
  threads = []

  def waiter(klass, tag):
    replica = bal.acquire(MODEL_TIER, klass=klass)
    order.append(tag)
    bal.release(replica.url, 'ok', klass=klass)

  def queued():
    return bal.qos_snapshot()['queued'].get(MODEL_TIER, 0)

  for i in range(3):
    t = threading.Thread(target=waiter, args=('bulk', f'bulk{i}'))
    t.start()
    threads.append(t)
    deadline = time.monotonic() + 10
    while queued() < i + 1 and time.monotonic() < deadline:
      time.sleep(0.005)
  assert queued() == 3
  t = threading.Thread(target=waiter, args=('interactive', 'int0'))
  t.start()
  threads.append(t)
  deadline = time.monotonic() + 10
  while queued() < 4 and time.monotonic() < deadline:
    time.sleep(0.005)
  # Free the slot: the interactive waiter must be served first even
  # though three bulk waiters queued before it.
  bal.release('a:1', 'ok', klass='bulk', client='hog')
  for t in threads:
    t.join(timeout=15)
  assert order[0] == 'int0'
  assert sorted(order[1:]) == ['bulk0', 'bulk1', 'bulk2']
  qos = bal.qos_snapshot()
  assert qos['queued'] == {}
  assert qos['class_in_flight'] == {}


def test_bulk_overflow_sheds_only_bulk_and_names_the_class():
  """Per-class queue bound: the class that overflows its own admission
  queue is the class that sheds — interactive still queues and places."""
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1')
  bal = LeastLoadedBalancer(reg, max_inflight=1, queue_wait_s=20.0,
                            max_queued_per_class=2)
  bal.acquire(MODEL_TIER, klass='bulk')  # hold the slot
  threads = []

  def waiter(klass):
    replica = bal.acquire(MODEL_TIER, klass=klass)
    bal.release(replica.url, 'ok', klass=klass)

  for _ in range(2):  # fill bulk's queue to its bound
    t = threading.Thread(target=waiter, args=('bulk',))
    t.start()
    threads.append(t)
  deadline = time.monotonic() + 10
  while (bal.qos_snapshot()['queued'].get(MODEL_TIER, 0) < 2
         and time.monotonic() < deadline):
    time.sleep(0.005)
  with pytest.raises(shared_faults.FleetRejection,
                     match="class 'bulk' admission queue is full"):
    bal.acquire(MODEL_TIER, klass='bulk')
  # Interactive is unaffected by bulk's overflow: it queues and places.
  t = threading.Thread(target=waiter, args=('interactive',))
  t.start()
  threads.append(t)
  bal.release('a:1', 'ok', klass='bulk')
  for t in threads:
    t.join(timeout=15)
  assert not any(t.is_alive() for t in threads)


def test_saturated_wait_sheds_with_typed_503_at_deadline():
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1')
  bal = LeastLoadedBalancer(reg, max_inflight=1, queue_wait_s=0.2)
  bal.acquire(MODEL_TIER)
  t0 = time.monotonic()
  with pytest.raises(shared_faults.FleetRejection,
                     match='weighted-fair wait') as e:
    bal.acquire(MODEL_TIER, klass='bulk')
  assert time.monotonic() - t0 >= 0.15
  assert e.value.http_status == 503
  assert e.value.kind == shared_faults.FaultKind.TRANSIENT


def test_client_quota_is_typed_429_charged_to_that_tenant_alone():
  reg = ReplicaRegistry()
  _ready_replica(reg, 'a:1')
  bal = LeastLoadedBalancer(reg, client_quota=2)
  bal.acquire(MODEL_TIER, client='tenant-a')
  bal.acquire(MODEL_TIER, client='tenant-a')
  with pytest.raises(shared_faults.QuotaExceededError) as e:
    bal.acquire(MODEL_TIER, client='tenant-a')
  assert e.value.http_status == 429
  assert e.value.kind == shared_faults.FaultKind.TRANSIENT
  assert 'RESOURCE_EXHAUSTED' in str(e.value)
  assert isinstance(e.value, shared_faults.FleetRejection)
  # Another tenant is unaffected by tenant-a's runaway concurrency.
  replica = bal.acquire(MODEL_TIER, client='tenant-b')
  bal.release(replica.url, 'ok', client='tenant-b')
  # Releasing a slot frees the quota.
  bal.release('a:1', 'ok', client='tenant-a')
  assert bal.acquire(MODEL_TIER, client='tenant-a').url == 'a:1'


# ----------------------------------------------------------------------
# Router integration (in-process HTTP fleet)


def test_multi_replica_byte_identity_vs_solo(fleet, params):
  """Concurrent clients through a 2-replica router each get exactly
  the bytes a solo replica returns."""
  f = fleet(n_replicas=2)
  rc = f.client()
  assert rc.wait_ready(10)
  solo = ServeClient(port=f.replicas[0][2], timeout=30)
  mols = [_mol(params, f'm/{i}/ccs', n=2 + i % 3, seed=i)
          for i in range(8)]
  want = [solo.polish(**m) for m in mols]
  got = [None] * len(mols)
  errors = []

  def worker(i):
    try:
      got[i] = ServeClient(port=f.port, timeout=30).polish(**mols[i])
    except Exception as e:  # noqa: BLE001 — surfaced via assert below
      errors.append(e)

  threads = [threading.Thread(target=worker, args=(i,))
             for i in range(len(mols))]
  for t in threads:
    t.start()
  for t in threads:
    t.join(30)
  assert not errors
  for i, (w, g) in enumerate(zip(want, got)):
    assert g['status'] == 'ok', i
    assert g['seq'] == w['seq'], i
    np.testing.assert_array_equal(g['quals'], w['quals'])
  # Both replicas actually served traffic.
  m = rc.metricz()
  served = [r for r in m['replicas'] if r['n_ok'] > 0]
  assert len(served) == 2, m['replicas']


def test_compact_features_through_router_byte_identical(fleet, params):
  f = fleet(n_replicas=1)
  rc = f.client()
  assert rc.wait_ready(10)
  solo = ServeClient(port=f.replicas[0][2], timeout=30)
  feats = _features(params, 'm/3/ccs', n=3, seed=3)
  want = solo.polish_features(feats, compact=False)
  got = rc.polish_features(feats, compact=True)
  assert got['status'] == 'ok'
  assert got['seq'] == want['seq']
  np.testing.assert_array_equal(got['quals'], want['quals'])


def test_disaggregated_bam_path_byte_identical_to_monolithic(
    fleet, params, synthetic_bams):
  """bam/1 -> router -> featurize worker -> model replica produces the
  same polished bytes as featurizing client-side (monolithic path) and
  posting the legacy frame straight to a replica."""
  f = fleet(n_replicas=1, n_workers=1)
  rc = f.client()
  assert rc.wait_ready(10)
  sub_path, ccs_path = synthetic_bams(n_zmws=1, n_subreads=3, seq_len=120)

  # Monolithic reference: featurize in-process, post to the replica.
  layout = FeatureLayout(params.max_passes, params.max_length,
                         params.use_ccs_bq)
  feeder, _ = create_proc_feeder(
      subreads_to_ccs=sub_path, ccs_bam=ccs_path, layout=layout)
  mono = None
  for zmw_input in feeder():
    subreads, name, lo, _split, window_widths = zmw_input
    mono = list(
        reads_to_pileup(subreads, name, lo, window_widths)
        .iter_window_features())
  assert mono
  solo = ServeClient(port=f.replicas[0][2], timeout=30)
  want = solo.polish_body(protocol.request_from_features(mono))

  with open(sub_path, 'rb') as fh:
    subreads_bam = fh.read()
  with open(ccs_path, 'rb') as fh:
    ccs_bam = fh.read()
  got = rc.polish_bam(subreads_bam, ccs_bam, name='z/1')
  assert got['status'] == 'ok'
  assert got['seq'] == want['seq']
  np.testing.assert_array_equal(got['quals'], want['quals'])

  m = rc.metricz()
  assert m['counters']['n_routed_featurize'] == 1
  assert m['latency']['featurize']['count'] == 1


def test_send_phase_failure_retries_on_another_replica(fleet, params):
  """A replica that never reads the request (connection refused) is
  transparently retried elsewhere and marked DEAD."""
  f = fleet(n_replicas=2, max_attempts=3)
  rc = f.client()
  assert rc.wait_ready(10)
  # Kill replica 0 without letting the prober notice first.
  service, httpd, port = f.replicas[0]
  httpd.shutdown()
  httpd.server_close()
  service.begin_drain()
  ok = sum(
      rc.polish(**_mol(params, f'r/{i}/ccs'))['status'] == 'ok'
      for i in range(4))
  assert ok == 4
  m = rc.metricz()
  states = {r['url']: r['state'] for r in m['replicas']}
  assert states[f'127.0.0.1:{port}'] == ReplicaState.DEAD


def test_post_send_death_is_typed_503_and_never_duplicated(
    fleet, params):
  """A replica that dies after fully reading the request surfaces as a
  typed 503 ReplicaLostError and the request is NOT re-placed: the
  surviving replica sees zero new requests from it."""
  f = fleet(n_replicas=1, max_attempts=3)
  rc = f.client()
  assert rc.wait_ready(10)

  # An "evil" replica: reads the whole POST, then slams the socket.
  evil = socket.socket()
  evil.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
  evil.bind(('127.0.0.1', 0))
  evil.listen(4)
  evil_port = evil.getsockname()[1]

  def evil_loop():
    while True:
      try:
        conn, _ = evil.accept()
      except OSError:
        return
      with conn:
        data = b''
        while b'\r\n\r\n' not in data:
          chunk = conn.recv(65536)
          if not chunk:
            break
          data += chunk
        head, _, rest = data.partition(b'\r\n\r\n')
        length = 0
        for line in head.split(b'\r\n'):
          if line.lower().startswith(b'content-length:'):
            length = int(line.split(b':', 1)[1])
        while len(rest) < length:
          chunk = conn.recv(65536)
          if not chunk:
            break
          rest += chunk
        # Fully acked, then die: RST, no response bytes.
        conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack('ii', 1, 0))

  threading.Thread(target=evil_loop, daemon=True).start()

  # Drive RouterCore directly: the evil replica is hand-promoted to
  # READY with a lower load than the healthy one, so the pick lands on
  # it first.
  registry = ReplicaRegistry()
  _ready_replica(registry, f'127.0.0.1:{evil_port}', queue_depth=0)
  healthy_port = f.replicas[0][2]
  _ready_replica(registry, f'127.0.0.1:{healthy_port}', queue_depth=50)
  core = router_lib.RouterCore(
      registry, router_lib.RouterOptions(max_attempts=3,
                                         upstream_timeout_s=10))
  before = f.replicas[0][0].stats()['counters']['n_requests']
  body = protocol.request_from_features(_features(params, 'd/1/ccs'))
  with pytest.raises(shared_faults.ReplicaLostError) as e:
    core.route(body)
  assert e.value.http_status == 503
  assert e.value.kind == shared_faults.FaultKind.TRANSIENT
  assert 'never duplicated' in str(e.value)
  after = f.replicas[0][0].stats()['counters']['n_requests']
  assert after == before  # the healthy replica never saw the request
  with registry.lock:
    assert (registry._replicas[f'127.0.0.1:{evil_port}'].state
            == ReplicaState.DEAD)
  evil.close()


def test_upstream_draining_503_moves_on_and_marks_draining(params):
  """An explicit 503 naming a drain flips the replica to DRAINING
  immediately (rolling-restart fast path) and the request succeeds on
  the next replica."""
  drain_payload = json.dumps(
      {'error': 'UNAVAILABLE: draining', 'kind': 'transient'}).encode()
  resp = (b'HTTP/1.1 503 Service Unavailable\r\n'
          b'Content-Type: application/json\r\n'
          + f'Content-Length: {len(drain_payload)}\r\n\r\n'.encode()
          + drain_payload)

  srv = socket.socket()
  srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
  srv.bind(('127.0.0.1', 0))
  srv.listen(4)
  drain_port = srv.getsockname()[1]

  def loop():
    while True:
      try:
        conn, _ = srv.accept()
      except OSError:
        return
      with conn:
        data = b''
        while b'\r\n\r\n' not in data:
          chunk = conn.recv(65536)
          if not chunk:
            break
          data += chunk
        head, _, rest = data.partition(b'\r\n\r\n')
        length = 0
        for line in head.split(b'\r\n'):
          if line.lower().startswith(b'content-length:'):
            length = int(line.split(b':', 1)[1])
        while len(rest) < length:
          chunk = conn.recv(65536)
          if not chunk:
            break
          rest += chunk
        conn.sendall(resp)

  threading.Thread(target=loop, daemon=True).start()

  params_local = params
  runner, options = _stub_runner(params_local)
  service = ConsensusService(
      runner, options, ServeOptions(io_timeout_s=5.0))
  service.warmup()
  service.start()
  httpd = server_lib.build_server(service, '127.0.0.1', 0)
  threading.Thread(target=httpd.serve_forever, daemon=True).start()
  good_port = httpd.server_address[1]
  try:
    registry = ReplicaRegistry()
    _ready_replica(registry, f'127.0.0.1:{drain_port}', queue_depth=0)
    _ready_replica(registry, f'127.0.0.1:{good_port}', queue_depth=50)
    core = router_lib.RouterCore(
        registry, router_lib.RouterOptions(max_attempts=3,
                                           upstream_timeout_s=10))
    body = protocol.request_from_features(
        _features(params_local, 'g/1/ccs'))
    status, data, _ = core.route(body)
    assert status == 200
    out = protocol.decode_response(data)
    assert out['status'] == 'ok'
    with registry.lock:
      assert (registry._replicas[f'127.0.0.1:{drain_port}'].state
              == ReplicaState.DRAINING)
    assert core.obs.counter_values()['n_retries'] == 1
  finally:
    srv.close()
    service.begin_drain()
    httpd.shutdown()
    httpd.server_close()
    service.drain(timeout=10)


def test_runtime_register_joins_health_gated(fleet, params):
  """POST /v1/register adds a replica as JOINING; the prober promotes
  it to READY and it starts taking traffic."""
  f = fleet(n_replicas=1)
  rc = f.client()
  assert rc.wait_ready(10)

  runner, options = _stub_runner(params)
  service = ConsensusService(
      runner, options, ServeOptions(io_timeout_s=5.0))
  service.warmup()
  service.start()
  httpd = server_lib.build_server(service, '127.0.0.1', 0)
  threading.Thread(target=httpd.serve_forever, daemon=True).start()
  new_port = httpd.server_address[1]
  try:
    status, body, _ = rc._request(
        'POST', '/v1/register',
        body=json.dumps({'url': f'127.0.0.1:{new_port}',
                         'tier': MODEL_TIER}).encode())
    assert status == 200, body
    assert json.loads(body)['state'] == ReplicaState.JOINING
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
      m = rc.metricz()
      states = {r['url']: r['state'] for r in m['replicas']}
      if states.get(f'127.0.0.1:{new_port}') == ReplicaState.READY:
        break
      time.sleep(0.05)
    else:
      pytest.fail(f'replica never became READY: {states}')
    # Malformed register is a typed 400.
    status, body, _ = rc._request('POST', '/v1/register', body=b'{}')
    assert status == 400
    status, body, _ = rc._request(
        'POST', '/v1/register',
        body=json.dumps({'url': 'x:1', 'tier': 'gpu'}).encode())
    assert status == 400
  finally:
    service.begin_drain()
    httpd.shutdown()
    httpd.server_close()
    service.drain(timeout=10)


def test_router_drain_refuses_new_work_and_exits_clean(fleet, params):
  f = fleet(n_replicas=1)
  rc = f.client()
  assert rc.wait_ready(10)
  assert rc.polish(**_mol(params, 'm/1/ccs'))['status'] == 'ok'
  f.router_stop.set()
  f.router_thread.join(timeout=15)
  assert f.router_stats.get('drained') is True
  assert f.router_stats['counters']['n_requests'] == 1


def test_fleet_down_is_typed_503_transient(fleet, params):
  f = fleet(n_replicas=1, max_attempts=2)
  rc = f.client()
  assert rc.wait_ready(10)
  service, httpd, _ = f.replicas[0]
  httpd.shutdown()
  httpd.server_close()
  service.begin_drain()
  time.sleep(0.4)  # a probe cycle marks it dead
  with pytest.raises(ServeClientError) as e:
    rc.polish(**_mol(params, 'x/1/ccs'))
  assert e.value.status == 503
  assert e.value.kind == shared_faults.FaultKind.TRANSIENT
  assert not rc.readyz().get('ready')


def test_router_metricz_aggregates_fleet(fleet, params):
  f = fleet(n_replicas=2)
  rc = f.client()
  assert rc.wait_ready(10)
  for i in range(4):
    rc.polish(**_mol(params, f'm/{i}/ccs'))
  time.sleep(0.3)  # let a probe refresh cached replica counters
  m = rc.metricz()
  assert m['counters']['n_requests'] == 4
  assert m['latency']['model']['count'] == 4
  assert m['latency']['model']['p50'] is not None
  assert m['latency']['model']['p99'] is not None
  assert {r['tier'] for r in m['replicas']} == {MODEL_TIER}
  assert m['fleet_counters'].get('n_requests', 0) == 4
  for r in m['replicas']:
    assert r['in_flight'] == 0
    assert r['n_routed'] == r['n_ok']


def test_router_and_worker_prom_endpoints(fleet, params):
  """All three tiers speak ?format=prom with tier-labeled dctpu_
  metrics (the replica's is covered in test_serve.py)."""
  import urllib.request

  f = fleet(n_replicas=1, n_workers=1)
  rc = f.client()
  assert rc.wait_ready(10)
  rc.polish(**_mol(params, 'm/1/ccs'))
  with urllib.request.urlopen(
      f'http://127.0.0.1:{f.port}/metricz?format=prom', timeout=10) as r:
    assert r.headers.get('Content-Type', '').startswith('text/plain')
    router_text = r.read().decode()
  assert 'dctpu_n_requests{tier="router"} 1' in router_text
  wport = f.workers[0][2]
  with urllib.request.urlopen(
      f'http://127.0.0.1:{wport}/metricz?format=prom', timeout=10) as r:
    worker_text = r.read().decode()
  assert 'tier="featurize"' in worker_text
  assert 'dctpu_' in worker_text


def test_trace_spans_connect_across_tiers(fleet, params, synthetic_bams,
                                          monkeypatch, tmp_path):
  """One bam/1 request leaves a connected trace: the router-minted (or
  client-supplied) trace id appears on the route, featurize, and
  serve_request spans in the shared trace file."""
  from deepconsensus_tpu import obs as obs_lib
  from deepconsensus_tpu.obs import summarize as summarize_lib

  trace_path = str(tmp_path / 'fleet_trace.jsonl')
  monkeypatch.setenv(obs_lib.trace.ENV_TRACE, trace_path)
  try:
    f = fleet(n_replicas=1, n_workers=1)
    rc = f.client()
    assert rc.wait_ready(10)
    sub_path, ccs_path = synthetic_bams(n_zmws=1, n_subreads=3,
                                        seq_len=120)
    with open(sub_path, 'rb') as fh:
      subreads_bam = fh.read()
    with open(ccs_path, 'rb') as fh:
      ccs_bam = fh.read()
    got = rc.polish_bam(subreads_bam, ccs_bam, name='z/1',
                        trace_id='c0ffeec0ffee0001')
    assert got['status'] == 'ok'
  finally:
    obs_lib.trace.configure(None)
  events = summarize_lib.load_trace(trace_path)
  mine = [e for e in events if e.get('ph') == 'X'
          and e.get('args', {}).get('trace_id') == 'c0ffeec0ffee0001']
  names = {e['name'] for e in mine}
  assert 'route' in names            # router leg
  assert 'featurize' in names        # featurize-worker leg
  assert 'serve_request' in names    # model-replica leg
  groups = summarize_lib.trace_groups(events)
  assert groups['c0ffeec0ffee0001']['n_spans'] >= 3


def test_featurize_worker_rejects_multi_molecule_and_garbage(
    params, synthetic_bams):
  svc = FeaturizeService(FeaturizeWorkerOptions(
      max_passes=params.max_passes, max_length=params.max_length))
  sub_path, ccs_path = synthetic_bams(n_zmws=2, n_subreads=3,
                                      seq_len=120)
  with open(sub_path, 'rb') as fh:
    subreads_bam = fh.read()
  with open(ccs_path, 'rb') as fh:
    ccs_bam = fh.read()
  with pytest.raises(shared_faults.BadRequestError,
                     match='one request per ZMW'):
    svc.featurize(protocol.encode_bam_request(subreads_bam, ccs_bam))
  with pytest.raises(shared_faults.BadRequestError):
    svc.featurize(protocol.encode_bam_request(b'garbage', b'junk'))
  assert svc.stats()['counters']['n_bad_requests'] == 2


def test_router_class_headers_histograms_and_validation(fleet, params):
  """End-to-end QoS plumbing: the client's class/client headers reach
  admission, per-class latency histograms land in /metricz next to the
  qos policy view, and a malformed class is a typed 400."""
  f = fleet(n_replicas=1, client_quota=3,
            class_weights={'interactive': 4.0, 'bulk': 1.0})
  rc = f.client()
  assert rc.wait_ready(10)
  bulk = ServeClient(port=f.port, timeout=30, klass='bulk',
                     client='tenant-a')
  assert bulk.polish(**_mol(params, 'q/1/ccs'))['status'] == 'ok'
  # An unlabeled request is charged to the default class.
  assert rc.polish(**_mol(params, 'q/2/ccs'))['status'] == 'ok'
  m = rc.metricz()
  assert m['class_latency']['bulk']['count'] == 1
  assert m['class_latency']['bulk']['p99'] is not None
  assert m['class_latency']['interactive']['count'] == 1
  qos = m['qos']
  assert qos['client_quota'] == 3
  assert qos['default_class'] == 'interactive'
  assert qos['class_weights'] == {'interactive': 4.0, 'bulk': 1.0}
  assert qos['class_in_flight'] == {}  # everything released
  assert m['counters']['n_quota_rejected'] == 0
  # A class value outside [a-z0-9_-]{1,32} is a typed 400, counted.
  bad = ServeClient(port=f.port, timeout=30, klass='NOT A CLASS')
  with pytest.raises(ServeClientError) as e:
    bad.polish(**_mol(params, 'q/3/ccs'))
  assert e.value.status == 400
  assert rc.metricz()['counters']['n_bad_requests'] == 1


def test_preemption_notice_drains_replica_and_exits_clean(
    params, monkeypatch):
  """The env-armed preemption notice (DCTPU_FAULT_PREEMPT_AT_S) flips
  a serving replica into the normal drain path: serve_main returns
  with preempted=True, drained=True — zero accepted requests lost."""
  monkeypatch.setenv(shared_faults.ENV_PREEMPT_AT_S, '0.8')
  runner, options = _stub_runner(params)
  result = {}
  ready = {}
  t = threading.Thread(
      target=lambda: result.update(server_lib.serve_main(
          runner, options, ServeOptions(io_timeout_s=5.0),
          port=0, ready_fn=ready.update)),
      daemon=True)
  t.start()
  deadline = time.monotonic() + 30
  while 'port' not in ready and time.monotonic() < deadline:
    time.sleep(0.01)
  assert 'port' in ready
  # The ready line says where start-up went, beside warmup_s.
  assert ready['warmup_s'] >= 0
  assert set(ready['startup']) == {
      'import_s', 'checkpoint_s', 'weights_s', 'jit_trace_s',
      'jit_lower_s', 'xla_compile_s', 'n_xla_compiles', 'n_xla_cache_hits'}
  assert ready['startup']['import_s'] > 0
  # The replica serves normally until the notice lands.
  client = ServeClient(port=ready['port'], timeout=10)
  assert client.polish(**_mol(params, 'p/1/ccs'))['status'] == 'ok'
  t.join(timeout=60)
  assert not t.is_alive(), 'serve_main never exited after the notice'
  assert result['preempted'] is True
  assert result['drained'] is True


# ----------------------------------------------------------------------
# Autoscaler control law (scripted signals, no subprocesses)


def _scaler_stats(replica_states, p99=None, queue_depth=0):
  """A router /metricz-shaped dict: replica_states is {url: state}."""
  return {
      'replicas': [
          {'url': url, 'tier': MODEL_TIER, 'state': state,
           'queue_depth': queue_depth}
          for url, state in replica_states.items()
      ],
      'class_latency': {
          'interactive': {'p50': p99, 'p99': p99,
                          'count': 0 if p99 is None else 8},
      },
      'latency': {},
  }


class _ScalerHarness:
  """Injected transports for Autoscaler: a mutable stats feed plus
  recording spawn/drain fakes."""

  def __init__(self, **options):
    self.feed = [_scaler_stats({})]
    self.spawned = []
    self.drained = []
    self._n = 0
    self.scaler = Autoscaler(
        AutoscalerOptions(**options), self.fetch, self.spawn,
        self.drained.append)

  def fetch(self):
    stats = self.feed[-1]
    if isinstance(stats, Exception):
      raise stats
    return stats

  def spawn(self):
    url = f'10.0.0.{self._n}:1'
    self._n += 1
    self.spawned.append(url)
    return url


def test_autoscaler_scales_out_on_slo_breach_and_in_when_cold():
  h = _ScalerHarness(min_replicas=1, max_replicas=3, target_p99_s=1.0,
                     target_queue_depth=4.0, scale_out_cooldown_s=0.0,
                     scale_in_cooldown_s=0.0)
  # p99 over target: +1 replica, spawned immediately (deficit fill).
  h.feed.append(_scaler_stats({'op:1': ReplicaState.READY}, p99=9.0))
  d = h.scaler.tick()
  assert d['action'] == 'scale_out'
  assert h.scaler.target == 2
  assert d['spawned'] == h.spawned[:1]
  # Queue depth alone also trips the breach.
  h.feed.append(_scaler_stats(
      {'op:1': ReplicaState.READY, h.spawned[0]: ReplicaState.READY},
      p99=0.1, queue_depth=50))
  assert h.scaler.tick()['action'] == 'scale_out'
  assert h.scaler.target == 3
  # At max_replicas a breach holds instead of growing without bound.
  h.feed.append(_scaler_stats(
      {'op:1': ReplicaState.READY, h.spawned[0]: ReplicaState.READY,
       h.spawned[1]: ReplicaState.READY}, p99=9.0))
  assert h.scaler.tick()['action'] == 'hold'
  assert h.scaler.target == 3
  # Cold (both signals far under target): scale in drains the NEWEST
  # managed replica — never the operator-started base replica.
  h.feed.append(_scaler_stats(
      {'op:1': ReplicaState.READY, h.spawned[0]: ReplicaState.READY,
       h.spawned[1]: ReplicaState.READY}, p99=0.01))
  d = h.scaler.tick()
  assert d['action'] == 'scale_in'
  assert d['drained'] == h.spawned[1]
  h.feed.append(_scaler_stats(
      {'op:1': ReplicaState.READY, h.spawned[0]: ReplicaState.READY},
      p99=0.01))
  assert h.scaler.tick()['drained'] == h.spawned[0]
  assert h.drained == [h.spawned[1], h.spawned[0]]
  # At min_replicas cold holds: the floor is never drained.
  h.feed.append(_scaler_stats({'op:1': ReplicaState.READY}, p99=0.01))
  assert h.scaler.tick()['action'] == 'hold'
  assert h.scaler.target == 1
  assert 'op:1' not in h.drained
  counters = h.scaler.stats()['counters']
  assert counters['n_scale_out'] == 2
  assert counters['n_scale_in'] == 2
  assert counters['n_spawned'] == 2
  assert counters['n_drained'] == 2


def test_autoscaler_replaces_preempted_capacity_and_survives_polls():
  h = _ScalerHarness(min_replicas=2, max_replicas=4,
                     scale_out_cooldown_s=0.0, scale_in_cooldown_s=0.0)
  # Steady state at target: hold.
  h.feed.append(_scaler_stats(
      {'a:1': ReplicaState.READY, 'b:1': ReplicaState.READY}, p99=0.1))
  assert h.scaler.tick()['action'] == 'hold'
  assert not h.spawned
  # b:1 takes a preemption notice -> DRAINING: it leaves the live set
  # and the deficit is respawned the same tick.
  h.feed.append(_scaler_stats(
      {'a:1': ReplicaState.READY, 'b:1': ReplicaState.DRAINING},
      p99=0.1))
  d = h.scaler.tick()
  assert d['action'] == 'replace'
  assert len(h.spawned) == 1
  assert h.scaler.stats()['counters']['n_replaced'] == 1
  # A router poll failure skips the tick without killing the loop.
  h.feed.append(OSError('router down'))
  d = h.scaler.tick()
  assert d['action'] == 'poll_error'
  assert h.scaler.stats()['counters']['n_poll_errors'] == 1
  assert h.scaler.target == 2
  # Shutdown with drain_managed drains only the autoscaler's spawns.
  h.feed.append(_scaler_stats(
      {'a:1': ReplicaState.READY, h.spawned[0]: ReplicaState.READY},
      p99=0.1))
  h.scaler.tick()
  managed = h.scaler.shutdown(drain_managed=True)
  assert managed == h.spawned
  assert h.drained == h.spawned
  assert 'a:1' not in h.drained


def test_autoscaler_cooldown_gates_scale_out_and_spawn_failures_count():
  h = _ScalerHarness(min_replicas=1, max_replicas=4, target_p99_s=1.0,
                     scale_out_cooldown_s=3600.0)
  hot = _scaler_stats({'op:1': ReplicaState.READY}, p99=9.0)
  h.feed.append(hot)
  assert h.scaler.tick()['action'] == 'scale_out'
  # Still hot, but inside the cooldown: the breach does not compound.
  h.feed.append(_scaler_stats(
      {'op:1': ReplicaState.READY, h.spawned[0]: ReplicaState.READY},
      p99=9.0))
  assert h.scaler.tick()['action'] == 'hold'
  assert h.scaler.target == 2
  assert h.scaler.stats()['counters']['n_scale_out'] == 1
  # A failed spawn is counted and retried next tick; the deficit (and
  # the target) persist.
  h.scaler.spawn_fn = lambda: (_ for _ in ()).throw(OSError('no slots'))
  h.feed.append(_scaler_stats({'op:1': ReplicaState.READY}, p99=0.1))
  h.scaler.tick()
  assert h.scaler.stats()['counters']['n_spawn_errors'] == 1
  assert h.scaler.target == 2
  h.scaler.spawn_fn = h.spawn
  h.scaler.tick()
  assert len(h.spawned) == 2
