"""Pod-scale training tests: partition rules, the pjit train step,
prefetch-overlapped transfers, and the training degradation ladder.

All multichip drills run over the 8 forced host-platform CPU devices
from conftest.py; real-chip scaling is not measured (no benchmark
cell trains yet; chip_smoke.py --chips 4 proves dp x tp trains on
four chips).

Cross-dp identity, precisely: at equal global batch and seed the
dp=8 run consumes byte-identical batches in the same order as dp=1
(the data pipeline is host-side and mesh-independent), so the loss
curves agree to all-reduce reduction order — empirically ~1e-6
relative on CPU, NOT bitwise, because sharding the batch changes the
summation order of the cross-device mean. The tests below pin that
contract two ways: np.allclose at rtol=1e-4 on the raw curves, and
equality of the 1e-4-quantized digest of the curve per dp point.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu.models import checkpoints as checkpoints_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import flywheel as flywheel_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.models import train as train_lib
from deepconsensus_tpu.parallel import mesh as mesh_lib
from deepconsensus_tpu.parallel import partition_rules
from jax.sharding import PartitionSpec as P

pytestmark = [pytest.mark.multichip, pytest.mark.resilience]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
  sys.path.insert(0, _REPO_ROOT)

MAX_PASSES = 5
MAX_LENGTH = 20
GLOBAL_BATCH = 16
N_EXAMPLES = 96  # 6 steps per epoch at the fixed global batch


@pytest.fixture
def fresh_faults(monkeypatch):
  """Fault hooks are consume-once per process; isolate each test."""
  monkeypatch.setattr(faults_lib, '_fired', set())


@pytest.fixture(scope='module')
def shards(tmp_path_factory):
  from scripts import inject_faults

  d = tmp_path_factory.mktemp('synth_shards')
  return inject_faults.write_synthetic_tfrecords(
      str(d), n_shards=4, n_examples=N_EXAMPLES,
      max_passes=MAX_PASSES, max_length=MAX_LENGTH,
  )


def tiny_params(**overrides):
  params = config_lib.get_config('fc+test')
  with params.unlocked():
    params.max_passes = MAX_PASSES
    params.max_length = MAX_LENGTH
  config_lib.finalize_params(params)
  with params.unlocked():
    params.dtype = 'float32'
    params.batch_size = GLOBAL_BATCH
    params.warmup_steps = 2
    params.log_every_n_steps = 1
    params.seed = 7
    for k, v in overrides.items():
      setattr(params, k, v)
  return params


def run_tiny_training(shards, out_dir, dp, **overrides):
  params = tiny_params(**overrides)
  mesh = mesh_lib.make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
  train_lib.run_training(
      params=params, out_dir=out_dir,
      train_patterns=list(shards), eval_patterns=list(shards),
      num_epochs=1, mesh=mesh, eval_every=1_000_000,
  )
  return out_dir


def metrics_entries(out_dir, split=None):
  entries = []
  with open(os.path.join(out_dir, 'metrics.jsonl')) as f:
    for line in f:
      e = json.loads(line)
      if split is None or e.get('split') == split:
        entries.append(e)
  return entries


def train_losses(out_dir):
  return [e['loss'] for e in metrics_entries(out_dir, 'train')]


def curve_digest_1e4(losses):
  import hashlib

  return hashlib.sha256(
      json.dumps([round(l, 4) for l in losses]).encode()
  ).hexdigest()[:16]


def final_checkpoint_params(out_dir):
  latest = checkpoints_lib.latest_valid_checkpoint(
      os.path.join(out_dir, 'checkpoints'))
  assert latest is not None
  return checkpoints_lib.load_params(latest)


@pytest.fixture(scope='module')
def dp8_run(shards, tmp_path_factory):
  """The undisturbed dp=8 baseline shared by the identity, overlap,
  and degradation tests."""
  out = str(tmp_path_factory.mktemp('dp8_baseline'))
  return run_tiny_training(shards, out, dp=8)


# ----------------------------------------------------------------------
# Partition rules: the declarative table every pjit entry point shares


def transformer_test_params():
  params = config_lib.get_config('transformer_learn_values+test')
  with params.unlocked():
    params.max_passes = MAX_PASSES
    params.max_length = MAX_LENGTH
  config_lib.finalize_params(params)
  return params


def test_partition_rules_cover_every_leaf_exactly_once():
  """Round-trip over the REAL transformer tree: explain_matches maps
  every leaf to exactly one rule, attention/ffn leaves to their
  dedicated (non-catch-all) rules, scalars to replication."""
  params = transformer_test_params()
  model = model_lib.get_model(params)
  rows = np.zeros(
      (1, params.total_rows, params.max_length, 1), np.float32)
  variables = model.init(jax.random.PRNGKey(0), rows)

  explained = partition_rules.explain_matches(
      partition_rules.DEFAULT_RULES, variables['params'])
  paths = {'/'.join(str(getattr(k, 'key', k)) for k in p)
           for p, _ in jax.tree_util.tree_flatten_with_path(
               variables['params'])[0]}
  # Exactly once: explain_matches is a dict keyed by leaf path, and it
  # covers the flattened tree — no leaf missing, none matched twice.
  assert set(explained) == paths

  scalar_paths = {
      '/'.join(str(getattr(k, 'key', k)) for k in p)
      for p, leaf in jax.tree_util.tree_flatten_with_path(
          variables['params'])[0]
      if np.ndim(leaf) == 0
  }
  catch_all = len(partition_rules.DEFAULT_RULES) - 1
  for path, idx in explained.items():
    last = path.rsplit('/', 1)[-1]
    if path in scalar_paths:
      # Scalars (the attention-wrapper alpha gates) replicate without
      # consulting the rules; explain_matches marks them -1.
      assert idx == -1, (path, idx)
    elif '/self_attention' in path and last == 'kernel':
      assert idx in (0, 1), (path, idx)
    elif '/ffn_' in path and (path.endswith('filter_layer/kernel')
                              or path.endswith('filter_layer/bias')
                              or path.endswith('output_layer/kernel')):
      assert idx in (2, 3, 4), (path, idx)
    else:
      assert idx == catch_all, (path, idx)

  # Under a tp=2 mesh the rules must actually shard the model axis.
  mesh = mesh_lib.make_mesh(dp=4, tp=2, devices=jax.devices()[:8])
  shardings = partition_rules.tree_shardings(mesh, variables['params'])
  n_model_sharded = sum(
      any(entry == mesh_lib.MODEL_AXIS
          or (isinstance(entry, tuple) and mesh_lib.MODEL_AXIS in entry)
          for entry in s.spec)
      for s in jax.tree_util.tree_leaves(shardings))
  assert n_model_sharded >= 36  # 4 kernels + 1 bias per layer, 6+ layers


def test_unmatched_leaf_raises_typed_error():
  rules_without_catchall = partition_rules.DEFAULT_RULES[:-1]
  tree = {'oddball': {'kernel': np.zeros((4, 4), np.float32)}}
  with pytest.raises(partition_rules.PartitionRuleError) as ei:
    partition_rules.match_partition_rules(rules_without_catchall, tree)
  assert 'oddball/kernel' in str(ei.value)
  # The CLI maps ValueError to exit 2; the typed error must stay one.
  assert isinstance(ei.value, ValueError)


def test_first_matching_rule_wins_and_scalars_replicate():
  rules = (
      (r'ffn_\d+/filter_layer/kernel', P(None, mesh_lib.MODEL_AXIS)),
      (r'ffn_\d+/.*', P()),
      (r'.*', P()),
  )
  tree = {
      'ffn_0': {'filter_layer': {'kernel': np.zeros((2, 4), np.float32),
                                 'bias': np.zeros((4,), np.float32)}},
      'count': np.float32(0),  # scalar: replicated regardless of rules
  }
  specs = partition_rules.match_partition_rules(rules, tree)
  assert specs['ffn_0']['filter_layer']['kernel'] == P(
      None, mesh_lib.MODEL_AXIS)
  assert specs['ffn_0']['filter_layer']['bias'] == P()
  assert specs['count'] == P()
  explained = partition_rules.explain_matches(rules, tree)
  assert explained['ffn_0/filter_layer/kernel'] == 0
  assert explained['ffn_0/filter_layer/bias'] == 1
  assert explained['count'] == -1


def test_optimizer_moments_shard_like_their_params(tmp_path):
  """The LAMB moment leaf paths CONTAIN the param paths, so one rule
  table shards optimizer state exactly like the parameters."""
  params = transformer_test_params()
  with params.unlocked():
    params.dtype = 'float32'
    params.batch_size = 8
  mesh = mesh_lib.make_mesh(dp=4, tp=2, devices=jax.devices()[:8])
  trainer = train_lib.Trainer(
      params=params, out_dir=str(tmp_path), mesh=mesh)
  state = trainer.init_state(steps_total=10)
  shardings = trainer.state_shardings(state)
  param_specs = jax.tree_util.tree_flatten_with_path(
      shardings.params)[0]
  sharded_params = {
      '/'.join(str(getattr(k, 'key', k)) for k in p)
      for p, s in param_specs if s.spec != P()
  }
  assert sharded_params  # tp=2 shards the attention/ffn kernels
  moment_specs = jax.tree_util.tree_flatten_with_path(
      shardings.opt_state)[0]
  moment_hits = set()
  for path, spec in moment_specs:
    joined = '/'.join(str(getattr(k, 'key', k)) for k in path)
    for pp in sharded_params:
      if pp in joined:
        # Moment mirrors its parameter: same spec, not replicated.
        assert spec.spec != P(), (joined, spec)
        moment_hits.add(pp)
  # Every sharded param has at least one sharded optimizer moment.
  assert moment_hits == sharded_params


# ----------------------------------------------------------------------
# Cross-dp loss-curve identity + prefetch overlap counters


@pytest.mark.parametrize('width', [None, 4], ids=['full', 'banded'])
def test_pallas_loss_runs_per_shard_on_a_dp_tp_mesh(width):
  """On a TPU the loss takes the Pallas wavefront by itself, and XLA
  cannot partition a Mosaic kernel: on a multi-device mesh the scorers
  run under a shard_map over the data axis. Loss and gradient must
  match the scan DP on unsharded inputs."""
  import jax.numpy as jnp

  from deepconsensus_tpu.models import losses as losses_lib

  mesh = mesh_lib.make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
  rng = np.random.default_rng(0)
  y_true = jnp.asarray(rng.integers(0, 5, size=(8, 20)), jnp.int32)
  y_pred = jax.nn.softmax(
      jnp.asarray(rng.normal(size=(8, 20, 5)), jnp.float32))
  scan = losses_lib.AlignmentLoss(10.0, 0.1, width=width)
  pallas = losses_lib.AlignmentLoss(10.0, 0.1, width=width, use_pallas=True,
                                    mesh=mesh)
  batch_sh = mesh_lib.batch_sharding(mesh)
  want, want_grad = jax.jit(jax.value_and_grad(scan, argnums=1))(
      y_true, y_pred)
  got, got_grad = jax.jit(jax.value_and_grad(pallas, argnums=1))(
      jax.device_put(y_true, batch_sh), jax.device_put(y_pred, batch_sh))
  np.testing.assert_allclose(got, want, rtol=1e-5)
  np.testing.assert_allclose(got_grad, want_grad, rtol=1e-4, atol=1e-4)


def test_dp8_loss_curve_matches_single_device(shards, dp8_run, tmp_path):
  """Equal global batch + equal seed => equal curve across dp, up to
  all-reduce reduction order (see module docstring)."""
  dp1 = run_tiny_training(shards, str(tmp_path / 'dp1'), dp=1)
  losses1 = train_losses(dp1)
  losses8 = train_losses(dp8_run)
  assert len(losses1) == len(losses8) == N_EXAMPLES // GLOBAL_BATCH
  np.testing.assert_allclose(losses1, losses8, rtol=1e-4)
  assert curve_digest_1e4(losses1) == curve_digest_1e4(losses8)
  # The curve must also be a real training signal, not a constant.
  assert losses1[-1] < losses1[0]


def test_prefetch_overlap_counters(dp8_run):
  """A clean N-step run launches N sharded transfers and overlaps all
  but the first under the previous step's compute: the sidecar must
  report exactly (N-1)/N."""
  faults = metrics_entries(dp8_run, 'faults')[-1]
  n_steps = N_EXAMPLES // GLOBAL_BATCH
  assert faults['n_batch_launches'] == n_steps
  assert faults['n_batches_prefetched'] == n_steps - 1
  assert faults['train_transfer_overlap_fraction'] == pytest.approx(
      (n_steps - 1) / n_steps, abs=1e-3)
  assert faults.get('n_batches_replaced', 0) == 0
  assert 'n_train_degraded' not in faults


# ----------------------------------------------------------------------
# Training degradation ladder: mid-training device loss, dp 8 -> 4


def test_device_lost_mid_training_degrades_dp8_to_dp4(
    shards, dp8_run, tmp_path, fresh_faults, monkeypatch):
  """DCTPU_FAULT_DEVICE_LOST_AT_STEP fires a permanent DeviceLostError
  mid-run; --on_device_error=degrade rebuilds the mesh at dp=4,
  carries the live state over IN MEMORY (no checkpoint rollback: the
  state survived the device), re-places the failed batch, and
  completes every step. Final weights must match the undisturbed dp=8
  run to reduction-order tolerance — the ladder changes where the
  math runs, not what it computes."""
  monkeypatch.setenv(faults_lib.ENV_DEVICE_LOST_AT_STEP, '3')
  out = run_tiny_training(
      shards, str(tmp_path / 'degraded'), dp=8,
      on_device_error='degrade')

  n_steps = N_EXAMPLES // GLOBAL_BATCH
  losses = train_losses(out)
  assert len(losses) == n_steps  # the failed step re-ran, none lost
  assert np.isfinite(losses).all()

  faults = metrics_entries(out, 'faults')[-1]
  assert faults['n_train_degraded'] == 1.0
  # The failed batch was re-placed directly on the rebuilt mesh.
  assert faults['n_batches_replaced'] >= 1
  # No NaN-sentinel rollback happened: degradation is not a rollback.
  assert faults.get('n_nan_rollbacks', 0) == 0

  # In-memory carry-over: the degraded curve tracks the undisturbed
  # dp=8 baseline, including the steps AFTER the device loss.
  baseline = train_losses(dp8_run)
  np.testing.assert_allclose(losses, baseline, rtol=1e-4)
  final = final_checkpoint_params(out)
  final_base = final_checkpoint_params(dp8_run)
  jax.tree_util.tree_map_with_path(
      lambda p, a, b: np.testing.assert_allclose(
          np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
          err_msg=str(p)),
      final, final_base)


def test_degrade_refused_at_dp1_reraises(shards, tmp_path, fresh_faults,
                                         monkeypatch):
  """dp=1 has no smaller mesh: the ladder refuses and the typed
  DeviceLostError surfaces instead of an infinite retry loop."""
  monkeypatch.setenv(faults_lib.ENV_DEVICE_LOST_AT_STEP, '2')
  with pytest.raises(faults_lib.DeviceLostError):
    run_tiny_training(shards, str(tmp_path / 'dp1'), dp=1,
                      on_device_error='degrade')


def test_device_lost_without_degrade_fails_fast(shards, tmp_path,
                                                fresh_faults,
                                                monkeypatch):
  monkeypatch.setenv(faults_lib.ENV_DEVICE_LOST_AT_STEP, '2')
  with pytest.raises(faults_lib.DeviceLostError):
    run_tiny_training(shards, str(tmp_path / 'fail'), dp=8)


# ----------------------------------------------------------------------
# Guard rails: bucket-set validation + flywheel gate enforcement


def test_invalid_bucket_sets_raise_typed(tmp_path):
  """Genuinely invalid bucket sets stay a typed config-time fault.
  Valid multi-bucket sets train (tests/test_longwin_training.py); what
  must still be refused is a bucket list that cannot work: widths out
  of order, or a model family whose parameter shapes depend on the
  window width."""
  # Non-ascending widths are operator error at config time.
  params = tiny_params()
  with params.unlocked():
    params.window_buckets = (40, 20)
  with pytest.raises(faults_lib.WindowBucketError):
    train_lib.Trainer(params=params, out_dir=str(tmp_path / 'order'),
                      mesh=None)
  # The FC head sizes its output Dense by max_length: one param tree
  # cannot serve two widths, so fc + multi-bucket is refused with the
  # remedy (use a transformer config).
  params = tiny_params()
  with params.unlocked():
    params.window_buckets = (20, 40)
  with pytest.raises(faults_lib.WindowBucketError) as ei:
    train_lib.Trainer(params=params, out_dir=str(tmp_path / 'fc'),
                      mesh=None)
  msg = str(ei.value)
  assert 'window_buckets' in msg and 'transformer' in msg
  # ValueError subclass: `dctpu train` maps it to exit code 2.
  assert isinstance(ei.value, ValueError)


def test_flywheel_gate_failure_is_typed(shards, tmp_path):
  """An impossible bf16 threshold must fail the gate and _enforce must
  raise the typed FlywheelGateError carrying the measurement."""
  params = tiny_params()
  trainer = train_lib.Trainer(
      params=params, out_dir=str(tmp_path), mesh=None)
  state = trainer.init_state(steps_total=4)
  variables = {'params': jax.device_get(state.params)}
  gate = flywheel_lib.bf16_qv_gate(
      params, variables, list(shards), threshold=-1, max_batches=1)
  assert not gate['passed']
  assert gate['measured'] >= 0
  with pytest.raises(faults_lib.FlywheelGateError) as ei:
    flywheel_lib._enforce([gate])
  err = ei.value
  assert err.gate == 'bf16_max_qv_delta'
  assert err.measured == gate['measured']
  assert err.threshold == -1
  # Sanity: a sane threshold passes the same measurement.
  ok = flywheel_lib.bf16_qv_gate(
      params, variables, list(shards),
      threshold=flywheel_lib.BF16_QV_GATE, max_batches=1)
  assert ok['passed']


def test_flywheel_manifest_written_atomically(tmp_path):
  manifest = {'stages': {}, 'gates': [
      {'name': 'g', 'measured': 1, 'threshold': 0, 'passed': False}],
      'ok': False}
  path = flywheel_lib._write_manifest(str(tmp_path), manifest)
  assert os.path.basename(path) == flywheel_lib.MANIFEST_NAME
  assert not os.path.exists(path + '.tmp')
  assert json.load(open(path)) == manifest
