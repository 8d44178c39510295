"""Bucketed multi-width training and the L=500 long-insert path.

Covers the training side of the window-bucket system (the inference
side lives in test_ragged_engine.py / test_inference_buckets.py):

* triage + per-bucket batches in both loaders (DatasetIterator epochs
  and the StreamingDataset reservoir), including the padding counters
  and the starvation-promotion flush,
* compile-once-per-bucket: over a mixed-width stream the jitted train
  step traces exactly len(window_buckets) times (no mid-run
  recompiles),
* dp8-vs-dp1 loss-curve identity for a two-bucket config at equal
  global batch (the test_train_parallel.py contract, bucketed),
* the blockwise ring-attention forward for windows past the fused
  kernel's VMEM limit: numerical parity with full_attention_reference
  at L=500 (forward AND gradients), and proof that a long-window
  training forward routes through it,
* the overflow-width quarantine (--on_shard_error=skip +
  n_width_rejected) vs the typed WindowBucketError under 'fail'.

The @slow drills (an L=500 run_training cycle, the L=100/200 flywheel
producing a servable artifact, the student-vs-baseline identity
record) run under `./run_all_tests.sh longwin`.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import data as data_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.models import train as train_lib
from deepconsensus_tpu.parallel import mesh as mesh_lib
from deepconsensus_tpu.parallel import ring_attention as ring_lib

pytestmark = [pytest.mark.multichip]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
  sys.path.insert(0, _REPO_ROOT)

MAX_PASSES = 5
GLOBAL_BATCH = 16
N_PER_WIDTH = 48  # 3 batches per bucket at the fixed global batch


@pytest.fixture(scope='module')
def mixed_shards(tmp_path_factory):
  """Two widths, 20 and 40: separate shard sets so tests can stream
  either width alone or both together."""
  from scripts import inject_faults

  d = tmp_path_factory.mktemp('mixed_shards')
  w20 = inject_faults.write_synthetic_tfrecords(
      str(d / 'w20'), n_shards=2, n_examples=N_PER_WIDTH,
      max_passes=MAX_PASSES, max_length=20)
  w40 = inject_faults.write_synthetic_tfrecords(
      str(d / 'w40'), n_shards=2, n_examples=N_PER_WIDTH,
      max_passes=MAX_PASSES, max_length=40, seed=5)
  return w20, w40


def bucketed_params(max_length=20, **overrides):
  """Tiny transformer (the length-agnostic family buckets require)."""
  params = config_lib.get_config('transformer_learn_values+test')
  with params.unlocked():
    params.max_passes = MAX_PASSES
    params.max_length = max_length
  config_lib.finalize_params(params)
  with params.unlocked():
    params.dtype = 'float32'
    params.batch_size = GLOBAL_BATCH
    params.num_hidden_layers = 1
    params.filter_size = 32
    params.warmup_steps = 2
    params.log_every_n_steps = 1
    params.seed = 7
    params.window_buckets = (max_length, 2 * max_length)
    for k, v in overrides.items():
      setattr(params, k, v)
  return params


def run_bucketed_training(mixed_shards, out_dir, dp, **overrides):
  w20, w40 = mixed_shards
  params = bucketed_params(**overrides)
  mesh = mesh_lib.make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp])
  train_lib.run_training(
      params=params, out_dir=out_dir,
      train_patterns=list(w20) + list(w40), eval_patterns=list(w20),
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


def curve_digest(losses, decimals):
  """The quantized curve digest (same construction as
  test_train_parallel.py's curve_digest_1e4, with the quantization
  step explicit)."""
  import hashlib

  return hashlib.sha256(
      json.dumps([round(l, decimals) for l in losses]).encode()
  ).hexdigest()[:16]


@pytest.fixture(scope='module')
def dp1_run(mixed_shards, tmp_path_factory):
  out = str(tmp_path_factory.mktemp('buck_dp1') / 'run')
  return run_bucketed_training(mixed_shards, out, dp=1)


# ----------------------------------------------------------------------
# Loaders


def test_dataset_iterator_groups_by_bucket(mixed_shards):
  w20, w40 = mixed_shards
  params = bucketed_params()
  ds = data_lib.DatasetIterator(
      patterns=list(w20) + list(w40), params=params,
      batch_size=GLOBAL_BATCH, seed=3)
  assert ds.window_buckets_present == (20, 40)
  assert len(ds) == 2 * N_PER_WIDTH
  widths_seen = set()
  for batch in ds.epoch():
    width = batch['rows'].shape[2]
    widths_seen.add(width)
    # Width-pure batches: label length matches the bucket geometry.
    assert batch['label'].shape == (GLOBAL_BATCH, width)
  assert widths_seen == {20, 40}
  assert ds.counters['n_train_batches_by_bucket_20'] == 3
  assert ds.counters['n_train_batches_by_bucket_40'] == 3
  # On-bucket corpus: no padding burned.
  assert ds.counters['n_train_padded_positions'] == 0
  assert ds.counters['n_train_window_positions'] == (
      3 * GLOBAL_BATCH * 20 + 3 * GLOBAL_BATCH * 40)


def test_narrow_windows_pad_into_their_bucket(mixed_shards):
  """A width-20 window under buckets (40,) pads to 40 (zero label/rows
  in the tail, which AlignmentLoss ignores as gap) and the padding
  counters record the burn."""
  w20, _ = mixed_shards
  params = bucketed_params(max_length=40, window_buckets=(40,))
  ds = data_lib.DatasetIterator(
      patterns=list(w20), params=params, batch_size=8, seed=3)
  batch = next(iter(ds.epoch()))
  assert batch['rows'].shape[2] == 40
  assert batch['label'].shape == (8, 40)
  np.testing.assert_array_equal(batch['rows'][:, :, 20:, :], 0)
  np.testing.assert_array_equal(batch['label'][:, 20:], 0)
  assert ds.counters['n_train_padded_positions'] == 8 * 20
  assert ds.counters['n_train_window_positions'] == 8 * 40


def test_streaming_overflow_fail_names_window(mixed_shards):
  """Under the default policy an overflow width is a typed fault."""
  _, w40 = mixed_shards
  params = bucketed_params(window_buckets=(20,))
  ds = data_lib.StreamingDataset(
      patterns=list(w40), params=params, batch_size=4, buffer_size=8,
      on_shard_error='fail')
  with pytest.raises(faults_lib.WindowBucketError) as ei:
    next(iter(ds))
  msg = str(ei.value)
  assert 'width 40' in msg and 'on_shard_error=skip' in msg


def test_streaming_overflow_skip_quarantines(mixed_shards):
  """--on_shard_error=skip quarantines overflow widths (counted as
  n_width_rejected) and keeps emitting on-bucket batches."""
  w20, w40 = mixed_shards
  params = bucketed_params(window_buckets=(20,))
  ds = data_lib.StreamingDataset(
      patterns=list(w20) + list(w40), params=params, batch_size=4,
      buffer_size=8, on_shard_error='skip')
  it = iter(ds)
  # Enough batches to consume more than one full shard cycle
  # (96 on-bucket + 48 overflow windows), so the overflow shards are
  # guaranteed to have streamed past the triage.
  for _ in range(30):
    batch = next(it)
    assert batch['rows'].shape[2] == 20
  it.close()
  assert ds.counters['n_width_rejected'] > 0
  assert ds.counters['n_train_batches_by_bucket_20'] == 30


def test_streaming_starvation_flush_promotes_narrow_windows(tmp_path):
  """A rare wide width never fills a batch on its own: after
  bucket_starvation_batches clock ticks the starved bucket flushes by
  promoting narrow windows (padded up), so wide windows don't go
  stale and every batch still carries batch_size real windows."""
  from scripts import inject_faults

  many = inject_faults.write_synthetic_tfrecords(
      str(tmp_path / 'w20'), n_shards=1, n_examples=64,
      max_passes=MAX_PASSES, max_length=20)
  rare = inject_faults.write_synthetic_tfrecords(
      str(tmp_path / 'w40'), n_shards=1, n_examples=2,
      max_passes=MAX_PASSES, max_length=40, seed=5)
  params = bucketed_params()
  with params.unlocked():
    params.bucket_starvation_batches = 2
  ds = data_lib.StreamingDataset(
      patterns=list(many) + list(rare), params=params, batch_size=8,
      buffer_size=16, on_shard_error='fail')
  it = iter(ds)
  widths = [next(it)['rows'].shape[2] for _ in range(12)]
  it.close()
  assert 40 in widths, widths
  assert ds.counters['n_train_starvation_flushes'] > 0
  assert ds.counters['n_train_promoted_windows'] > 0
  # Promoted (width-20) windows padded into the 40 bucket.
  assert ds.counters['n_train_padded_positions'] > 0


# ----------------------------------------------------------------------
# Compile-once + cross-dp identity


def test_bucketed_training_compiles_once_per_bucket(dp1_run):
  faults = metrics_entries(dp1_run, 'faults')[-1]
  assert faults['n_train_forward_shapes'] == 2.0
  assert faults['n_train_batches_by_bucket_20'] == 3
  assert faults['n_train_batches_by_bucket_40'] == 3
  # On-bucket synthetic corpus: the padding fraction is exactly zero.
  assert faults['train_padding_fraction'] == 0.0
  # Six optimizer steps landed (3 per bucket).
  assert len(train_losses(dp1_run)) == 6


def test_bucketed_dp8_matches_dp1(mixed_shards, dp1_run, tmp_path):
  """Equal global batch + seed: the bucketed batch schedule is host-
  side and mesh-independent, so dp=8 consumes the identical per-bucket
  batch sequence and the loss curves agree to all-reduce reduction
  order (same contract as the fixed-shape test, see
  test_train_parallel.py module docstring)."""
  dp8 = run_bucketed_training(
      mixed_shards, str(tmp_path / 'dp8'), dp=8)
  losses1 = train_losses(dp1_run)
  losses8 = train_losses(dp8)
  assert len(losses1) == len(losses8) == 6
  np.testing.assert_allclose(losses1, losses8, rtol=1e-4)
  # The two-bucket curve's losses are O(100), so the 1e-4 ABSOLUTE
  # quantization of curve_digest_1e4 is finer than the ~1e-7-relative
  # all-reduce reduction-order noise (measured: <= 1.4e-7 rel);
  # digest at 1e-3 where the quantization cell is safely wider.
  assert curve_digest(losses1, 3) == curve_digest(losses8, 3)
  faults8 = metrics_entries(dp8, 'faults')[-1]
  assert faults8['n_train_forward_shapes'] == 2.0


# ----------------------------------------------------------------------
# The L=500 long-insert forward: blockwise ring attention


def make_qkv(b, l, h, d, seed=0):
  rng = np.random.default_rng(seed)
  mk = lambda: jnp.asarray(rng.normal(size=(b, l, h, d)).astype(np.float32))
  return mk(), mk(), mk()


def test_blockwise_ring_matches_reference_l500():
  """Forward parity at the long-insert width. Measured max abs error
  on CPU f32 is ~5e-7 (one extra renormalization per 128-block);
  atol=1e-5 matches the sharded ring-attention tests' tolerance."""
  q, k, v = make_qkv(2, 500, 2, 8, seed=0)
  want = ring_lib.full_attention_reference(q, k, v, attn_win_size=12)
  got = ring_lib.ring_attention_blockwise(q, k, v, attn_win_size=12)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             atol=1e-5)


def test_blockwise_ring_grads_match_reference_l500():
  """Gradient parity: the blockwise scan is plain differentiable ops
  (no custom VJP), so training can backprop through it. Measured max
  abs grad error ~8e-7 on CPU f32; atol=1e-5."""
  q, k, v = make_qkv(2, 500, 2, 8, seed=1)

  def loss(attn):
    def f(q, k, v):
      o = attn(q, k, v, 12)
      return jnp.sum(o * jnp.cos(o))
    return f

  g_ref = jax.grad(loss(ring_lib.full_attention_reference),
                   argnums=(0, 1, 2))(q, k, v)
  g_blk = jax.grad(loss(ring_lib.ring_attention_blockwise),
                   argnums=(0, 1, 2))(q, k, v)
  for a, b in zip(g_ref, g_blk):
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_l500_training_forward_routes_through_ring(monkeypatch):
  """A train-mode forward at the long-insert width goes through the
  blockwise ring scan (trace counter moves), produces the same values
  as the XLA einsum path, and backprops to finite grads. The fused
  Pallas hot path is structurally unreachable here: it requires
  eval-mode (not train) AND L <= its VMEM window limit (128)."""
  params = config_lib.get_config('transformer_learn_values+test')
  with params.unlocked():
    params.max_passes = MAX_PASSES
    params.max_length = config_lib.LONG_INSERT_WINDOW_LEN
  config_lib.finalize_params(params)
  with params.unlocked():
    params.dtype = 'float32'
    params.num_hidden_layers = 1
    params.filter_size = 32
    params.attention_dropout = 0.0  # ring precondition (no weights)
  model = model_lib.get_model(params)
  rows = jnp.zeros(
      (2, params.total_rows, config_lib.LONG_INSERT_WINDOW_LEN, 1))
  variables = model.init(jax.random.PRNGKey(0), rows)
  rngs = {'dropout': jax.random.PRNGKey(1)}

  before = ring_lib.n_blockwise_traces
  out_ring = model.apply(variables, rows, train=True, rngs=rngs)
  assert ring_lib.n_blockwise_traces == before + 1
  assert out_ring.shape == (2, config_lib.LONG_INSERT_WINDOW_LEN, 5)

  # Same params, ring crossover pushed out of reach -> XLA einsum path;
  # values must agree (exact attention either way).
  monkeypatch.setattr(config_lib, 'RING_ATTENTION_MIN_LEN', 10**9)
  out_xla = model.apply(variables, rows, train=True, rngs=rngs)
  monkeypatch.undo()
  np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_xla),
                             atol=1e-4)

  def train_loss(p):
    o = model.apply({'params': p['params']}, rows, train=True, rngs=rngs)
    return jnp.sum(o * o)

  grads = jax.grad(train_loss)(variables)
  flat = jax.tree_util.tree_leaves(grads)
  assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)


# ----------------------------------------------------------------------
# @slow end-to-end drills (./run_all_tests.sh longwin)


@pytest.mark.slow
def test_l500_run_training_uses_ring_and_reports_identity(
    tmp_path_factory):
  """An L=500 config trains end to end: the sidecar proves the forward
  traced through the blockwise ring scan (n_ring_attention_traces) and
  the final eval reports alignment-identity metrics for the long
  windows."""
  from scripts import inject_faults

  d = tmp_path_factory.mktemp('l500')
  shards = inject_faults.write_synthetic_tfrecords(
      str(d / 'shards'), n_shards=1, n_examples=8,
      max_passes=MAX_PASSES, max_length=500)
  params = bucketed_params(
      max_length=500, window_buckets=(500,), batch_size=4,
      attention_dropout=0.0)  # ring precondition: no attn dropout
  mesh = mesh_lib.make_mesh(dp=1, tp=1, devices=jax.devices()[:1])
  out = str(d / 'out')
  train_lib.run_training(
      params=params, out_dir=out, train_patterns=list(shards),
      eval_patterns=list(shards), num_epochs=1, mesh=mesh)
  faults = metrics_entries(out, 'faults')[-1]
  assert faults.get('n_ring_attention_traces', 0) >= 1
  assert faults['n_train_forward_shapes'] == 1.0
  evals = metrics_entries(out, 'eval')
  assert evals and 'eval/identity_pred' in evals[-1]
  assert np.isfinite(evals[-1]['eval/identity_pred'])


@pytest.mark.slow
def test_long_insert_identity_record_vs_baseline(mixed_shards, dp1_run,
                                                 tmp_path):
  """The flywheel's informational gate record: student identity vs a
  reference checkpoint on the same shards, and the typed-error branch
  when the baseline cannot consume the long windows."""
  from deepconsensus_tpu.models import checkpoints as checkpoints_lib
  from deepconsensus_tpu.models import flywheel as flywheel_lib

  w20, w40 = mixed_shards
  ckpt = checkpoints_lib.latest_valid_checkpoint(
      os.path.join(dp1_run, 'checkpoints'))
  assert ckpt is not None
  student_params = config_lib.read_params_from_json(ckpt)
  config_lib.finalize_params(student_params, is_training=False)
  variables = {'params': checkpoints_lib.load_params(ckpt)}

  # Baseline == the same checkpoint: both sides evaluate, delta == 0.
  rec = flywheel_lib.long_insert_identity_record(
      student_params, variables, ckpt, list(w20), str(tmp_path / 'a'))
  assert rec['name'] == 'long_insert_identity_vs_baseline'
  assert rec['passed'] is True
  assert rec['measured'] == pytest.approx(0.0, abs=1e-9)
  assert rec['detail']['student_identity'] == (
      rec['detail']['baseline_identity'])

  # A baseline that cannot be evaluated (missing, or its buckets don't
  # cover the long windows) records the error instead of aborting the
  # flywheel cycle: the record is informational, never a veto.
  rec2 = flywheel_lib.long_insert_identity_record(
      student_params, variables, str(tmp_path / 'missing_ckpt'),
      list(w40), str(tmp_path / 'b'))
  assert rec2['passed'] is True
  assert rec2['measured'] is None
  assert 'baseline_error' in rec2['detail']
  assert 'student_identity' in rec2['detail']


@pytest.mark.slow
def test_flywheel_bucketed_long_windows_exports_artifact(
    tmp_path_factory):
  """`dctpu flywheel --window_buckets 100,200` on mixed L=100/L=200
  shards: train -> distill -> gates -> export completes and the
  artifact serves. The distill stage IS the 'real L>100 config'
  acceptance run, at CI scale."""
  from scripts import inject_faults

  d = tmp_path_factory.mktemp('fw_longwin')
  inject_faults.write_synthetic_tfrecords(
      str(d / 'shards'), n_shards=1, n_examples=16,
      max_passes=MAX_PASSES, max_length=100)
  inject_faults.write_synthetic_tfrecords(
      str(d / 'shards2'), n_shards=1, n_examples=16,
      max_passes=MAX_PASSES, max_length=200, seed=5)
  glob_all = [os.path.join(str(d / 'shards'), 'shard-*'),
              os.path.join(str(d / 'shards2'), 'shard-*')]
  out = str(d / 'fw')
  sets = []
  for flag in ('--set', '--student_set'):
    sets += [flag, f'max_passes={MAX_PASSES}', flag, 'max_length=100',
             flag, 'num_hidden_layers=1', flag, 'filter_size=32']
  env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=_REPO_ROOT,
             XLA_FLAGS='--xla_force_host_platform_device_count=1')
  result = subprocess.run(
      [sys.executable, '-m', 'deepconsensus_tpu.cli', 'flywheel',
       '--out_dir', out, '--train_path', *glob_all,
       '--eval_path', glob_all[0],
       '--batch_size', '8', '--num_epochs', '1',
       '--export_batch_size', '8', '--window_buckets', '100,200',
       *sets],
      env=env, cwd=_REPO_ROOT, capture_output=True, text=True,
      timeout=1200)
  assert result.returncode == 0, result.stderr[-4000:]
  manifest = json.load(
      open(os.path.join(out, 'flywheel_manifest.json')))
  assert manifest['stages']['export']['artifact']
  # Both training stages consumed both widths with one trace each.
  for stage_dir in ('teacher', 'student'):
    faults = metrics_entries(os.path.join(out, stage_dir), 'faults')[-1]
    assert faults['n_train_forward_shapes'] == 2.0
    assert faults['n_train_batches_by_bucket_100'] >= 1
    assert faults['n_train_batches_by_bucket_200'] >= 1
  # The artifact serves the export geometry.
  from deepconsensus_tpu.inference import runner as runner_lib

  rows = np.random.RandomState(0).uniform(
      0.0, 10.0, size=(8, 4 * MAX_PASSES + 5, 100, 1)).astype(np.float32)
  # The manifest records the artifact FILE; from_exported loads the
  # containing export directory.
  runner = runner_lib.ModelRunner.from_exported(
      os.path.dirname(manifest['stages']['export']['artifact']),
      runner_lib.InferenceOptions(batch_size=8))
  ids, quals = runner.predict(rows)
  assert np.asarray(ids).shape[0] == 8
