"""Byte-identity and plumbing tests for the device-resident output
plane (--device_epilogue): the forward emits final uint8 (ids, quals)
planes on device and finalize becomes a pure 2-bytes/position drain.

The contract under test: FASTQ output is byte-identical with the
epilogue on or off, across the quantization levers, dp sharding, the
serve/engine boundary, and exported artifacts — and with it on, the
host never touches per-position float math again.

The fast tier's gate (`run_all_tests.sh fast` / `epilogue`) runs the
single-device subset via `-k identity -m 'not multichip'`; name any
new identity invariant accordingly.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu.calibration import lib as calibration_lib
from deepconsensus_tpu.inference import engine as engine_lib
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.io import fastx
from deepconsensus_tpu.models import (
    config as config_lib,
    export as export_lib,
    model as model_lib,
)


def _params(layers=2, **kw):
  params = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(params, is_training=False)
  with params.unlocked():
    params.dtype = 'float32'
    params.num_hidden_layers = layers
    params.filter_size = 64
    params.batch_size = 4
    for k, v in kw.items():
      params[k] = v
  return params


def _init_variables(params, seed=0):
  model = model_lib.get_model(params)
  rows = jnp.zeros((1, params.total_rows, params.max_length, 1))
  return model.init(jax.random.PRNGKey(seed), rows)


def _rows(params, n, seed=7):
  rng = np.random.default_rng(seed)
  return rng.integers(
      0, 4, size=(n, params.total_rows, params.max_length, 1)
  ).astype(np.float32)


def _runner(variables, device_epilogue, mesh=None, batch_size=8, **opt_kw):
  options = runner_lib.InferenceOptions(
      batch_size=batch_size, device_epilogue=device_epilogue, **opt_kw)
  p = _params()
  runner_lib._apply_quant_levers(p, options)
  return runner_lib.ModelRunner(p, variables, options, mesh=mesh)


def _ids_quals(runner, rows):
  ids, quals = runner.predict(rows)
  return np.asarray(ids, np.int64), np.asarray(quals, np.int64)


# ---------------------------------------------------------------------------
# End-to-end FASTQ byte identity (the fast-tier gate).
# ---------------------------------------------------------------------------


def test_fastq_byte_identity_host_vs_device(tmp_path, synthetic_bams):
  """The headline invariant: the device epilogue changes the transfer
  format (uint8 planes, 4x fewer D2H bytes), never a single FASTQ
  byte."""
  subreads, ccs = synthetic_bams()
  params = _params()
  variables = _init_variables(params, seed=4)

  def run(tag, device_epilogue):
    options = runner_lib.InferenceOptions(
        batch_size=32, batch_zmws=4, min_quality=0,
        device_epilogue=device_epilogue)
    p = _params()
    runner_lib._apply_quant_levers(p, options)
    runner = runner_lib.ModelRunner(p, variables, options)
    out = str(tmp_path / f'{tag}.fastq')
    counters = runner_lib.run_inference(
        subreads_to_ccs=subreads, ccs_bam=ccs, checkpoint=None,
        output=out, options=options, runner=runner)
    return counters, out

  counters_dev, out_dev = run('device', True)
  counters_host, out_host = run('host', False)
  assert counters_dev['n_zmw_pass'] == counters_host['n_zmw_pass'] > 0
  with open(out_dev, 'rb') as f_dev, open(out_host, 'rb') as f_host:
    assert f_dev.read() == f_host.read()
  # Same reads parse out (guards against an identical-but-empty pair).
  assert len(list(fastx.read_fastq(out_dev))) > 0


@pytest.mark.parametrize('levers', [
    dict(inference_dtype='bfloat16'),
    dict(quantize_matmuls='int8'),
    dict(inference_dtype='bfloat16', quantize_matmuls='int8'),
])
def test_predict_identity_across_quant_levers(levers):
  """Each quantization lever changes the logits, but for a FIXED lever
  the epilogue on/off outputs must stay byte-identical (the model's
  output head is f32 regardless of lever, so one threshold table
  serves them all)."""
  params = _params()
  variables = _init_variables(params, seed=6)
  rows = _rows(params, 8)
  on = _runner(variables, True, **levers)
  off = _runner(variables, False, **levers)
  ids_on, quals_on = _ids_quals(on, rows)
  ids_off, quals_off = _ids_quals(off, rows)
  np.testing.assert_array_equal(ids_on, ids_off)
  np.testing.assert_array_equal(quals_on, quals_off)
  assert on.dispatch_stats()['device_epilogue'] == 1
  assert off.dispatch_stats()['device_epilogue'] == 0


@pytest.mark.parametrize('calibration,maxq', [
    ('0,0.9,2.5', 93),
    ('15,1.1,2', 93),
    ('skip', 40),
])
def test_predict_identity_with_calibration(calibration, maxq):
  """Calibration and clamp knobs ride inside the threshold table; the
  identity holds for every representable combination."""
  params = _params()
  variables = _init_variables(params, seed=8)
  rows = _rows(params, 8, seed=9)
  cv = calibration_lib.parse_calibration_string(calibration)
  on = _runner(variables, True,
               dc_calibration_values=cv, max_base_quality=maxq)
  off = _runner(variables, False,
                dc_calibration_values=cv, max_base_quality=maxq)
  assert on.dispatch_stats()['device_epilogue'] == 1
  ids_on, quals_on = _ids_quals(on, rows)
  ids_off, quals_off = _ids_quals(off, rows)
  np.testing.assert_array_equal(ids_on, ids_off)
  np.testing.assert_array_equal(quals_on, quals_off)


def test_fused_hotpath_identity():
  """The fused hot path hands its predictions to the same device
  epilogue; same identity bar."""
  params = _params()
  variables = _init_variables(params, seed=10)
  rows = _rows(params, 8, seed=11)
  options = runner_lib.InferenceOptions(batch_size=8, device_epilogue=True)
  p = _params(use_fused_hotpath=True)
  runner_lib._apply_quant_levers(p, options)
  on = runner_lib.ModelRunner(p, variables, options)
  off_options = runner_lib.InferenceOptions(
      batch_size=8, device_epilogue=False)
  p_off = _params(use_fused_hotpath=True)
  runner_lib._apply_quant_levers(p_off, off_options)
  off = runner_lib.ModelRunner(p_off, variables, off_options)
  ids_on, quals_on = _ids_quals(on, rows)
  ids_off, quals_off = _ids_quals(off, rows)
  np.testing.assert_array_equal(ids_on, ids_off)
  np.testing.assert_array_equal(quals_on, quals_off)


@pytest.mark.multichip
def test_dp8_predict_identity():
  """dp-sharded dispatch with the device epilogue (the uint8 planes
  shard with the same out_shardings) matches the single-device host
  path — full and padded-partial packs."""
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  if len(jax.devices()) < 8:
    pytest.skip('needs the 8-device virtual mesh')
  params = _params()
  variables = _init_variables(params, seed=12)
  mesh = mesh_lib.make_mesh(dp=8, tp=1, devices=jax.devices()[:8])
  sharded = _runner(variables, True, mesh=mesh, batch_size=64)
  host = _runner(variables, False, batch_size=64)
  for n in (64, 37):
    rows = _rows(params, n, seed=n)
    ids_s, quals_s = _ids_quals(sharded, rows)
    ids_h, quals_h = _ids_quals(host, rows)
    np.testing.assert_array_equal(ids_s, ids_h)
    np.testing.assert_array_equal(quals_s, quals_h)
  assert sharded.dispatch_stats()['n_epilogue_packs'] == 2


# ---------------------------------------------------------------------------
# Serve/engine boundary.
# ---------------------------------------------------------------------------


def _engine_options(params, device_epilogue):
  options = runner_lib.InferenceOptions(
      batch_size=8, device_epilogue=device_epilogue)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  return options


def test_engine_predict_windows_identity():
  """The serve path's engine boundary delivers identical uint8 results
  with the epilogue on or off (engine._deliver_pack already casts the
  host path's int32 to uint8)."""
  params = _params()
  variables = _init_variables(params, seed=14)
  raw = _rows(params, 11, seed=15)
  results = {}
  for device_epilogue in (True, False):
    options = _engine_options(params, device_epilogue)
    p = _params()
    runner_lib._apply_quant_levers(p, options)
    runner = runner_lib.ModelRunner(p, variables, options)
    engine = engine_lib.ConsensusEngine(
        runner, options, deliver=lambda t, ids, quals: None)
    results[device_epilogue] = engine.predict_windows(raw)
  ids_on, quals_on = results[True]
  ids_off, quals_off = results[False]
  assert ids_on.dtype == np.uint8 and quals_on.dtype == np.uint8
  assert ids_off.dtype == np.uint8 and quals_off.dtype == np.uint8
  np.testing.assert_array_equal(ids_on, ids_off)
  np.testing.assert_array_equal(quals_on, quals_off)


def test_serve_stats_surface_epilogue_counters():
  from deepconsensus_tpu.serve.service import ConsensusService, ServeOptions

  params = _params()
  variables = _init_variables(params, seed=16)
  options = _engine_options(params, True)
  p = _params()
  runner_lib._apply_quant_levers(p, options)
  runner = runner_lib.ModelRunner(p, variables, options)
  service = ConsensusService(runner, options, ServeOptions())
  faults = service.stats()['counters']
  assert faults['device_epilogue'] == 1
  assert faults['n_epilogue_packs'] == 0
  assert faults['d2h_bytes_per_pack'] == 0


# ---------------------------------------------------------------------------
# Finalize is a pure drain; counters measure the saved bytes.
# ---------------------------------------------------------------------------


def test_finalize_pure_drain_when_epilogue_on(monkeypatch):
  """With the epilogue on, _finalize_sync must not touch per-position
  float math: no np.log10, no np.round. (Runners are built and warmed
  BEFORE patching — the threshold build itself legitimately calls
  log10, and the first finalize pays jit tracing.)"""
  params = _params()
  variables = _init_variables(params, seed=18)
  rows = _rows(params, 8, seed=19)
  on = _runner(variables, True)
  off = _runner(variables, False)
  on.predict(rows)
  off.predict(rows)

  calls = []

  def spy(name, fn):
    def wrapped(*args, **kwargs):
      calls.append(name)
      return fn(*args, **kwargs)
    return wrapped

  monkeypatch.setattr(np, 'log10', spy('log10', np.log10))
  monkeypatch.setattr(np, 'round', spy('round', np.round))

  ids, quals = on.finalize(on.dispatch(rows))
  assert 'log10' not in calls and 'round' not in calls
  assert ids.dtype == np.uint8 and quals.dtype == np.uint8

  calls.clear()
  off.finalize(off.dispatch(rows))
  assert 'log10' in calls and 'round' in calls


def test_d2h_counters_show_4x_reduction():
  params = _params()
  variables = _init_variables(params, seed=20)
  rows = _rows(params, 8, seed=21)
  on = _runner(variables, True)
  off = _runner(variables, False)
  on.predict(rows)
  off.predict(rows)
  stats_on = on.dispatch_stats()
  stats_off = off.dispatch_stats()
  assert stats_on['device_epilogue'] == 1
  assert stats_on['n_epilogue_packs'] == 1
  assert stats_off['device_epilogue'] == 0
  assert stats_off['n_epilogue_packs'] == 0
  # Measured from the actual drained device arrays: 2 uint8 planes vs
  # int32 ids + f32 max_prob.
  assert stats_on['d2h_bytes_per_pack'] > 0
  assert stats_off['d2h_bytes_per_pack'] == (
      4 * stats_on['d2h_bytes_per_pack'])


def test_non_representable_calibration_falls_back(caplog):
  """A non-monotone calibration cannot ride the threshold table; the
  runner warns and serves the host path (still correct, just 8
  bytes/position)."""
  cv = calibration_lib.parse_calibration_string('0,-1,50')
  params = _params()
  variables = _init_variables(params, seed=22)
  with caplog.at_level(logging.WARNING):
    runner = _runner(variables, True, dc_calibration_values=cv)
  assert runner.dispatch_stats()['device_epilogue'] == 0
  assert any('falling back to host quality math' in r.message
             for r in caplog.records)
  rows = _rows(params, 8, seed=23)
  host = _runner(variables, False, dc_calibration_values=cv)
  ids_a, quals_a = _ids_quals(runner, rows)
  ids_b, quals_b = _ids_quals(host, rows)
  np.testing.assert_array_equal(ids_a, ids_b)
  np.testing.assert_array_equal(quals_a, quals_b)


# ---------------------------------------------------------------------------
# Exported artifacts: epilogue baked into the program + metadata.
# ---------------------------------------------------------------------------


def _export(tmp_path, tag, **kw):
  params = _params(layers=1)
  variables = _init_variables(params)
  export_dir = str(tmp_path / tag)
  export_lib.export_model(
      checkpoint_path=export_dir, out_dir=export_dir, batch_size=8,
      variables=variables, params=params, **kw)
  return export_dir, params, variables


def test_exported_epilogue_identity(tmp_path):
  """An epilogue artifact's baked program reproduces the checkpoint
  host path byte-for-byte; a pre-epilogue artifact does too (via the
  host fallback)."""
  export_dir, params, variables = _export(tmp_path, 'epi')
  import json
  with open(f'{export_dir}/export_meta.json') as f:
    meta = json.load(f)
  assert meta['device_epilogue'] is True
  assert meta['max_base_quality'] == 93
  assert meta['dc_calibration'] == 'skip'

  rows = _rows(params, 8, seed=24)
  host = runner_lib.ModelRunner(
      _params(layers=1), variables,
      runner_lib.InferenceOptions(batch_size=8, device_epilogue=False))
  exported = runner_lib.ModelRunner.from_exported(
      export_dir, runner_lib.InferenceOptions(batch_size=8))
  assert exported.dispatch_stats()['device_epilogue'] == 1
  ids_h, quals_h = _ids_quals(host, rows)
  ids_e, quals_e = _ids_quals(exported, rows)
  np.testing.assert_array_equal(ids_e, ids_h)
  np.testing.assert_array_equal(quals_e, quals_h)

  plain_dir, _, _ = _export(tmp_path, 'plain', device_epilogue=False)
  plain = runner_lib.ModelRunner.from_exported(
      plain_dir, runner_lib.InferenceOptions(batch_size=8))
  assert plain.dispatch_stats()['device_epilogue'] == 0
  ids_p, quals_p = _ids_quals(plain, rows)
  np.testing.assert_array_equal(ids_p, ids_h)
  np.testing.assert_array_equal(quals_p, quals_h)


def test_exported_epilogue_mismatch_both_directions(tmp_path):
  epi_dir, _, _ = _export(tmp_path, 'epi')
  plain_dir, _, _ = _export(tmp_path, 'plain', device_epilogue=False)

  # Baked epilogue, caller explicitly demands the host path.
  with pytest.raises(faults_lib.ExportedArtifactMismatchError) as excinfo:
    runner_lib.ModelRunner.from_exported(
        epi_dir,
        runner_lib.InferenceOptions(batch_size=8, device_epilogue=False))
  err = excinfo.value
  assert err.reexport_command and 'dctpu export' in err.reexport_command
  assert '--no_device_epilogue' in err.reexport_command
  assert err.reexport_command in str(err)

  # Baked pre-epilogue, caller explicitly demands the device plane.
  with pytest.raises(faults_lib.ExportedArtifactMismatchError) as excinfo:
    runner_lib.ModelRunner.from_exported(
        plain_dir,
        runner_lib.InferenceOptions(batch_size=8, device_epilogue=True))
  assert '--device_epilogue' in excinfo.value.reexport_command


def test_exported_epilogue_quality_knob_mismatch(tmp_path):
  """An epilogue artifact bakes its calibration and clamp into the
  compiled program; a disagreeing serving knob is a refusal naming the
  exact re-export command, never a silent override."""
  epi_dir, _, _ = _export(tmp_path, 'epi')

  with pytest.raises(faults_lib.ExportedArtifactMismatchError) as excinfo:
    runner_lib.ModelRunner.from_exported(
        epi_dir,
        runner_lib.InferenceOptions(batch_size=8, max_base_quality=40))
  assert '--max_base_quality 40' in excinfo.value.reexport_command

  cv = calibration_lib.parse_calibration_string('0,0.9,2.5')
  with pytest.raises(faults_lib.ExportedArtifactMismatchError) as excinfo:
    runner_lib.ModelRunner.from_exported(
        epi_dir,
        runner_lib.InferenceOptions(batch_size=8,
                                    dc_calibration_values=cv))
  assert '--dc_calibration 0,0.9,2.5' in excinfo.value.reexport_command

  # A pre-epilogue artifact leaves the quality knobs host-side: no
  # baking, no refusal.
  plain_dir, _, _ = _export(tmp_path, 'plain', device_epilogue=False)
  runner_lib.ModelRunner.from_exported(
      plain_dir,
      runner_lib.InferenceOptions(batch_size=8, max_base_quality=40,
                                  dc_calibration_values=cv))
