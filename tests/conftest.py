"""Test configuration: force an 8-device virtual CPU mesh before jax loads.

Multi-chip sharding is validated on virtual CPU devices since tests run
off-TPU, with every Pallas kernel in interpret mode. What the TPU
compiler accepts is asked by tests/test_tpu_compile.py (a described
device, no chip); real-TPU execution is chip_smoke.py's.
"""
import os

os.environ['JAX_PLATFORMS'] = 'cpu'
# The reference Keras model (test_tf_forward_parity) needs Keras 2
# (tf.keras.layers.experimental.EinsumDense, legacy add_weight); must
# be set before the first tensorflow import anywhere in the process.
os.environ.setdefault('TF_USE_LEGACY_KERAS', '1')
_flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in _flags:
  os.environ['XLA_FLAGS'] = (
      _flags + ' --xla_force_host_platform_device_count=8'
  ).strip()

import pathlib

import pytest

REFERENCE_TESTDATA = pathlib.Path('/root/reference/deepconsensus/testdata')


def pytest_configure(config):
  config.addinivalue_line(
      'markers',
      'resilience: fault-injection tests for the inference and '
      'training fault-tolerance layers (scripts/run_resilience.sh)',
  )
  config.addinivalue_line(
      'markers',
      'multichip: data-parallel sharded-dispatch tests driven over '
      'the 8 forced host-platform devices (run_all_tests.sh multichip)',
  )
  config.addinivalue_line(
      'markers',
      'quant: quantized-inference lever tests (bf16 end-to-end, int8 '
      'matmuls) — accuracy gates and export plumbing '
      '(run_all_tests.sh quant)',
  )
  config.addinivalue_line(
      'markers',
      'fleet: multi-replica fleet tier tests — dctpu route balancing/'
      'retry semantics, featurize workers, protocol version '
      'negotiation (run_all_tests.sh fleet)',
  )


@pytest.fixture(scope='session')
def testdata_dir() -> pathlib.Path:
  if not REFERENCE_TESTDATA.exists():
    pytest.skip('reference testdata not available')
  return REFERENCE_TESTDATA


@pytest.fixture(scope='session')
def scripts_importable():
  """Puts the repo root on sys.path so tests can import the scripts/
  package regardless of the checkout location."""
  import sys

  repo_root = str(pathlib.Path(__file__).resolve().parent.parent)
  if repo_root not in sys.path:
    sys.path.insert(0, repo_root)
  return repo_root


@pytest.fixture
def synthetic_bams(tmp_path, scripts_importable):
  """Factory for synthetic (subreads_to_ccs.bam, ccs.bam) pairs built
  by the fault-injection harness — no reference testdata needed."""
  from scripts import inject_faults

  def make(subdir: str = 'bams', **kwargs):
    return inject_faults.write_synthetic_zmw_bams(
        str(tmp_path / subdir), **kwargs)

  return make
