"""Perf smoke: the inference forward compiles once per shape.

The whole point of fixed-shape packed batches is that the compiled
forward is reused for every pack; a recompile per featurize batch (or
per ragged tail) would silently erase the pipeline win. Asserted via
JAX's lowering counters, so it runs in seconds on CPU — no timing, no
flakiness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import test_util as jtu

from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib

BATCH = 8


@pytest.fixture(scope='module')
def runner():
  params = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(params, is_training=False)
  model = model_lib.get_model(params)
  variables = model.init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))
  options = runner_lib.InferenceOptions(batch_size=BATCH)
  return runner_lib.ModelRunner(params, variables, options)


def _rows(runner, n, seed):
  rng = np.random.default_rng(seed)
  params = runner.params
  shape = (n, params.total_rows, params.max_length, 1)
  return rng.integers(0, 5, size=shape).astype(np.float32)


def test_forward_compiles_once_per_shape(runner):
  out = runner.predict(_rows(runner, BATCH, 0))  # pays the one compile
  assert out[0].shape == (BATCH, runner.params.max_length)
  with jtu.count_jit_and_pmap_lowerings() as count:
    # Steady state: full packs AND ragged tails (dispatch pads them to
    # the compiled batch shape) must all hit the same executable.
    for i, n in enumerate((BATCH, BATCH, BATCH // 2, 3, 1)):
      ids, quals = runner.predict(_rows(runner, n, i + 1))
      assert ids.shape == (n, runner.params.max_length)
  assert count() == 0, (
      f'{count()} re-lowerings in steady state: the forward is being '
      'recompiled per batch instead of reused per shape')


def test_a_second_batch_size_is_one_compile_under_its_launch(
    runner, tmp_path):
  """What XLA compiled is counted by the program itself
  (obs/compiles.py), not guessed from the shapes: a pack at a new batch
  size raises `n_xla_compiles` by one, and the span says which launch
  of which pack paid for it."""
  import json

  from deepconsensus_tpu.obs import compiles as compiles_lib
  from deepconsensus_tpu.obs import trace as trace_lib

  runner.predict(_rows(runner, BATCH, 0))  # the shape the others use
  # The process's compiles count into the registry bound last: this
  # runner's again, whatever other tests have built since.
  compiles_lib.install(runner.obs)
  trace_lib.clear_early()
  before = runner.dispatch_stats()
  path = str(tmp_path / 'trace.jsonl')
  trace_lib.configure(path, tier='run')
  try:
    handle = runner.dispatch(_rows(runner, BATCH // 2, 1),
                             batch_size=BATCH // 2)
    runner.finalize(handle)
  finally:
    trace_lib.configure(None)
  after = runner.dispatch_stats()
  assert after['n_forward_shapes'] == before['n_forward_shapes'] + 1
  assert after['n_xla_compiles'] == before['n_xla_compiles'] + 1
  assert 0 <= after['n_xla_cache_hits'] <= after['n_xla_compiles']
  with open(path) as f:
    events = [json.loads(line.rstrip().rstrip(','))
              for line in f if line.startswith('{')]
  (launch,) = [e for e in events if e['name'] == 'forward_launch']
  (compiled,) = [e for e in events if e['name'] == 'xla_compile']
  assert compiled['args']['fun'] == 'jit(forward)'
  assert compiled['args']['under'] == 'forward_launch'
  assert compiled['args']['pack'] == launch['args']['pack'] == handle.seq
  assert compiled['args']['parent'] == launch['args']['span']
  traced = [e for e in events if e['name'] == 'jit_trace']
  assert traced and all(
      e['args']['parent'] == launch['args']['span'] for e in traced)
  # The same size again: nothing compiles, nothing is written.
  runner.finalize(runner.dispatch(_rows(runner, BATCH // 2, 2),
                                  batch_size=BATCH // 2))
  assert runner.dispatch_stats()['n_xla_compiles'] == (
      after['n_xla_compiles'])
