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
