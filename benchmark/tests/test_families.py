"""What belongs to a model family is found by name (benchmark/families/):
the default family is the old code bit for bit, and a second family is new
files only. Toy sizes on the CPU, but for the seeded trees of the two
published configurations.

Run with: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import json
import os
import re
import shutil

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, 'fixtures')
TOY = os.path.join(FIXTURES, 'BENCHMARK.toy.json')
BENCH = os.path.join(ROOT, 'BENCHMARK.json')
CELLS = ('teacher_polish', 'student_polish')
SEEDS = (3, 3000027001, 2**31 + 27)


def load(bench, cell):
  from benchmark import run
  return run.load_cell(bench, cell)


# ------------------------------------------- the default family is the old code

@pytest.mark.parametrize('cell', CELLS)
def test_a_configuration_without_the_key_gets_the_default_family(cell):
  loaded = load(BENCH, cell)
  assert 'family' not in loaded.config
  assert loaded.family.__file__ == os.path.join(
      ROOT, 'benchmark', 'families', 'gap_aware_encoder.py')


@pytest.mark.parametrize('cell', CELLS)
@pytest.mark.parametrize('seed', SEEDS)
def test_default_family_makes_the_old_tree_leaf_by_leaf(cell, seed, no_cache):
  import jax
  from benchmark.lib import weights
  loaded = load(BENCH, cell)
  shape = loaded.family.shape_of(loaded.config)
  new = loaded.family.make_params(shape, seed)
  old = weights.make_params(shape, seed)
  flat_new, tree_new = jax.tree_util.tree_flatten(new)
  flat_old, tree_old = jax.tree_util.tree_flatten(old)
  assert tree_new == tree_old and len(flat_new) > 50
  for a, b in zip(flat_new, flat_old):
    assert a.dtype == b.dtype == np.float32
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize('cell', CELLS)
def test_default_family_states_the_sizes_the_harness_used_to(cell):
  """`shape_of` and `stated` as run.py had them before PR 27, written out."""
  from benchmark import run
  loaded = load(BENCH, cell)
  config, family = loaded.config, loaded.family
  old_keys = ('num_hidden_layers', 'hidden_size', 'filter_size', 'num_heads',
              'attn_win_size', 'max_passes', 'max_length', 'total_rows',
              'condense_input_size', 'embedding', 'PW_MAX', 'IP_MAX',
              'STRAND_MAX', 'SN_MAX')
  assert family.shape_of(config) == {k: config[k] for k in old_keys}
  params = run.program_params(config, family)
  assert family.stated(params) == {
      'num_hidden_layers': params.num_hidden_layers,
      'hidden_size': params.hidden_size,
      'filter_size': params.filter_size,
      'num_heads': params.num_heads,
      'attn_win_size': params.attn_win_size,
      'max_passes': params.max_passes,
      'max_length': params.max_length,
      'total_rows': params.total_rows,
      'use_ccs_bq': params.use_ccs_bq,
      'PW_MAX': params.PW_MAX, 'IP_MAX': params.IP_MAX,
      'STRAND_MAX': params.STRAND_MAX, 'SN_MAX': params.SN_MAX,
      'dtype': params.dtype,
      'rezero': params.rezero,
      'use_fused_hotpath': params.use_fused_hotpath,
      'embedding': {
          'bases': params.per_base_hidden_size, 'pw': params.pw_hidden_size,
          'ip': params.ip_hidden_size, 'strand': params.strand_hidden_size,
          'sn': params.sn_hidden_size},
  }
  assert {k: config[k] for k in family.stated(params)} == family.stated(params)
  with pytest.raises(KeyError):
    family.shape_of({k: v for k, v in config.items() if k != 'attn_win_size'})


@pytest.mark.parametrize('cell,ms_per_pack', [('teacher_polish', 74.85),
                                              ('student_polish', 62.59)])
def test_default_family_counts_the_old_work(cell, ms_per_pack):
  from benchmark.lib import peaks, work
  loaded = load(BENCH, cell)
  family, shape = loaded.family, loaded.family.shape_of(loaded.config)
  v5e = peaks.peaks_for('TPU v5e')
  assert family.flops_per_window(shape) == work.flops_per_window(shape)
  assert family.bytes_per_pack(shape, 8192) == work.bytes_per_pack(shape, 8192)
  assert family.param_count(shape) == work.param_count(shape)
  least = family.least_seconds_per_pack(shape, 8192, v5e)
  assert least == work.least_seconds_per_pack(shape, 8192, v5e)
  # PERF.md section 3: the least time for a pack of 8,192.
  assert 1e3 * least['seconds'] == pytest.approx(ms_per_pack, abs=0.005)


def test_default_family_gives_the_old_reference_logits(no_cache):
  """`compare.reference_logits` as it was before PR 27, written out."""
  from benchmark.reference import forward as ref
  loaded = load(TOY, 'toy_polish')
  family, shape = loaded.family, loaded.family.shape_of(loaded.config)
  from benchmark.generators import pileup_windows as gen
  windows = gen.make(shape, loaded.traffic, 2**31 + 9)[:48]
  windows[:, 5:15] *= 9.0  # kinetics past the tables' range: the clip bites
  tree = family.make_params(shape, 2**31 + 9)

  def old(params, windows, shape, precision='float32', block=256):
    rows = np.asarray(windows, np.float32)[..., 0]
    p = shape['max_passes']
    rows = rows.copy()
    rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
    rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
    rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
    geometry = dict(max_passes=shape['max_passes'],
                    num_layers=shape['num_hidden_layers'],
                    num_heads=shape['num_heads'], band=shape['attn_win_size'])
    return ref.forward_blocks(params, rows, geometry=geometry,
                              precision=precision, block=block)

  assert windows[:, 5:15].max() > shape['PW_MAX']
  for precision in ('float32', 'bfloat16', 'fp8'):
    new = family.reference_logits(tree, windows, shape, precision, block=16)
    assert new.shape == (48, 20, 5)
    assert np.array_equal(new, old(tree, windows, shape, precision, block=16))


# --------------------------------------------------- a second family, by files

@pytest.mark.parametrize('trace', [False, True])
def test_second_family_runs_end_to_end_from_fixture_files(
    tmp_path, no_cache, trace):
  """The program's fully connected baseline, which `ModelRunner` serves on
  its normal path: its own tree, work count and reference."""
  import jax
  from benchmark import run
  result = run.run_cell(TOY, 'toy_fc_polish', 2**31 + 27, 0.3, trace,
                        require_chip=False, out_dir=str(tmp_path))
  assert result['correct'] is True and result['failed'] == 0
  assert result['attempted'] > 0 and result['attempted'] % 32 == 0
  assert result['compared']['id_gap_mean']['value'] <= 1e-6
  if trace:
    assert result['metrics']['pad_row_share']['value'] == 0.0
    assert 'forward_mfu' not in result['metrics']  # never off a chip
  loaded = load(TOY, 'toy_fc_polish')
  family, encoder = loaded.family, load(TOY, 'toy_polish').family
  assert family.__file__ == os.path.join(FIXTURES, 'families', 'toy_fc.py')
  shape = family.shape_of(loaded.config)
  assert 'num_heads' not in shape and shape['fc_size'] == [256, 512, 256, 128]
  tree = family.make_params(shape, 5)
  assert sorted(tree) == [f'Dense_{n}' for n in range(5)]
  assert tree['Dense_0']['kernel'].shape == (25 * 20, 256)
  assert tree['Dense_4']['kernel'].shape == (128, 20 * 5)
  n_leaves = len(jax.tree_util.tree_leaves(tree))
  assert n_leaves == 10 != len(jax.tree_util.tree_leaves(
      encoder.make_params(encoder.shape_of(load(TOY, 'toy_polish').config), 5)))
  # A hand count: five Dense layers, 2 x fan_in x fan_out each.
  hand = 2 * (500 * 256 + 256 * 512 + 512 * 256 + 256 * 128 + 128 * 100)
  assert family.flops_per_window(shape)['total'] == hand == 871_424
  assert family.param_count(shape) == hand // 2 + 256 + 512 + 256 + 128 + 100


def test_second_familys_tree_is_the_programs_and_its_reference_agrees(no_cache):
  import jax
  import jax.numpy as jnp
  from benchmark import run
  from benchmark.generators import pileup_windows as gen
  from benchmark.lib import compare
  from deepconsensus_tpu.models import model as model_lib

  loaded = load(TOY, 'toy_fc_polish')
  family, shape = loaded.family, loaded.family.shape_of(loaded.config)
  params = run.program_params(loaded.config, family)
  model = model_lib.get_model(params)
  want = jax.eval_shape(
      lambda k: model.init(k, jnp.zeros((1, 25, 20, 1))),
      jax.random.PRNGKey(0))['params']
  tree = family.make_params(shape, 2**31 + 5)
  shapes = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), t)
  assert shapes(tree) == shapes(want)
  windows = gen.make(shape, loaded.traffic, 2**31 + 5)[:32]
  with jax.default_matmul_precision('highest'):
    probs = np.asarray(model.apply({'params': tree}, jnp.asarray(windows)))
  ref = family.reference_logits(tree, windows, shape, block=32)
  e = np.exp(ref - ref.max(axis=-1, keepdims=True))
  assert np.abs(probs - e / e.sum(axis=-1, keepdims=True)).max() < 1e-5
  # The served qualities spread (a saturated head would compare nothing),
  # and the control a step below float32 fails the cell's limits.
  ids, quals = compare.served_from_logits(ref)
  assert len(np.unique(quals)) > 10 and len(np.unique(ids)) == 5
  low = family.reference_logits(tree, windows, shape, 'bfloat16', block=32)
  judged = compare.judge(
      compare.numbers(ref, *compare.served_from_logits(low)), loaded.limits)
  assert judged and not all(ok for *_r, ok in judged)


def _copy_of_the_toy_bench(tmp_path, family_text):
  """A bench directory of its own: the fixture's toy_fc cell with its
  family module replaced by `family_text`."""
  for kind in ('configs', 'traffic', 'limits'):
    shutil.copytree(os.path.join(FIXTURES, kind), tmp_path / kind)
  (tmp_path / 'families').mkdir()
  (tmp_path / 'families' / 'toy_fc.py').write_text(family_text)
  shutil.copy(TOY, tmp_path / 'BENCHMARK.toy.json')
  return str(tmp_path / 'BENCHMARK.toy.json')


@pytest.mark.parametrize('missing', ['stated', 'param_count',
                                     'reference_logits'])
def test_a_family_file_that_lacks_a_function_fails_at_load(tmp_path, missing):
  with open(os.path.join(FIXTURES, 'families', 'toy_fc.py')) as f:
    text = f.read()
  assert f'\ndef {missing}(' in text
  bench = _copy_of_the_toy_bench(
      tmp_path, text.replace(f'\ndef {missing}(', f'\ndef _{missing}('))
  with pytest.raises(SystemExit, match=rf'toy_fc\.py lacks {missing}\(\)'):
    load(bench, 'toy_fc_polish')
  with pytest.raises(FileNotFoundError, match='families/no_such_family.py'):
    from benchmark import run
    run.load_family(str(tmp_path), 'no_such_family')


@pytest.mark.parametrize('cell,key,value', [
    ('toy_polish', 'attn_win_size', 11),
    ('toy_fc_polish', 'fc_size', [256, 512, 256, 64]),
    ('toy_fc_polish', 'model_name', 'transformer')])
def test_file_and_program_disagreeing_in_a_family_size_exits(cell, key, value):
  from benchmark import run
  loaded = load(TOY, cell)
  run.program_params(loaded.config, loaded.family)  # as committed: agrees
  config = dict(loaded.config, **{key: value})
  with pytest.raises(SystemExit, match='configuration file and program '
                     f"disagree: .*'{key}'"):
    run.program_params(config, loaded.family)


# ------------------------------------------------ the harness names no family

ENCODER_NAMES = re.compile(
    r'attn_win_size|rezero|num_heads|num_hidden_layers|hidden_size|filter_size'
    r'|condense_input_size|use_fused_hotpath|PW_MAX|IP_MAX|STRAND_MAX|SN_MAX'
    r'|self_attention|ffn_|alpha|condenser|embedding|geometry|fc_size|Dense_')
FAMILY_IMPORTS = re.compile(
    r'^\s*(from|import)\s+benchmark\.(lib\.weights|lib\.work|reference)\b'
    r'|^\s*from\s+benchmark\.lib\s+import\s+.*\b(weights|work)\b'
    r'|^\s*from\s+benchmark\s+import\s+.*\breference\b', re.M)


def test_outside_the_families_the_harness_names_no_size_leaf_or_reference():
  """Weights, work and reference are reached through the family module
  alone; `lib/compare.py` keeps the Phred epilogue that every family with
  the 5-way head shares."""
  own = {os.path.join('lib', 'weights.py'), os.path.join('lib', 'work.py'),
         os.path.join('reference', 'forward.py')}
  seen = 0
  base = os.path.join(ROOT, 'benchmark')
  for dirpath, _dirs, files in os.walk(base):
    rel_dir = os.path.relpath(dirpath, base)
    if rel_dir.split(os.sep)[0] in ('tests', 'families'):
      continue
    for name in files:
      rel = os.path.normpath(os.path.join(rel_dir, name))
      if not name.endswith('.py') or rel in own:
        continue
      with open(os.path.join(dirpath, name)) as f:
        text = f.read()
      if text.startswith('"""'):  # the module's docstring may give examples
        text = text.split('"""', 2)[2]
      seen += 1
      assert not ENCODER_NAMES.search(text), (rel, ENCODER_NAMES.search(text))
      imports = [line.strip() for line in text.splitlines()
                 if FAMILY_IMPORTS.search(line)]
      if rel == os.path.join('lib', 'compare.py'):
        assert imports == ['from benchmark.reference import forward as ref']
        assert re.findall(r'\bref\.(\w+)', text) == ['phred', 'phred']
      else:
        assert imports == [], (rel, imports)
  assert seen > 25
  with open(os.path.join(base, 'families', 'gap_aware_encoder.py')) as f:
    assert len(FAMILY_IMPORTS.findall(f.read())) == 2
