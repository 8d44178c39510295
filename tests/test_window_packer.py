"""Cross-batch window packer + array-native output plane.

Covers the packer's edge cases (empty model set, sub-batch tail flush,
molecules spanning pack boundaries, packed-batch failure attribution)
through the full pipeline with a stubbed model forward — the stub
echoes each window's draft-CCS row, so correct scatter/stitch is
observable as the CCS sequence coming back out — plus direct
array-plane vs string-plane stitch parity.
"""
import json

import numpy as np
import pytest

from deepconsensus_tpu import constants
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.io import bam as bam_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.postprocess import stitch
from deepconsensus_tpu.utils import phred

pytestmark = pytest.mark.resilience

N_ZMWS = 6
SEQ_LEN = 600
STUB_QUAL = 40


@pytest.fixture(scope='module')
def params():
  p = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(p, is_training=False)
  return p


def _stub_model(runner, params, fail=False):
  """Replaces the jitted forward: finalize returns each window's
  draft-CCS row as the prediction with a constant quality, making the
  pack -> scatter -> stitch path verifiable without weights."""
  mp = params.max_passes

  def dispatch_pack(main_u8, sn, n_rows=None, batch_size=None):
    if fail:
      raise RuntimeError('stub model pack failure')
    return main_u8[:n_rows]

  def finalize(rows):
    ids = rows[:, 4 * mp, :, 0].astype(np.int32)
    return ids, np.full(ids.shape, STUB_QUAL, np.int32)

  runner.dispatch_pack = dispatch_pack
  runner.finalize = finalize


def _run(tmp_path, synthetic_bams, params, name, fail=False, **kw):
  subreads, ccs = synthetic_bams(
      subdir=f'bams_{name}', n_zmws=N_ZMWS, seq_len=SEQ_LEN)
  kw.setdefault('batch_zmws', 2)
  kw.setdefault('skip_windows_above', 0)  # falsy: no quality skips
  kw.setdefault('min_quality', 0)
  options = runner_lib.InferenceOptions(**kw)
  runner = runner_lib.ModelRunner(params, {}, options)
  _stub_model(runner, params, fail=fail)
  out = str(tmp_path / f'{name}.fastq')
  counters = runner_lib.run_inference(
      subreads_to_ccs=subreads, ccs_bam=ccs, checkpoint=None,
      output=out, options=options, runner=runner)
  return out, counters, ccs


def _reads(path):
  with open(path) as f:
    lines = [line.rstrip('\n') for line in f]
  return {lines[i][1:]: (lines[i + 1], lines[i + 3])
          for i in range(0, len(lines), 4)}


def _ccs_seqs(ccs_bam):
  with bam_lib.BamReader(ccs_bam) as r:
    return {rec.qname: rec.seq for rec in r}


def test_empty_model_set(tmp_path, synthetic_bams, params):
  """All windows quality-skipped: the packer must never dispatch (the
  stub would raise on weightless variables anyway via fail=True)."""
  out, counters, ccs = _run(tmp_path, synthetic_bams, params, 'empty',
                            fail=True, skip_windows_above=1,
                            batch_size=32)
  assert counters['n_model_packs'] == 0
  assert counters['n_model_pack_rows'] == 0
  assert sorted(_reads(out)) == sorted(_ccs_seqs(ccs))


def test_tail_flush_pads_final_pack(tmp_path, synthetic_bams, params):
  """36 windows at batch_size=8: 4 full packs cut across featurize
  batches + one padded tail pack at end-of-input."""
  out, counters, ccs = _run(tmp_path, synthetic_bams, params, 'tail',
                            batch_size=8)
  assert counters['n_model_packs'] == 5
  assert counters['n_model_pack_rows'] == 36
  assert counters['n_model_pad_rows'] == 5 * 8 - 36
  reads, seqs = _reads(out), _ccs_seqs(ccs)
  assert sorted(reads) == sorted(seqs)
  for name, (seq, qual) in reads.items():
    assert seq == seqs[name]  # stub echoes the draft CCS
    assert qual == chr(STUB_QUAL + 33) * SEQ_LEN


def test_sidecar_reports_starvation_counters(tmp_path, synthetic_bams,
                                             params):
  """run_inference copies the engine's starvation accounting into the
  counters sidecar: fixed-width streams never starve, so both keys are
  present at their zero values (the live values are exercised at the
  engine boundary in test_engine.py)."""
  _out, counters, _ccs = _run(tmp_path, synthetic_bams, params,
                              'starve_keys', batch_size=8)
  assert counters['n_starvation_flushes'] == 0
  assert counters['flush_padding_fraction'] == 0.0


def test_molecules_span_pack_boundaries(tmp_path, synthetic_bams, params):
  """batch_size < windows-per-molecule: every molecule's windows land
  in different packs (and different featurize batches' packs) and must
  still scatter back and stitch in order."""
  out, counters, ccs = _run(tmp_path, synthetic_bams, params, 'span',
                            batch_size=4)
  assert counters['n_model_packs'] == 9  # 36 windows / 4
  assert counters['n_model_pad_rows'] == 0
  reads, seqs = _reads(out), _ccs_seqs(ccs)
  for name, (seq, _) in reads.items():
    assert seq == seqs[name]


def test_cross_batch_packing_output_invariance(tmp_path, synthetic_bams,
                                               params):
  """Where a pack is cut must not change a single output byte: at
  batch 8 packs straddle the 12-window featurize batches and the tail
  pads; batch 4 divides every featurize batch, so no pack crosses one
  and none pads."""
  packed, c_packed, _ = _run(tmp_path, synthetic_bams, params, 'packed',
                             batch_size=8)
  aligned, c_aligned, _ = _run(tmp_path, synthetic_bams, params,
                               'aligned', batch_size=4)
  with open(packed, 'rb') as a, open(aligned, 'rb') as b:
    assert a.read() == b.read()
  assert c_packed['n_model_packs'] == 5
  assert c_packed['n_model_pad_rows'] == 4
  assert c_aligned['n_model_packs'] == 9
  assert c_aligned['n_model_pad_rows'] == 0


def test_pack_failure_attributes_member_molecules(tmp_path,
                                                 synthetic_bams, params):
  """A failed pack quarantines exactly its member molecules, recording
  which pack took them down; under ccs-fallback every member degrades
  to its draft CCS (original base qualities) instead of vanishing."""
  out, counters, ccs = _run(tmp_path, synthetic_bams, params, 'fail',
                            fail=True, batch_size=8,
                            on_zmw_error='ccs-fallback')
  reads, seqs = _reads(out), _ccs_seqs(ccs)
  assert sorted(reads) == sorted(seqs)
  for name, (seq, qual) in reads.items():
    assert seq == seqs[name]
    assert qual == chr(30 + 33) * SEQ_LEN  # synthetic base_qual=30
  with open(out + '.failed.jsonl') as f:
    entries = [json.loads(line) for line in f]
  assert {e['zmw'] for e in entries} == set(seqs)
  for e in entries:
    assert e['stage'] == 'model'
    assert e['action'] == 'ccs-fallback'
    assert 'model_pack' in e and 'n_windows_in_pack' in e


def _string_plane(name, windows, max_length, min_quality, min_length):
  counter = stitch.OutcomeCounter()
  preds = [
      stitch.DCModelOutput(
          molecule_name=name, window_pos=pos,
          sequence=phred.encoded_sequence_to_string(ids),
          quality_string=phred.quality_scores_to_string(quals))
      for pos, ids, quals in windows
  ]
  preds.sort(key=lambda p: (p.molecule_name, p.window_pos))
  fastq = stitch.stitch_to_fastq(
      molecule_name=name, predictions=preds, max_length=max_length,
      min_quality=min_quality, min_length=min_length,
      outcome_counter=counter)
  return fastq, counter


def _array_plane(name, windows, max_length, min_quality, min_length):
  counter = stitch.OutcomeCounter()
  result = stitch.stitch_arrays(
      name,
      np.asarray([w[0] for w in windows], dtype=np.int64),
      np.stack([w[1] for w in windows]).astype(np.uint8),
      np.stack([w[2] for w in windows]).astype(np.uint8),
      max_length=max_length, min_quality=min_quality,
      min_length=min_length, outcome_counter=counter)
  fastq = (None if result is None
           else stitch.format_fastq_bytes(name, *result).decode('ascii'))
  return fastq, counter


def test_array_plane_matches_string_plane():
  """stitch_arrays + format_fastq_bytes must be byte-for-byte the
  legacy stitch_to_fastq, including which outcome counter each filter
  path charges."""
  rng = np.random.default_rng(11)
  L = 25

  def win(pos, gap_frac=0.2, qual_lo=20, qual_hi=60):
    ids = rng.integers(1, len(constants.SEQ_VOCAB), size=L)
    ids[rng.random(L) < gap_frac] = constants.GAP_INT
    quals = rng.integers(qual_lo, qual_hi, size=L)
    return pos, ids, quals

  cases = {
      'success': ([win(0), win(L), win(2 * L)], dict()),
      # Windows arrive shuffled; the stable pos sort must fix it.
      'shuffled': ([win(2 * L), win(0), win(L)], dict()),
      'missing_window': ([win(0), win(2 * L)], dict()),
      'gaps_only': ([win(0, gap_frac=1.0)], dict()),
      'low_quality': ([win(0, qual_lo=1, qual_hi=5)],
                      dict(min_quality=30)),
      'too_short': ([win(0, gap_frac=0.9)], dict(min_length=20)),
  }
  for name, (windows, kw) in cases.items():
    kw = dict(min_quality=kw.get('min_quality', 10),
              min_length=kw.get('min_length', 0))
    old, c_old = _string_plane(name, windows, L, **kw)
    new, c_new = _array_plane(name, windows, L, **kw)
    assert old == new, name
    assert c_old == c_new, name
