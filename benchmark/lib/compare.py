"""The comparison that decides `correct` for window-level answers.

What the timed path delivered for a sample of windows (uint8 base ids and
Phred qualities per position) is held against the plain float32 reference
of the configuration's family (`reference_logits` of
benchmark/families/<family>.py) run once over the same windows:

  id_gap_mean        mean gap by which a served base's reference logit
                     lies below the reference's best, over all positions
                     (0 where the served base is the reference's best)
  id_gap_max         the widest such gap (reported, not held to a limit:
                     it swings from seed to seed, see PERF.md)
  id_mismatch_share  share of positions whose served base is not the
                     reference's best
  qual_diff_mean     mean |served quality - reference quality|, Phred units

How far rounding moves these depends on the seed's weights (a tenfold
range over a dozen seeds, the same on the program and on a reference that
rounds), so the two numbers that are held to limits are taken against a
yardstick computed on the same windows: the same reference with every
matmul operand rounded to bfloat16, the precision the configurations state.

  id_gap_mean_vs_bf16     id_gap_mean / the bfloat16 reference's id_gap_mean
  qual_diff_mean_vs_bf16  qual_diff_mean / the bfloat16 reference's

Each has a limit of its own in benchmark/limits/<cell>.json. The control is
the reference with every matmul operand rounded to fp8 (e4m3), the next
precision below bfloat16, put in the program's place:
`served_from_logits` turns its logits into the answers the program would
have served.
"""
from __future__ import annotations

import numpy as np

# The Phred epilogue, which every family with the 5-way head shares.
from benchmark.reference import forward as ref


def _softmax(z: np.ndarray) -> np.ndarray:
  z = z.astype(np.float64)
  e = np.exp(z - z.max(axis=-1, keepdims=True))
  return e / e.sum(axis=-1, keepdims=True)


def served_from_logits(logits: np.ndarray):
  """(ids, quals) that a path with these logits would have served."""
  probs = _softmax(logits)
  return (probs.argmax(axis=-1).astype(np.uint8),
          ref.phred(probs.max(axis=-1)).astype(np.uint8))


def numbers(ref_logits: np.ndarray, ids: np.ndarray, quals: np.ndarray,
            yard_logits: np.ndarray = None):
  """The compared numbers for served (ids, quals) [S, L] against the
  reference's logits [S, L, 5]; with the bfloat16 reference's logits as
  `yard_logits`, also the two numbers taken against that yardstick."""
  out = _absolute(ref_logits, ids, quals)
  if yard_logits is not None:
    yard = _absolute(ref_logits, *served_from_logits(yard_logits))
    for name in ('id_gap_mean', 'qual_diff_mean'):
      out[name + '_vs_bf16'] = (
          out[name] / yard[name] if yard[name] > 0 else float('inf'))
  return out


def _absolute(ref_logits, ids, quals):
  z = ref_logits.astype(np.float64)
  best = z.max(axis=-1)
  served = np.take_along_axis(z, ids[..., None].astype(np.int64), -1)[..., 0]
  gap = best - served
  ref_q = ref.phred(_softmax(z).max(axis=-1))
  dq = np.abs(quals.astype(np.int64) - ref_q)
  return {
      'id_gap_mean': float(gap.mean()),
      'id_gap_max': float(gap.max()),
      'id_mismatch_share': float((gap > 0).mean()),
      'qual_diff_mean': float(dq.mean()),
      'qual_diff_max': float(dq.max()),
  }


def judge(values: dict, limits: dict):
  """[(name, value, limit, ok), ...] for every number that has a limit;
  a number that is missing or not finite fails."""
  out = []
  for name, spec in limits.items():
    value = values.get(name)
    limit = float(spec['limit'])
    ok = value is not None and np.isfinite(value) and value <= limit
    out.append((name, value, limit, bool(ok)))
  return out
