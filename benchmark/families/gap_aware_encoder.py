"""Family `gap_aware_encoder`: the published DeepConsensus model, what a
configuration file without a `family` key is.

A family is what belongs to one model architecture, found by the name in
the configuration file (`benchmark/families/<family>.py`):

  shape_of(config)              the sizes the family needs (KeyError on a
                                missing one)
  stated(params)                what the program's finalized preset says of
                                those sizes; the harness refuses to run
                                where the file and the program disagree
  make_params(shape, seed)      the seeded parameter tree on the device, in
                                the type it is served in (here float32)
  flops_per_window, bytes_per_pack, param_count, least_seconds_per_pack
                                the work one window or pack needs, from
                                shapes alone; metrics reach them as
                                `reading.work`
  reference_logits(params, windows, shape, precision, block) -> [S, L, 5]
                                the plain reference, input clipping
                                included

This one is thin: the tree is benchmark/lib/weights.py, the work is
benchmark/lib/work.py and the reference is benchmark/reference/forward.py.
"""
from __future__ import annotations

import numpy as np

from benchmark.lib import weights, work
from benchmark.reference import forward as ref

SIZE_KEYS = ('num_hidden_layers', 'hidden_size', 'filter_size', 'num_heads',
             'attn_win_size', 'max_passes', 'max_length', 'total_rows',
             'condense_input_size', 'embedding', 'PW_MAX', 'IP_MAX',
             'STRAND_MAX', 'SN_MAX')


def shape_of(config: dict) -> dict:
  return {k: config[k] for k in SIZE_KEYS}


def stated(params) -> dict:
  return {
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


make_params = weights.make_params
flops_per_window = work.flops_per_window
bytes_per_pack = work.bytes_per_pack
param_count = work.param_count
least_seconds_per_pack = work.least_seconds_per_pack


def geometry(shape: dict) -> dict:
  return dict(max_passes=shape['max_passes'],
              num_layers=shape['num_hidden_layers'],
              num_heads=shape['num_heads'], band=shape['attn_win_size'])


def reference_logits(params, windows: np.ndarray, shape: dict,
                     precision: str = 'float32', block: int = 256):
  """windows [S, R, L, 1] as generated -> reference logits [S, L, 5]."""
  rows = np.asarray(windows, np.float32)[..., 0]
  # The published input pipeline clips kinetics and SN to the embedding
  # tables' ranges before the model sees them.
  p = shape['max_passes']
  rows = rows.copy()
  rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
  rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
  rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
  return ref.forward_blocks(params, rows, geometry=geometry(shape),
                            precision=precision, block=block)
