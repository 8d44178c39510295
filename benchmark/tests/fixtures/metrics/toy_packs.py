"""Fixture metric: shows that a per-layer metric is one new file."""


def read(r):
  return float(r.result['counters']['n_packs'])
