"""Share of dispatched rows that were padding: n_pad_rows over all rows of
the packs the window cut (exact counts of the engine's packer)."""


def read(r):
  c = r.result['counters']
  total = (c.get('n_pack_rows') or 0) + (c.get('n_pad_rows') or 0)
  if not total:
    return None
  return 100.0 * c['n_pad_rows'] / total
