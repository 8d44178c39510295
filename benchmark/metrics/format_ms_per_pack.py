"""Host time of the `format_rows` spans (`format_rows_batch`) inside the
window, per pack."""


def read(r):
  spans = r.spans.get('format_rows')
  packs = r.result['counters'].get('n_packs')
  if not spans or not packs:
    return None
  lo, hi = r.span_window
  return 1e3 * r.spans_lib.seconds_in(spans, lo, hi) / packs
