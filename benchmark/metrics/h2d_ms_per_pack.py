"""Host time of the `h2d_transfer` spans inside the window, per pack."""


def read(r):
  spans = r.spans.get('h2d_transfer')
  packs = r.result['counters'].get('n_packs')
  if not spans or not packs:
    return None
  lo, hi = r.span_window
  return 1e3 * r.spans_lib.seconds_in(spans, lo, hi) / packs
