"""Host time of the `deliver` spans inside the window, per pack: two casts
and the per-ticket callbacks."""


def read(r):
  spans = r.spans.get('deliver')
  packs = r.result['counters'].get('n_packs')
  if not spans or not packs:
    return None
  lo, hi = r.span_window
  return 1e3 * r.spans_lib.seconds_in(spans, lo, hi) / packs
