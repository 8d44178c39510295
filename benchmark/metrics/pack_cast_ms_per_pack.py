"""Host time of the `pack_cast` spans inside the window, per pack: pad, uint8
cast, bq bias and SN gather in `ModelRunner.dispatch`."""


def read(r):
  spans = r.spans.get('pack_cast')
  packs = r.result['counters'].get('n_packs')
  if not spans or not packs:
    return None
  lo, hi = r.span_window
  return 1e3 * r.spans_lib.seconds_in(spans, lo, hi) / packs
