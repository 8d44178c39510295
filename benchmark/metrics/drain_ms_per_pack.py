"""Host time of the `finalize_drain` spans inside the window, per pack: the
blocking wait for a pack's planes and their copy to the host."""


def read(r):
  spans = r.spans.get('finalize_drain')
  packs = r.result['counters'].get('n_packs')
  if not spans or not packs:
    return None
  lo, hi = r.span_window
  return 1e3 * r.spans_lib.seconds_in(spans, lo, hi) / packs
