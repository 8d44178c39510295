"""Host time of the `pack_cut` spans inside the window, per pack: the packer's
concatenate of the carried tail with the new rows, the slice and the
ticket-list cut, not the dispatch that follows."""


def read(r):
  spans = r.spans.get('pack_cut')
  packs = r.result['counters'].get('n_packs')
  if not spans or not packs:
    return None
  lo, hi = r.span_window
  return 1e3 * r.spans_lib.seconds_in(spans, lo, hi) / packs
