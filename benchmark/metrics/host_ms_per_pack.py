"""Host time of the `submit` and `flush` spans inside the window, per pack:
the main thread is always inside one of them, so this is the pack period
as the program itself sees it."""


def read(r):
  spans = [s for name in ('submit', 'flush') for s in r.spans.get(name, ())]
  packs = r.result['counters'].get('n_packs')
  if not spans or not packs:
    return None
  lo, hi = r.span_window
  return 1e3 * r.spans_lib.seconds_in(spans, lo, hi) / packs
