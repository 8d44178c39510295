"""Host time of the `stack_windows` spans inside the window, per pack: what
taking a list of per-window views and not an array costs (the grouping by
width, then each np.stack)."""


def read(r):
  spans = r.spans.get('stack_windows')
  packs = r.result['counters'].get('n_packs')
  if not spans or not packs:
    return None
  lo, hi = r.span_window
  return 1e3 * r.spans_lib.seconds_in(spans, lo, hi) / packs
