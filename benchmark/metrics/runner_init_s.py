"""Seconds of `ModelRunner.__init__` (`runner_init`: preparing the weights,
placing them on the device and waiting for them, building the jitted
forward): the last one that ended before the window started."""


def read(r):
  start = r.span_window[0]
  spans = [s for s in r.spans.get('runner_init', ()) if s[1] <= start]
  if not spans:
    return None
  a, b, _ = max(spans, key=lambda s: s[1])
  return b - a
