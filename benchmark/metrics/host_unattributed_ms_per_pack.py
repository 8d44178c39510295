"""Self time of the `submit`, `flush` and `dispatch` spans inside the window,
per pack: each one's duration minus what its child spans cover (the spans
whose `args.parent` is its `args.span`; children of one parent run one after
the other on its thread). Host time that no leaf span names: if it grows, a
site is missing."""

OWNERS = ('submit', 'flush', 'dispatch')


def read(r):
  owners = [s for name in OWNERS for s in r.spans.get(name, ())]
  packs = r.result['counters'].get('n_packs')
  if not owners or not packs:
    return None
  lo, hi = r.span_window

  def inside(a, b):
    return max(0.0, min(b, hi) - max(a, lo))

  covered = {}
  for spans in r.spans.values():
    for a, b, args in spans:
      parent = args.get('parent')
      if parent is not None:
        covered[parent] = covered.get(parent, 0.0) + inside(a, b)
  own = sum(max(0.0, inside(a, b) - covered.get(args.get('span'), 0.0))
            for a, b, args in owners)
  return 1e3 * own / packs
