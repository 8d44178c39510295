"""Parameter bytes resident on the device, in GiB, as the program's
`forward_launch` spans inside the window state them (`weight_bytes`: the
runner's leaves by their dtype): a second copy or an upcast of the
weights shows here before it shows as a failed allocation."""


def read(r):
  lo, hi = r.span_window
  stated = [args['weight_bytes'] for start, _end, args in
            r.spans.get('forward_launch', ())
            if lo <= start < hi and args.get('weight_bytes')]
  return max(stated) / 2**30 if stated else None
