"""How unevenly the router loads the held experts, over the EXPERT layers
alone: the most assignments any one held expert took in one layer of one
pack, over the mean a held expert took (assignments on held experts /
experts held / expert layers), as the program's `finalize_drain` spans
inside the window state them; the largest over the window's packs. 1.0 is
an even router; the grouped products wait for the fullest group.
`expert_load_max_over_mean` reckons one expert layer a letter of
`layer_pattern`; this one reads the family's `expert_layers`. From the
program's counts, so on any device."""


def read(r):
  expert_layers = getattr(r.work, 'expert_layers', None)
  if expert_layers is None:
    return None
  lo, hi = r.span_window
  packs = [args for start, _end, args in r.spans.get('finalize_drain', ())
           if lo <= start < hi and args.get('moe_assignments_held')]
  if not packs:
    return None
  first, end = r.shape['experts_held']
  groups = (end - first) * expert_layers(r.shape)
  return max(a['moe_expert_load_max'] * groups / a['moe_assignments_held']
             for a in packs)
