"""How unevenly the router loads the held experts: the most assignments
any one held expert took in one layer of one pack, over the mean a held
expert took (assignments on held experts / experts held / layers), as the
program's `finalize_drain` spans inside the window state them
(`moe_expert_load_max`, `moe_assignments_held`); the largest over the
window's packs. 1.0 is an even router; the grouped products wait for the
fullest group. From the program's counts, so on any device."""


def read(r):
  lo, hi = r.span_window
  packs = [args for start, _end, args in r.spans.get('finalize_drain', ())
           if lo <= start < hi and args.get('moe_assignments_held')]
  if not packs:
    return None
  groups = r.shape['num_experts'] * len(r.shape['layer_pattern'])
  return max(a['moe_expert_load_max'] * groups / a['moe_assignments_held']
             for a in packs)
