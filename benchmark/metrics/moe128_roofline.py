"""The routed experts' share of their roofline in a stack whose leading
layers are dense: `moe_roofline` over the EXPERT layers alone. What router,
dispatch, grouped products and combine need (the family's `moe_work`: the
router's product for every routed position and three products for every
assignment that fell on a held expert, both as the program's
`finalize_drain` spans inside the window counted them; the stream in and
out, the router's weights once a pack and the held experts' once a turn as
bytes) / device seconds in scope `moe` with the compiler's `ragged-dot`
calls (`moe_roofline.moe_seconds`: the grouped products lose their scope).
An uneven router cannot read over 100%: the work is what was routed. Only
on a chip, and only from a program that counts its assignments.

`moe_roofline` reckons one expert layer a letter of `layer_pattern`; this
one reads the family's `expert_layers` (the letters `E` of `ffn_pattern`)."""
from benchmark.metrics import moe_roofline


def counted(r):
  """(positions routed, assignments on held experts, packs) of the packs
  drained inside the window, or None where the program counts none."""
  expert_layers = getattr(r.work, 'expert_layers', None)
  if expert_layers is None:
    return None
  lo, hi = r.span_window
  packs = [args for start, _end, args in r.spans.get('finalize_drain', ())
           if lo <= start < hi and args.get('moe_assignments_total')]
  if not packs:
    return None
  per_position = r.shape['num_experts_per_tok'] * expert_layers(r.shape)
  return (sum(a['moe_assignments_total'] for a in packs) // per_position,
          sum(a['moe_assignments_held'] for a in packs), len(packs))


def read(r):
  moe_work = getattr(r.work, 'moe_work', None)
  if not r.on_chip or r.peaks is None or moe_work is None:
    return None
  count = counted(r)
  seconds = moe_roofline.moe_seconds(r)
  if count is None or not seconds:
    return None
  need = moe_work(r.shape, *count)
  least = max(need['flops'] / r.peaks['bf16_flops_per_s'],
              need['bytes'] / r.peaks['hbm_bytes_per_s'])
  return 100.0 * least / (seconds * r.chips)
