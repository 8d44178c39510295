"""The routed experts' share of their roofline: what router, dispatch,
grouped products and combine need (the family's `moe_work`: the router's
product for every routed position and three products for every assignment
that fell on a held expert, both as the program's `finalize_drain` spans
inside the window counted them, not as expected; the held experts' and the
router's weights once a pack and the stream in and out as bytes) / device
seconds in scope `moe` (inside `ffn`; the shared expert has a scope of its
own). An uneven router cannot read over 100%: the work is what was routed.
Only on a chip, and only from a program that counts its assignments.

The TPU compiler rewrites each grouped product (`jax.lax.ragged_dot`) into
two custom calls that it names `ragged-dot-none` and `ragged-dot-metadata`,
and their name path is that name alone: the scope the program gave them is
lost (seen in the trace, PR 32). The routed experts are the only grouped
products of the program, so operations so named count as scope `moe`."""
from benchmark.lib import scope_roofline

MOE_SCOPES = scope_roofline.scope_pattern('moe') + '|^ragged-dot'


def moe_seconds(r) -> float:
  """Device seconds of the routed experts in the traced window."""
  lo, hi = r.trace_window
  return r.xplane.scope_seconds(r.planes, lo, hi, MOE_SCOPES)


def counted(r):
  """(positions routed, assignments on held experts, packs) of the packs
  drained inside the window, or None where the program counts none."""
  lo, hi = r.span_window
  packs = [args for start, _end, args in r.spans.get('finalize_drain', ())
           if lo <= start < hi and args.get('moe_assignments_total')]
  if not packs:
    return None
  layers = len(r.shape['layer_pattern'])
  per_position = r.shape['num_experts_per_tok'] * layers
  return (sum(a['moe_assignments_total'] for a in packs) // per_position,
          sum(a['moe_assignments_held'] for a in packs), len(packs))


def read(r):
  moe_work = getattr(r.work, 'moe_work', None)
  if not r.on_chip or r.peaks is None or moe_work is None:
    return None
  count = counted(r)
  seconds = moe_seconds(r)
  if count is None or not seconds:
    return None
  need = moe_work(r.shape, *count)
  least = max(need['flops'] / r.peaks['bf16_flops_per_s'],
              need['bytes'] / r.peaks['hbm_bytes_per_s'])
  return 100.0 * least / (seconds * r.chips)
