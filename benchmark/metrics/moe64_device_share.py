"""Share of the device's busy time that the routed experts take: device
seconds in scope `moe` (router, dispatch, grouped products, combine) / busy
seconds, in the traced window; `moe_device_share` under the name of the
cell whose 64 experts a layer are all held and nothing else feeds forward.
Only on a chip."""
from benchmark.metrics import moe_device_share


def read(r):
  return moe_device_share.read(r)
