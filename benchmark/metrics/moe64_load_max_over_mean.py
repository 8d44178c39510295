"""How unevenly the softmax router loads the 64 held experts: the most
assignments any one held expert took in one layer of one pack, over the
mean a held expert took, the largest over the window's packs;
`moe128_load_max_over_mean` under the name of the cell whose experts are 64
a layer. From the program's counts, so on any device."""
from benchmark.metrics import moe128_load_max_over_mean


def read(r):
  return moe128_load_max_over_mean.read(r)
