"""What the TPU compiler accepts, asked without a TPU.

The only file in the repo that describes the chip: every kernel and
jitted forward the main path runs is lowered and compiled for a
described (not attached) `v5e:2x2` device at production shapes
(6 layers x hidden 280 x filter 2048, 85 rows x L=100, batch 1024 for
inference, the attention sublayer kernel the bfloat16 forward takes by
itself among them, and 256 for the loss; two layers of the power-retention block
kind at hidden 5120, batch 256; one period of the gated-delta and
sparse-experts kind at hidden 2048 with 256 of 512 experts, batch 512; one
dense and one expert layer of the latent-attention kind at hidden 2048 with
128 experts, batch 512, and its attention's kernel alone at that pack; one period of the parallel window-and-full kind at
hidden 4096 with 16 of 128 experts, batch 256, and of the window-and-full
kind at hidden 2304, batch 512, with the grouped-head attention's kernel
alone at both packs and at the Mamba hybrid's group of 20; one Mamba
mixer at hidden 2560, batch 256, its selective scan's kernel inside; the grouped-product kernel
and the combine's kernel the three take, each alone at one turn of each). A compile that passes
here is not a
chip run — chip_smoke.py is — but a kernel Mosaic refuses fails here
first, at no chip time.

Rules this file keeps (they are what makes it safe under pytest-xdist):
the topology is described inside a module-scoped fixture, never at
import/collection time; every compile happens in this process; the
persistent compile cache is off around the module (a described-device
executable cannot be read back without a chip).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from deepconsensus_tpu.calibration import lib as calibration_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import losses as losses_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.models import quantize as quantize_lib
from deepconsensus_tpu.ops import pallas_util

BATCH = 1024
TRAIN_BATCH = 256


@pytest.fixture(scope='module')
def topo():
  os.environ.setdefault('TPU_LOG_DIR', 'disabled')
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache

  try:
    desc = topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2')
  except Exception as e:  # any failure to describe means: not here
    pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
  cache_was_on = jax.config.jax_enable_compilation_cache
  jax.config.update('jax_enable_compilation_cache', False)
  compilation_cache.reset_cache()
  yield desc
  jax.config.update('jax_enable_compilation_cache', cache_was_on)
  compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
  return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
  """Steers every Pallas call to the Mosaic compiler: the backend here
  is still the CPU, so the default would resolve to interpret mode."""
  monkeypatch.setattr(pallas_util, 'resolve_interpret', lambda _: False)


def _params(**overrides):
  p = config_lib.get_config('transformer_learn_values+test')
  with p.unlocked():
    for key, value in overrides.items():
      p[key] = value
  config_lib.finalize_params(p, is_training=False)
  return p


def _abstract(tree, sharding):
  return jax.tree.map(
      lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
      tree)


def _variables(p):
  """Real (CPU) variables for p with the load-time levers applied — the
  int8 variant needs values to quantize, not just shapes."""
  variables = model_lib.get_model(p).init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32))
  return quantize_lib.prepare_inference_variables(variables, p)[0]


def _compile_forward(p, sharding, batch=BATCH, length=None, lengths=None):
  model = model_lib.get_model(p)
  variables = _abstract(_variables(p), sharding)
  rows = jax.ShapeDtypeStruct(
      (batch, p.total_rows, length or p.max_length, 1), jnp.float32,
      sharding=sharding)
  if lengths is None:
    return jax.jit(model.apply).lower(variables, rows).compile()
  lens = jax.ShapeDtypeStruct(lengths, jnp.int32, sharding=sharding)
  fn = lambda v, r, l: model.apply(v, r, window_lengths=l)
  return jax.jit(fn).lower(variables, rows, lens).compile()


def _n_kernels(compiled):
  return compiled.as_text().count('tpu_custom_call')


def _entry_computation(text):
  """The instructions whose results are buffers of the program: what a
  fused computation holds inside is never written to memory."""
  return text[text.index('\nENTRY '):].splitlines()


def test_xla_forward_b1024(one_chip):
  compiled = _compile_forward(_params(), one_chip)
  assert _n_kernels(compiled) == 0
  # Every embedding table is looked up by a product with a one-hot that
  # XLA fuses into the product's operand: no gather is left (the parent of
  # PR 29 had six, bound by the index at 1.7 ns each on the chip), and no
  # one-hot over a vocabulary (256 for pw and ip, 501 for sn) is written.
  text = compiled.as_text()
  assert not re.search(r'\bgather\(', text)
  assert 'iota_compare_fusion' in text
  # Each of the five tables is rounded to bfloat16 by an operation the
  # compiler keeps: without it the chip read one rounding fewer than the
  # CPU and than the gather form did (PERF.md section 6, PR 29).
  assert text.count('reduce-precision(') == 5
  one_hots = [line for line in _entry_computation(text)
              if re.search(r'= \(?\w+\[[0-9,]*,(256|501)\]', line)]
  assert not one_hots, one_hots
  # The temporaries of a pack of 1,024: 671,083,008 bytes with the gathers
  # (their [2048000, 8] outputs, the transposing copies, the int32 indices),
  # 216,762,880 with the products, which write into the concat in place.
  assert compiled.memory_analysis().temp_size_in_bytes < 300 << 20


def test_power_retention_forward_b256_at_published_widths(one_chip):
  """The second block kind as it is served: hidden 5120, 40 / 8 heads of
  128, SwiGLU 17408, bfloat16 leaves, a pack of 256 windows; two of the
  layers, by shape alone (no array of the 1.3 GB is made)."""
  p = config_lib.get_config('transformer_learn_values_retention+custom')
  with p.unlocked():
    p.num_hidden_layers = 2
  config_lib.finalize_params(p, is_training=False)
  model = model_lib.get_model(p)
  tree = jax.eval_shape(
      lambda key: model.init(
          key, jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)),
      jax.random.PRNGKey(0))
  variables = jax.tree.map(
      lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                     sharding=one_chip), tree)
  rows = jax.ShapeDtypeStruct(
      (256, p.total_rows, p.max_length, 1), jnp.float32, sharding=one_chip)
  compiled = jax.jit(model.apply).lower(variables, rows).compile()
  assert _n_kernels(compiled) == 0
  memory = compiled.memory_analysis()
  # Two layers of 330,352,904 bfloat16 parameters and what lies outside.
  assert 2 * 2 * 330_352_904 < memory.argument_size_in_bytes < 1.4e9
  # The temporaries of a pack do not grow with depth: 8 layers (5.3 GB of
  # weights) leave the chip's other 10 GB to them.
  assert memory.temp_size_in_bytes < 3 << 30
  # Grouped heads: no repeat of k or v to 40 heads is materialised.
  assert 'bf16[256,100,40,128]' in compiled.as_text()
  assert 'repeat' not in compiled.as_text()


def test_gated_delta_hybrid_forward_b512_at_published_widths(
    one_chip, compiled_kernels, monkeypatch):
  """The third block kind as it is served on one chip: one period of the
  pattern (three Gated DeltaNet layers, one gated softmax layer), experts
  0-255 of 512 in each, bfloat16 leaves, a pack of 512 windows, by shape
  alone (no array of the 6.3 GiB is made). As ModelRunner traces it
  without a mesh: the delta rule takes its window kernel, the grouped
  products the kernel whose grid follows the groups, the combine the
  kernel a tile of tokens."""
  p = config_lib.get_config('transformer_learn_values_gdn_moe+custom')
  with p.unlocked():
    p.num_hidden_layers = 4
    p.experts_held_count = 256
  config_lib.finalize_params(p, is_training=False)
  model = model_lib.get_model(p)
  tree = jax.eval_shape(
      lambda key: model.init(
          key, jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)),
      jax.random.PRNGKey(0))['params']
  variables = {'params': jax.tree.map(
      lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                     sharding=one_chip), tree)}
  rows = jax.ShapeDtypeStruct(
      (512, p.total_rows, p.max_length, 1), jnp.float32, sharding=one_chip)
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)

  def forward(variables, rows):
    with pallas_util.single_device_inference():
      return model.apply(variables, rows, mutable=['moe_counts'])

  compiled = jax.jit(forward).lower(variables, rows).compile()
  text = compiled.as_text()
  # One window kernel a DeltaNet layer, and in every layer two calls of the
  # grouped products' kernel (gate and up as one, down) and one of the
  # combine's: none of the compiler's own grouped products, no masked dense
  # product a group.
  assert text.count('gated_delta_window') >= 3
  # The kernel takes the flat stream of each direction as the convolution
  # leaves it and writes the gated norm's output in the stream's type: no
  # copy of q, k, v padded to 128 positions, stacked or re-laid by heads,
  # no float32 copy of the rule's output.
  assert 'bf16[2,512,128,' not in text
  assert 'bf16[2,512,100,' not in text
  assert 'f32[512,128,4096]' not in text
  assert 'f32[512,100,4096]' not in text
  assert 'ragged-dot' not in text
  # (A layer's two turns are one loop, which the compiler may unroll.)
  assert text.count(' custom-call(') >= 3 + 4 * 3
  assert len(re.findall(r'%grouped_gated_up\S* = ', text)) in (4, 8)
  assert len(re.findall(r'%grouped_product\S* = ', text)) in (4, 8)
  assert len(re.findall(r'%moe_combine\S* = ', text)) in (4, 8)
  assert _n_kernels(compiled) in (3 + 4 * 3, 3 + 8 * 3)
  # The combine's kernel copies the held rows itself: no gather of the
  # down product's output is left, and no [k, tokens, hidden] array of the
  # rows that came back.
  assert 'combine/jit(_take)/gather' not in text
  assert 'bf16[10,25600,2048]' not in text
  # Gate and up never leave their kernel: no [rows, width] pair in float32
  # and no second bfloat16 one beside the gated product.
  assert 'f32[256000,512]' not in text
  # A turn's 25,600 tokens stay in VMEM for the dispatch's gather to read.
  assert 'bf16[25600,2048]{1,0:T(8,128)(2,1)S(1)}' in text
  memory = compiled.memory_analysis()
  # 3,366,446,144 block parameters and what lies outside, 2 bytes each.
  assert 2 * 3_366_446_144 < memory.argument_size_in_bytes < 6.8e9
  # With the weights, a pack's temporaries have to leave room on a chip of
  # 15.75 GiB: the convolution's output of both directions (what the
  # delta rule reads, 0.84 GB each) and one turn of the experts' sorted rows are
  # the largest. 4.20e9 as compiled (PR 33; 6.2 GiB before it), and a
  # tenth.
  assert memory.temp_size_in_bytes < 4.65e9
  # The experts' sorted rows are one turn of 25,600 tokens in bfloat16
  # (the temporaries above would not hold the pack's 512,000 at once).
  assert 'bf16[256000,2048]' in text


def test_latent_attention_moe_forward_b512_at_published_widths(
    one_chip, compiled_kernels, monkeypatch):
  """The fourth block kind as it is served on one chip, by shape alone: the
  leading dense layer and one expert layer (of the seven a chip holds) at
  the published widths, all 128 experts, bfloat16 leaves, a pack of 512
  windows. As ModelRunner traces it without a mesh: the stream flat, the
  latent attention's operator the kernel a tile of windows with its
  operands where the flat products write them, the grouped products the
  kernel whose grid follows the groups, the combine the kernel a tile of
  tokens."""
  p = config_lib.get_config('transformer_learn_values_mla_moe+custom')
  with p.unlocked():
    p.num_hidden_layers = 2
  config_lib.finalize_params(p, is_training=False)
  assert config_lib.ffn_pattern(p) == 'DE'
  model = model_lib.get_model(p)
  tree = jax.eval_shape(
      lambda key: model.init(
          key, jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)),
      jax.random.PRNGKey(0))['params']
  variables = {'params': jax.tree.map(
      lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                     sharding=one_chip), tree)}
  rows = jax.ShapeDtypeStruct(
      (512, p.total_rows, p.max_length, 1), jnp.float32, sharding=one_chip)
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)

  def forward(variables, rows):
    with pallas_util.single_device_inference():
      return model.apply(variables, rows, mutable=['moe_counts'])

  compiled = jax.jit(forward).lower(variables, rows).compile()
  text = compiled.as_text()
  # The expert layer's grouped products as two calls of the kernel (gate
  # and up as one, down): none of the compiler's own, no masked dense
  # product a group.
  assert 'ragged-dot' not in text
  # (The layer's two turns are one loop, which the compiler may unroll.)
  assert len(re.findall(r'%grouped_gated_up\S* = ', text)) in (1, 2)
  assert len(re.findall(r'%grouped_product\S* = ', text)) in (1, 2)
  # And the combine as one call of its own: no gather of the down
  # product's output, no [k, tokens, hidden] array.
  assert len(re.findall(r'%moe_combine\S* = ', text)) in (1, 2)
  # And each layer's latent attention as one call: no score tensor, no
  # [B, L, N, D] or [B, N, L, D] array of q, k or v.
  calls = re.findall(
      r'%latent_window_tile\S* = \S+ custom-call\(([^)]*)\)', text)
  assert len(calls) == 2
  assert _n_kernels(compiled) in (5, 8)
  assert 'f32[512,32,100,100]' not in text
  assert 'bf16[512,32,100,100]' not in text
  assert not re.search(r'bf16\[512,(100,32|32,100),(64|128|192|256)\]', text)
  # The call reads what the products' fusions (and the rotation's, and the
  # placed keys') wrote and the output product reads what it wrote: XLA
  # puts no copy in front of an operand or behind the result. The only
  # copies of the stream's size are its own on the way in and out,
  # [512,100,2048] <-> [51200,2048].
  made_by = dict(re.findall(r'\n\s*(%\S+) = \S+ (\S+?)\(', text))
  for operands in calls:
    for operand in operands.split(', '):
      assert made_by[operand.split(' ')[-1]] in (
          'fusion', 'get-tuple-element'), operand
  assert not re.search(
      r'= bf16\[(51200|512,100),(4096|8192|1024)\]\S* copy\(', text)
  assert len(re.findall(r'= bf16\[51200,2048\]\S* copy\(', text)) == 0
  assert len(re.findall(r'= bf16\[512,100,2048\]\S* copy\(', text)) <= 2
  assert 'combine/jit(_take)/gather' not in text
  assert 'bf16[6,25600,2048]' not in text
  assert 'f32[153600,768]' not in text
  # A turn's 25,600 tokens (100 MiB) stay in VMEM for the dispatch's gather
  # to read, as they did beside the compiler's own grouped products: with
  # the sort's weights handed to the kernel's call as they were, XLA's
  # memory assignment left them in HBM and the gather ran 7x as long.
  assert 'bf16[25600,2048]{1,0:T(8,128)(2,1)S(1)}' in text
  # The one rotary key is scored out of four placed copies a layer
  # ([51200, 1024]: a lane tile a half a head of a group): neither a key
  # of 192 a head nor the rotary key repeated to 32 heads is laid out.
  assert 'bf16[51200,1024]' in text
  assert 'bf16[51200,6144]' in text  # the dense layer's gate and up
  assert not re.search(r'bf16\[51200,(12288|2048,192)\]', text)
  memory = compiled.memory_analysis()
  # 64,098,816 + 640,029,312 block parameters and what lies outside.
  assert 2 * 704_128_128 < memory.argument_size_in_bytes < 1.45e9
  # A pack's temporaries do not grow with depth (2.80 GiB at 8 layers, my
  # compile of PR 34): with 8.46 GiB of weights they leave the chip room.
  assert memory.temp_size_in_bytes < 3.2 * 2**30
  # The experts' sorted rows are one turn of 25,600 tokens, six
  # assignments each, in bfloat16.
  assert 'bf16[153600,2048]' in text


def test_parallel_window_moe_forward_b256_at_published_widths(
    one_chip, compiled_kernels, monkeypatch):
  """The fifth block kind as it is served on one chip, by shape alone (no
  array of the 8.57 GiB is made): one period of the pattern (three window
  layers, one full layer) at the published widths, experts 0-15 of 128 in
  each, bfloat16 leaves, a pack of 256 windows. As ModelRunner traces it
  without a mesh: the stream flat, the attention flat products round the
  grouped-head kernel a tile of windows (the window layers' rotation in
  it), the grouped products the kernel whose grid follows the groups with
  a [4096, 4096] matrix in column blocks, the combine the kernel a tile of
  tokens."""
  p = config_lib.get_config('transformer_learn_values_parallel_moe+custom')
  with p.unlocked():
    p.num_hidden_layers = 4
    p.experts_held_count = 16
  config_lib.finalize_params(p, is_training=False)
  assert config_lib.layer_pattern(p) == 'WWWF'
  model = model_lib.get_model(p)
  tree = jax.eval_shape(
      lambda key: model.init(
          key, jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)),
      jax.random.PRNGKey(0))['params']
  variables = {'params': jax.tree.map(
      lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                     sharding=one_chip), tree)}
  rows = jax.ShapeDtypeStruct(
      (256, p.total_rows, p.max_length, 1), jnp.float32, sharding=one_chip)
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)

  def forward(variables, rows):
    with pallas_util.single_device_inference():
      return model.apply(variables, rows, mutable=['moe_counts'])

  compiled = jax.jit(forward).lower(variables, rows).compile()
  text = compiled.as_text()
  # In every layer two calls of the grouped products' kernel (gate and up
  # as one, down) and one of the combine's: none of the compiler's own
  # grouped products. (A layer's two turns are one loop, which the compiler
  # may unroll.)
  assert 'ragged-dot' not in text
  assert len(re.findall(r'%grouped_gated_up\S* = ', text)) in (4, 8)
  assert len(re.findall(r'%grouped_product\S* = ', text)) in (4, 8)
  assert len(re.findall(r'%moe_combine\S* = ', text)) in (4, 8)
  # And each layer's grouped-head attention as one call, the three window
  # layers' with their tables.
  calls = re.findall(
      r'%grouped_window_tile\S* = \S+ custom-call\(([^)]*)\)', text)
  assert sorted(len(c.split(', ')) for c in calls) == [3, 5, 5, 5]
  assert _n_kernels(compiled) in (16, 28)
  assert 'combine/jit(_take)/gather' not in text
  # A turn is 12,800 tokens of 8 assignments: rows of 8 kB, two turns a
  # pack, never the pack's 204,800 at once.
  assert 'bf16[102400,4096]' in text and 'bf16[204800,4096]' not in text
  # A turn's 12,800 tokens (100 MiB) stay in VMEM for the dispatch's gather
  # to read.
  assert 'bf16[12800,4096]{1,0:T(8,128)(2,1)S(1)}' in text
  # No score tensor, no query in float32 for its rotation, no [B, L, N, D]
  # array of q, k or v, and no k or v repeated to the 128 query heads.
  assert not re.search(r'f32\[256,8,16,100,100\]|\[256,100,(128|8),128\]',
                       text)
  # At L=100 the window of 4,096 masks nothing and builds no mask.
  assert 'pred[100,100]' not in text
  memory = compiled.memory_analysis()
  # 4,599,070,720 block parameters and what lies outside, 2 bytes each.
  assert 2 * 4_599_070_720 < memory.argument_size_in_bytes < 9.25e9
  # With 8.57 GiB of weights a pack's temporaries have to leave room on a
  # chip of 15.75 GiB: 2.58 GiB as compiled, where the plain attention's
  # were 4.89 (a window layer's query in float32 for its rotation, 1.56
  # GiB, and the scores, 1.22 GiB, were the largest); a twentieth over.
  assert memory.temp_size_in_bytes < 2.71 * 2**30


def test_window_moe_forward_b512_at_published_widths(
    one_chip, compiled_kernels, monkeypatch):
  """The sixth block kind as it is served on one chip, by shape alone (no
  array of the 6.22 GiB is made): two periods of the listed pattern
  (`WWWFWWWF`) at the published widths, all 64 experts of each layer,
  bfloat16 leaves, a pack of 512 windows. As ModelRunner traces it without
  a mesh: the stream flat, the attention flat products round the
  grouped-head kernel a tile of windows with the rotation in it (no scope
  `rotary` left), the grouped products and the combine their kernels, and
  no shared expert anywhere."""
  p = config_lib.get_config('transformer_learn_values_window_moe+custom')
  with p.unlocked():
    p.num_hidden_layers = 8
    p.layer_types = list(p.layer_types)[:8]
    p.mlp_layer_types = list(p.mlp_layer_types)[:8]
  config_lib.finalize_params(p, is_training=False)
  assert config_lib.layer_pattern(p) == 'WWWFWWWF'
  model = model_lib.get_model(p)
  tree = jax.eval_shape(
      lambda key: model.init(
          key, jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)),
      jax.random.PRNGKey(0))['params']
  variables = {'params': jax.tree.map(
      lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                     sharding=one_chip), tree)}
  rows = jax.ShapeDtypeStruct(
      (512, p.total_rows, p.max_length, 1), jnp.float32, sharding=one_chip)
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)

  def forward(variables, rows):
    with pallas_util.single_device_inference():
      return model.apply(variables, rows, mutable=['moe_counts'])

  compiled = jax.jit(forward).lower(variables, rows).compile()
  text = compiled.as_text()
  # In every layer two calls of the grouped products' kernel and one of
  # the combine's (a layer's four turns are one loop, which the compiler may
  # unroll); none of the compiler's own grouped products.
  assert 'ragged-dot' not in text
  assert len(re.findall(r'%grouped_gated_up\S* = ', text)) in (8, 32)
  assert len(re.findall(r'%grouped_product\S* = ', text)) in (8, 32)
  assert len(re.findall(r'%moe_combine\S* = ', text)) in (8, 32)
  assert 'shared_expert' not in text
  # A turn is 12,800 tokens of 8 assignments, four turns a pack: two turns'
  # 25,600 tokens (112.5 MiB) would stay in HBM, where the dispatch's gather
  # reads a row about four times as slowly; 12,800 (56.25 MiB) stay in VMEM.
  assert 'bf16[102400,2304]' in text
  assert 'bf16[204800,2304]' not in text and 'bf16[409600,2304]' not in text
  assert 'bf16[12800,2304]{1,0:T(8,128)(2,1)S(1)}' in text
  assert 'bf16[25600,2304]' not in text
  # Each layer's grouped-head attention as one call with its layer type's
  # two tables: no score tensor, no float32 q or k, no [B, L, N, D] array;
  # at L=100 the window of 1,024 masks nothing and builds no mask.
  calls = re.findall(
      r'%grouped_window_tile\S* = \S+ custom-call\(([^)]*)\)', text)
  assert [len(c.split(', ')) for c in calls] == [5] * 8
  assert not re.search(r'f32\[512,4,8,100,100\]|\[512,100,(32|4),128\]',
                       text)
  assert '/rotary/' not in text
  assert 'pred[100,100]' not in text
  memory = compiled.memory_analysis()
  # 3,341,979,648 block parameters and what lies outside, 2 bytes each.
  assert 2 * 3_341_979_648 < memory.argument_size_in_bytes < 6.72e9
  # 6.24 GiB of arguments and 1.51 GiB of temporaries as compiled, where
  # two turns a pack took 2.13 (and the plain attention 2.24); a twentieth
  # over.
  assert memory.temp_size_in_bytes < 1.59 * 2**30


def test_mamba_mixer_b256_at_published_widths(one_chip, compiled_kernels,
                                              monkeypatch):
  """The seventh block kind's mixer as a ModelRunner without a mesh traces
  it, by shape alone: one MambaMixer at the published widths (hidden 2560,
  inner 5120, state 16, dt rank 160, 4 taps) on the flat stream of a pack of
  256 windows of 100, bfloat16 leaves. The scan is one call of its kernel
  that reads its operands where in_proj, the convolution and dt_proj left
  them: no [25600, 5120] copy into another layout, no [L, I, S] array. (The
  whole 28 layers compile to 5.34 GiB of arguments and 1.87 of
  temporaries; the attention layers' grouped-head call at a group of 20 is
  `test_grouped_window_tile_kernel_at_a_pack_of_each_cell[jamba_polish_F]`.)"""
  mixer = model_lib.MambaMixer(
      hidden_size=2560, inner=5120, state_size=16, dt_rank=160,
      conv_kernel=4, conv_bias=True, rms_norm_eps=1e-6, dtype=jnp.bfloat16)
  x = jax.ShapeDtypeStruct((25600, 2560), jnp.bfloat16, sharding=one_chip)
  tree = jax.eval_shape(
      lambda key: mixer.init(key, jnp.zeros((4, 100, 2560), jnp.bfloat16),
                             True), jax.random.PRNGKey(0))
  variables = jax.tree.map(
      lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                     sharding=one_chip), tree)
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)

  def forward(variables, x):
    with pallas_util.single_device_inference():
      return mixer.apply(variables, x, True, window_length=100)

  compiled = jax.jit(forward).lower(variables, x).compile()
  text = compiled.as_text()
  calls = re.findall(
      r'%ssm_scan_window\S* = (\S+) custom-call\(([^)]*)\)', text)
  assert [out.split('{')[0] for out, _ in calls] == ['bf16[25600,5120]']
  assert [len(args.split(', ')) for _, args in calls] == [9]
  entry = _entry_computation(text)
  assert not [line for line in entry if ' copy(' in line
              and re.search(r'\[(256,100|25600),(5120|10240)\]', line)]
  assert not re.search(r'\[\S*100,5120,16\]|\[\S*5120,16,100\]', text)
  memory = compiled.memory_analysis()
  # in_proj's output (0.49 GiB), both directions' convolution and dt
  # (0.98), the scan's y and what lies round them: 1.71 GiB as compiled; a
  # twentieth over.
  assert memory.temp_size_in_bytes < 1.80 * 2**30


@pytest.mark.parametrize('rows,groups,hidden,width,columns,held_mib', [
    (153_600, 128, 2048, 768, (768, 2048), 2),
    (256_000, 256, 2048, 512, (512, 2048), 2),
    (102_400, 16, 4096, 4096, (512, 1024), 2),
    (102_400, 64, 2304, 896, (896, 2304), 2)],
                         ids=['kanana_polish', 'qwen3next_polish',
                              'commanda_polish', 'mellum_polish'])
def test_grouped_product_kernel_at_one_turn_of_each_cell(
    one_chip, compiled_kernels, rows, groups, hidden, width, columns,
    held_mib):
  """The grouped products' kernel alone at one turn of the four cells
  that run it (25,600 tokens of 6 and of 10 assignments at hidden 2048,
  12,800 of 8 at hidden 4096, 12,800 of 8 at hidden 2304): gate and up as one call, then the down
  product, within pallas_util.GROUPED_PRODUCT_VMEM_LIMIT_BYTES: a group's
  matrices resident whole at widths 768, 512 and 896 (seven lane tiles),
  in column blocks of 512 and 1,024 where one matrix is [4096, 4096]."""
  from deepconsensus_tpu.ops import grouped_product

  assert grouped_product.tiles(rows, hidden, width, matrices=2) == (
      512, columns[0])
  assert grouped_product.tiles(rows, width, hidden) == (512, columns[1])
  sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
      shape, dtype, sharding=one_chip)
  bounds = sds((groups + 1,), jnp.int32)
  up = jax.jit(grouped_product.gated_up).lower(
      sds((rows, hidden)), sds((groups, hidden, width)),
      sds((groups, hidden, width)), sds((rows,), jnp.float32),
      bounds).compile()
  assert 'grouped_gated_up' in up.as_text() and _n_kernels(up) == 1
  down = jax.jit(grouped_product.grouped_product).lower(
      sds((rows, width)), sds((groups, width, hidden)), bounds).compile()
  assert 'grouped_product' in down.as_text() and _n_kernels(down) == 1
  # Nothing of the rows' size beside the operands and the result, but the
  # routing weights as a column, which the chip pads to a lane tile a row.
  # (With column blocks the call holds 1.7 MB of its own beside them; at
  # 204,800 rows over 64 groups 6.5 MiB, whatever the width, and 0.2 MiB
  # at the 102,400 rows of a turn of mellum_polish: the compiler's, nothing
  # of [rows, width].)
  assert down.memory_analysis().temp_size_in_bytes < held_mib << 20
  assert up.memory_analysis().temp_size_in_bytes < rows * 128 * 4 + (1 << 20)


def test_latent_window_tile_kernel_at_a_pack_of_the_cell(one_chip,
                                                        compiled_kernels):
  """The latent attention's kernel alone at kanana_polish's pack: 512
  windows of 100 positions, 32 heads of 128 + 64 / 128, the flat operands
  as the products write them; Mosaic takes the row slices of a window
  (100 rows are no whole tiles of bfloat16) and the step's 16 head-windows
  within the call's VMEM limit."""
  from deepconsensus_tpu.ops import latent_attention

  rows, heads = 512 * 100, 32
  flat = lambda width: jax.ShapeDtypeStruct(
      (rows, width), jnp.bfloat16, sharding=one_chip)
  call = lambda *operands: latent_attention.window_tile_attention(
      *operands, length=100, num_heads=heads, scale=192 ** -0.5)
  compiled = jax.jit(call).lower(
      flat(heads * 128), flat(heads * 32), flat(heads * 32),
      flat(heads * 256), flat(4 * 256)).compile()
  assert _n_kernels(compiled) == 1
  memory = compiled.memory_analysis()
  assert memory.output_size_in_bytes == rows * heads * 128 * 2
  assert memory.temp_size_in_bytes == 0  # nothing beside the operands


@pytest.mark.parametrize('batch,heads,kv_heads,rotated', [
    (512, 32, 4, True), (256, 128, 8, True), (256, 128, 8, False),
    (256, 20, 1, False)],
                         ids=['mellum_polish', 'commanda_polish_W',
                              'commanda_polish_F', 'jamba_polish_F'])
def test_grouped_window_tile_kernel_at_a_pack_of_each_cell(
    one_chip, compiled_kernels, batch, heads, kv_heads, rotated):
  """The grouped-head attention's kernel alone at the packs of the cells
  that run it: windows of 100 positions, 32 / 4, 128 / 8 and 20 / 1 heads of
  128 (groups of 8, 16 and 20), q, k and v flat as the products write them, the
  rotation in VMEM (`pltpu.roll` of a half lane tile) or none; the step's
  32 and 64 head-windows within the call's VMEM limit."""
  from deepconsensus_tpu.ops import grouped_attention

  rows = batch * 100
  flat = lambda width: jax.ShapeDtypeStruct(
      (rows, width), jnp.bfloat16, sharding=one_chip)
  tables = grouped_attention.signed_tables(
      *model_lib.rotary_tables(100, 128, 5e5)) if rotated else (None, None)
  call = lambda q, k, v: grouped_attention.window_tile_attention(
      q, k, v, *tables, length=100, num_heads=heads,
      num_kv_heads=kv_heads, scale=128 ** -0.5)
  compiled = jax.jit(call).lower(
      flat(heads * 128), flat(kv_heads * 128), flat(kv_heads * 128)).compile()
  assert _n_kernels(compiled) == 1
  memory = compiled.memory_analysis()
  assert memory.output_size_in_bytes == rows * heads * 128 * 2
  # Nothing beside the operands but the two tables of a step's 400 rows.
  assert memory.temp_size_in_bytes <= 2 * 400 * 128 * 4


@pytest.mark.parametrize('tokens,k,groups,hidden', [
    (25_600, 6, 128, 2048), (25_600, 10, 256, 2048), (12_800, 8, 16, 4096),
    (12_800, 8, 64, 2304)],
                         ids=['kanana_polish', 'qwen3next_polish',
                              'commanda_polish', 'mellum_polish'])
def test_combine_kernel_at_one_turn_of_each_cell(one_chip, compiled_kernels,
                                                 tokens, k, groups, hidden):
  """The combine's kernel alone at one turn of the four cells that run it
  (25,600 tokens of 6 assignments over 128 held experts and of 10 over
  256 at hidden 2048; 12,800 of 8 over 16 at hidden 4096; 12,800 of 8 over
  64 at hidden 2304, 18 lane tiles): 8-row copies
  out of a [rows, hidden] array in HBM, the 0/1 product, two buffers of a
  tile's runs within pallas_util.COMBINE_VMEM_LIMIT_BYTES."""
  from deepconsensus_tpu.ops import moe_combine

  assert moe_combine.fits(tokens, k, groups, hidden)
  sds = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
      shape, dtype, sharding=one_chip)
  compiled = jax.jit(moe_combine.combine).lower(
      sds((tokens * k, hidden), jnp.bfloat16), sds((tokens, k)),
      sds((tokens, k)), sds((groups + 1,))).compile()
  assert 'moe_combine' in compiled.as_text() and _n_kernels(compiled) == 1
  # Beside the operands and y: the one copy of `out` that zeroing the rows
  # behind the last held group costs where `out` is an argument (in the
  # forward it is a temporary, updated where it lies), the tiles' lists of
  # blocks and of buffer rows; nothing of [k, tokens, hidden].
  assert compiled.memory_analysis().temp_size_in_bytes < (
      tokens * k * hidden * 2 + (16 << 20))


@pytest.mark.parametrize('length', [130, 512])
def test_gated_delta_window_kernel_beyond_one_lane_tile_of_positions(
    one_chip, compiled_kernels, length):
  """The delta rule's window kernel alone at the published heads (16 key /
  32 value heads of 128) where the padded window is 256 and 512
  positions: it takes fewer problems abreast as their matrices grow and
  asks for the scoped VMEM its blocks need, so every length the rule
  `delta_rule_path` sends it compiles."""
  from deepconsensus_tpu.ops import gated_delta

  hk, hv, d, batch = 16, 32, 128, 8
  sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
      shape, dtype, sharding=one_chip)
  stream = sds((batch, length, (2 * hk + hv) * d))
  compiled = jax.jit(functools.partial(
      gated_delta._window_kernel_call, num_key_heads=hk, num_value_heads=hv,
      epsilon=1e-6)).lower(
          (stream, stream), sds((batch, length, hv * d)),
          sds((batch, length, hv), jnp.float32),
          sds((batch, length, hv), jnp.float32),
          sds((d,), jnp.float32)).compile()
  assert 'gated_delta_window' in compiled.as_text()
  assert _n_kernels(compiled) == 1


def test_fused_front_end_b1024(one_chip, compiled_kernels):
  from deepconsensus_tpu.ops import fused_window_attention as fwa

  p = _params()
  specs, table_keys, cond_in = fwa.build_family_specs(p)
  h = p.hidden_size
  sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
      shape, dt, sharding=one_chip)
  tables = {
      s.name if s.name != 'ccs' else 'bases': sds((s.vocab, s.width))
      for s in specs}
  tables = {k: tables[k] for k in table_keys}

  def fn(rows, tables, w_cond, wq, wk, wv, wo, pos):
    return fwa.fused_embed_condense_attention(
        rows, tables, w_cond, wq, wk, wv, wo, pos, specs=specs,
        table_keys=table_keys, num_heads=p.num_heads,
        attn_win_size=p.attn_win_size, compute_dtype=jnp.bfloat16)

  compiled = jax.jit(fn).lower(
      sds((BATCH, p.total_rows, p.max_length)), tables, sds((cond_in, h)),
      sds((h, h)), sds((h, h)), sds((h, h)), sds((h, h)),
      sds((p.max_length, h))).compile()
  assert _n_kernels(compiled) == 1


def test_attention_sublayer_forward_b1024(one_chip, compiled_kernels,
                                          monkeypatch):
  """The teacher's bfloat16 forward as a ModelRunner without a mesh
  traces it on a TPU: every layer's attention sublayer is one Mosaic
  call under scope `attention` (model_lib.kernel_paths; no option asks
  for it), and no score tensor is left in the program. Mosaic refusing
  the kernel at the served widths fails here, before any chip time."""
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  p = _params(dtype='bfloat16')
  model = model_lib.get_model(p)

  def forward(variables, rows):
    with pallas_util.single_device_inference():
      return model.apply(variables, rows)

  variables = _abstract(_variables(p), one_chip)
  rows = jax.ShapeDtypeStruct(
      (BATCH, p.total_rows, p.max_length, 1), jnp.float32, sharding=one_chip)
  text = jax.jit(forward).lower(variables, rows).compile().as_text()
  calls = [line for line in _entry_computation(text)
           if 'tpu_custom_call' in line]
  assert len(calls) == p.num_hidden_layers == 6
  for line in calls:
    assert re.search(r'op_name="[^"]*/attention/[^"]*pallas_call"', line)
    # The flat stream in the compute dtype, in and out.
    assert f'bf16[{BATCH * p.max_length},{p.hidden_size}]' in line
  # q, k, v, scores and weights never reach memory: the modules' program
  # holds [B,2,100,100] scores and [B,100,2,140] heads, this one neither.
  assert f'[{BATCH},{p.num_heads},100,100]' not in text
  assert f'[{BATCH},100,{p.num_heads},140]' not in text
  # The same forward, undeclared (a mesh, an export, a training step's
  # evaluation): the modules, no kernel.
  plain = jax.jit(model.apply).lower(variables, rows).compile().as_text()
  assert 'tpu_custom_call' not in plain
  assert f'[{BATCH},{p.num_heads},100,100]' in plain


@pytest.mark.parametrize('levers', [
    dict(dtype='float32'),
    dict(dtype='bfloat16', inference_dtype='bfloat16'),
    dict(dtype='bfloat16', inference_dtype='bfloat16',
         quantize_matmuls='int8'),
], ids=['f32', 'bf16', 'bf16_int8'])
def test_fused_hotpath_forward_b1024(one_chip, compiled_kernels, levers):
  """Front end + the fused encoder stack (one kernel per block), the
  whole use_fused_hotpath forward the runner would jit."""
  p = _params(use_fused_hotpath=True, **levers)
  compiled = _compile_forward(p, one_chip)
  assert _n_kernels(compiled) == 1 + p.num_hidden_layers


def test_ragged_forward_b512(one_chip, compiled_kernels):
  """The single ragged pack stream: 200-wide slots holding two windows
  each, the shape `--use_ragged_kernel --window_buckets 100,200`
  dispatches (batch 1024 windows = 512 slots)."""
  p = _params(use_fused_hotpath=True, window_buckets=(100, 200),
              dtype='bfloat16', inference_dtype='bfloat16')
  compiled = _compile_forward(
      p, one_chip, batch=BATCH // 2, length=200, lengths=(BATCH // 2, 2))
  assert _n_kernels(compiled) == 1 + p.num_hidden_layers


@pytest.mark.parametrize('grad', [False, True], ids=['forward', 'grad'])
def test_wavefront_loss_b256(one_chip, compiled_kernels, grad):
  """What `dctpu train` takes by itself on a TPU backend
  (train.resolve_pallas_wavefront)."""
  loss = losses_lib.AlignmentLoss(del_cost=10.0, loss_reg=0.1,
                                  use_pallas=True)
  y_true = jax.ShapeDtypeStruct((TRAIN_BATCH, 100), jnp.int32,
                                sharding=one_chip)
  y_pred = jax.ShapeDtypeStruct((TRAIN_BATCH, 100, 5), jnp.float32,
                                sharding=one_chip)
  fn = jax.grad(loss, argnums=1) if grad else loss
  compiled = jax.jit(fn).lower(y_true, y_pred).compile()
  assert _n_kernels(compiled) >= 1


def test_wavefront_loss_grad_on_dp2_tp2_mesh(topo, compiled_kernels):
  """`dctpu train --dp 2 --tp 2`: XLA refuses to partition a Mosaic
  kernel by itself, so the loss runs its scorers under a shard_map."""
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  mesh = Mesh(np.array(topo.devices).reshape(2, 2),
              (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
  batch_sh = mesh_lib.batch_sharding(mesh)
  loss = losses_lib.AlignmentLoss(del_cost=10.0, loss_reg=0.1,
                                  use_pallas=True, mesh=mesh)
  y_true = jax.ShapeDtypeStruct((TRAIN_BATCH, 100), jnp.int32,
                                sharding=batch_sh)
  y_pred = jax.ShapeDtypeStruct((TRAIN_BATCH, 100, 5), jnp.float32,
                                sharding=batch_sh)
  compiled = jax.jit(jax.grad(loss, argnums=1)).lower(
      y_true, y_pred).compile()
  assert _n_kernels(compiled) >= 1


def test_banded_attention_l100(one_chip, compiled_kernels):
  from deepconsensus_tpu.ops import banded_attention

  qkv = jax.ShapeDtypeStruct((TRAIN_BATCH, 100, 2, 140), jnp.bfloat16,
                             sharding=one_chip)
  compiled = jax.jit(
      lambda q, k, v: banded_attention.banded_attention(q, k, v, 12)
  ).lower(qkv, qkv, qkv).compile()
  assert _n_kernels(compiled) >= 1


def test_flash_band_attention_l500(one_chip, compiled_kernels):
  from deepconsensus_tpu.ops import flash_band_attention

  qkv = jax.ShapeDtypeStruct((64, 500, 2, 140), jnp.bfloat16,
                             sharding=one_chip)
  compiled = jax.jit(
      lambda q, k, v: flash_band_attention.flash_band_attention(q, k, v, 12)
  ).lower(qkv, qkv, qkv).compile()
  assert _n_kernels(compiled) >= 1


def test_dp4_forward_on_v5e_2x2_mesh(topo):
  """`dctpu run --dp 4`: the runner's jitted forward (uint8 pack in,
  epilogue planes out) with the batch sharded over all four chips."""
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.ops import output_plane
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  p = _params()
  mesh = Mesh(np.array(topo.devices).reshape(4, 1),
              (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
  replicated = NamedSharding(mesh, PartitionSpec())
  batch_sh = mesh_lib.batch_sharding(mesh)
  model = model_lib.get_model(p)
  thresholds = output_plane.quality_thresholds(
      calibration_lib.parse_calibration_string('skip'), 93)

  def forward(variables, main_u8, sn):
    rows = runner_lib._assemble_rows(main_u8, sn, None)
    return output_plane.phred_epilogue(model.apply(variables, rows),
                                       thresholds)

  variables = _abstract(_variables(p), replicated)
  main_u8 = jax.ShapeDtypeStruct(
      (BATCH, p.total_rows - 4, p.max_length, 1), jnp.uint8,
      sharding=batch_sh)
  sn = jax.ShapeDtypeStruct((BATCH, 4), jnp.float32, sharding=batch_sh)
  compiled = jax.jit(
      forward, in_shardings=(replicated, batch_sh, batch_sh),
      out_shardings=(batch_sh, batch_sh),
  ).lower(variables, main_u8, sn).compile()
  ids, quals = compiled.output_shardings
  assert ids.spec == quals.spec == batch_sh.spec
  assert len(ids.device_set) == 4
