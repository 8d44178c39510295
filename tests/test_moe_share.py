"""ops/moe.py and SparseExpertsFeedForward: the share adds up.

A process holds a share of a layer's experts; the router keeps its full
width. At a small size on the CPU: the routed parts that the shares
[0, E/2) and [E/2, E) compute, plus the shared expert counted once, equal
what the test-local plain reference (tests/gdn_moe_reference.py) gives for
the uncut layer; no assignment is lost or doubled when one expert takes
every token and when an expert takes none; the counts that come back sum
to positions x k. Each for both routers the one class serves: the softmax
router with a gated shared expert (the third block kind) and the sigmoid
router with a selection bias, a scaling factor and an ungated shared expert
(the fourth; reference tests/mla_moe_reference.py), whose bias changes who
is chosen and never a weight. And for the fifth kind's layer as eight chips
share it: 128 experts, 16 a chip, a sigmoid router without bias or factor,
four averaged shared experts, in a parallel block whose norm and attention
every chip computes alike (reference tests/parallel_moe_reference.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.ops import moe
from tests import gdn_moe_reference as ref
from tests import mla_moe_reference as sigmoid_ref

H, E, K, F = 32, 16, 4, 24
FACTOR = 2.448
# What the sigmoid router adds to the class's sizes.
SIGMOID = dict(scoring='sigmoid', selection_bias=True, routed_scale=FACTOR,
               shared_gate=False)
ROUTERS = pytest.mark.parametrize('router', ['softmax', 'sigmoid'])


def layer(first=0, count=E, dtype=jnp.float32, norm_topk=True,
          router='softmax'):
  return model_lib.SparseExpertsFeedForward(
      hidden_size=H, num_experts=E, experts_per_token=K, expert_width=F,
      shared_width=F, norm_topk=norm_topk, held_first=first, held_count=count,
      dtype=dtype, **(SIGMOID if router == 'sigmoid' else {}))


def whole_layer_weights(seed=0, router='softmax'):
  """The uncut layer's leaves, drawn so that every part counts."""
  rng = np.random.default_rng(seed)
  draw = lambda *shape: jnp.asarray(
      rng.normal(0, shape[-2] ** -0.5, shape), jnp.float32)
  weights = {
      'router': {'kernel': draw(H, E) * 3.0},
      'experts_gate': draw(E, H, F), 'experts_up': draw(E, H, F),
      'experts_down': draw(E, F, H),
      'shared_expert': {'gate_layer': {'kernel': draw(H, F)},
                        'up_layer': {'kernel': draw(H, F)},
                        'output_layer': {'kernel': draw(F, H)}},
      'shared_expert_gate': {'kernel': draw(H, 1)},
  }
  if router == 'sigmoid':
    del weights['shared_expert_gate']  # the shared expert has no gate
    # A bias of the size of the gaps between neighbouring scores.
    weights['router_selection_bias'] = jnp.asarray(
        rng.uniform(-0.2, 0.2, E), jnp.float32)
  return weights


def reference_routed(weights, n, router='softmax', **kwargs):
  """(moe(n), counts) of the router's plain reference."""
  if router == 'sigmoid':
    return sigmoid_ref.routed_experts(weights, n, top_k=K, factor=FACTOR,
                                      **kwargs)
  return ref.routed_experts(weights, n, top_k=K, **kwargs)


def share_of(weights, first, count):
  """What the process holding experts first ... first + count - 1 has."""
  cut = dict(weights)
  for name in ('experts_gate', 'experts_up', 'experts_down'):
    cut[name] = weights[name][first:first + count]
  return cut


def tokens(batch=3, length=20, seed=1):
  return jnp.asarray(
      np.random.default_rng(seed).normal(size=(batch, length, H)), jnp.float32)


def apply(first, count, weights, x, **kwargs):
  out, sown = layer(first, count, **kwargs).apply(
      {'params': share_of(weights, first, count)}, x, deterministic=True,
      mutable=['moe_counts'])
  return out, np.asarray(sown['moe_counts']['assignments'][0])


def shared_part(weights, x):
  flat = x.reshape(-1, H)
  s = weights['shared_expert']
  out = ref.swiglu(flat, s['gate_layer']['kernel'], s['up_layer']['kernel'],
                   s['output_layer']['kernel'])
  if 'shared_expert_gate' in weights:
    out = jax.nn.sigmoid(flat @ weights['shared_expert_gate']['kernel']) * out
  return out.reshape(x.shape)


@ROUTERS
@pytest.mark.parametrize('cuts', [((0, 8), (8, 8)), ((0, 4), (4, 4), (8, 8)),
                                  ((0, 1), (1, 15))],
                         ids=['halves', 'three_shares', 'one_and_the_rest'])
def test_shares_and_the_shared_expert_once_are_the_uncut_layer(cuts, router):
  weights, x = whole_layer_weights(router=router), tokens()
  want, want_counts = reference_routed(weights, x.reshape(-1, H), router)
  shared = shared_part(weights, x)
  total, counts = 0.0, []
  for first, count in cuts:
    out, took = apply(first, count, weights, x, router=router)
    total = total + (out - shared)  # the routed part of this share
    counts.append(took)
  total = total + shared  # what every chip computes alike, once
  np.testing.assert_allclose(np.asarray(total).reshape(-1, H),
                             np.asarray(want), atol=2e-5)
  assert np.array_equal(np.concatenate(counts), want_counts)
  assert want_counts.sum() == x.shape[0] * x.shape[1] * K


@ROUTERS
@pytest.mark.parametrize('cuts', [((0, 8), (8, 8)), ((0, 1), (1, 15))],
                         ids=['halves', 'one_and_the_rest'])
def test_shares_add_up_through_the_group_kernel(cuts, router, monkeypatch):
  """As on one TPU: at widths of a lane tile in bfloat16 the rules
  (ops/moe.py::grouped_product_path, ::combine_path) take the Pallas
  kernels, interpreted here: the grouped products' and the combine's, a
  tile of 128 tokens a step. The shares still add up to the uncut layer,
  the counts are those `ragged_dot`'s path gives, and no assignment is lost
  or doubled."""
  import sys

  from deepconsensus_tpu.ops import moe_combine
  from deepconsensus_tpu.ops import pallas_util

  for name in ('H', 'F'):
    monkeypatch.setattr(sys.modules[__name__], name, 128)
  weights = whole_layer_weights(seed=20, router=router)
  x = tokens(batch=4, length=32, seed=21).astype(jnp.bfloat16)
  want, _ = reference_routed(weights, x.reshape(-1, H).astype(jnp.float32),
                             router)
  shared = shared_part(weights, x.astype(jnp.float32))
  plain = [apply(first, count, weights, x, router=router, dtype=jnp.bfloat16)
           for first, count in cuts]
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  monkeypatch.setattr(pallas_util, 'resolve_interpret', lambda _: True)
  with pallas_util.single_device_inference():
    assert moe.grouped_product_path(4 * 32 * K, 8, H, F, x.dtype) == (
        moe.GROUPED_GROUP_KERNEL)
    combined = []
    real = moe_combine.combine
    monkeypatch.setattr(
        moe_combine, 'combine',
        lambda *a, **k: combined.append(1) or real(*a, **k))
    for _, count in cuts:
      assert moe.combine_path(4 * 32, K, count, H, x.dtype) == (
          moe.COMBINE_TOKEN_TILE_KERNEL)
    kernel = [apply(first, count, weights, x, router=router,
                    dtype=jnp.bfloat16) for first, count in cuts]
    assert len(combined) == len(cuts)
  total = shared
  for (out, took), (plain_out, plain_took) in zip(kernel, plain):
    assert np.array_equal(took, plain_took)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(plain_out, np.float32),
                               rtol=2 ** -6, atol=2 ** -5)
    total = total + (np.asarray(out, np.float32) - shared)
  assert sum(took.sum() for _, took in kernel) == 4 * 32 * K
  # bfloat16 keeps 8 bits of every operand and of each share's output, and
  # may send a token's near-tie to another expert.
  gap = np.abs(np.asarray(total).reshape(-1, H) - np.asarray(want))
  assert gap.mean() < 0.02 and (gap > 0.12).mean() < 1e-3


@ROUTERS
@pytest.mark.parametrize('first,count', [(0, 16), (0, 8), (8, 8), (5, 3)])
def test_a_share_is_the_references_routed_part_for_that_share(first, count,
                                                              router):
  weights, x = whole_layer_weights(seed=3, router=router), tokens(seed=4)
  got, took = apply(first, count, weights, x, router=router)
  want, want_counts = reference_routed(
      share_of(weights, first, count), x.reshape(-1, H), router, first=first)
  np.testing.assert_allclose(np.asarray(got).reshape(-1, H),
                             np.asarray(want), atol=2e-5)
  assert np.array_equal(took, want_counts)


def routed(logits, first, count, weights, x):
  w, e = moe.route_top_k(logits, K, True)
  cut = share_of(weights, first, count)
  return moe.held_experts(x, w, e, cut['experts_gate'], cut['experts_up'],
                          cut['experts_down'], first)


@pytest.mark.parametrize('first,count', [(0, 8), (8, 8), (0, 16)])
def test_one_expert_taking_every_token_and_one_taking_none(first, count):
  """Expert 3 is in every token's top-k and expert 9 in no token's: the
  fullest group holds every token once, the empty one nothing, and what
  comes back is still the plain loop's sum."""
  weights = whole_layer_weights(seed=5)
  x = tokens(seed=6).reshape(-1, H)
  logits = jnp.asarray(np.random.default_rng(7).normal(size=(len(x), E)),
                       jnp.float32)
  logits = logits.at[:, 3].set(20.0).at[:, 9].set(-20.0)
  got, counts = routed(logits, first, count, weights, x)
  counts = np.asarray(counts)
  if first <= 3 < first + count:
    assert counts[3 - first] == len(x)
  if first <= 9 < first + count:
    assert counts[9 - first] == 0
  top_p, top_e = (np.asarray(a) for a in moe.route_top_k(logits, K, True))
  want = np.zeros((len(x), H), np.float32)
  for e in range(first, first + count):
    token, slot = np.nonzero(top_e == e)
    assert counts[e - first] == len(token)
    want[token] += top_p[token, slot][:, None] * np.asarray(ref.swiglu(
        x[token], weights['experts_gate'][e], weights['experts_up'][e],
        weights['experts_down'][e]))
  np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
  if count == E:
    assert counts.sum() == len(x) * K


def test_every_token_on_experts_held_elsewhere_adds_nothing():
  weights = whole_layer_weights(seed=8)
  x = tokens(seed=9).reshape(-1, H)
  logits = jnp.zeros((len(x), E)).at[:, 8:12].set(10.0)
  got, counts = routed(logits, 0, 8, weights, x)
  assert np.asarray(counts).sum() == 0
  assert np.abs(np.asarray(got)).max() == 0.0
  _got, counts = routed(logits, 8, 8, weights, x)
  assert np.array_equal(np.asarray(counts), [len(x)] * 4 + [0] * 4)


def test_more_assignments_than_one_go_holds_are_taken_in_turn(monkeypatch):
  weights, x = whole_layer_weights(seed=10), tokens(batch=4, seed=11)
  whole, counts = apply(4, 8, weights, x)
  # One window a turn: 20 positions of K float32 rows of H.
  monkeypatch.setattr(moe, 'MAX_TURN_BYTES', 20 * K * H * 4)
  in_turn, counts_in_turn = apply(4, 8, weights, x)
  np.testing.assert_allclose(np.asarray(in_turn), np.asarray(whole),
                             atol=1e-6)
  assert np.array_equal(counts, counts_in_turn)


@pytest.mark.parametrize('renormalise', [True, False])
def test_router_keeps_the_k_largest_of_a_float32_softmax(renormalise):
  logits = jnp.asarray(np.random.default_rng(12).normal(size=(50, E)) * 2,
                       jnp.bfloat16)
  weights, experts = moe.route_top_k(logits, K, renormalise)
  assert weights.dtype == jnp.float32 and experts.dtype == jnp.int32
  probs = np.asarray(jax.nn.softmax(logits.astype(jnp.float32), axis=-1))
  order = np.argsort(-probs, axis=-1)[:, :K]
  assert np.array_equal(np.sort(np.asarray(experts)), np.sort(order))
  kept = np.take_along_axis(probs, np.asarray(experts), axis=-1)
  if renormalise:
    kept = kept / kept.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
  np.testing.assert_allclose(np.asarray(weights), kept, atol=1e-6)


@pytest.mark.parametrize('renormalise', [True, False])
def test_sigmoid_router_chooses_by_the_bias_and_weighs_without_it(
    renormalise):
  """Scores are a sigmoid each; the k largest of s + b are kept; the
  weights are s of the chosen over their sum, times the factor: a non-zero
  b changes who is chosen and never a weight."""
  rng = np.random.default_rng(17)
  logits = jnp.asarray(rng.normal(size=(200, E)), jnp.bfloat16)
  bias = jnp.asarray(rng.uniform(-0.2, 0.2, E), jnp.float32)
  route = lambda b: tuple(np.asarray(a) for a in moe.route_top_k(
      logits, K, renormalise, scoring='sigmoid', bias=b, scale=FACTOR))
  weights, experts = route(bias)
  assert weights.dtype == np.float32 and experts.dtype == np.int32
  scores = np.asarray(jax.nn.sigmoid(logits.astype(jnp.float32)))
  order = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :K]
  assert np.array_equal(np.sort(experts), np.sort(order))
  kept = np.take_along_axis(scores, experts, axis=-1)
  if renormalise:
    kept = kept / kept.sum(-1, keepdims=True)
    np.testing.assert_allclose(weights.sum(-1), FACTOR, rtol=1e-6)
  np.testing.assert_allclose(weights, kept * FACTOR, rtol=1e-6)
  # Without the bias other experts are chosen for a share of the tokens;
  # where the same expert is chosen its unnormalised weight is the same.
  plain_weights, plain_experts = route(jnp.zeros(E))
  moved = (np.sort(plain_experts) != np.sort(experts)).any(axis=-1)
  assert 0.1 < moved.mean() < 1.0
  if not renormalise:
    same = plain_experts[:, :, None] == experts[:, None, :]
    i, j, l = np.nonzero(same)
    np.testing.assert_array_equal(plain_weights[i, j], weights[i, l])
  # No bias at all is the bias of zeros.
  none = moe.route_top_k(logits, K, renormalise, scoring='sigmoid',
                         scale=FACTOR)
  np.testing.assert_array_equal(np.asarray(none[1]), plain_experts)
  np.testing.assert_array_equal(np.asarray(none[0]), plain_weights)
  with pytest.raises(ValueError, match="unknown router scoring 'tanh'"):
    moe.route_top_k(logits, K, True, scoring='tanh')


@ROUTERS
def test_counts_sum_to_positions_times_k_and_weights_to_the_factor(router):
  weights, x = whole_layer_weights(seed=18, router=router), tokens(seed=19)
  _out, counts = apply(0, E, weights, x, router=router)
  assert counts.sum() == x.shape[0] * x.shape[1] * K
  logits = x.reshape(-1, H) @ weights['router']['kernel']
  kwargs = dict(scoring='sigmoid', bias=weights['router_selection_bias'],
                scale=FACTOR) if router == 'sigmoid' else {}
  top_p, _ = moe.route_top_k(logits, K, True, **kwargs)
  np.testing.assert_allclose(
      np.asarray(top_p).sum(-1), FACTOR if router == 'sigmoid' else 1.0,
      rtol=1e-6)


def test_top_k_weights_left_unnormalised_are_a_different_layer():
  weights, x = whole_layer_weights(seed=13), tokens(seed=14)
  a, _ = apply(0, 16, weights, x)
  b, _ = apply(0, 16, weights, x, norm_topk=False)
  assert np.abs(np.asarray(a - b)).max() > 0.01


def test_stream_in_bfloat16_keeps_router_and_combine_in_float32():
  weights, x = whole_layer_weights(seed=15), tokens(seed=16)
  low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), weights)
  got, took = apply(0, 8, low, x.astype(jnp.bfloat16), dtype=jnp.bfloat16)
  assert got.dtype == jnp.bfloat16
  rounded = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), low)
  want, want_took = apply(
      0, 8, rounded, x.astype(jnp.bfloat16).astype(jnp.float32))
  # The same routing unless two experts tie within bfloat16's rounding of
  # the stream; the sums agree to bfloat16's 8 bits of the largest term.
  assert np.abs(took - want_took).sum() <= 4
  assert np.abs(np.asarray(got, np.float32) - np.asarray(want)).max() < (
      0.05 * np.abs(np.asarray(want)).max())


@pytest.mark.parametrize('first,count', [(8, 9), (0, 0), (-1, 4)])
def test_a_share_that_is_no_share_of_the_experts_is_refused(first, count):
  with pytest.raises(ValueError, match='are not a share of 16'):
    layer(first, count).init(jax.random.PRNGKey(0), tokens(),
                             deterministic=True)


# ----------------------- the fifth kind: an eighth of the experts a chip

def test_eight_shares_of_sixteen_experts_add_up_to_the_uncut_parallel_layer():
  """The parallel block's layer as 8 chips share it (128 experts, 16 a
  chip, 8 a token; sigmoid router without bias or factor; four shared
  experts averaged): the routed parts of the eight shares, with what every
  chip computes alike (the norm, the attention, the shared experts)
  counted once, are the plain reference's uncut layer x + attn(u) + ffn(u)
  (tests/parallel_moe_reference.py)."""
  from tests import parallel_moe_reference as parallel_ref

  experts, held, top_k, n_shared = 128, 16, 8, 4
  heads, kv_heads, d = 8, 2, 8
  rng = np.random.default_rng(20)
  draw = lambda *shape, fan=None: jnp.asarray(
      rng.normal(0, (fan or shape[-2]) ** -0.5, shape), jnp.float32)
  weights = {
      'router': {'kernel': draw(H, experts)},
      'experts_gate': draw(experts, H, F), 'experts_up': draw(experts, H, F),
      'experts_down': draw(experts, F, H),
      'shared_expert': {'gate_layer': {'kernel': draw(H, n_shared * F)},
                        'up_layer': {'kernel': draw(H, n_shared * F)},
                        'output_layer': {'kernel': draw(n_shared * F, H)}},
  }
  attention_weights = {
      'query': {'kernel': draw(H, heads, d, fan=H)},
      'key': {'kernel': draw(H, kv_heads, d, fan=H)},
      'value': {'kernel': draw(H, kv_heads, d, fan=H)},
      'output_transform': {'kernel': draw(heads, d, H, fan=heads * d)}}
  scale = jnp.asarray(rng.uniform(0.5, 1.5, H), jnp.float32)
  x = tokens(seed=21)

  with jax.default_matmul_precision('highest'):
    u = model_lib.BiasFreeLayerNorm(1e-5).apply(
        {'params': {'scale': scale}}, x)
    attended = model_lib.GroupedSoftmaxAttention(
        hidden_size=H, num_heads=heads, num_kv_heads=kv_heads, head_dim=d,
        rotary_dim=d, rope=5e4, output_gate=False, qk_norm=False,
        window=6).apply({'params': attention_weights}, u, deterministic=True)
    ffn = lambda first: model_lib.SparseExpertsFeedForward(
        hidden_size=H, num_experts=experts, experts_per_token=top_k,
        expert_width=F, shared_width=n_shared * F, norm_topk=True,
        held_first=first, held_count=held, scoring='sigmoid',
        selection_bias=False, routed_scale=1.0, shared_gate=False,
        shared_scale=1.0 / n_shared).apply(
            {'params': share_of(weights, first, held)}, u,
            deterministic=True, mutable=['moe_counts'])
    flat = u.reshape(-1, H)
    shared = parallel_ref.shared_experts(
        weights['shared_expert'], flat, n_shared).reshape(x.shape)
    total, counts = x + attended + shared, []
    for first in range(0, experts, held):
      out, sown = ffn(first)
      total = total + (out - shared)  # the routed part of this share
      counts.append(np.asarray(sown['moe_counts']['assignments'][0]))
    # The uncut layer of the reference: every expert held.
    want_u = parallel_ref.layer_norm(x, scale, 1e-5)
    routed, want_counts = parallel_ref.routed_experts(
        weights, want_u.reshape(-1, H), top_k=top_k)
    want = x + parallel_ref.attention(
        attention_weights, want_u, rotated=True, window=6, theta=5e4) + (
            routed + parallel_ref.shared_experts(
                weights['shared_expert'], want_u.reshape(-1, H),
                n_shared)).reshape(x.shape)
  # Eight subtractions of the shared part and float32 sums in two orders.
  np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
  assert np.array_equal(np.concatenate(counts), want_counts)
  assert want_counts.sum() == x.shape[0] * x.shape[1] * top_k
  # The layer is not its residual alone: every part counts.
  assert np.abs(np.asarray(want - x)).max() > 0.5


def test_sigmoid_router_without_bias_or_factor_sums_to_one():
  """The combination the fifth kind runs: a sigmoid each, the k largest of
  the scores themselves, renormalised, no factor."""
  rng = np.random.default_rng(22)
  logits = jnp.asarray(rng.normal(size=(200, 128)), jnp.float32)
  weights, experts = (np.asarray(a) for a in moe.route_top_k(
      logits, 8, True, scoring='sigmoid', bias=None, scale=1.0))
  scores = np.asarray(jax.nn.sigmoid(logits))
  assert np.array_equal(np.sort(experts), np.sort(
      np.argsort(-scores, axis=-1)[:, :8]))
  np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
  kept = np.take_along_axis(scores, experts, axis=-1)
  np.testing.assert_allclose(weights, kept / kept.sum(-1, keepdims=True),
                             rtol=1e-6)
