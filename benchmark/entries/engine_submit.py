"""Entry `engine_submit`: windows through `ConsensusEngine.submit`, as
`run_inference` calls it (`inference/runner.py:2000`).

One submit is one featurize batch: a LIST of per-window float32 tensors,
each a strided view `[rows, i, :, None]` into its ZMW's `[rows, n, L]`
pile-up matrix (`preprocess/pileup.py:447`), so `submit` takes its
`_group_by_width` + `np.stack` branch. The closed loop of the traffic
file: one thread submits batch after batch as fast as `submit` returns,
the ZMW order rotated by one each time; at the deadline one last short
batch completes the pack that is begun, then `flush()`, and the window
ends when the last delivery has come back. Every delivery is kept (the
last answer per pool window), so the comparison reads what the window
itself served.
"""
from __future__ import annotations

import statistics
import sys
import time

import jax
import numpy as np

from benchmark.lib import compare

ANNOTATIONS = ('bench_submit', 'bench_flush')


class Entry:

  def __init__(self, ctx):
    self.ctx = ctx

  def _engine(self):
    from deepconsensus_tpu.inference import engine as engine_lib

    # A failed pack must not end the run: its windows are never delivered,
    # and the window counts them as failed (submitted minus delivered).
    return engine_lib.ConsensusEngine(
        self.ctx.runner, self.ctx.options, deliver=self._deliver,
        on_pack_failure=lambda tickets, seq, error: None)

  def _deliver(self, ticket, ids, quals):
    self.out_ids[ticket] = ids
    self.out_quals[ticket] = quals
    self.delivered += 1

  def prepare(self):
    """Inputs from the seed, laid out as the featurizer leaves them, and
    one full pack through the same calls the window makes, so that the one
    forward shape is compiled."""
    ctx = self.ctx
    pool = ctx.generator.make(ctx.shape, ctx.traffic, ctx.seed)
    n, rows, length, _ = pool.shape
    per_zmw = int(ctx.traffic['windows_per_zmw'])
    if n % per_zmw:
      raise ValueError('pool_windows must be a multiple of windows_per_zmw')
    self.per_zmw = per_zmw
    # One [rows, windows, L] matrix per ZMW; a window is a view into it.
    self.zmws = [
        np.ascontiguousarray(pool[lo:lo + per_zmw, :, :, 0].transpose(1, 0, 2))
        for lo in range(0, n, per_zmw)]
    self.views = [m[:, i, :, None] for m in self.zmws for i in range(per_zmw)]
    assert self.views[0].shape == (rows, length, 1)
    self.out_ids = np.zeros((n, length), np.uint8)
    self.out_quals = np.zeros((n, length), np.uint8)
    self.seen = np.zeros(n, bool)
    self.delivered = 0
    engine = self._engine()
    done = 0
    while done < ctx.batch:
      done += self._submit(engine, 0, min(n, ctx.batch - done))
    engine.flush()
    self.delivered = 0
    self.out_ids[:] = 0
    self.out_quals[:] = 0
    self.seen[:] = False

  def _submit(self, engine, first_zmw: int, count: int) -> int:
    """One featurize batch: `count` windows, starting at ZMW `first_zmw`
    and wrapping round the pool."""
    n = len(self.views)
    lo = (first_zmw * self.per_zmw) % n
    tickets = list(range(lo, n)) + list(range(lo))
    tickets = tickets[:count]
    with jax.profiler.TraceAnnotation('bench_submit'):
      engine.submit([self.views[t] for t in tickets], tickets)
    self.seen[tickets] = True
    return len(tickets)

  def window(self, seconds: float):
    ctx = self.ctx
    n = len(self.views)
    engine = self._engine()
    submitted = 0
    step = 0
    t0 = time.perf_counter()
    wall0 = time.time()
    deadline = t0 + seconds
    submit_s = []
    while time.perf_counter() < deadline:
      t_submit = time.perf_counter()
      submitted += self._submit(engine, step, n)
      submit_s.append(time.perf_counter() - t_submit)
      step += 1
    # How steady the host was inside the window: a slow run shows here as
    # every submit slow, or as a few.
    print(f'bench: submits={len(submit_s)} submit_s min={min(submit_s):.3f} '
          f'median={statistics.median(submit_s):.3f} max={max(submit_s):.3f}',
          file=sys.stderr, flush=True)
    # A run's last featurize batch is short: here, as many windows as
    # complete the pack that is begun, so every run ends on a full pack
    # and the tail's padding is not noise.
    if submitted % ctx.batch:
      submitted += self._submit(engine, step, -submitted % ctx.batch)
    with jax.profiler.TraceAnnotation('bench_flush'):
      engine.flush()
    t1 = time.perf_counter()
    window_s = t1 - t0
    stats = engine.stats()
    return {
        'window_s': window_s,
        'wall': (wall0, wall0 + window_s),
        'attempted': submitted,
        'failed': submitted - self.delivered,
        'windows_delivered': self.delivered,
        'rates': {'windows_per_s': self.delivered / window_s},
        'counters': {
            'n_packs': engine.n_packs,
            'n_pack_rows': engine.n_pack_rows,
            'n_pad_rows': engine.n_pad_rows,
            'n_forward_shapes': stats.get('n_forward_shapes'),
            'flush_padding_fraction': stats.get('flush_padding_fraction'),
        },
    }

  def release(self):
    """Drops what holds device memory, before the reference runs."""
    self.ctx.runner = None

  def compare(self, params):
    """Numbers for a seed-drawn sample of the windows the run delivered."""
    ctx = self.ctx
    served = np.flatnonzero(self.seen)
    rng = np.random.default_rng(ctx.seed)
    take = min(int(ctx.traffic['compare_windows']), len(served))
    sample = np.sort(rng.choice(served, size=take, replace=False))
    windows = np.stack([self.views[i] for i in sample])
    logits = ctx.family.reference_logits(params, windows, ctx.shape)
    yard = ctx.family.reference_logits(params, windows, ctx.shape, 'bfloat16')
    return compare.numbers(logits, self.out_ids[sample],
                           self.out_quals[sample], yard)
