"""Command-line interface: dctpu {preprocess,run,train,calibrate,filter_reads}.

Mirrors the reference's subcommand surface (reference:
deepconsensus/cli.py:50-118) with argparse.
"""
from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def _coerce_override(raw: str, current):
  """Parses a --set value against the config entry's current type."""
  if raw.lower() in ('none', 'null'):
    return None
  if isinstance(current, bool):
    if raw.lower() in ('true', '1', 'yes'):
      return True
    if raw.lower() in ('false', '0', 'no'):
      return False
    raise ValueError(f'expected a boolean, got {raw!r}')
  for cast in (int, float):
    if isinstance(current, cast):
      return cast(raw)
  if current is None:
    # Untyped (e.g. band_width / use_pallas_wavefront default to
    # None): best-effort bool, then numeric.
    if raw.lower() in ('true', 'yes'):
      return True
    if raw.lower() in ('false', 'no'):
      return False
    for cast in (int, float):
      try:
        return cast(raw)
      except ValueError:
        continue
  return raw


def _apply_overrides(params, overrides: List[str]) -> None:
  """Applies --set KEY=VALUE items to an unlocked-able config. Must run
  before finalize_params so derived values (total_rows, hidden_size)
  see the overrides. Transformer size keys (num_hidden_layers,
  num_heads, filter_size) only materialize inside finalize_params,
  which fills them from the size preset ONLY when absent — so
  pre-setting them here is legal and wins over the preset."""
  from deepconsensus_tpu.models import config as config_lib

  late_keys = frozenset(
      k for preset in config_lib.TRANSFORMER_SIZE_PARAMS.values()
      for k in preset)
  with params.unlocked():
    for item in overrides:
      key, eq, raw = item.partition('=')
      if not eq or not (hasattr(params, key) or key in late_keys):
        raise ValueError(f'unknown config override {item!r}')
      setattr(params, key,
              _coerce_override(raw, getattr(params, key, None)))


def _add_preprocess(sub):
  p = sub.add_parser('preprocess', help='Generate examples from BAMs.')
  p.add_argument('--subreads_to_ccs', required=True)
  p.add_argument('--ccs_bam', required=True)
  p.add_argument('--output', required=True,
                 help="Output path; '@split' expands per split.")
  p.add_argument('--max_passes', type=int, default=20)
  p.add_argument('--example_width', type=int, default=100)
  p.add_argument('--use_ccs_bq', action='store_true')
  p.add_argument('--ins_trim', type=int, default=5)
  p.add_argument('--use_ccs_smart_windows', action='store_true')
  p.add_argument('--truth_bed')
  p.add_argument('--truth_to_ccs')
  p.add_argument('--truth_split')
  p.add_argument('--limit', type=int, default=0)
  p.add_argument('--cpus', type=int, default=0)
  p.add_argument('--shard', default=None, metavar='I/N',
                 type=_parse_shard,
                 help='Process only ZMWs with zm %% N == I (fleet '
                 'scaling; shard the output paths too).')
  p.add_argument('--compression', choices=['bgzf', 'gzip'], default='bgzf',
                 help='.gz shard framing: bgzf (default; valid gzip, '
                 'parallel-decodable blocks) or single-member gzip.')


def _add_run(sub):
  p = sub.add_parser('run', help='Run inference: BAMs -> polished FASTQ.')
  p.add_argument('--subreads_to_ccs', required=True)
  p.add_argument('--ccs_bam', required=True)
  p.add_argument('--checkpoint', required=True)
  p.add_argument('--output', required=True)
  p.add_argument('--batch_size', type=int, default=1024)
  p.add_argument('--batch_zmws', type=int, default=100)
  p.add_argument('--min_length', type=int, default=0)
  p.add_argument('--min_quality', type=int, default=20)
  p.add_argument('--skip_windows_above', type=int, default=45)
  p.add_argument('--ins_trim', type=int, default=5)
  p.add_argument('--use_ccs_smart_windows', action='store_true')
  p.add_argument('--max_base_quality', type=int, default=93)
  p.add_argument('--dc_calibration', default=None)
  p.add_argument('--ccs_calibration', default='skip')
  p.add_argument('--limit', type=int, default=0)
  p.add_argument('--dp', type=int, default=0,
                 help='Shard the window batch over this many devices '
                 '(0 = single device).')
  p.add_argument('--tp', type=int, default=1,
                 help='Tensor-parallel mesh size per data shard '
                 '(attention heads / FFN filter shard).')
  p.add_argument('--cpus', type=int, default=0,
                 help='Featurization worker processes (0 or 1 = '
                 'in-process; tensors travel via shared memory).')
  p.add_argument('--end_after_stage', default='full',
                 choices=['dc_input', 'tf_examples', 'run_model', 'full'],
                 help='Stop the pipeline early for debugging/timing '
                 '(reference DebugStage).')
  p.add_argument('--shard', default=None, metavar='I/N',
                 type=_parse_shard,
                 help='Process only ZMWs with zm %% N == I, e.g. 3/500 '
                 '— fleet scaling over one shared BAM without '
                 'splitting it.')
  p.add_argument('--on_zmw_error', default='fail',
                 choices=['fail', 'skip', 'ccs-fallback'],
                 help='Per-ZMW fault policy: fail aborts the run '
                 '(historical behavior); skip quarantines the ZMW to '
                 '<output>.failed.jsonl; ccs-fallback additionally '
                 'emits the draft CCS read with its original base '
                 'qualities.')
  p.add_argument('--batch_timeout', type=float, default=0.0,
                 help='Watchdog timeout (s) per featurization batch '
                 'when --cpus > 1; a hung or killed worker triggers '
                 'pool re-spawn and retry (0 disables).')
  p.add_argument('--batch_retries', type=int, default=2,
                 help='Watchdog retries per featurization batch before '
                 'the batch is quarantined.')
  p.add_argument('--resume', action='store_true',
                 help='Resume an interrupted run from '
                 '<output>.progress.json + <output>.tmp, replaying the '
                 'feeder past already-committed ZMWs.')
  p.add_argument('--dispatch_depth', type=int, default=8,
                 help='Model packs kept in flight on the device before '
                 'the oldest is drained; raise to hide host-side '
                 'stacking latency, lower to bound memory.')
  p.add_argument('--emit_queue_depth', type=int, default=4,
                 help='Featurize batches buffered between the model '
                 'stage and the stitch/emit worker before the model '
                 'stage blocks.')
  p.add_argument('--max_record_bytes', type=int, default=64 << 20,
                 help='Per-record allocation cap for the BAM decoders: '
                 'a record claiming more than this many bytes is '
                 'treated as corrupt (quarantined under '
                 '--on_zmw_error=skip) instead of allocated.')
  _add_epilogue_flag(p)
  _add_quant_flags(p)
  _add_bucket_flag(p)
  _add_device_fault_flags(p)
  _add_trace_flag(p)


def _add_trace_flag(p):
  p.add_argument('--trace', default=None, metavar='TRACE.jsonl',
                 help='Append Chrome-trace-event spans (Perfetto-'
                 'loadable) to this file. Equivalent to setting '
                 'DCTPU_TRACE; fleet tiers may share one file. '
                 'Summarize with `dctpu trace`.')


def _add_epilogue_flag(p):
  # Tri-state (None/auto by default): an explicit choice is enforced
  # against exported-artifact metadata, auto follows it.
  g = p.add_mutually_exclusive_group()
  g.add_argument('--device_epilogue', dest='device_epilogue',
                 action='store_true', default=None,
                 help='Device-resident output plane: compute argmax + '
                 'Phred quality (threshold table, byte-identical to '
                 'the host math) on device and drain uint8 planes — 2 '
                 'bytes/position D2H instead of 8. Default: on for '
                 'checkpoints, follow-the-artifact for exported runs.')
  g.add_argument('--no_device_epilogue', dest='device_epilogue',
                 action='store_false',
                 help='Force the host quality path (ship int32 ids + '
                 'f32 max_prob and do the Phred math on the host).')


def _add_quant_flags(p):
  p.add_argument('--inference_dtype', default=None,
                 choices=['float32', 'bfloat16'],
                 help='Inference weight/activation dtype: bfloat16 '
                 'casts checkpoint weights once at load and runs the '
                 'model end-to-end in bf16 (softmax accumulation '
                 'stays f32). Default keeps the checkpoint dtype.')
  p.add_argument('--quantize_matmuls', default=None,
                 choices=['none', 'int8'],
                 help='int8: per-channel symmetric weight '
                 'quantization of the encoder attention/FFN matmuls '
                 'at load; dequant runs in the fused-kernel epilogue.')


def _parse_window_buckets(text):
  try:
    buckets = tuple(int(x) for x in text.split(',') if x.strip())
  except ValueError:
    raise argparse.ArgumentTypeError(
        f'--window_buckets must be comma-separated ints, got {text!r}')
  if not buckets:
    raise argparse.ArgumentTypeError('--window_buckets is empty')
  return buckets


def _add_bucket_flag(p):
  p.add_argument('--window_buckets', default=None,
                 type=_parse_window_buckets, metavar='L1,L2,...',
                 help='Window length buckets, e.g. 100,200: each '
                 'variable-width (smart) window pads to the smallest '
                 'bucket that fits instead of pad-to-max, and each '
                 'bucket dispatches through its own compile-once '
                 'forward (fused hot path for L<=128, XLA above). The '
                 'smallest bucket must equal the model max_length. '
                 'Default: the checkpoint\'s params.window_buckets '
                 '(single-shape when unset).')
  p.add_argument('--use_ragged_kernel', action='store_true',
                 default=False,
                 help='Single-pack-stream ragged dispatch: pack mixed-'
                 'width windows back-to-back into fixed-length slots '
                 '(slot = the largest bucket) with a per-slot lengths '
                 'vector and run ONE compiled ragged forward for every '
                 'width — no per-bucket packer fleet, no starvation '
                 'flush, n_forward_shapes == 1. Requires buckets that '
                 'form a divisibility chain (the default 100,200 '
                 'does). Off: the per-bucket packers (byte-identical '
                 'output either way).')


def _add_train_bucket_flag(p):
  # Training-side counterpart of _add_bucket_flag: buckets only (the
  # ragged pack stream is an inference dispatch mode).
  p.add_argument('--window_buckets', default=None,
                 type=_parse_window_buckets, metavar='L1,L2,...',
                 help='Bucketed multi-width training, e.g. 100,200: '
                 'each window pads to the smallest bucket that fits, '
                 'batches stay width-pure, and each bucket compiles '
                 'exactly ONE train step over the shared param tree '
                 '(n_train_forward_shapes == number of buckets, zero '
                 'mid-run recompiles). Widths at or past 256 route '
                 'attention through the blockwise ring scan (the L=500 '
                 'long-insert path; requires attention_dropout=0). The '
                 'smallest bucket must equal max_length. Default: '
                 'single-shape pad-to-max.')


def _add_device_fault_flags(p):
  p.add_argument('--on_device_error', default='fail',
                 choices=['fail', 'degrade'],
                 help='Device fault policy: fail propagates device '
                 'runtime errors (historical behavior); degrade '
                 'bisects RESOURCE_EXHAUSTED packs to half batch and '
                 'rebuilds the mesh one dp step down (8->4->2->1) '
                 'after a lost/halted device, resubmitting the failed '
                 'pack in featurize order.')
  p.add_argument('--dispatch_timeout', type=float, default=0.0,
                 help='Dispatch watchdog: bound each pack\'s blocking '
                 'finalize to this many seconds; a hung forward '
                 'surfaces as DispatchTimeoutError through pack '
                 'failure attribution instead of wedging the model '
                 'loop (0 disables).')


def _add_serve(sub):
  p = sub.add_parser(
      'serve',
      help='Resident consensus service: keep the compiled forward '
      'warm and polish molecules over a local HTTP endpoint.')
  p.add_argument('--checkpoint', default=None,
                 help='Checkpoint or exported-artifact dir (required '
                 'unless --random_init).')
  p.add_argument('--host', default='127.0.0.1')
  p.add_argument('--port', type=int, default=8764,
                 help='Listen port (0 = pick a free port; the bound '
                 'port is printed in the ready line).')
  p.add_argument('--batch_size', type=int, default=1024)
  p.add_argument('--dispatch_depth', type=int, default=8)
  p.add_argument('--min_length', type=int, default=0)
  p.add_argument('--min_quality', type=int, default=20)
  p.add_argument('--skip_windows_above', type=int, default=45)
  p.add_argument('--max_base_quality', type=int, default=93)
  p.add_argument('--dc_calibration', default=None)
  p.add_argument('--ccs_calibration', default='skip')
  p.add_argument('--max_pending', type=int, default=64,
                 help='Outstanding admitted requests before new ones '
                 'are shed with 429 backpressure.')
  p.add_argument('--admit_queue_depth', type=int, default=32,
                 help='Requests queued ahead of the model loop before '
                 'admission sheds with 429.')
  p.add_argument('--max_windows_per_request', type=int, default=512)
  p.add_argument('--max_body_mb', type=int, default=64,
                 help='Request bodies above this are rejected (413) '
                 'before any bytes are read.')
  p.add_argument('--default_deadline_s', type=float, default=120.0,
                 help='Per-request deadline when the client sends no '
                 'X-Dctpu-Deadline-S header; expiry cancels the '
                 'request (504) and reclaims its queued windows.')
  p.add_argument('--max_deadline_s', type=float, default=600.0)
  p.add_argument('--io_timeout_s', type=float, default=20.0,
                 help='Per-socket read/write timeout; a slow-drip or '
                 'half-dead client is cut after this long.')
  p.add_argument('--on_request_error', default='ccs-fallback',
                 choices=['skip', 'ccs-fallback'],
                 help='Policy for a request whose windows fail the '
                 'model stage twice (shared pack + isolation retry).')
  p.add_argument('--dead_letter', default=None,
                 help='Append quarantined-request records (with '
                 'request attribution) to this JSONL sidecar.')
  p.add_argument('--random_init', action='store_true',
                 help='Serve randomly initialized weights from '
                 '--config instead of a checkpoint (tests/demos).')
  p.add_argument('--config', default='transformer_learn_values+test',
                 help='Model preset for --random_init.')
  p.add_argument('--dp', type=int, default=0,
                 help='Data-parallel devices: each pack is dp-sharded '
                 'over the mesh data axis (batch_size must divide '
                 'evenly). 0 = single-device serving.')
  p.add_argument('--tp', type=int, default=1,
                 help='Tensor-parallel devices per replica (model-axis '
                 'sharded attention/FFN weights); exported artifacts '
                 'require tp=1.')
  _add_epilogue_flag(p)
  _add_quant_flags(p)
  _add_bucket_flag(p)
  _add_device_fault_flags(p)
  _add_trace_flag(p)


def _add_route(sub):
  p = sub.add_parser(
      'route',
      help='Fleet front tier: load-balance /v1/polish across dctpu '
      'serve replicas, steering bam/1 bodies through featurize '
      'workers first.')
  p.add_argument('--replica', action='append', default=[],
                 metavar='HOST:PORT',
                 help='Model replica address; repeatable. Replicas '
                 'join health-gated (no traffic until /readyz '
                 'passes); more can join at runtime via '
                 'POST /v1/register.')
  p.add_argument('--featurize_worker', action='append', default=[],
                 metavar='HOST:PORT',
                 help='Featurize worker address; repeatable. bam/1 '
                 'requests are featurized here before a model '
                 'replica sees them.')
  p.add_argument('--host', default='127.0.0.1')
  p.add_argument('--port', type=int, default=8765)
  p.add_argument('--probe_interval_s', type=float, default=0.5,
                 help='Health/signal probe cadence per replica '
                 '(/readyz + /metricz).')
  p.add_argument('--max_inflight', type=int, default=8,
                 help='Bounded in-flight requests per replica, scaled '
                 'by its mesh_dp; when every ready replica is at its '
                 'bound the router sheds with a typed 503.')
  p.add_argument('--max_attempts', type=int, default=3,
                 help='Distinct replicas tried per request; only '
                 'requests a replica provably never accepted are '
                 'retried.')
  p.add_argument('--io_timeout_s', type=float, default=20.0)
  p.add_argument('--upstream_timeout_s', type=float, default=300.0,
                 help='End-to-end budget for one forwarded request.')
  p.add_argument('--max_body_mb', type=int, default=64)
  p.add_argument('--default_class', default='interactive',
                 help='Priority class for requests without an '
                 'X-Dctpu-Class header.')
  p.add_argument('--class_weight', action='append', default=[],
                 metavar='CLASS=WEIGHT',
                 help='Weighted-fair admission share for a priority '
                 'class; repeatable (default: interactive=4 bulk=1).')
  p.add_argument('--client_quota', type=int, default=0,
                 help='Max concurrent requests per client id (429 '
                 'RESOURCE_EXHAUSTED above it); 0 = unlimited.')
  p.add_argument('--queue_wait_s', type=float, default=0.0,
                 help='How long a saturated request may wait its '
                 'weighted-fair turn before shedding (0 = shed '
                 'immediately).')
  p.add_argument('--max_queued_per_class', type=int, default=16,
                 help='Waiting requests per class before that class '
                 '(and only that class) sheds.')
  _add_trace_flag(p)


def _add_autoscale(sub):
  p = sub.add_parser(
      'autoscale',
      help='SLO autoscaler: watch a router\'s /metricz and '
      'spawn/drain serve replicas to hold a p99/queue-depth target, '
      'replacing preempted replicas.')
  p.add_argument('--router', required=True, metavar='HOST:PORT',
                 help='The dctpu route endpoint to watch and register '
                 'spawned replicas with.')
  p.add_argument('--tier', default='model', choices=['model', 'featurize'])
  p.add_argument('--min_replicas', type=int, default=1)
  p.add_argument('--max_replicas', type=int, default=4)
  p.add_argument('--target_p99_s', type=float, default=2.0,
                 help='SLO: scale out while the slo_class p99 exceeds '
                 'this.')
  p.add_argument('--target_queue_depth', type=float, default=4.0,
                 help='Scale out while mean READY-replica queue depth '
                 'exceeds this.')
  p.add_argument('--slo_class', default='interactive',
                 help='Priority class whose p99 drives scaling.')
  p.add_argument('--poll_interval_s', type=float, default=1.0)
  p.add_argument('--scale_out_cooldown_s', type=float, default=5.0)
  p.add_argument('--scale_in_cooldown_s', type=float, default=60.0)
  p.add_argument('--spawn_ready_timeout_s', type=float, default=180.0,
                 help='How long a spawned replica may take to print '
                 'its ready line (first spawn pays the jit compile; '
                 'later ones hit the shared compilation cache).')
  p.add_argument('--serve_arg', action='append', default=[],
                 metavar='ARG',
                 help='Extra argv token for spawned `dctpu serve` '
                 'replicas; repeatable (e.g. --serve_arg=--random_init). '
                 'Replicas inherit JAX_COMPILATION_CACHE_DIR. Spawns always get --host 127.0.0.1 --port 0.')
  p.add_argument('--leave_managed', action='store_true',
                 help='On exit, leave spawned replicas serving instead '
                 'of draining them (an autoscaler restart then adopts '
                 'nothing but the fleet stays up).')
  _add_trace_flag(p)


def _add_featurize_worker(sub):
  p = sub.add_parser(
      'featurize-worker',
      help='Disaggregated featurize tier: BAM decode/pileup on CPU '
      'boxes, shipping compact uint8 window packs to model replicas.')
  p.add_argument('--host', default='127.0.0.1')
  p.add_argument('--port', type=int, default=8766)
  p.add_argument('--config', default='transformer_learn_values+test',
                 help='Model preset naming the feature layout '
                 '(max_passes/max_length/use_ccs_bq) this worker '
                 'produces; must match the model replicas behind the '
                 'same router.')
  p.add_argument('--ins_trim', type=int, default=0)
  p.add_argument('--use_ccs_smart_windows', action='store_true')
  p.add_argument('--work_dir', default=None,
                 help='Scratch dir for per-request mini BAMs (use a '
                 'tmpfs in production).')
  p.add_argument('--no_compact', action='store_true',
                 help='Always ship legacy float32 frames instead of '
                 'features/1 uint8 packs.')
  p.add_argument('--io_timeout_s', type=float, default=20.0)
  p.add_argument('--max_body_mb', type=int, default=64)
  _add_bucket_flag(p)
  _add_trace_flag(p)


def _add_validate(sub):
  p = sub.add_parser(
      'validate',
      help='Preflight-check inputs before spending TPU time on them.')
  p.add_argument('--subreads_to_ccs', default=None,
                 help='actc output BAM (subreads aligned to ccs).')
  p.add_argument('--ccs_bam', default=None,
                 help='ccs BAM; with --subreads_to_ccs also checks '
                 'name/order consistency between the pair.')
  p.add_argument('--tfrecord', action='append', default=[],
                 metavar='GLOB',
                 help='TFRecord path or glob (repeatable); every '
                 'matching shard is CRC-checked end to end.')
  p.add_argument('--max_record_bytes', type=int, default=None,
                 help='Per-record allocation cap (default 64 MiB).')
  p.add_argument('--report', default=None,
                 help='Also write the JSON report to this path '
                 '(always printed to stdout).')


def _add_lint(sub):
  p = sub.add_parser(
      'lint',
      help='AST static analysis over the package (tools/dclint): '
      'typed-faults, jit-hazards, guarded-by, shape-literals.')
  p.add_argument('lint_paths', nargs='*', metavar='PATH',
                 help='Files/dirs to lint (default: the whole '
                 'deepconsensus_tpu package).')
  p.add_argument('--root', default=None, dest='lint_root',
                 help='Repository root (default: autodetected).')
  p.add_argument('--baseline', default=None, dest='lint_baseline',
                 help='Baseline JSON path (default: '
                 'tools/dclint/baseline.json).')
  p.add_argument('--update-baseline', action='store_true',
                 help='Rewrite the baseline with the current findings '
                 '(refuses typed-faults/guarded-by entries: those get '
                 'fixed, not suppressed).')
  p.add_argument('--no-baseline', action='store_true',
                 help='Ignore the baseline; report and fail on every '
                 'finding.')
  p.add_argument('--format', choices=('text', 'json'), default='text',
                 dest='lint_format')


def _add_trace(sub):
  p = sub.add_parser(
      'trace',
      help='Summarize a DCTPU_TRACE span file: per-stage breakdown, '
      'critical-path attribution, straggler packs, span-derived '
      'transfer overlap.')
  p.add_argument('trace_file', metavar='TRACE.jsonl',
                 help='Trace written by --trace / DCTPU_TRACE '
                 '(one file, possibly shared by a whole fleet).')
  p.add_argument('--json', action='store_true', dest='trace_json',
                 help='Emit the summary as JSON instead of text.')
  p.add_argument('--top', type=int, default=10,
                 help='Max straggler packs listed (default 10).')


def _add_train(sub):
  p = sub.add_parser('train', help='Train a model.')
  p.add_argument('--config', default='transformer_learn_values+test',
                 help='{model}+{dataset} preset name.')
  p.add_argument('--out_dir', required=True)
  p.add_argument('--train_path', nargs='*')
  p.add_argument('--eval_path', nargs='*')
  p.add_argument('--num_epochs', type=int)
  p.add_argument('--batch_size', type=int)
  p.add_argument('--set', action='append', default=[], metavar='KEY=VALUE',
                 dest='overrides',
                 help='Config override, repeatable (e.g. '
                 '--set use_pallas_wavefront=true --set loss_reg=0.5).')
  p.add_argument('--checkpoint', help='Warm-start checkpoint.')
  p.add_argument('--on_shard_error', choices=('fail', 'skip'),
                 help='Streaming-loader policy for an undecodable '
                 'shard: fail (default) aborts, skip counts + logs '
                 'the shard and keeps training.')
  p.add_argument('--tp', type=int, default=1,
                 help='Tensor-parallel mesh size.')
  p.add_argument('--dp', type=int, default=None,
                 help='Data-parallel mesh size (default: all devices '
                 'not used by --tp).')
  p.add_argument('--on_device_error', default='fail',
                 choices=['fail', 'degrade'],
                 help='Mid-training device fault policy: fail '
                 'propagates (the retry wrapper restarts from the '
                 'last checkpoint at full dp), degrade rebuilds the '
                 'mesh one dp step down over the surviving devices, '
                 're-places the live state, and keeps training.')
  p.add_argument('--coordinator_address',
                 help='host:port of process 0 (multi-host training).')
  p.add_argument('--num_processes', type=int,
                 help='Total number of hosts (multi-host training).')
  p.add_argument('--process_id', type=int,
                 help='This host\'s index (multi-host training).')
  p.add_argument('--elastic', action='store_true',
                 help='Elastic multi-host mode: every cross-host '
                 'collective is a bounded barrier over a shared '
                 'filesystem under <out_dir>/.pod, a lost host '
                 'triggers a coordinated pod rebuild instead of a '
                 'hang, and a recovered host is re-admitted at the '
                 'next step boundary. Uses --process_id/'
                 '--num_processes for membership; jax.distributed is '
                 'NOT initialized (the pod owns cross-host transport).')
  p.add_argument('--on_host_error', default='degrade',
                 choices=['fail', 'degrade'],
                 help='Elastic policy when a barrier times out on a '
                 'missing host: fail propagates HostLostError (the '
                 'retry wrapper restarts from the last checkpoint), '
                 'degrade rebuilds the pod over the surviving hosts, '
                 're-places the live state, and resumes from the '
                 'failed step (default).')
  p.add_argument('--elastic_barrier_timeout', type=float, default=30.0,
                 help='Deadline in seconds for every elastic '
                 'collective (step sync, checkpoint barrier, '
                 'stop-vote). On expiry the missing host is named in '
                 'a typed HostLostError; no collective waits '
                 'unbounded (default 30).')
  p.add_argument('--elastic_readmit', dest='elastic_readmit',
                 action='store_true', default=True,
                 help='Allow a recovered host to rejoin the pod at a '
                 'step boundary (default on).')
  p.add_argument('--no_elastic_readmit', dest='elastic_readmit',
                 action='store_false',
                 help='Refuse re-admission; a lost host stays lost '
                 'until the run restarts.')
  _add_train_bucket_flag(p)


def _add_evaluate(sub):
  p = sub.add_parser(
      'evaluate',
      help='Offline eval over labeled TFRecords -> inference.csv '
      '(counterpart of the reference model_inference binary).',
  )
  p.add_argument('--checkpoint', required=True)
  p.add_argument('--eval_path', nargs='+', required=True)
  p.add_argument('--out_dir', required=True)
  p.add_argument('--limit', type=int, default=-1,
                 help='Max eval examples (-1 = all).')
  p.add_argument('--batch_size', type=int)


def _add_port(sub):
  p = sub.add_parser(
      'port',
      help='Port a reference TF checkpoint to a servable orbax '
      'checkpoint (requires tensorflow).',
  )
  p.add_argument('--tf_checkpoint', required=True,
                 help='TF checkpoint prefix (.../checkpoint-N).')
  p.add_argument('--params', required=True,
                 help='params.json path or directory containing it.')
  p.add_argument('--out_dir', required=True)


def _add_export(sub):
  p = sub.add_parser(
      'export',
      help='Export a checkpoint as a serving artifact (StableHLO), the '
      'counterpart of the reference convert_to_saved_model tool.',
  )
  p.add_argument('--checkpoint', required=True,
                 help='Orbax checkpoint directory (with params.json).')
  p.add_argument('--output', required=True, help='Output directory.')
  p.add_argument('--batch_size', type=int, default=1024,
                 help='Recommended serving batch size recorded in the '
                 'artifact metadata. The export is batch-polymorphic '
                 '(serves any batch size) unless symbolic export fails, '
                 'in which case this size is baked in.')
  p.add_argument('--strict_polymorphic', action='store_true',
                 help='Fail instead of falling back to a fixed-batch '
                 'artifact when batch-polymorphic export fails.')
  p.add_argument('--device_epilogue', dest='device_epilogue',
                 action='store_true', default=True,
                 help='Bake the device output plane into the artifact: '
                 'the serving call returns final uint8 (ids, quals) '
                 'planes with the calibration/clamp below compiled in '
                 '(default).')
  p.add_argument('--no_device_epilogue', dest='device_epilogue',
                 action='store_false',
                 help='Export a pre-epilogue artifact that returns '
                 'softmax preds (host computes qualities).')
  p.add_argument('--max_base_quality', type=int, default=93,
                 help='Quality clamp baked into the device epilogue '
                 '(must match serving; recorded in the metadata).')
  p.add_argument('--dc_calibration', default=None,
                 help='Calibration string baked into the device '
                 'epilogue; default reads dc_calibration from the '
                 'checkpoint params.json (like dctpu run).')
  _add_quant_flags(p)


def _add_distill(sub):
  p = sub.add_parser('distill', help='Distill a teacher into a student.')
  p.add_argument('--teacher_checkpoint', required=True)
  p.add_argument('--config', default='transformer_learn_values_distill+test')
  p.add_argument('--out_dir', required=True)
  p.add_argument('--train_path', nargs='*')
  p.add_argument('--eval_path', nargs='*')
  p.add_argument('--num_epochs', type=int)
  p.add_argument('--batch_size', type=int)
  p.add_argument('--set', action='append', default=[], metavar='KEY=VALUE',
                 dest='overrides',
                 help='Student config override, repeatable (same semantics '
                 'as train --set; applied before finalize_params).')
  _add_train_bucket_flag(p)


def _add_flywheel(sub):
  p = sub.add_parser(
      'flywheel',
      help='Train -> distill -> quantization gates -> export, one '
      'command: produces a servable baked artifact plus a manifest '
      'recording every stage and gate result. A failed gate aborts '
      'before export (exit 3).',
  )
  p.add_argument('--out_dir', required=True,
                 help='Flywheel root; stages land in teacher/, '
                 'student/, gates/, export/ plus flywheel_manifest.json.')
  p.add_argument('--train_path', nargs='+', required=True)
  p.add_argument('--eval_path', nargs='+', required=True)
  p.add_argument('--config', default='transformer_learn_values+test',
                 help='Teacher {model}+{dataset} preset.')
  p.add_argument('--student_config',
                 default='transformer_learn_values_distill+test',
                 help='Student (distillation) preset.')
  p.add_argument('--teacher_checkpoint', default=None,
                 help='Existing teacher checkpoint: skip the training '
                 'stage and spin the flywheel from here (the common '
                 'retrain-student loop).')
  p.add_argument('--num_epochs', type=int)
  p.add_argument('--batch_size', type=int)
  p.add_argument('--set', action='append', default=[], metavar='KEY=VALUE',
                 dest='overrides',
                 help='Teacher config override, repeatable.')
  p.add_argument('--student_set', action='append', default=[],
                 metavar='KEY=VALUE', dest='student_overrides',
                 help='Student config override, repeatable.')
  p.add_argument('--export_batch_size', type=int, default=1024)
  p.add_argument('--int8_gate', type=float, default=None,
                 help='Override the int8 alignment-identity delta gate '
                 '(default 0.002, from the acceptance test).')
  p.add_argument('--bf16_gate', type=int, default=None,
                 help='Override the bf16 max per-base QV delta gate '
                 '(default 3, from the acceptance test).')
  p.add_argument('--tp', type=int, default=1,
                 help='Tensor-parallel mesh size for train/distill.')
  p.add_argument('--resume', action='store_true',
                 help='Adopt <out_dir>/flywheel_journal.json: skip '
                 'completed stages (inputs re-validated — a changed '
                 'flag raises a typed FlywheelResumeError, exit 2) and '
                 're-enter the in-flight stage idempotently.')
  p.add_argument('--elastic', action='store_true',
                 help='Run the train and distill stages under the '
                 'elastic pod protocol (dctpu train --elastic); a lost '
                 'host degrades the pod at the stage retry instead of '
                 'killing the cycle.')
  p.add_argument('--num_processes', type=int, default=None,
                 help='Elastic pod size (hosts).')
  p.add_argument('--process_id', type=int, default=None,
                 help='This host\'s id within the elastic pod.')
  p.add_argument('--on_host_error', choices=('fail', 'degrade'),
                 default='degrade')
  p.add_argument('--elastic_barrier_timeout', type=float, default=30.0)
  p.add_argument('--elastic_readmit', dest='elastic_readmit',
                 action='store_true', default=True)
  p.add_argument('--no_elastic_readmit', dest='elastic_readmit',
                 action='store_false')
  _add_train_bucket_flag(p)
  p.add_argument('--baseline_checkpoint', default=None,
                 help='Reference checkpoint (e.g. the L=100 production '
                 'model) to evaluate on the same eval shards as the '
                 'student: the gates stage records an informational '
                 'long_insert_identity_vs_baseline entry comparing '
                 'alignment_identity student vs baseline in the '
                 'manifest (never vetoes export).')
  _add_quant_flags(p)


def _add_calibrate(sub):
  p = sub.add_parser(
      'calibrate', help='Measure empirical base-quality calibration.')
  p.add_argument('--bam', required=True,
                 help='Predictions aligned to the reference genome.')
  p.add_argument('--ref', required=True, help='Reference FASTA.')
  p.add_argument('--output', required=True, help='Output CSV.')
  p.add_argument('--region')
  p.add_argument('--cpus', type=int, default=0)


def _add_yield_metrics(sub):
  p = sub.add_parser(
      'yield_metrics', help='Yield@Q table from truth-aligned reads.')
  p.add_argument('--bam', required=True,
                 help='Polished reads aligned to the truth.')
  p.add_argument('--ref', required=True, help='Truth FASTA.')
  p.add_argument('--output', required=True, help='Output CSV.')
  p.add_argument('--identity_bar', type=float, default=0.999)


def _add_filter_reads(sub):
  p = sub.add_parser('filter_reads', help='Filter reads by avg quality.')
  p.add_argument('--input', required=True, help='FASTQ or BAM input.')
  p.add_argument('--output', required=True, help='FASTQ output (.gz ok).')
  p.add_argument('--quality', type=int, required=True)


def _parse_shard(value):
  """argparse type: 'I/N' -> (i, n) with 0 <= i < n."""
  try:
    i_str, n_str = value.split('/')
    i, n = int(i_str), int(n_str)
  except ValueError:
    raise argparse.ArgumentTypeError(
        f'expected I/N (e.g. 3/500), got {value!r}'
    )
  if not 0 <= i < n:
    raise argparse.ArgumentTypeError(f'need 0 <= I < N, got {value!r}')
  return (i, n)


def build_parser() -> argparse.ArgumentParser:
  parser = argparse.ArgumentParser(
      prog='dctpu',
      description='DeepConsensus-TPU: TPU-native CCS polishing.',
  )
  sub = parser.add_subparsers(dest='command', required=True)
  _add_preprocess(sub)
  _add_run(sub)
  _add_serve(sub)
  _add_route(sub)
  _add_autoscale(sub)
  _add_featurize_worker(sub)
  _add_validate(sub)
  _add_lint(sub)
  _add_trace(sub)
  _add_train(sub)
  _add_distill(sub)
  _add_flywheel(sub)
  _add_export(sub)
  _add_port(sub)
  _add_evaluate(sub)
  _add_calibrate(sub)
  _add_yield_metrics(sub)
  _add_filter_reads(sub)
  return parser


def main(argv: Optional[List[str]] = None) -> int:
  try:
    return _dispatch(build_parser().parse_args(argv))
  except FileNotFoundError as e:
    print(f'dctpu: file not found: {e}', file=sys.stderr)
    return 2
  except ValueError as e:
    print(f'dctpu: {e}', file=sys.stderr)
    return 2
  except KeyboardInterrupt:
    print('dctpu: interrupted', file=sys.stderr)
    return 130


_JIT_COMMANDS = frozenset({
    'run', 'serve', 'train', 'evaluate', 'export', 'distill', 'flywheel',
})


def _dispatch(args) -> int:
  if getattr(args, 'trace', None):
    # --trace is sugar for DCTPU_TRACE: the env var is what each tier's
    # *_main reads (and what spawned fleet processes inherit).
    import os

    os.environ['DCTPU_TRACE'] = args.trace

  if args.command in _JIT_COMMANDS:
    # One persistent compile cache for every command that jits:
    # $JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache.
    from deepconsensus_tpu.utils import compile_cache

    compile_cache.enable()

  if args.command == 'trace':
    import json

    from deepconsensus_tpu import faults as faults_lib
    from deepconsensus_tpu.obs import summarize as summarize_lib

    try:
      events = summarize_lib.load_trace(args.trace_file)
      summary = summarize_lib.summarize(events)
    except faults_lib.CorruptInputError as e:
      print(f'dctpu: {e}', file=sys.stderr)
      return 2
    summary['stragglers'] = summary['stragglers'][:max(args.top, 0)]
    if args.trace_json:
      print(json.dumps(summary, indent=2))
    else:
      print(summarize_lib.format_summary(summary))
    return 0

  if args.command == 'preprocess':
    from deepconsensus_tpu.preprocess.driver import run_preprocess

    run_preprocess(
        subreads_to_ccs=args.subreads_to_ccs,
        ccs_bam=args.ccs_bam,
        output=args.output,
        max_passes=args.max_passes,
        example_width=args.example_width,
        use_ccs_bq=args.use_ccs_bq,
        ins_trim=args.ins_trim,
        use_ccs_smart_windows=args.use_ccs_smart_windows,
        truth_bed=args.truth_bed,
        truth_to_ccs=args.truth_to_ccs,
        truth_split=args.truth_split,
        limit=args.limit,
        cpus=args.cpus,
        shard=args.shard,
        compression=args.compression.upper(),
    )
    return 0

  if args.command == 'validate':
    import json

    from deepconsensus_tpu.io import validate as validate_lib

    if (args.subreads_to_ccs is None and args.ccs_bam is None
        and not args.tfrecord):
      raise ValueError(
          'validate needs at least one of --subreads_to_ccs, '
          '--ccs_bam, --tfrecord')
    report = validate_lib.validate_inputs(
        subreads_to_ccs=args.subreads_to_ccs,
        ccs_bam=args.ccs_bam,
        tfrecords=args.tfrecord,
        max_record_bytes=args.max_record_bytes,
    )
    text = json.dumps(report, indent=2)
    print(text)
    if args.report:
      with open(args.report, 'w') as f:
        f.write(text + '\n')
    return 0 if report['ok'] else 1

  if args.command == 'lint':
    import os

    try:
      from tools.dclint import __main__ as dclint_main
    except ImportError:
      # Installed-package invocation: tools/ is not shipped, but a
      # source checkout keeps it two levels above this file.
      import deepconsensus_tpu

      repo_root = os.path.dirname(os.path.dirname(
          os.path.abspath(deepconsensus_tpu.__file__)))
      if not os.path.isdir(os.path.join(repo_root, 'tools', 'dclint')):
        raise ValueError(
            'dctpu lint needs a source checkout (tools/dclint not '
            f'found under {repo_root})')
      sys.path.insert(0, repo_root)
      from tools.dclint import __main__ as dclint_main
    lint_argv = list(args.lint_paths)
    if args.lint_root:
      lint_argv += ['--root', args.lint_root]
    if args.lint_baseline:
      lint_argv += ['--baseline', args.lint_baseline]
    if args.update_baseline:
      lint_argv.append('--update-baseline')
    if args.no_baseline:
      lint_argv.append('--no-baseline')
    lint_argv += ['--format', args.lint_format]
    return dclint_main.run(lint_argv)

  if args.command == 'serve':
    import json

    from deepconsensus_tpu.calibration import lib as calibration_lib
    from deepconsensus_tpu.inference import runner as runner_lib
    from deepconsensus_tpu.models import config as config_lib
    from deepconsensus_tpu.serve import server as server_lib
    from deepconsensus_tpu.serve.service import ServeOptions

    dc_cal = args.dc_calibration
    if dc_cal is None and args.checkpoint:
      params_json = config_lib.read_params_from_json(args.checkpoint)
      dc_cal = params_json.get('dc_calibration', 'skip') or 'skip'
    options = runner_lib.InferenceOptions(
        batch_size=args.batch_size,
        dispatch_depth=args.dispatch_depth,
        min_length=args.min_length,
        min_quality=args.min_quality,
        skip_windows_above=args.skip_windows_above,
        max_base_quality=args.max_base_quality,
        on_device_error=args.on_device_error,
        dispatch_timeout=args.dispatch_timeout,
        inference_dtype=args.inference_dtype,
        quantize_matmuls=args.quantize_matmuls,
        device_epilogue=args.device_epilogue,
        window_buckets=args.window_buckets,
        use_ragged_kernel=args.use_ragged_kernel,
        dc_calibration_values=calibration_lib.parse_calibration_string(
            dc_cal or 'skip'),
        ccs_calibration_values=calibration_lib.parse_calibration_string(
            args.ccs_calibration),
    )
    mesh = None
    if args.dp or args.tp > 1:
      import jax

      from deepconsensus_tpu.parallel import mesh as mesh_lib

      dp = args.dp or 1
      mesh = mesh_lib.make_mesh(
          dp=dp, tp=args.tp, devices=jax.devices()[:dp * args.tp]
      )
    if args.random_init:
      import jax
      import jax.numpy as jnp

      from deepconsensus_tpu.models import model as model_lib

      params = config_lib.get_config(args.config)
      config_lib.finalize_params(params, is_training=False)
      # Checkpoint loads fold the levers in inside from_checkpoint;
      # random-init weights get the same treatment here so --random_init
      # serves exercise the identical quantized path.
      runner_lib._apply_quant_levers(params, options)
      variables = model_lib.get_model(params).init(
          jax.random.PRNGKey(0),
          jnp.zeros((1, params.total_rows, params.max_length, 1)))
      runner = runner_lib.ModelRunner(params, variables, options,
                                      mesh=mesh)
    elif args.checkpoint:
      runner = runner_lib.ModelRunner.from_checkpoint(
          args.checkpoint, options, mesh=mesh)
    else:
      raise ValueError('serve needs --checkpoint or --random_init')
    options.max_passes = runner.params.max_passes
    options.max_length = runner.params.max_length
    options.use_ccs_bq = runner.params.use_ccs_bq
    options.window_buckets = config_lib.normalize_window_buckets(
        options.window_buckets
        or getattr(runner.params, 'window_buckets', None),
        runner.params.max_length)
    serve_options = ServeOptions(
        max_pending=args.max_pending,
        admit_queue_depth=args.admit_queue_depth,
        max_windows_per_request=args.max_windows_per_request,
        max_body_bytes=args.max_body_mb << 20,
        default_deadline_s=args.default_deadline_s,
        max_deadline_s=args.max_deadline_s,
        io_timeout_s=args.io_timeout_s,
        on_request_error=args.on_request_error,
        dead_letter_path=args.dead_letter,
    )
    stats = server_lib.serve_main(
        runner, options, serve_options,
        host=args.host, port=args.port,
        ready_fn=lambda info: print(json.dumps(info), flush=True))
    print(json.dumps({'event': 'drained', **stats}, default=str),
          flush=True)
    return 0 if stats.get('drained') else 1

  if args.command == 'route':
    import json

    from deepconsensus_tpu.fleet import router as router_lib

    if not args.replica and not args.featurize_worker:
      raise ValueError(
          'route needs at least one --replica or --featurize_worker')
    class_weights = None
    if args.class_weight:
      class_weights = {}
      for spec in args.class_weight:
        name, sep, weight = spec.partition('=')
        if not sep:
          raise ValueError(
              f'--class_weight expects CLASS=WEIGHT, got {spec!r}')
        class_weights[name] = float(weight)
    options = router_lib.RouterOptions(
        max_body_bytes=args.max_body_mb << 20,
        io_timeout_s=args.io_timeout_s,
        upstream_timeout_s=args.upstream_timeout_s,
        probe_interval_s=args.probe_interval_s,
        max_inflight=args.max_inflight,
        max_attempts=args.max_attempts,
        class_weights=class_weights,
        default_class=args.default_class,
        client_quota=args.client_quota,
        queue_wait_s=args.queue_wait_s,
        max_queued_per_class=args.max_queued_per_class,
    )
    stats = router_lib.route_main(
        replicas=args.replica,
        featurize_workers=args.featurize_worker,
        options=options,
        host=args.host, port=args.port,
        ready_fn=lambda info: print(json.dumps(info), flush=True))
    print(json.dumps({'event': 'drained', **stats}, default=str),
          flush=True)
    return 0 if stats.get('drained') else 1

  if args.command == 'autoscale':
    import json
    import signal as signal_lib
    import subprocess
    import threading
    import time

    from deepconsensus_tpu import obs as obs_lib
    from deepconsensus_tpu.fleet import autoscaler as autoscaler_lib
    from deepconsensus_tpu.serve.client import ServeClient
    from deepconsensus_tpu.serve.server import _StopFlag

    obs_lib.trace.configure_from_env(tier='autoscaler')
    router_host, _, router_port = args.router.partition(':')
    router_client = ServeClient(
        router_host or '127.0.0.1', int(router_port), timeout=10.0)
    subcommand = 'serve' if args.tier == 'model' else 'featurize-worker'
    procs = {}  # url -> Popen; only the autoscale loop thread touches it
    all_procs = []  # every Popen ever spawned, for final reaping

    def spawn():
      cmd = ([sys.executable, '-m', 'deepconsensus_tpu.cli', subcommand,
              '--host', '127.0.0.1', '--port', '0']
             + list(args.serve_arg))
      proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
      deadline = time.monotonic() + args.spawn_ready_timeout_s
      info = None
      while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
          raise RuntimeError(
              f'spawned {subcommand} replica exited rc={proc.poll()} '
              'before its ready line')
        try:
          parsed = json.loads(line)
        except ValueError:
          continue
        if parsed.get('event') == 'ready':
          info = parsed
          break
      if info is None:
        proc.kill()
        raise RuntimeError(
            f'spawned {subcommand} replica not ready within '
            f'{args.spawn_ready_timeout_s}s')
      url = f'127.0.0.1:{info["port"]}'
      status, body, _ = router_client._request(
          'POST', '/v1/register',
          body=json.dumps({'url': url, 'tier': args.tier}).encode(),
          headers={'Content-Type': 'application/json'})
      if status != 200:
        proc.terminate()
        raise RuntimeError(
            f'router register of {url} failed: HTTP {status} '
            f'{body[:200].decode("latin-1")}')
      procs[url] = proc
      all_procs.append(proc)
      print(json.dumps({'event': 'spawned', 'url': url,
                        'tier': args.tier}), flush=True)
      return url

    def drain(url):
      proc = procs.pop(url, None)
      if proc is None or proc.poll() is not None:
        return
      proc.send_signal(signal_lib.SIGTERM)
      # Reap off-thread: the SIGTERM drain may take max_deadline_s and
      # must not stall the control loop.
      threading.Thread(target=proc.wait, daemon=True).start()

    options = autoscaler_lib.AutoscalerOptions(
        tier=args.tier,
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        target_p99_s=args.target_p99_s,
        target_queue_depth=args.target_queue_depth,
        slo_class=args.slo_class,
        poll_interval_s=args.poll_interval_s,
        scale_out_cooldown_s=args.scale_out_cooldown_s,
        scale_in_cooldown_s=args.scale_in_cooldown_s,
    )
    scaler = autoscaler_lib.Autoscaler(
        options, fetch_stats=router_client.metricz,
        spawn_fn=spawn, drain_fn=drain,
        on_decision=lambda d: d['action'] not in ('hold',) and print(
            json.dumps({'event': 'autoscale', **d}), flush=True))
    stop = _StopFlag()
    stop.install()
    print(json.dumps({'event': 'ready', 'router': args.router,
                      'tier': args.tier,
                      'min': args.min_replicas,
                      'max': args.max_replicas}), flush=True)
    try:
      scaler.run(stop_event=stop.event)
    finally:
      stop.restore()
      scaler.shutdown(drain_managed=not args.leave_managed)
      if not args.leave_managed:
        for proc in all_procs:
          try:
            proc.wait(timeout=60)
          except subprocess.TimeoutExpired:
            proc.kill()
    stats = scaler.stats()
    print(json.dumps({'event': 'drained', **stats}, default=str),
          flush=True)
    return 0

  if args.command == 'featurize-worker':
    import json

    from deepconsensus_tpu.fleet import featurize_worker as worker_lib
    from deepconsensus_tpu.models import config as config_lib

    params = config_lib.get_config(args.config)
    config_lib.finalize_params(params, is_training=False)
    buckets = config_lib.normalize_window_buckets(
        args.window_buckets
        or getattr(params, 'window_buckets', None),
        params.max_length)
    options = worker_lib.FeaturizeWorkerOptions(
        max_passes=params.max_passes,
        max_length=params.max_length,
        use_ccs_bq=params.use_ccs_bq,
        window_buckets=tuple(buckets or ()),
        ins_trim=args.ins_trim,
        use_ccs_smart_windows=args.use_ccs_smart_windows,
        work_dir=args.work_dir,
        compact=not args.no_compact,
        max_body_bytes=args.max_body_mb << 20,
        io_timeout_s=args.io_timeout_s,
    )
    stats = worker_lib.worker_main(
        options, host=args.host, port=args.port,
        ready_fn=lambda info: print(json.dumps(info), flush=True))
    print(json.dumps({'event': 'drained', **stats}, default=str),
          flush=True)
    return 0 if stats.get('drained') else 1

  if args.command == 'run':
    from deepconsensus_tpu.calibration import lib as calibration_lib
    from deepconsensus_tpu.inference import runner as runner_lib
    from deepconsensus_tpu.models import config as config_lib

    dc_cal = args.dc_calibration
    if dc_cal is None:
      params = config_lib.read_params_from_json(args.checkpoint)
      dc_cal = params.get('dc_calibration', 'skip') or 'skip'
    options = runner_lib.InferenceOptions(
        batch_size=args.batch_size,
        batch_zmws=args.batch_zmws,
        min_length=args.min_length,
        min_quality=args.min_quality,
        skip_windows_above=args.skip_windows_above,
        ins_trim=args.ins_trim,
        use_ccs_smart_windows=args.use_ccs_smart_windows,
        max_base_quality=args.max_base_quality,
        limit=args.limit,
        cpus=args.cpus,
        end_after_stage=args.end_after_stage,
        shard=args.shard,
        on_zmw_error=args.on_zmw_error,
        batch_timeout=args.batch_timeout,
        batch_retries=args.batch_retries,
        resume=args.resume,
        dispatch_depth=args.dispatch_depth,
        emit_queue_depth=args.emit_queue_depth,
        on_device_error=args.on_device_error,
        dispatch_timeout=args.dispatch_timeout,
        inference_dtype=args.inference_dtype,
        quantize_matmuls=args.quantize_matmuls,
        device_epilogue=args.device_epilogue,
        window_buckets=args.window_buckets,
        use_ragged_kernel=args.use_ragged_kernel,
        max_record_bytes=args.max_record_bytes,
        dc_calibration_values=calibration_lib.parse_calibration_string(
            dc_cal
        ),
        ccs_calibration_values=calibration_lib.parse_calibration_string(
            args.ccs_calibration
        ),
    )
    mesh = None
    if args.dp or args.tp > 1:
      import jax

      from deepconsensus_tpu.parallel import mesh as mesh_lib

      dp = args.dp or 1
      mesh = mesh_lib.make_mesh(
          dp=dp, tp=args.tp, devices=jax.devices()[:dp * args.tp]
      )
    from deepconsensus_tpu import obs as obs_lib

    # SIGUSR2 -> short on-demand jax.profiler capture next to the
    # output (the batch counterpart of serve's /debugz/profile).
    obs_lib.profiler.install_sigusr2(args.output + '.profile')
    counters = runner_lib.run_inference(
        subreads_to_ccs=args.subreads_to_ccs,
        ccs_bam=args.ccs_bam,
        checkpoint=args.checkpoint,
        output=args.output,
        options=options,
        mesh=mesh,
    )
    if args.end_after_stage != 'full':
      # Debug-truncated runs never stitch reads; completing the
      # requested stages is the success criterion.
      return 0
    # ccs-fallback emissions count as yield: a run whose every read
    # degraded to the draft CCS still produced usable output (exit 0),
    # while the dead-letter sidecar carries the forensic detail.
    if counters.get('success', 0) > 0:
      return 0
    if counters.get('n_fallback_emitted', 0) > 0:
      return 0
    return 1

  if args.command == 'train':
    from deepconsensus_tpu.models import config as config_lib
    from deepconsensus_tpu.models import train as train_lib
    from deepconsensus_tpu.parallel import mesh as mesh_lib

    params = config_lib.get_config(args.config)
    _apply_overrides(params, args.overrides)
    config_lib.finalize_params(params)
    with params.unlocked():
      if args.batch_size:
        params.batch_size = args.batch_size
      if args.on_shard_error:
        params.on_shard_error = args.on_shard_error
      if args.window_buckets:
        params.window_buckets = args.window_buckets
      params.on_device_error = args.on_device_error
      params.on_host_error = args.on_host_error
      params.elastic_barrier_timeout = args.elastic_barrier_timeout
      params.tp = args.tp  # local_mesh size in elastic mode
    elastic_config = None
    if args.elastic:
      # The pod owns cross-host transport (bounded file barriers under
      # <out_dir>/.pod); jax.distributed must NOT be initialized or its
      # unbounded collectives would race the pod's membership protocol.
      elastic_config = {
          'host_id': args.process_id or 0,
          'n_hosts': args.num_processes or 1,
          'barrier_timeout': args.elastic_barrier_timeout,
          'on_host_error': args.on_host_error,
          'readmit': args.elastic_readmit,
      }
    elif (args.coordinator_address or args.num_processes
          or args.process_id is not None):
      # Initialize before the mesh is built so it spans all hosts
      # (run_training's own distributed_config hook is for programmatic
      # callers; the CLI must init before make_mesh below).
      from deepconsensus_tpu.parallel import distributed

      distributed.initialize(
          coordinator_address=args.coordinator_address,
          num_processes=args.num_processes,
          process_id=args.process_id,
      )
    if elastic_config is not None:
      # Each elastic host runs a LOCAL mesh over its own devices;
      # run_training builds it (mesh_lib.local_mesh) so state
      # re-placement after a rebuild stays host-local.
      mesh = None
    elif args.dp:
      import jax

      mesh = mesh_lib.make_mesh(
          dp=args.dp, tp=args.tp,
          devices=jax.devices()[:args.dp * args.tp])
    else:
      mesh = mesh_lib.make_mesh(tp=args.tp)
    train_lib.run_training_with_retry(
        params=params,
        out_dir=args.out_dir,
        train_patterns=args.train_path,
        eval_patterns=args.eval_path,
        num_epochs=args.num_epochs,
        mesh=mesh,
        warm_start=args.checkpoint,
        elastic_config=elastic_config,
    )
    return 0

  if args.command == 'evaluate':
    from deepconsensus_tpu.models import config as config_lib
    from deepconsensus_tpu.models import evaluate as evaluate_lib

    params = config_lib.read_params_from_json(args.checkpoint)
    config_lib.finalize_params(params, is_training=False)
    with params.unlocked():
      if args.batch_size:
        params.batch_size = args.batch_size
    metrics = evaluate_lib.run_evaluation(
        params=params,
        checkpoint_path=args.checkpoint,
        eval_patterns=args.eval_path,
        out_dir=args.out_dir,
        limit=args.limit,
    )
    print(' '.join(f'{k}={v:.5f}' for k, v in sorted(metrics.items())))
    return 0

  if args.command == 'port':
    from deepconsensus_tpu.models import port_tf_checkpoint as port_lib

    path = port_lib.port_to_orbax(
        args.tf_checkpoint, args.params, args.out_dir
    )
    print(f'ported: {path}')
    return 0

  if args.command == 'export':
    from deepconsensus_tpu.models import config as config_lib
    from deepconsensus_tpu.models import export as export_lib

    dc_cal = args.dc_calibration
    if dc_cal is None:
      params = config_lib.read_params_from_json(args.checkpoint)
      dc_cal = params.get('dc_calibration', 'skip') or 'skip'
    artifact = export_lib.export_model(
        checkpoint_path=args.checkpoint,
        out_dir=args.output,
        batch_size=args.batch_size,
        strict_polymorphic=args.strict_polymorphic,
        inference_dtype=args.inference_dtype,
        quantize_matmuls=args.quantize_matmuls,
        device_epilogue=args.device_epilogue,
        max_base_quality=args.max_base_quality,
        dc_calibration=dc_cal,
    )
    print(f'exported: {artifact}')
    return 0

  if args.command == 'distill':
    from deepconsensus_tpu.models.checkpoints import load_params
    from deepconsensus_tpu.models import config as config_lib
    from deepconsensus_tpu.models import distill as distill_lib

    teacher_params = config_lib.read_params_from_json(
        args.teacher_checkpoint
    )
    config_lib.finalize_params(teacher_params)
    teacher_weights = load_params(args.teacher_checkpoint)
    student_params = config_lib.get_config(args.config)
    _apply_overrides(student_params, args.overrides)
    config_lib.finalize_params(student_params)
    with student_params.unlocked():
      if args.batch_size:
        student_params.batch_size = args.batch_size
      if args.window_buckets:
        student_params.window_buckets = args.window_buckets
    distill_lib.run_distillation(
        params=student_params,
        teacher_params_cfg=teacher_params,
        teacher_variables={'params': teacher_weights},
        out_dir=args.out_dir,
        train_patterns=args.train_path,
        eval_patterns=args.eval_path,
        num_epochs=args.num_epochs,
    )
    return 0

  if args.command == 'flywheel':
    import json

    from deepconsensus_tpu import faults as faults_lib
    from deepconsensus_tpu.models import flywheel as flywheel_lib
    from deepconsensus_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(tp=args.tp) if args.tp > 1 else None
    kwargs = {}
    if args.int8_gate is not None:
      kwargs['int8_gate_threshold'] = args.int8_gate
    if args.bf16_gate is not None:
      kwargs['bf16_gate_threshold'] = args.bf16_gate
    elastic_config = None
    if args.elastic:
      elastic_config = {
          'host_id': args.process_id or 0,
          'n_hosts': args.num_processes or 1,
          'barrier_timeout': args.elastic_barrier_timeout,
          'on_host_error': args.on_host_error,
          'readmit': args.elastic_readmit,
      }
    try:
      manifest = flywheel_lib.run_flywheel(
          out_dir=args.out_dir,
          train_patterns=args.train_path,
          eval_patterns=args.eval_path,
          teacher_config=args.config,
          student_config=args.student_config,
          teacher_checkpoint=args.teacher_checkpoint,
          teacher_overrides=args.overrides,
          student_overrides=args.student_overrides,
          num_epochs=args.num_epochs,
          batch_size=args.batch_size,
          export_batch_size=args.export_batch_size,
          inference_dtype=args.inference_dtype,
          quantize_matmuls=args.quantize_matmuls,
          mesh=mesh,
          resume=args.resume,
          elastic_config=elastic_config,
          window_buckets=args.window_buckets,
          baseline_checkpoint=args.baseline_checkpoint,
          **kwargs,
      )
    except faults_lib.FlywheelGateError as e:
      # The partial manifest (with the failing gate recorded) is
      # already on disk; exit 3 distinguishes a gate veto from the
      # operator-error exit 2. (FlywheelResumeError is a ValueError:
      # main() maps it to the operator-error exit 2.)
      print(f'dctpu: {e}', file=sys.stderr)
      return 3
    if manifest.get('interrupted'):
      # Preemption mid-cycle is a clean exit, not a failure: the
      # journal records the stage to re-enter and --resume on the same
      # out_dir picks the cycle back up.
      print(json.dumps({
          'interrupted': manifest['interrupted'],
          'journal': f'{args.out_dir}/{flywheel_lib.JOURNAL_NAME}',
          'resume': 'rerun with --resume',
      }, indent=2))
      return 0
    print(json.dumps({
        'artifact': manifest['stages']['export']['artifact'],
        'manifest': f'{args.out_dir}/{flywheel_lib.MANIFEST_NAME}',
        'gates': [{k: g[k] for k in ('name', 'measured', 'threshold',
                                     'passed')}
                  for g in manifest['gates']],
    }, indent=2))
    return 0

  if args.command == 'calibrate':
    from deepconsensus_tpu.calibration.measure import (
        calculate_quality_calibration,
    )

    calculate_quality_calibration(
        bam=args.bam,
        ref=args.ref,
        output=args.output,
        region=args.region,
        cpus=args.cpus,
    )
    return 0

  if args.command == 'yield_metrics':
    from deepconsensus_tpu.calibration.yield_metrics import (
        calculate_yield_metrics,
    )

    calculate_yield_metrics(
        bam=args.bam,
        ref=args.ref,
        output=args.output,
        identity_bar=args.identity_bar,
    )
    return 0

  if args.command == 'filter_reads':
    from deepconsensus_tpu.calibration.filter_reads import (
        filter_bam_or_fastq_by_quality,
    )

    filter_bam_or_fastq_by_quality(
        input_path=args.input,
        output_path=args.output,
        min_quality=args.quality,
    )
    return 0

  return 2


if __name__ == '__main__':
  sys.exit(main())
