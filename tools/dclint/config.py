"""Repo-specific configuration for the dclint checkers.

Everything path-like is a repo-relative posix path (or a prefix of
one).  Checkers decide whether a file is in scope by matching these
prefixes, so fixture tests can exercise a checker by handing it a
virtual path like ``deepconsensus_tpu/io/x.py``.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# Shared
# ---------------------------------------------------------------------------

# Files dclint walks when given a directory.  tests/ and tools/ are
# deliberately out of scope: fixtures seed violations on purpose.
WALK_ROOTS = ('deepconsensus_tpu',)
EXCLUDE_PARTS = ('__pycache__',)

# ---------------------------------------------------------------------------
# typed-faults
# ---------------------------------------------------------------------------

# Data-plane modules where every `raise` must be a typed fault.
TYPED_FAULTS_SCOPE = (
    'deepconsensus_tpu/io/',
    'deepconsensus_tpu/inference/',
    'deepconsensus_tpu/serve/',
    'deepconsensus_tpu/fleet/',
    'deepconsensus_tpu/models/data.py',
    # The observability plane is crossed by every request: a bare raise
    # in trace/metrics/summarize code takes the data plane down with it.
    'deepconsensus_tpu/obs/',
    # The elastic pod layer sits under every multi-host training step:
    # an untyped raise in a barrier/agreement path escapes the
    # HostLostError rebuild handler and kills the whole pod.
    'deepconsensus_tpu/parallel/elastic.py',
)

# The typed fault taxonomy (deepconsensus_tpu/faults.py plus the
# inference-side additions in inference/faults.py).  Kept static so the
# checker behaves identically on fixture trees; tests/test_dclint.py
# asserts this list stays in sync with the real modules.
FAULT_TYPES = frozenset({
    # deepconsensus_tpu/faults.py
    'CorruptInputError',
    'ServeRejection',
    'BackpressureError',
    'DrainingError',
    'DeadlineExceededError',
    'BadRequestError',
    'RequestTooLargeError',
    'CrashLoopError',
    'NonFiniteTrainingError',
    'WindowBucketError',
    'FlywheelGateError',
    'FlywheelStageError',
    'FlywheelResumeError',
    'ExportedArtifactMismatchError',
    'DeviceFault',
    'DeviceOomError',
    'DeviceLostError',
    'DispatchTimeoutError',
    'FleetRejection',
    'ReplicaLostError',
    'QuotaExceededError',
    'HostLostError',
    'ElasticRebuildError',
    'InjectedHostDeath',
    # deepconsensus_tpu/inference/faults.py
    'ZmwFault',
    'WatchdogTimeout',
})

# Exceptions that are control flow / interop, not fault reporting.
CONTROL_FLOW_EXCEPTIONS = frozenset({
    'StopIteration',
    'StopAsyncIteration',
    'KeyboardInterrupt',
    'SystemExit',
    'NotImplementedError',
})

# Local helper functions that construct-and-return a typed fault
# (`raise corrupt(...)` in io/bam.py).
FAULT_CONSTRUCTOR_HELPERS = frozenset({'corrupt'})

# Module-local exception classes that are deliberately NOT faults.py
# types.  Each entry documents why.
TYPED_FAULTS_EXTRA_ALLOWED = {
    'ServeClientError': (
        'client-side transport error: raised in the client process, '
        'never crosses the serve data plane'),
}

# A broad `except Exception:` handler passes if it re-raises, or if it
# hands the caught exception to a call whose dotted name contains one
# of these markers (quarantine.record_failure, dead-letter writers,
# _on_pack_failure, emit_queue.put, ...).
ROUTING_NAME_MARKERS = (
    'quarantine', 'record', 'dead_letter', 'fail', 'put', 'handle',
)

# ---------------------------------------------------------------------------
# jit-hazards
# ---------------------------------------------------------------------------

# Files whose hot functions are scanned for host syncs / jit traps.
JIT_SCOPE = (
    'deepconsensus_tpu/inference/engine.py',
    'deepconsensus_tpu/inference/runner.py',
    'deepconsensus_tpu/serve/service.py',
    'deepconsensus_tpu/models/train.py',
)

# Per-batch functions: called once (or more) per dispatched pack, so a
# jax.jit construction or an implicit device->host sync here hits the
# continuous-batching latency directly.
HOT_FUNCTIONS = {
    'deepconsensus_tpu/inference/engine.py': frozenset({
        'add', '_cut_packs', '_dispatch', '_drain_one', '_deliver_pack',
        'flush', 'submit', 'submit_formatted',
    }),
    'deepconsensus_tpu/inference/runner.py': frozenset({
        'dispatch', 'dispatch_ragged', 'finalize', '_finalize_sync',
        'predict', '_launch', '_launch_pending', 'raw_outputs',
    }),
    'deepconsensus_tpu/serve/service.py': frozenset({
        '_model_loop', '_ingest', '_deliver', '_process_retries',
        '_finish',
    }),
    # Training-batch prefetcher (TrainBatchPrefetcher): these run once
    # per training step, so a host sync on the prefetched transfer
    # before train_step consumes it serializes H2D against compute.
    'deepconsensus_tpu/models/train.py': frozenset({
        '_produce', '_launch', '_put', '__next__', 'place',
    }),
}

# Calls whose results live on device: assigning from one of these makes
# the target a device value for host-sync tracking.  Matched on the
# last dotted segment.
DEVICE_SOURCE_CALLS = frozenset({
    '_jit_forward', '_jit_ragged_forward', 'device_put', 'dispatch',
    'dispatch_ragged',
    # Output-plane epilogues (ops/output_plane.py): their uint8 planes
    # are device values until the finalize drain.
    'phred_epilogue',
})

# Function parameters known to carry device values (the engine hands
# `ModelRunner.dispatch` results straight to `finalize` /
# `raw_outputs`, and `_launch` receives the in-flight handle).
DEVICE_PARAMS = {
    ('deepconsensus_tpu/inference/runner.py', 'finalize'): frozenset(
        {'dispatched'}),
    ('deepconsensus_tpu/inference/runner.py', '_finalize_sync'): frozenset(
        {'dispatched'}),
    ('deepconsensus_tpu/inference/runner.py', 'raw_outputs'): frozenset(
        {'dispatched'}),
    ('deepconsensus_tpu/inference/runner.py', '_launch'): frozenset(
        {'handle'}),
}

# Host-materialising calls: flagged when applied to a device value.
HOST_SYNC_CALLS = frozenset({'float', 'int', 'bool', 'asarray', 'array'})

# The jitted forward call (last dotted segment) that consumes a
# double-buffered `device_put` transfer.  A host-materialising use of a
# transfer result BEFORE this call is an implicit sync that defeats the
# transfer/compute overlap (jit-hazards double-buffer rule).
FORWARD_CALLS = frozenset({'_forward', '_ragged_forward',
                           'ragged_forward', 'phred_epilogue',
                           'train_step'})

# dtype-downcast sub-rule: modules where an unannotated cast to a
# reduced-precision dtype is flagged.  With bf16 inference live, a
# stray `astype(jnp.bfloat16)` (or a cast through the compute-dtype
# knobs) in model/kernel code silently halves the mantissa of a value
# the author may have assumed stayed f32; every deliberate downcast
# carries `# dclint: allow=dtype-downcast (reason)`.
DTYPE_DOWNCAST_SCOPE = (
    'deepconsensus_tpu/models/',
    'deepconsensus_tpu/ops/',
)

# Literal / attribute dtype targets that are reduced-precision.
HALF_DTYPES = frozenset({'bfloat16', 'float16'})

# Config-driven dtype names: casting to these is a downcast whenever
# the inference_dtype lever is bf16, so the cast site must be
# deliberate and annotated.
COMPUTE_DTYPE_NAMES = frozenset({'compute_dtype', 'inference_dtype'})

# Cast-shaped calls (last dotted segment) the dtype-downcast rule
# inspects: `x.astype(d)` and `jnp.asarray(x, d)` / `jnp.array(x, d)`.
DTYPE_CAST_CALLS = frozenset({'astype', 'asarray', 'array'})

# ---------------------------------------------------------------------------
# guarded-by
# ---------------------------------------------------------------------------

GUARDED_BY_SCOPE = (
    'deepconsensus_tpu/serve/service.py',
    'deepconsensus_tpu/inference/engine.py',
    'deepconsensus_tpu/inference/runner.py',
    'deepconsensus_tpu/fleet/registry.py',
    'deepconsensus_tpu/fleet/router.py',
    # The autoscaler's control loop, ledger and decision counters are
    # shared between its poll thread and the CLI lifecycle thread.
    'deepconsensus_tpu/fleet/autoscaler.py',
    # TrainBatchPrefetcher's producer thread shares counters and the
    # mesh-generation with the training loop.
    'deepconsensus_tpu/models/train.py',
    # StreamingDataset's shard-reader thread shares the parse counters
    # and the per-bucket accumulators with the consuming train loop.
    'deepconsensus_tpu/models/data.py',
    # The flywheel orchestration dispatch (train/distill drive their
    # own threads through run_training's machinery).
    'deepconsensus_tpu/cli.py',
    # The metrics registry and trace writer are mutated from every
    # handler/model/producer thread in a tier process.
    'deepconsensus_tpu/obs/',
    # ElasticPod's membership state is shared between the heartbeat
    # daemon thread and the training loop's barrier/rebuild calls.
    'deepconsensus_tpu/parallel/elastic.py',
)

# Attribute initialisers of these types are synchronisation primitives
# or thread-safe containers themselves; they never need a guard.
THREADSAFE_INIT_CALLS = frozenset({
    'Lock', 'RLock', 'Condition', 'Event', 'Semaphore',
    'BoundedSemaphore', 'Barrier', 'Queue', 'SimpleQueue',
    'LifoQueue', 'PriorityQueue',
})

# Method calls that mutate their receiver (used to classify closure
# variable accesses as writes).
MUTATING_METHODS = frozenset({
    'append', 'appendleft', 'extend', 'insert', 'add', 'update',
    'pop', 'popleft', 'remove', 'discard', 'clear', 'setdefault',
    'record',
})

# ---------------------------------------------------------------------------
# registry-writes
# ---------------------------------------------------------------------------

# Modules converted to the obs/ metrics registry: ad-hoc counter-dict
# writes here are regressions (ISSUE 15).
REGISTRY_WRITES_SCOPE = (
    'deepconsensus_tpu/serve/service.py',
    'deepconsensus_tpu/fleet/router.py',
    'deepconsensus_tpu/fleet/featurize_worker.py',
    'deepconsensus_tpu/obs/',
)

# The registry implementation is the one legitimate owner of counter
# container writes.
REGISTRY_WRITES_EXEMPT = ('deepconsensus_tpu/obs/metrics.py',)

# ---------------------------------------------------------------------------
# shape-literals
# ---------------------------------------------------------------------------

SHAPE_LITERAL_VALUES = frozenset({100, 128, 200, 256, 500})

# The one place window-shape defaults may live.
SHAPE_LITERALS_EXEMPT = ('deepconsensus_tpu/models/config.py',)

# Keyword arguments whose value being 100/128 marks a window-shape
# assumption.
SHAPE_KEYWORDS = frozenset({
    'max_length', 'example_width', 'width', 'window_size',
    'max_window_len', 'padded_len', 'window_len', 'max_passes',
})

# Name fragments that mark a comparison / assignment target as
# shape-ish (`if length > 100`, `max_length = 100`, `L <= 128`).
SHAPE_NAME_FRAGMENTS = ('length', 'width', 'window')
SHAPE_SHORT_NAMES = frozenset({'L', 'l'})
