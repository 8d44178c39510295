"""One place that decides where JAX's persistent compilation cache lives.

Every entry point that jits (`dctpu run/serve/train/distill/...`,
chip_smoke.py's children) calls `enable()` before its first compile.
The directory is part of the cache key's surroundings, so it must not
move between processes that are meant to share compiles.
"""
from __future__ import annotations

import os
import re
from typing import Optional

ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'

# <checkout>/.jax_cache — git-ignored, the same for every entry point.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    '.jax_cache')

_FORCED_HOST_DEVICES = re.compile(
    r'xla_force_host_platform_device_count=(\d+)')


def virtual_cpu_devices() -> bool:
  """Whether this process asked for several virtual CPU devices (the
  test mesh, a multi-chip rehearsal). Read from the flags, not from the
  backend: `dctpu train` calls enable() before
  jax.distributed.initialize, which must precede backend start-up."""
  import jax

  forced = _FORCED_HOST_DEVICES.search(os.environ.get('XLA_FLAGS', ''))
  return bool((forced and int(forced.group(1)) > 1)
              or jax.config.jax_num_cpu_devices > 1)


def enable() -> Optional[str]:
  """Turns the persistent cache on; returns the directory set in code.

  With JAX_COMPILATION_CACHE_DIR in the environment JAX already reads
  the directory from there (and so does every child process), so no
  directory is set in code and None is returned. Otherwise the cache
  goes to the fixed path inside the checkout. Every compile is kept,
  however short: a warm restart should compile nothing.

  The one exception is a process on virtual CPU devices: XLA:CPU of
  this installation aborts (minutes later, inside a collective) when it
  runs a multi-device executable reloaded from the cache, so there the
  cache is switched off, whatever the variable says.
  """
  import jax

  if virtual_cpu_devices():
    jax.config.update('jax_enable_compilation_cache', False)
    return None
  jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
  jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
  if os.environ.get(ENV_VAR):
    return None
  jax.config.update('jax_compilation_cache_dir', CHECKOUT_CACHE_DIR)
  return CHECKOUT_CACHE_DIR
