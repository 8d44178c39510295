"""Native (C++) accelerators with build-on-first-use and ctypes bindings.

The reference's native surface is htslib via pysam; here the equivalent
is a small C++ library (bgzf.cpp) compiled on demand with the system
toolchain. Everything degrades gracefully to the pure-Python paths when
a compiler is unavailable.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, 'bgzf.cpp')
_LIB = os.path.join(_DIR, 'libdcnative.so')

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _build() -> bool:
  # Link into a private name and rename into place: another process
  # (an xdist worker, a pool child) never loads a half-written library.
  tmp = f'{_LIB}.{os.getpid()}.tmp'
  cmd = [
      'g++', '-O3', '-shared', '-fPIC', '-std=c++17', _SRC,
      '-o', tmp, '-lz', '-lpthread',
  ]
  try:
    subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    os.replace(tmp, _LIB)
    return True
  except (subprocess.CalledProcessError, FileNotFoundError,
          subprocess.TimeoutExpired, OSError) as e:
    log.warning('native build failed (%s); using pure-Python fallback', e)
    return False


def get_lib() -> Optional[ctypes.CDLL]:
  """Loads (building if needed) the native library, or None.

  DC_TPU_NO_NATIVE=1 disables it (emergency off-switch to the
  pure-Python decoders; checked per call so spawn-based worker
  processes honor it too)."""
  if os.environ.get('DC_TPU_NO_NATIVE') == '1':
    return None
  global _lib, _build_failed
  with _lock:
    if _lib is not None:
      return _lib
    if _build_failed:
      return None
    if not os.path.exists(_LIB) or (
        os.path.exists(_SRC)
        and os.path.getmtime(_SRC) > os.path.getmtime(_LIB)
    ):
      if not _build():
        _build_failed = True
        return None
    try:
      lib = ctypes.CDLL(_LIB)
    except OSError as e:
      log.warning('native load failed (%s)', e)
      _build_failed = True
      return None
    lib.dc_bgzf_decompress_file.restype = ctypes.c_int
    lib.dc_bgzf_decompress_file.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_size_t,
    ]
    lib.dc_free.argtypes = [ctypes.c_void_p]
    lib.dc_crc32c.restype = ctypes.c_uint32
    lib.dc_crc32c.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32
    ]
    lib.dc_bgzf_decompress.restype = ctypes.c_int
    lib.dc_bgzf_decompress.argtypes = [
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_size_t,
    ]
    lib.dc_gzip_decompress.restype = ctypes.c_int
    lib.dc_gzip_decompress.argtypes = [
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_size_t,
    ]
    lib.dc_tfrecord_index.restype = ctypes.c_int
    lib.dc_tfrecord_index.argtypes = [
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    _lib = lib
    return _lib


def bgzf_decompress_file(path: str, n_threads: int = 4,
                         max_out: int = 0) -> Optional[bytes]:
  """Decompresses a whole BGZF file in parallel; None -> use fallback.

  max_out (0 = unlimited) bounds the decompressed size: the BGZF block
  scan knows the total before inflating anything, so an oversized (or
  length-field-inflated) file returns None without allocating."""
  lib = get_lib()
  if lib is None:
    return None
  out = ctypes.POINTER(ctypes.c_uint8)()
  out_len = ctypes.c_size_t()
  rc = lib.dc_bgzf_decompress_file(
      path.encode(), n_threads, ctypes.byref(out), ctypes.byref(out_len),
      max_out
  )
  if rc != 0:
    return None
  try:
    return ctypes.string_at(out, out_len.value)
  finally:
    lib.dc_free(out)


def crc32c(data: bytes, seed: int = 0) -> Optional[int]:
  lib = get_lib()
  if lib is None:
    return None
  return int(lib.dc_crc32c(data, len(data), seed))


def _looks_bgzf(raw: bytes) -> bool:
  return (len(raw) > 18 and raw[:2] == b'\x1f\x8b'
          and bool(raw[3] & 4))


def read_tfrecord_records(path: str, n_threads: int = 4,
                          compressed: Optional[bool] = None,
                          max_out: int = 0):
  """Decodes a whole TFRecord shard natively: gzip/BGZF inflate (BGZF
  blocks in parallel) + record framing in C, one Python slice per
  record. Returns a list of record payload bytes, or None -> caller
  must use the streaming Python fallback. Whole-shard decode trades
  memory (the decompressed shard) for the per-record Python
  read/struct overhead that dominates the measured decode path.

  max_out (0 = unlimited) bounds the decompressed size in C: BGZF
  rejects from the block scan before inflating anything; arbitrary
  gzip aborts as soon as output exceeds the cap. Either way the caller
  gets None and must stream."""
  lib = get_lib()
  if lib is None:
    return None
  try:
    with open(path, 'rb') as f:
      raw = f.read()
  except OSError:
    return None
  if compressed is None:
    compressed = path.endswith('.gz')
  if not compressed:
    return _index_and_slice(lib, raw, len(raw))
  out = ctypes.POINTER(ctypes.c_uint8)()
  out_len = ctypes.c_size_t()
  rc = 1
  if _looks_bgzf(raw):
    rc = lib.dc_bgzf_decompress(raw, len(raw), n_threads,
                                ctypes.byref(out), ctypes.byref(out_len),
                                max_out)
    if rc == 6:  # over max_out — retrying via gzip would just re-reject
      return None
  if rc != 0:
    rc = lib.dc_gzip_decompress(raw, len(raw),
                                ctypes.byref(out), ctypes.byref(out_len),
                                max_out)
  if rc != 0:
    return None
  del raw  # compressed copy no longer needed; keep the peak low
  try:
    # Index and slice records straight off the C buffer: copying it
    # wholesale into a Python bytes first would add a full extra
    # decompressed-shard copy to the peak (the records themselves are
    # the one unavoidable copy).
    return _index_and_slice(
        lib, ctypes.cast(out, ctypes.c_char_p), out_len.value,
        base=ctypes.addressof(out.contents))
  finally:
    lib.dc_free(out)


def _index_and_slice(lib, buf, buf_len: int, base: Optional[int] = None):
  """Runs dc_tfrecord_index over `buf` (bytes, or a C pointer with
  `base` set to its address) and returns the record payload slices."""
  pairs = ctypes.POINTER(ctypes.c_uint64)()
  n_records = ctypes.c_size_t()
  rc = lib.dc_tfrecord_index(buf, buf_len, ctypes.byref(pairs),
                             ctypes.byref(n_records))
  if rc != 0:
    return None
  try:
    n = n_records.value
    if base is not None:
      return [ctypes.string_at(base + pairs[2 * i], pairs[2 * i + 1])
              for i in range(n)]
    return [buf[pairs[2 * i]:pairs[2 * i] + pairs[2 * i + 1]]
            for i in range(n)]
  finally:
    lib.dc_free(pairs)
