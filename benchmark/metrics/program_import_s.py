"""Seconds the program took to import its runner and all that pulls in
(`import_runner`, stamped at `inference/runner.py`'s module scope; `jax`
itself only where the caller had not imported it: `args.jax_preloaded`)."""


def read(r):
  spans = r.spans.get('import_runner')
  if not spans:
    return None
  a, b, _ = spans[0]
  return b - a
