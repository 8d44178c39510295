"""Peak device memory of the fullest chip after the window, in GiB
(`memory_stats()`: `peak_bytes_in_use`, the live arrays, plus
`peak_bytes_reserved`, the scratch set aside for a program's temporaries;
`run.memory_peak`)."""


def read(r):
  if not r.on_chip or not r.memory_peak_bytes:
    return None
  return r.memory_peak_bytes / 2**30
