import os
import sys

import pytest

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


@pytest.fixture(scope='module')
def no_cache():
  import jax
  jax.config.update('jax_enable_compilation_cache', False)
