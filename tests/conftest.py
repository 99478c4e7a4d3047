"""Test harness: force an 8-device virtual CPU mesh BEFORE jax initializes.

The reference tests distributed behavior single-process by parameterizing
(rank, worldsize) (ref:tests/test_datasets.py). We go further — JAX can
simulate an 8-device mesh on CPU, so sharding/collective correctness is
unit-testable (SURVEY.md §4 implication).
"""

import os
import sys

# Tests always run on the virtual CPU mesh, whatever the session's
# environment says: set both before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests neither read nor write the persistent compilation cache the entry
# points place (utils/compile_cache.py): a run must not depend on what an
# earlier one left in the checkout, and a cold cache costs only writes.
# Children the tests start inherit this.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
