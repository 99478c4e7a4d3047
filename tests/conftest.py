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

import pytest  # noqa: E402


@pytest.fixture
def walked_blocks():
    """``check(engine, prompt_lengths, max_new, grid, rows=1, row_len=int)``: serve streams of
    known lengths, all admitted in the first step and all alive to the
    last, and hold the step records' ``attn_blocks`` (and the counter
    ``serve.decode_attn_blocks``) to the sum reckoned by hand:
    ``seq_len // block + 1`` over the live streams of each decode step
    dispatched, ``rows`` kernel rows a stream (``row_len``: a row's length
    for a query at position t where it is not t), and the gauge
    ``serve.decode_attn_grid_blocks`` to ``grid``, the blocks of a grid of
    every block a slot could hold. An engine whose decode program does
    not run the ragged paged kernel (``rows=0``) reads 0 in all three."""

    def check(eng, prompt_lengths, max_new, grid, rows=1, row_len=int):
        reqs = [
            eng.submit([1 + (i + j) % 50 for j in range(p)], max_new)
            for i, p in enumerate(prompt_lengths)
        ]
        eng.run()
        assert all(r.state == "finished" for r in reqs)
        block = eng.adapter.block_kv
        by_hand = [
            rows * sum(row_len(p + d) // block + 1 for p in prompt_lengths)
            if rows else 0
            for d in range(max_new - 1)  # a stream's first token is its prefill's
        ]
        log = list(eng.step_log)
        dispatched = [r for r in log if r["live"]]
        assert [r["live"] for r in dispatched] == [len(reqs)] * (max_new - 1)
        assert [r["kv_tokens"] for r in dispatched] == [
            sum(p + d for p in prompt_lengths) for d in range(max_new - 1)]
        assert [r["attn_blocks"] for r in dispatched] == by_hand
        assert all(r["attn_blocks"] == 0 for r in log if not r["live"])
        value = eng.registry.counter("serve.decode_attn_blocks").value
        assert value == sum(by_hand)
        gauge = eng.registry.gauge("serve.decode_attn_grid_blocks").value
        assert gauge == (grid if rows else 0)
        return by_hand

    return check
