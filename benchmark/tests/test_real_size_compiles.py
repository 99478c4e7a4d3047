"""Each cell's timed program compiled at its real size for a described
TPU v5e (no chip attached): what the chip's compiler would refuse, it
refuses here. All in this one file, the topology inside a fixture, so
that only the worker that runs this file loads the TPU's library
(/opt/skills/guides/on-chip-measurement, section 2).
"""

import os
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
HBM = 15.75 * 2**30  # what the compiler has of a v5e's 16 GB


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a deviceless executable written to the persistent cache cannot be
    # read back: keep the cache off around these compiles
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _run(cell, root=ROOT):
    from benchmark import harness

    args = types.SimpleNamespace(
        workload=cell, seed=1, seconds=1.0, trace=0, rehearse=False, control=0)
    return harness.Run(args, root, time.perf_counter())


def _peak(compiled):
    m = compiled.memory_analysis()
    return getattr(m, "peak_memory_in_bytes", 0) or (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_serve_programs_compile_and_fit(topo):
    """The decode program at the cell's slots and cache, and the largest
    prefill program, with the weights of the cell's depth beside them."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import SingleDeviceSharding

    from benchmark import weights
    from fms_fsdp_tpu.models.mixtral import (
        mixtral_paged_decode_step, mixtral_prefill)

    run = _run("mixtral-8x7b.serve-chat-over")
    c, eng = run.config, run.cell_file["engine"]
    model_cfg = run.family.model_config(c)
    sh = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sh)

    params = weights.unflatten({
        p: S(s["shape"], jnp.bfloat16)
        for p, s in run.reference.param_spec(c).items()})
    B, L, page = eng["max_batch"], c["num_hidden_layers"], 64
    pages = eng["max_seq_len"] // page
    pools = {k: S((L, B * pages + 2, page, c["num_key_value_heads"],
                   c["hidden_size"] // c["num_attention_heads"]), jnp.bfloat16)
             for k in ("k", "v")}
    decode = jax.jit(
        lambda p, pl, table, lens, toks: mixtral_paged_decode_step(
            p, pl, table, lens, toks, model_cfg, page_size=page,
            compute_dtype=jnp.bfloat16, moe_impl=eng["moe_impl"]),
        donate_argnums=(1,))
    compiled = decode.lower(
        params, pools, S((B, pages), jnp.int32), S((B,), jnp.int32),
        S((B,), jnp.int32)).compile()
    assert 0.25 * 16e9 < _peak(compiled) < HBM
    top = run.traffic["prompt_tokens"]["max"]
    prefill = jax.jit(partial(
        mixtral_prefill, cfg=model_cfg, max_seq_len=top,
        compute_dtype=jnp.bfloat16, full_logits=True))
    assert _peak(prefill.lower(params, S((1, top), jnp.int32)).compile()) < HBM
    # one layer more does not fit: the depth is the largest that does
    deeper = dict(c, num_hidden_layers=L + 1)
    params = weights.unflatten({
        p: S(s["shape"], jnp.bfloat16)
        for p, s in run.reference.param_spec(deeper).items()})
    pools = {k: S((L + 1,) + v.shape[1:], v.dtype) for k, v in pools.items()}
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|memory"):
        decode.lower(
            params, pools, S((B, pages), jnp.int32), S((B,), jnp.int32),
            S((B,), jnp.int32)).compile()
