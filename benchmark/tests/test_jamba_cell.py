"""The Jamba cell: its rehearsal with the control failing, its
configuration against the catalog's row, and its two programs compiled
at published widths for a described TPU v5e (no chip attached)."""

import json
import os
import re
import subprocess
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "jamba2-3b.serve-docs-over"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "jamba2-3b.1chip.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HBM = 15.75 * 2**30  # what the compiler has of a v5e's 16 GB


def rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", CELL, "--seed", "2147483700", "--seconds", "3",
           "--trace", "0", "--rehearse", *extra]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_passes_and_its_control_fails():
    p, sound = rehearse()
    q, control = rehearse("--control", "1")
    assert sound["rehearsal_checks_passed"] is True, p.stdout[-2000:]
    assert control["rehearsal_checks_passed"] is False, q.stdout[-2000:]
    assert "check served_token_logit_gap_mean" in q.stdout
    assert "NOT ok" in q.stdout


def test_configuration_is_the_catalogs_row_uncut():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    assert {k: mine[k] for k in row["config"]} == row["config"]
    assert mine["reduced"] == [] and mine["published"] == {}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "jamba2-3b.1chip")
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    import jax
    from jax.experimental import topologies

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _largest_array(text):
    """Elements of the largest array shape named anywhere in an HLO text."""
    best = 0
    for dims in re.findall(r"\b(?:f32|bf16|s32|u32|pred|f16)\[([\d,]+)\]", text):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        best = max(best, n)
    return best


def test_programs_compile_at_published_widths_and_fit(topo, monkeypatch):
    """The decode program at the cell's 16 slots and the prefill program
    of the longest prompt (8192 tokens), all 28 layers: both fit beside
    6.06 GB of weights, the scan and the attention are Mosaic kernels,
    and no array of the order of (S, d_inner, N) exists in either."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness, weights
    from benchmark.drivers.serve_hybrid import as_program_tree
    from fms_fsdp_tpu.models.mamba import init_mamba_decode_state
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families.mamba import (
        decode_program, page_geometry, prefill_program)

    # the program picks its kernels by the backend it finds: say "tpu",
    # as the chip will
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = types.SimpleNamespace(
        workload=CELL, seed=1, seconds=1.0, trace=0, rehearse=False, control=0)
    run = harness.Run(args, ROOT, time.perf_counter())
    c = run.config
    cfg = run.family.model_config(c)
    scfg = ServeConfig(**run.cell_file["engine"])
    sh = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sh)

    bf16 = jnp.bfloat16
    params = as_program_tree(weights.unflatten({
        p: S(s["shape"], bf16)
        for p, s in run.reference.param_spec(c).items()}))
    assert sum(
        x.size for x in jax.tree.leaves(params)) * 2 > 0.25 * 16e9  # 6.06 GB
    page, max_pages, num_pages = page_geometry(cfg, scfg)
    B = scfg.max_batch
    state = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda: init_mamba_decode_state(cfg, B, bf16)))
    pools = {k: S((2, num_pages, page, 1, 128), bf16) for k in ("k", "v")}
    decode = decode_program(cfg, scfg, page, bf16).lower(
        params, state, pools, S((B, max_pages), jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.int32), S((2,), jnp.uint32)).compile()
    top = run.traffic["prompt_tokens"]["max"]
    prefill = prefill_program(cfg, scfg, top, top, bf16).lower(
        params, S((1, top), jnp.int32), S((1,), jnp.int32)).compile()
    history = top * cfg.d_inner * cfg.d_state
    for compiled in (decode, prefill):
        m = compiled.memory_analysis()
        assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
        assert _largest_array(compiled.as_text()) < history / 2
    # 26 selective scans and 2 flash attentions
    assert prefill.as_text().count("tpu_custom_call") == 28


def test_a_prefetch_without_a_name_takes_its_consumers_scope():
    from benchmark.program_scopes_jamba import fill_from_users

    text = """
ENTRY %main (p0: bf16[2,8,8]) -> bf16[4,8] {
  %p0 = bf16[2,8,8]{2,1,0} parameter(0)
  %slice-start.1 = ((bf16[2,8,8]), bf16[1,8,8], s32[]) slice-start(%p0), slice={[0:1], [0:8], [0:8]}
  %slice-done.1 = bf16[1,8,8]{2,1,0} slice-done(%slice-start.1)
  %fusion.7 = bf16[4,8]{1,0} fusion(%x, %slice-done.1), kind=kOutput, calls=%fused
  %copy.3 = bf16[4,8]{1,0} copy(%q)
}
"""
    table = {"p0": "", "slice-start.1": "", "slice-done.1": "",
             "fusion.7": "ssm_in_proj", "copy.3": ""}
    got = fill_from_users(text, table)
    assert got["slice-start.1"] == got["slice-done.1"] == "ssm_in_proj"
    assert got["copy.3"] == "" and got["fusion.7"] == "ssm_in_proj"
    assert table["slice-done.1"] == ""  # a new table, the old one untouched


def test_balanced_schedule_is_the_generators_set_in_an_even_order():
    import numpy as np

    from benchmark import traffic
    from benchmark.drivers.serve_hybrid import balanced_schedule

    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        mix = json.load(f)["traffic"]
    plain = traffic.serve_schedule(7, mix, 45.0, 65536)
    a = balanced_schedule(7, mix, 45.0, 65536)
    b = balanced_schedule(8, mix, 45.0, 65536)
    lens = lambda s: (sorted(len(p) for _, p, _ in s), sorted(o for _, _, o in s))  # noqa: E731
    assert lens(a) == lens(b) == lens(plain) and len(a) == 229
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]
    assert a == balanced_schedule(7, mix, 45.0, 65536)
    assert all(1 <= t < 65536 for _, p, _ in a[:3] for t in p)
    # every prefix holds the quartiles of both lengths alike
    for s in (a, b):
        for col in (lambda r: len(r[1]), lambda r: r[2]):
            cuts = np.quantile([col(r) for r in s], [0.25, 0.5, 0.75])
            for n in (32, 96, 160):
                counts = np.bincount(
                    np.searchsorted(cuts, [col(r) for r in s[:n]]), minlength=4)
                assert counts.max() - counts.min() <= 3, (n, counts)
    # and so offers a window the same work whatever the seed: padded
    # prompt tokens and output tokens of the first 160 within 1%
    def work(s):
        pad = sum(-(-len(p) // 2048) * 2048 for _, p, _ in s[:160])
        return pad, sum(o for _, _, o in s[:160])
    works = [work(balanced_schedule(seed, mix, 45.0, 65536)) for seed in range(6)]
    for k in (0, 1):
        col = [w[k] for w in works]
        assert (max(col) - min(col)) / min(col) < 0.03, col
