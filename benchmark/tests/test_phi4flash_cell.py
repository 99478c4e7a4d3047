"""The phi4flash cell: its rehearsal with the control failing, its
configuration against the catalog's row, its traffic as the issue states
it, its readers on events made by hand and on another family's run, its
cost functions at the published sizes and against the arrays a compiled
decode step takes, for a described TPU v5e (no chip attached)."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "phi-4-mini-flash.serve-reasoning-over"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "phi-4-mini-flash.1chip.json")
WORKLOAD = os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HBM = 15.75 * 2**30  # what the compiler has of a v5e's 16 GB
NEW_READERS = (
    "phi4flash_decode_roofline", "decode_shared_kv_attn_ms",
    "shared_kv_decode_attn_roofline", "decode_gmu_ms",
    "prefill_cross_positions_share", "phi4flash_decode_ssm_ms",
    "phi4flash_decode_window_attn_ms")


def rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", CELL, "--seed", "2147483700", "--seconds", "3",
           "--rehearse", *extra]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_passes_and_its_control_fails():
    p, sound = rehearse("--trace", "1")
    q, control = rehearse("--trace", "0", "--control", "1")
    assert sound["rehearsal_checks_passed"] is True, p.stdout[-2000:]
    assert sound["correct"] is False and sound["metrics"] == {}
    assert set(sound) >= {"attempted", "failed", "device", "rehearsal"}
    assert control["rehearsal_checks_passed"] is False, q.stdout[-2000:]
    for what in ("served_token_logit_gap_mean",
                 "served_token_logit_gap_share_over"):
        assert re.search(f"check {what}: .* -> NOT ok", q.stdout), what
    # the prefill's own counts reach the trace: one position a prompt
    # through the second half of the stack
    read = re.search(r"rehearsal read per-layer metrics: (\[.*\])", p.stdout)
    assert "prefill_cross_positions_share" in read.group(1)
    first, second = map(int, re.search(
        r"(\d+) positions through the first half of the stack and (\d+) "
        r"through the second", p.stdout).groups())
    assert second == sound["attempted"] and first >= 16 * second


def test_configuration_is_the_catalogs_row_uncut():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if mine.get(k) != v] == []
    assert mine["reduced"] == [] and mine["published"] == {}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "phi-4-mini-flash.1chip")
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]
    assert set(mine["assumed"]) >= {
        "mamba_sizes", "layer_rule", "memory", "differential_attention",
        "attention_biases", "no_rotary", "window", "scan_state_dtype",
        "serving_dtype"}
    from benchmark.reference import phi4flash as reference

    assert reference.n_params(mine) == mine["n_params"] == 3852562944
    assert mine["weight_bytes_bfloat16"] == 2 * mine["n_params"]


def test_traffic_is_what_the_issue_states():
    with open(WORKLOAD) as f:
        w = json.load(f)
    assert w["kind"] == "serve_phi4flash"
    t, e = w["traffic"], w["engine"]
    assert t["queued_at_open"] == 256 == 2 * e["max_batch"]
    assert t["prompt_tokens"] == {
        "median": 512, "sigma": 0.8, "min": 64, "max": 4096}
    assert t["output_tokens"] == {
        "median": 1024, "sigma": 0.7, "min": 128, "max": 4096}
    assert (e["max_batch"], e["max_seq_len"], e["prefill_bucket"],
            e["attn_impl"], e["compute_dtype"]) == (
        128, 8192, 256, "auto", "bfloat16")
    # the longest prompt and the longest output together fit a stream
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] <= e[
        "max_seq_len"]
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families import load_model_config, program_len

    with open(CONFIG) as f:
        cfg = load_model_config(json.load(f))
    scfg = ServeConfig(**e)
    # five prefill programs: 256 to 4096 by doubling
    lengths = {program_len(-(-p // 256) * 256, 256, scfg.max_seq_len)
               for p in range(64, 4097)}
    assert sorted(lengths) == [256, 512, 1024, 2048, 4096]
    from fms_fsdp_tpu.serve.families.phi4flash import page_geometry

    page, block_kv, max_pages, num_pages = page_geometry(cfg, scfg)
    assert (page, block_kv, max_pages) == (128, 512, 64)
    # a pool of 2.0 GB for one layer at 5120 B a position
    assert 1.99e9 < (num_pages - 2) * page * 5120 < 2.01e9


def test_readers_on_events_made_by_hand():
    """Each new reader on a trace made by hand (decode steps by scope, the
    costs from the configuration's file), on a run of this family without
    these scopes and on another family's run, where each finds nothing
    and says so."""
    from benchmark import costs_phi4flash as costs
    from benchmark import harness
    from benchmark import program_scopes_phi4flash as scopes
    from benchmark.program_scopes_kexaone import KExaoneTrace

    with open(CONFIG) as f:
        c = json.load(f)
    ms = 1e6
    ft = KExaoneTrace()
    ft.coarse = KExaoneTrace(decode_steps=[
        {"attn_full": 2.0 * ms, "attn_cross": 14.0 * ms, "gmu": 1.5 * ms,
         "ssm_in_proj": 0.6 * ms, "ssm_conv": 0.1 * ms, "ssm_params": 0.2 * ms,
         "ssm_scan": 0.3 * ms, "ssm_gate_out": 0.3 * ms,
         "attn_window": 4.0 * ms, "win_write": 0.5 * ms, "mlp": 6 * ms,
         "lm_head": 1.0 * ms, "": 0.2 * ms}] * 3)
    live = {"streams": 126.0, "kv_tokens": 200_000.0,
            "ring_positions": 60_000.0, "steps": 900}
    span = types.SimpleNamespace
    pt = span(spans=[
        span(name="prefill.done", stats={
            "computed_tokens": 1024, "self_positions": 1024,
            "cross_positions": 1}),
        span(name="prefill.done", stats={
            "computed_tokens": 512, "self_positions": 512,
            "cross_positions": 1}),
        span(name="decode", stats={})])
    run = types.SimpleNamespace(
        phi4flash_trace=ft, program_trace=pt, config=c, trace_data=None,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        facts={"phi4flash_live": live})

    def read(name):
        return harness.read_layer_metric(
            os.path.join(ROOT, "benchmark"), name, run)

    assert read("decode_shared_kv_attn_ms") == pytest.approx(16.0)
    assert read("decode_gmu_ms") == pytest.approx(1.5)
    assert read("phi4flash_decode_ssm_ms") == pytest.approx(1.5)
    assert read("phi4flash_decode_window_attn_ms") == pytest.approx(4.5)
    # 200k live positions x 8 reading layers x 5120 B over 819 GB/s =
    # 10.0 ms of the 16 ms (the bytes bound it)
    byts = costs.shared_kv_attn_bytes(c, 200_000)
    assert byts == 8 * 200_000 * 5120
    assert costs.shared_kv_attn_ops(c, 200_000) / 197e12 < byts / 819e9
    assert read("shared_kv_decode_attn_roofline") == pytest.approx(
        100 * byts / 819e9 / 16e-3)
    assert 0 < read("shared_kv_decode_attn_roofline") < 100
    assert read("prefill_cross_positions_share") == pytest.approx(
        100 * 2 / 1536)
    assert read("phi4flash_decode_roofline") is None  # no trace: no time
    # a run of this family without these scopes or counts
    run.phi4flash_trace = KExaoneTrace()
    run.phi4flash_trace.coarse = KExaoneTrace()
    run.program_trace = span(spans=[span(name="prefill.done", stats={
        "computed_tokens": 512})])
    run.facts = {}
    for name in NEW_READERS:
        assert read(name) is None, name
    # another family's run (its driver leaves no such means, its programs
    # have no such scopes; the parent has no such family at all)
    with open(os.path.join(
            ROOT, "benchmark", "configs", "k-exaone-236b.1chip.json")) as f:
        other = json.load(f)
    run = types.SimpleNamespace(
        config=other, trace_data=None, rehearse=False, peaks=run.peaks,
        facts={"steps_log": [(0.0, 0.02, 32, 40_000, 0)]}, family=None,
        cell_file={})
    for name in NEW_READERS:
        assert read(name) is None, name
    run.trace_data = object()  # and where it holds a trace
    assert scopes.scope_tables(run, {2048}) is None


def test_costs_at_the_published_sizes():
    from benchmark import costs_phi4flash as costs

    with open(CONFIG) as f:
        c = json.load(f)
    assert costs.layers(c) == {
        "mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert costs.kv_row_bytes(c) == 5120 and costs.shared_kv_readers(c) == 8
    assert costs.slab_bytes(c) == 9 * (5120 * 16 * 4 + 5120 * 3 * 2)
    assert costs.ring_bytes(c, 512) == 8 * 512 * 5120
    assert costs.weight_bytes(c) == c["weight_bytes_bfloat16"]
    # the issue's arithmetic: 128 streams at a mean context of 1.7k
    need = costs.decode_bytes(c, 128, 128 * 1700, 128 * 512)
    assert round(costs.weight_bytes(c) / 1e9, 2) == 7.71
    assert round(costs.ring_bytes(c, 128 * 512) / 1e9, 2) == 2.68
    assert round(costs.shared_kv_attn_bytes(c, 128 * 1700) / 1e9, 1) == 8.9
    assert 20.0e9 < need < 20.3e9
    # more than half of a step's bytes the state, two fifths the pages
    assert 0.4 < costs.shared_kv_attn_bytes(c, 128 * 1700) / need < 0.5
    # a stream shorter than the window reads its own entries alone
    assert costs.decode_bytes(c, 1, 100, 100) < costs.weight_bytes(c) + 20e6


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


def test_resident_bytes_are_the_arrays_a_compiled_decode_step_takes(
        topo, monkeypatch):
    """``costs_phi4flash.resident_bytes`` (weights, rings, slabs, the one
    layer's pool: what ``decode_bytes`` is reckoned from) against the
    arguments of the decode program compiled at the published widths and
    the cell's 128 slots: equal but for the page table, the lengths, the
    tokens and the key."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import costs_phi4flash as costs
    from fms_fsdp_tpu.models.phi4flash import init_phi4flash_params
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families import load_model_config
    from fms_fsdp_tpu.serve.families.phi4flash import (
        decode_program, page_geometry, pool_row, state_shapes)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(CONFIG) as f:
        c = json.load(f)
    with open(WORKLOAD) as f:
        scfg = ServeConfig(**json.load(f)["engine"])
    cfg = load_model_config(c)
    chip = SingleDeviceSharding(topo.devices[0])
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: init_phi4flash_params(k, cfg, bf16),
                       jax.random.PRNGKey(0)))
    page, block_kv, max_pages, num_pages = page_geometry(cfg, scfg)
    B = scfg.max_batch
    pool = (1, num_pages) + pool_row(cfg, page)
    compiled = decode_program(cfg, scfg, page, block_kv, bf16).lower(
        params, {k: sds(*s) for k, s in state_shapes(cfg, B, bf16).items()},
        {k: sds(pool, bf16) for k in ("k", "v")},
        sds((B, max_pages), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32), sds((2,), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    small = B * max_pages * 4 + 2 * B * 4 + 8
    want = costs.resident_bytes(c, B, num_pages * page)
    assert abs(mem.argument_size_in_bytes - small - want) < 1e6
    assert mem.peak_memory_in_bytes < HBM
    assert mem.temp_size_in_bytes < 0.5e9
