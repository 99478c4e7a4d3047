"""The kexaone cell: its rehearsal with the control failing, its
configuration against the catalog's row, its readers on events made by
hand, its cost functions at the published sizes, and its two programs
compiled at published widths for a described TPU v5e (no chip
attached)."""

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
CELL = "k-exaone-236b.serve-mixed-over"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "k-exaone-236b.1chip.json")
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
    for what in ("served_token_logit_gap_mean",
                 "served_token_logit_gap_share_over"):
        assert re.search(f"check {what}: .* -> NOT ok", q.stdout), what
    # a quarter of the router's experts are held: about a quarter of the
    # routed pairs land here
    share = float(re.search(r"held experts \(([\d.]+)\)", p.stdout).group(1))
    assert 0.15 < share < 0.4
    # the rehearsal keeps both kinds of layer, prompts longer than the
    # window and outputs that wrap the ring
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        tiny = json.load(f)["rehearse"]
    assert set(tiny["config"]["layer_types"]) == {
        "sliding_attention", "full_attention"}
    assert tiny["traffic"]["prompt_tokens"]["max"] > 4 * tiny["config"]["sliding_window"]
    assert tiny["traffic"]["output_tokens"]["median"] >= 2 * tiny["config"]["sliding_window"]


def test_configuration_is_the_catalogs_row_cut_as_it_says():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if mine[k] != v)
    assert differs == sorted(mine["reduced"]) == [
        "layer_types", "mlp_layer_types", "num_experts", "num_hidden_layers",
        "num_nextn_predict_layers", "sliding_windows", "vocab_size"]
    assert mine["published"] == {k: row["config"][k] for k in mine["reduced"]}
    # the three per-layer lists follow the depth: the first 8 entries,
    # two whole periods LLLG, the leading layer dense
    L = mine["num_hidden_layers"]
    for k in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert mine[k] == row["config"][k][:L]
    assert L == 8 and mine["sliding_window_pattern"] == "LLLG"
    # the guide's floors: a leading dense layer and at least four after
    # it, 8 routed experts, an eighth of the vocabulary; no width cut
    assert L - mine["first_k_dense_replace"] >= 4
    assert mine["num_experts"] >= 8 and mine["first_expert_held"] == 0
    assert mine["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert mine["num_nextn_predict_layers"] == 0
    assert sorted(mine["assumed"]) == [
        "norm_placement", "qk_norm", "rotary_on_window_layers_only",
        "router_bias", "serving_dtype"]
    assert sorted(mine["omitted"]) == ["multi_token_prediction"]
    assert "eight chips share each layer" in mine["deployment"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "k-exaone-236b.1chip")
    assert entry["reduced"] == mine["reduced"]
    assert entry["source"] == row["source_url"]
    from fms_fsdp_tpu.serve.families import load_model_config

    assert load_model_config(mine).n_params() == mine["parameters_held"]
    assert mine["weight_bytes_bfloat16"] == 2 * mine["parameters_held"]


def test_traffic_is_what_the_issue_states():
    from benchmark import traffic
    from benchmark.drivers.serve_sarvam import stratified_schedule

    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert cell["kind"] == "serve_sarvam"  # its walk over stacks by kind
    mix = cell["traffic"]
    assert mix["queued_at_open"] == 64
    assert mix["prompt_tokens"] == {
        "median": 2048, "sigma": 1.0, "min": 256, "max": 16384}
    assert mix["output_tokens"] == {
        "median": 256, "sigma": 0.7, "min": 32, "max": 1024}
    eng = cell["engine"]
    assert (eng["max_batch"], eng["prefill_bucket"], eng["attn_impl"],
            eng["moe_impl"], eng["compute_dtype"]) == (
        32, 2048, "auto", "routed", "bfloat16")
    a = stratified_schedule(7, mix, 45.0, 19200, 2048)
    b = stratified_schedule(8, mix, 45.0, 19200, 2048)
    plain = traffic.serve_schedule(7, mix, 45.0, 19200)
    lens = lambda s: (sorted(len(p) for _, p, _ in s), sorted(o for _, _, o in s))  # noqa: E731
    assert lens(a) == lens(b) == lens(plain)
    assert ([-(-len(p) // 2048) for _, p, _ in a]
            == [-(-len(p) // 2048) for _, p, _ in b])
    # the longest prompt with the longest output fits a stream
    assert max(len(p) for _, p, _ in a) + max(o for _, _, o in a) <= eng["max_seq_len"]
    assert max(len(p) + o for _, p, o in a) <= eng["max_seq_len"]
    assert all(1 <= t < 19200 for _, p, _ in a[:3] for t in p)
    # both in one queue: a quarter under 1024 and a quarter over 4096
    prompts = sorted(len(p) for _, p, _ in a)
    n = len(prompts)
    assert 0.2 < sum(p < 1024 for p in prompts) / n < 0.3
    assert 0.2 < sum(p > 4096 for p in prompts) / n < 0.3


def test_prefill_modules_are_counted_with_the_done_span_that_follows():
    from benchmark.program_scopes_kexaone import pair_with_done_spans
    from benchmark.trace_reduce import Event

    def module(start, dur, n):
        return ("lines", Event(f"jit__prefill_{n}", start, dur), n)

    def done(start, rid, computed):
        return Event("prefill.done", start, 0.0, {
            "rid": rid, "computed_tokens": computed, "moe_pairs_held": 1,
            "moe_pairs_routed": computed * 56})

    mods = [module(100, 50, 2048), module(300, 80, 4096), module(600, 40, 2048)]
    spans = [
        Event("prefill", 90, 100, {"rid": 3, "prompt_tokens": 1500,
                                   "padded_tokens": 2048}),
        Event("prefill", 290, 120, {"rid": 4, "prompt_tokens": 2300,
                                    "padded_tokens": 4096}),
        done(40, 2, 2048),  # of a prefill before the trace began
        done(160, 3, 2048), done(390, 4, 4096),
        # the third module's span fell after the trace's end
    ]
    got = pair_with_done_spans(mods, spans)
    assert [(n, c["computed_tokens"], c["prompt_tokens"])
            for _, _, n, c in got] == [(2048, 2048, 1500), (4096, 4096, 2300)]


def test_readers_on_events_made_by_hand():
    """Each new reader on a trace made by hand: decode steps and prefills
    by scope, the costs from the configuration's file."""
    from benchmark import costs_kexaone as costs
    from benchmark import harness
    from benchmark import program_scopes_kexaone as scopes

    with open(CONFIG) as f:
        c = json.load(f)
    ms = 1e6
    kt = scopes.KExaoneTrace(
        decode_steps=[
            {"attn_full": 2.0 * ms, "kv_write": 0.1 * ms, "win_write": 0.2 * ms,
             "attn_window": 0.8 * ms, "moe_experts": 12 * ms,
             "moe_router": 0.5 * ms, "moe_shared": 1.0 * ms,
             "moe_combine": 0.5 * ms, "qkv": 2.0 * ms, "": 0.5 * ms}] * 3,
        prefills=[(2048, {"attn_window": 10 * ms, "attn_full": 6 * ms,
                          "moe_experts": 60 * ms, "mlp": 24 * ms},
                   {"computed_tokens": 2048, "prompt_tokens": 2000})])
    steps_log = [(0.0, 0.02, 32, 110_000, 0)] * 4
    run = types.SimpleNamespace(
        kexaone_trace=kt, config=c, trace_data=object(),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        facts={"steps_log": steps_log})

    def read(name):
        return harness.read_layer_metric(
            os.path.join(ROOT, "benchmark"), name, run)

    assert read("decode_full_attn_ms") == pytest.approx(2.1)
    assert read("decode_window_attn_ms") == pytest.approx(1.0)
    assert read("kexaone_decode_moe_ms") == pytest.approx(14.0)
    # 110k live positions x 2 full layers x 4096 B over 819 GB/s = 1.1 ms
    # of the 2.0 ms under attn_full (the bytes bound it, not the products)
    ops, byts = costs.full_decode_attn_cost(c, 110_000)
    assert byts == 2 * 110_000 * 4096 and ops / 197e12 < byts / 819e9
    assert read("full_decode_attn_roofline") == pytest.approx(
        100 * byts / 819e9 / 2.0e-3)
    assert read("prefill_window_attn_share") == pytest.approx(10.0)
    want = 6 * 4 * (128 * 2000 - 128 * 127 // 2) * 64 * 128
    assert costs.prefill_window_attn_ops(c, 2000) == want
    assert read("prefill_window_attn_roofline") == pytest.approx(
        100 * want / 197e12 / 10e-3)
    assert 0 < read("prefill_window_attn_roofline") < 100
    # a program without these programs or scopes: nothing to read
    run.kexaone_trace = scopes.KExaoneTrace()
    for name in ("decode_full_attn_ms", "decode_window_attn_ms",
                 "kexaone_decode_moe_ms", "full_decode_attn_roofline",
                 "prefill_window_attn_share", "prefill_window_attn_roofline"):
        assert read(name) is None, name


def test_costs_at_the_published_sizes():
    from benchmark import costs_kexaone as costs

    with open(CONFIG) as f:
        c = json.load(f)
    assert costs.layers(c) == (6, 2, 1, 7)
    assert costs.kv_row_bytes(c) == 4096
    assert round(costs.attention_params(c) / 1e6, 2) == 113.26
    assert round(costs.expert_params(c) / 1e6, 2) == 37.75
    # 32 tokens of 8 choices in 128: 14 of the 16 held are hit
    assert 13.9 < costs.expected_distinct_held(c, 32) < 14.0
    # 32 streams at 3.4k positions: 10.65 GB of weights (14 of 16 held
    # experts a layer, 32 of the embedding's rows), 0.9 GB of full-layer
    # pages, 0.1 GB of rings
    need = costs.kexaone_decode_bytes(c, 32, 110_000)
    assert 11.5e9 < need < 11.8e9
    assert costs.band_pairs(5, 128) == 15
    assert costs.band_pairs(128, 128) == 128 * 129 // 2
    assert costs.band_pairs(1000, 128) == 128 * 1000 - 128 * 127 // 2


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


def test_programs_compile_at_published_widths_and_fit(topo, monkeypatch):
    """The decode program at the cell's 32 slots and the prefill program
    of the longest prompt (16384 tokens), layers 0-7: both fit beside
    11.96 GB of weights, the full layers' pool and the rings; the pools
    have a layer axis over the two full layers alone and are donated and
    updated in place; no weight is copied; the scope tables name every
    scope the readers ask for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness, weights
    from benchmark import program_scopes_kexaone as scopes
    from fms_fsdp_tpu.obs.scopes import KEXAONE_SCOPES, scope_table
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families.kexaone import (
        cache_bytes, decode_program, page_geometry, prefill_program,
        ring_shape)

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
    params = weights.unflatten({
        p: S(s["shape"], bf16)
        for p, s in run.reference.param_spec(c).items()})
    weight_bytes = sum(x.size for x in jax.tree.leaves(params)) * 2
    assert weight_bytes == c["weight_bytes_bfloat16"]
    page, block_kv, max_pages, num_pages = page_geometry(cfg, scfg)
    assert (page, block_kv) == (128, 512) and max_pages * page == scfg.max_seq_len
    cost = cache_bytes(cfg, bf16)
    assert cost == {"per_token": 8192, "per_stream": 6 * 128 * 4096}
    pool_shape = (2, num_pages, page, 8, 128)
    pool_bytes = num_pages * page * cost["per_token"]
    ring_bytes = scfg.max_batch * cost["per_stream"]
    assert ring_bytes == 100663296
    assert weight_bytes + pool_bytes + ring_bytes > 0.25 * 16e9
    B, top = scfg.max_batch, run.traffic["prompt_tokens"]["max"]
    ring = {k: S(ring_shape(cfg, scfg), bf16) for k in ("k", "v")}
    decode = decode_program(cfg, scfg, page, block_kv, bf16).lower(
        params, ring, {k: S(pool_shape, bf16) for k in ("k", "v")},
        S((B, max_pages), jnp.int32), S((B,), jnp.int32), S((B,), jnp.int32),
        S((2,), jnp.uint32)).compile()
    prefill = prefill_program(cfg, scfg, top, top, bf16).lower(
        params, S((1, top), jnp.int32), S((1,), jnp.int32)).compile()
    m = decode.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
    assert m.temp_size_in_bytes < 0.1e9  # no copy of a pool or of a weight
    m = prefill.memory_analysis()  # pools and rings stand beside it
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes + pool_bytes
            + ring_bytes) < HBM
    dtext, ptext = decode.as_text(), prefill.as_text()
    assert ptext.startswith(f"HloModule jit__prefill_{top},")
    assert dtext.startswith("HloModule jit__step,")
    # the paged kernel once a full layer; in the prefill a windowed flash
    # call a window layer, two flash calls a full layer (the chunk's own
    # block, the walk over earlier ones) and three grouped matmuls a
    # sparse layer
    assert dtext.count("tpu_custom_call") == 2
    assert ptext.count("tpu_custom_call") == 6 + 2 * 2 + 3 * 7
    for text in (dtext, ptext):  # no weight laid out again (W_q by head)
        assert not re.search(r"= bf16\[(8192,6144|6144,8192)\]\S* copy\(", text)
    for compiled, want in (
            (decode, scopes.FULL_ATTN_DECODE[:1] + scopes.FULL_ATTN_DECODE[2:]
             + scopes.WINDOW_ATTN_DECODE + scopes.MOE_DECODE
             + ("qkv", "qk_norm", "rope", "attn_out", "mlp", "norm", "embed",
                "lm_head", "sample")),
            (prefill, scopes.WINDOW_ATTN_PREFILL
             + ("win_write", "kv_write", "attn_full", "qkv", "qk_norm",
                "rope", "attn_out", "moe_router", "moe_shared", "moe_group",
                "moe_experts", "moe_combine", "mlp", "norm", "embed",
                "lm_head"))):
        found = set(scope_table(compiled.as_text(), KEXAONE_SCOPES).values())
        assert set(want) <= found, sorted(set(want) - found)
