"""The minicpm_sala cell: its rehearsal with and without ``--trace 1`` and
with the control failing, its configuration against the catalog's row,
its readers on events made by hand, its cost functions at the published
sizes, and its two programs compiled at published widths for a described
TPU v5e (no chip attached)."""

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
CELL = "minicpm-sala-9b.serve-longdoc-over"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "minicpm-sala-9b.1chip.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HBM = 15.75 * 2**30  # what the compiler has of a v5e's 16 GB
NEW = ("decode_sparse_attn_ms", "decode_lin_attn_ms",
       "sparse_decode_attn_roofline", "sala_decode_roofline",
       "prefill_sparse_attn_share", "prefill_lin_attn_share",
       "sparse_prefill_attn_roofline", "lin_scan_roofline",
       "sparse_chosen_share")


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
    p, sound = rehearse("--trace", "0")
    q, control = rehearse("--trace", "0", "--control", "1")
    assert sound["rehearsal_checks_passed"] is True, p.stdout[-2000:]
    assert control["rehearsal_checks_passed"] is False, q.stdout[-2000:]
    assert re.search(
        "check served_token_logit_gap_mean: .* -> NOT ok", q.stdout)
    assert re.search(
        "check longest_compared_prompt_past_dense_len: .* -> ok", p.stdout)
    # the rehearsal keeps both kinds of layer, prompts past dense_len and
    # contexts of more blocks than are chosen
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        tiny = json.load(f)["rehearse"]
    assert set(tiny["config"]["mixer_types"]) == {"minicpm4", "lightning-attn"}
    sc = tiny["config"]["sparse_config"]
    assert tiny["traffic"]["prompt_tokens"]["median"] > sc["dense_len"]
    assert (tiny["traffic"]["prompt_tokens"]["max"]
            > 2 * sc["topk"] * sc["block_size"])
    # positions chose, and fewer blocks than their context holds
    chose, chosen, context = map(float, re.search(
        r"(\d+) of them chose (\d+) of (\d+) blocks", p.stdout).groups())
    assert chose > 0 and 0 < chosen < context


def test_traced_rehearsal_walks_every_reader():
    p, line = rehearse("--trace", "1")
    assert line["rehearsal_checks_passed"] is True, p.stdout[-2000:]
    read = re.search(r"rehearsal read per-layer metrics: (\[.*\])", p.stdout)
    names = json.loads(read.group(1).replace("'", '"'))
    # a CPU's trace has no device events: the counted metric reads, the
    # device metrics find nothing and say so without raising
    assert "sparse_chosen_share" in names
    assert "engine_step_ms.decode" in names
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    from benchmark.harness import metrics_of

    listed = [m["name"] for m in metrics_of(manifest, CELL, "per_layer")]
    assert listed[-len(NEW):] == list(NEW)
    assert set(names) <= set(listed)
    for m in manifest["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
    # the other cells' lists did not gain the new metrics
    other = {m["name"] for m in metrics_of(
        manifest, "k-exaone-236b.serve-mixed-over", "per_layer")}
    assert not other & set(NEW)
    assert len(manifest["workloads"]) == 5 and len(manifest["configs"]) == 5


def test_configuration_is_the_catalogs_row_cut_as_it_says():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if mine[k] != v)
    assert differs == sorted(mine["reduced"]) == [
        "mixer_types", "num_hidden_layers"]
    assert mine["published"] == {k: row["config"][k] for k in mine["reduced"]}
    # layers 9-16: a sparse layer, six lightning layers, a sparse layer,
    # the kinds 2:6 as the published 8:24
    first = mine["first_layer_held"]
    assert (first, mine["num_hidden_layers"]) == (9, 8)
    assert mine["mixer_types"] == row["config"]["mixer_types"][first:first + 8]
    assert mine["mixer_types"].count("minicpm4") == 2
    assert row["config"]["mixer_types"].count("minicpm4") == 8
    # what config.json fixes is not under ``assumed``
    assert not set(mine["assumed"]) & set(row["config"])
    assert sorted(mine["assumed"]) == [
        "block_score", "dense_or_sparse_by_position", "lightning_activation",
        "lightning_decay", "lightning_rope", "output_norm_form", "precisions",
        "qk_norm_form", "residual_and_logit_scaling", "sparse_config"]
    assert mine["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assert "layers 9-16" in mine["deployment"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "minicpm-sala-9b.1chip")
    assert entry["reduced"] == mine["reduced"]
    assert entry["source"] == row["source_url"]
    from fms_fsdp_tpu.serve.families import load_model_config

    assert load_model_config(mine).n_params() == mine["parameters_held"]
    assert mine["weight_bytes_bfloat16"] == 2 * mine["parameters_held"]


def test_traffic_is_what_the_issue_states():
    from benchmark import traffic
    from benchmark.drivers.serve_sala import ordered_schedule

    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert cell["kind"] == "serve_sala"
    mix = cell["traffic"]
    assert mix["queued_at_open"] == 64
    assert mix["prompt_tokens"] == {
        "median": 8192, "sigma": 1.0, "min": 1024, "max": 65536}
    assert mix["output_tokens"] == {
        "median": 384, "sigma": 0.7, "min": 48, "max": 1536}
    eng = cell["engine"]
    assert (eng["max_batch"], eng["max_seq_len"], eng["page_size"],
            eng["prefill_bucket"], eng["attn_impl"], eng["compute_dtype"]) == (
        32, 67584, 64, 2048, "auto", "bfloat16")
    a = ordered_schedule(7, mix, 45.0, 73448, 2048)
    b = ordered_schedule(2147483700, mix, 45.0, 73448, 2048)
    plain = traffic.serve_schedule(7, mix, 45.0, 73448)
    lens = lambda s: (sorted(len(p) for _, p, _ in s), sorted(o for _, _, o in s))  # noqa: E731
    assert lens(a) == lens(b) == lens(plain)
    # the buckets and the outputs in one order for every seed; the seed
    # draws the prompts' lengths within their buckets, the gaps, the ids
    assert ([-(-len(p) // 2048) for _, p, _ in a]
            == [-(-len(p) // 2048) for _, p, _ in b])
    assert [o for _, _, o in a] == [o for _, _, o in b]
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]
    assert [t for t, _, _ in a] != [t for t, _, _ in b]
    assert max(len(p) + o for _, p, o in a) <= eng["max_seq_len"]
    assert all(1 <= t < 73448 for _, p, _ in a[:3] for t in p)
    # long requests only: a quarter under 4k, a quarter over 16k, and
    # most prompt positions in prompts past dense_len
    prompts = sorted(len(p) for _, p, _ in a)
    n = len(prompts)
    assert 0.2 < sum(p < 4096 for p in prompts) / n < 0.3
    assert 0.2 < sum(p > 16384 for p in prompts) / n < 0.3
    assert sum(p for p in prompts if p > 8192) / sum(prompts) > 0.8
    # the pool holds the pages the issue states beside the two reserved
    assert eng["num_pages"] == 24576 + 2


def test_prefill_modules_are_counted_with_the_done_span_that_follows():
    from benchmark.program_scopes_sala import pair_with_done_spans
    from benchmark.trace_reduce import Event

    def module(start, dur, n):
        return ("lines", Event(f"jit__prefill_{n}", start, dur), n)

    def done(start, rid, computed, chose):
        return Event("prefill.done", start, 0.0, {
            "rid": rid, "computed_tokens": computed, "chose_tokens": chose,
            "chosen_blocks": 64 * chose, "context_blocks": 200 * chose})

    mods = [module(100, 50, 2048), module(300, 80, 16384), module(600, 40, 2048)]
    spans = [
        done(40, 2, 2048, 0),  # of a prefill before the trace began
        done(160, 3, 2048, 0), done(390, 4, 10240, 1900),
        Event("prefill.done", 700, 0.0, {"rid": 9, "computed_tokens": 0}),
    ]
    got = pair_with_done_spans(mods, spans)
    assert [(n, c["computed_tokens"], c["chose_tokens"])
            for _, _, n, c in got] == [(2048, 2048, 0), (16384, 10240, 1900)]


def test_readers_on_events_made_by_hand():
    from benchmark import costs_sala as costs
    from benchmark import harness
    from benchmark import program_scopes_sala as scopes

    with open(CONFIG) as f:
        c = json.load(f)
    ms = 1e6
    st = scopes.SalaTrace(
        chunk=2048,
        decode_steps=[
            {"sparse_attn": 0.6 * ms, "sparse_select": 0.4 * ms,
             "index_write": 0.1 * ms, "kv_write": 0.1 * ms,
             "lin_step": 1.5 * ms, "lin_gate": 0.5 * ms, "mlp": 5 * ms,
             "qkv": 1.0 * ms, "": 0.3 * ms}] * 3,
        prefills=[(16384, {"sparse_attn": 40 * ms, "sparse_select": 10 * ms,
                           "sparse_compress": 2 * ms, "attn": 8 * ms,
                           "lin_scan": 30 * ms, "lin_gate": 10 * ms,
                           "mlp": 300 * ms},
                   {"computed_tokens": 10240, "chose_tokens": 2000,
                    "chosen_blocks": 128000, "context_blocks": 290000})])
    live = {"streams": 30.0, "attended": 30 * 4096.0, "scored": 30 * 800.0}
    run = types.SimpleNamespace(
        sala_trace=st, config=c, trace_data=object(),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        facts={"sala_live": live, "sparse_chosen_blocks": 128000.0,
               "sparse_context_blocks": 290000.0})

    def read(name):
        return harness.read_layer_metric(
            os.path.join(ROOT, "benchmark"), name, run)

    assert read("decode_sparse_attn_ms") == pytest.approx(1.2)
    assert read("decode_lin_attn_ms") == pytest.approx(2.0)
    # 30 streams x 4096 chosen positions x 2 layers x 1024 B + their 800
    # compressed keys x 512 B over 819 GB/s = 0.34 ms of the 1.0 ms under
    # sparse_select and sparse_attn (the bytes bound it, not the products)
    ops, byts = costs.sparse_decode_attn_cost(c, 30 * 4096, 30 * 800)
    assert byts == 2 * (30 * 4096 * 1024 + 30 * 800 * 512)
    assert ops / 197e12 < byts / 819e9
    assert read("sparse_decode_attn_roofline") == pytest.approx(
        100 * byts / 819e9 / 1.0e-3)
    assert read("prefill_sparse_attn_share") == pytest.approx(15.0)
    assert read("prefill_lin_attn_share") == pytest.approx(10.0)
    ops, byts = costs.sparse_prefill_attn_cost(c, 10240, 2048)
    assert ops / 197e12 > byts / 819e9  # the products bound it
    assert read("sparse_prefill_attn_roofline") == pytest.approx(
        100 * ops / 197e12 / 58e-3)
    ops, byts = costs.lin_scan_cost(c, 10240)
    assert read("lin_scan_roofline") == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) / 30e-3)
    for name in ("sparse_decode_attn_roofline", "sparse_prefill_attn_roofline",
                 "lin_scan_roofline"):
        assert 0 < read(name) < 100, name
    assert read("sparse_chosen_share") == pytest.approx(100 * 128 / 290)
    # a program without these programs, scopes or counts: nothing to read
    run.sala_trace = scopes.SalaTrace()
    run.facts = {}
    for name in NEW[:3] + NEW[4:]:
        assert read(name) is None, name


def test_costs_at_the_published_sizes():
    from benchmark import costs_sala as costs

    with open(CONFIG) as f:
        c = json.load(f)
    assert costs.layers(c) == (2, 6)
    # the hand count: q, k, v, o and the gate 5 x 16.8M and the MLP 201.3M
    assert round(costs.lightning_layer_params(c) / 1e6, 1) == 285.2
    assert round(costs.mlp_params(c) / 1e6, 1) == 201.3
    # q, o and the gate 3 x 16.8M, k and v 2 x 1.05M, the MLP
    assert round(costs.sparse_layer_params(c) / 1e6, 1) == 253.8
    assert round(costs.held_params(c) / 1e6) == 2821
    assert costs.held_params(c) == c["parameters_held"]
    assert round(2 * costs.held_params(c) / 1e9, 2) == 5.64
    # 1 KB a position and layer of keys and values, 32 B of index cache
    assert costs.kv_row_bytes(c) == 1024
    assert costs.index_row_bytes(c) / c["sparse_config"]["kernel_stride"] == 32
    assert costs.lightning_state_bytes(c) == 6 * 32 * 128 * 128 * 4
    # dense up to 8192 positions, then 64 blocks, the query's own the last
    assert costs.attended_positions(c, 8192) == 8192
    assert costs.attended_positions(c, 8193) == 63 * 64 + 1
    assert costs.attended_positions(c, 65536) == 4096
    assert costs.attended_positions(c, 100) == 100
    assert costs.index_rows(c, 31) == 0 and costs.index_rows(c, 32) == 1
    assert costs.index_rows(c, 65536) == 4095
    # at 32k positions a stream reads 4.2 MB of chosen pages a layer where
    # the whole cache is 32 MB, and 1 MB of compressed keys
    assert costs.attended_positions(c, 32768) * 1024 == 4 * 2**20
    assert 32768 * 1024 == 32 * 2**20
    assert costs.index_rows(c, 32768) * 512 == 2047 * 512
    # a step over 32 streams at 13k: 5.0 GB of weights, 0.8 GB of states
    # read and written, 0.3 GB of pages and compressed keys
    need = costs.sala_decode_bytes(c, 32, 32 * 4096, 32 * 800)
    assert 6.0e9 < need < 6.3e9
    ops, byts = costs.lin_scan_cost(c, 2048)
    assert ops == 6 * 32 * (4 * 128 * 8 * 256 * 257 // 2 + 4 * 128 * 128 * 2048)


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
    of the longest prompts (65536 positions), layers 9-16: both fit beside
    5.64 GB of weights, the sparse layers' pool, its index cache and the
    lightning states; pools and states are donated and updated in place;
    the scope tables name every scope the readers ask for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness, weights
    from benchmark import program_scopes_sala as scopes
    from fms_fsdp_tpu.obs.scopes import SALA_SCOPES, scope_table
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families.minicpm_sala import (
        cache_bytes, decode_program, page_geometry, prefill_program,
        program_len, state_shape)

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
    assert (page, block_kv) == (64, 512) and max_pages * page == scfg.max_seq_len
    cost = cache_bytes(cfg, bf16)
    assert cost == {"per_token": 2048, "index_per_token": 64,
                    "per_stream": 6 * 32 * 128 * 128 * 4}
    pool_bytes = num_pages * page * (cost["per_token"] + cost["index_per_token"])
    state_bytes = scfg.max_batch * cost["per_stream"]
    assert round(pool_bytes / 1e9, 1) == 3.3 and round(state_bytes / 1e9, 1) == 0.4
    assert weight_bytes + pool_bytes + state_bytes > 0.25 * 16e9
    B, top = scfg.max_batch, run.traffic["prompt_tokens"]["max"]
    assert program_len(top, scfg.prefill_bucket, 67584) == 65536
    assert program_len(34816, scfg.prefill_bucket, 67584) == 65536
    assert program_len(67584, scfg.prefill_bucket, 67584) == 67584
    assert [program_len(n, 2048, 67584) for n in (2048, 4096, 6144)] == [
        2048, 4096, 8192]
    L = 2 * 2
    pools = {"k": S((L, num_pages, page, 1, 128), bf16),
             "v": S((L, num_pages, page, 1, 128), bf16),
             "kc": S((L, num_pages, 4, 128), bf16)}
    state = {"S": S(state_shape(cfg, scfg), jnp.float32)}
    decode = decode_program(cfg, scfg, page, block_kv, bf16).lower(
        params, state, pools, S((B, max_pages), jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.int32), S((2,), jnp.uint32)).compile()
    prefill = prefill_program(cfg, scfg, top, bf16).lower(
        params, S((1, top), jnp.int32), S((1,), jnp.int32)).compile()
    m = decode.memory_analysis()
    print("decode peak bytes", m.argument_size_in_bytes + m.temp_size_in_bytes,
          "temp", m.temp_size_in_bytes)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
    assert m.temp_size_in_bytes < 0.6e9  # no copy of a pool, a state or a weight
    m = prefill.memory_analysis()  # pools and states stand beside it
    print("prefill peak bytes", m.argument_size_in_bytes + m.temp_size_in_bytes
          + pool_bytes + state_bytes, "temp", m.temp_size_in_bytes)
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes + pool_bytes
            + state_bytes) < HBM
    dtext, ptext = decode.as_text(), prefill.as_text()
    assert ptext.startswith(f"HloModule jit__prefill_{top},")
    assert dtext.startswith("HloModule jit__step,")
    # the paged kernel once a sparse layer over the chosen pages; in the
    # prefill two flash calls a sparse layer (a dense chunk's own block,
    # the walk over earlier ones)
    assert dtext.count("tpu_custom_call") == 2
    assert ptext.count("tpu_custom_call") == 2 * 2
    for compiled, want in (
            (decode, scopes.SPARSE_ATTN_DECODE + scopes.LIN_ATTN_DECODE
             + ("qkv", "qk_norm", "rope", "attn_out", "mlp", "norm", "embed",
                "lm_head", "sample")),
            (prefill, scopes.SPARSE_ATTN_PREFILL + scopes.LIN_ATTN_PREFILL
             + ("kv_write", "qkv", "qk_norm", "rope", "attn_out", "mlp",
                "norm", "embed", "lm_head"))):
        found = set(scope_table(compiled.as_text(), SALA_SCOPES).values())
        assert set(want) <= found, sorted(set(want) - found)
