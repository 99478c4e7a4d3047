"""The lfm2 cell: its rehearsal with the control failing and with a
dropped convolution window failing, its configuration against the
catalog's row, its readers on events made by hand and on another
family's run, its cost functions at the published sizes, and its two
programs compiled at published widths for a described TPU v5e (no chip
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
CELL = "lfm2-24b-a2b.serve-turns-over"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.1chip.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HBM = 15.75 * 2**30  # what the compiler has of a v5e's 16 GB
NEW_READERS = (
    "lfm2_decode_moe_ms", "lfm2_decode_conv_ms", "lfm2_decode_attn_ms",
    "head64_decode_attn_roofline", "head64_prefill_attn_roofline",
    "lfm2_decode_roofline", "lfm2_prefill_moe_share",
    "moe_experts_touched_share")

# the rehearsal's prompts (4-96 positions) in chunks of 16, so that most
# cross a chunk boundary of the prefill's loop; ``dropped``: every chunk
# starts behind a window of zeros (the decode step's window is left)
PRELUDE = """
import sys
sys.path.insert(0, {root!r})
from fms_fsdp_tpu.models import lfm2 as M
M.PREFILL_CHUNK = 16
if {dropped!r}:
    conv = M._short_conv
    M._short_conv = lambda tail, z, w: conv(
        tail * 0 if z.shape[1] > 1 else tail, z, w)
from benchmark import run
sys.exit(run.main({argv!r}))
"""


def rehearse(*extra, dropped=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    argv = ["--workload", CELL, "--seed", "2147483700", "--seconds", "3",
            "--trace", "0", "--rehearse", *extra]
    if dropped is None:
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *argv]
    else:
        cmd = [sys.executable, "-c", PRELUDE.format(
            root=ROOT, dropped=dropped, argv=argv)]
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
    # every expert is here: the decode steps' own count is read
    share = float(re.search(
        r"\(layer, expert\) pairs \(([\d.]+)\)", p.stdout).group(1))
    assert 0.1 < share < 0.9
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        tiny = json.load(f)["rehearse"]
    assert set(tiny["config"]["layer_types"]) == {"conv", "full_attention"}


def test_a_dropped_convolution_window_fails_a_limit():
    """The same rehearsal with the prefill in chunks of 16: sound where
    the windows go from chunk to chunk, and failing a limit where every
    chunk starts behind zeros (read on the CPU: mean gap 0.020 and share
    0.087 where the sound run reads 0.0009 and 0.005)."""
    p, sound = rehearse(dropped=False)
    q, dropped = rehearse(dropped=True)
    assert sound["rehearsal_checks_passed"] is True, p.stdout[-2000:]
    assert dropped["rehearsal_checks_passed"] is False, q.stdout[-2000:]
    assert re.search(r"check served_token_logit_gap_\w+: .* -> NOT ok",
                     q.stdout)
    # the same requests were served: only the logits moved
    assert dropped["attempted"] == sound["attempted"] and not dropped["failed"]


def test_configuration_is_the_catalogs_row_cut_as_it_says():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if mine[k] != v)
    assert differs == sorted(mine["reduced"]) == [
        "layer_types", "num_hidden_layers"]
    assert mine["published"] == {k: row["config"][k] for k in mine["reduced"]}
    # depth alone: the first 10 entries, the two leading dense layers and
    # two whole periods attn conv conv conv behind them
    L = mine["num_hidden_layers"]
    assert L == 10 and mine["layer_types"] == row["config"]["layer_types"][:L]
    behind = mine["layer_types"][mine["num_dense_layers"]:]
    assert behind == ["full_attention", "conv", "conv", "conv"] * 2
    whole = row["config"]["layer_types"][mine["num_dense_layers"]:]
    assert whole[:8] == behind and whole.count("full_attention") == 10
    # the guide's floors: a whole period, at least four layers behind the
    # dense ones, every expert, the whole vocabulary; no width cut
    assert L - mine["num_dense_layers"] >= 4
    assert (mine["hidden_size"], mine["num_attention_heads"],
            mine["num_key_value_heads"], mine["intermediate_size"],
            mine["moe_intermediate_size"], mine["num_experts"],
            mine["num_experts_per_tok"], mine["vocab_size"],
            mine["conv_L_cache"]) == (
        2048, 32, 8, 11776, 1536, 64, 4, 65536, 3)
    assert sorted(mine["assumed"]) == [
        "conv_activation", "head_dim", "qk_norm_and_rotary",
        "router_sum_eps", "serving_dtype", "tie_word_embeddings"]
    assert "pipeline" in mine["deployment"]
    assert "host's share of a step larger" in mine["deployment"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2-24b-a2b.1chip")
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
    assert cell["kind"] == "serve_lfm2"
    mix = cell["traffic"]
    assert mix["queued_at_open"] == 256
    assert mix["prompt_tokens"] == {
        "median": 512, "sigma": 0.8, "min": 64, "max": 4096}
    assert mix["output_tokens"] == {
        "median": 256, "sigma": 0.7, "min": 32, "max": 1024}
    eng = cell["engine"]
    assert (eng["max_batch"], eng["max_seq_len"], eng["prefill_bucket"],
            eng["attn_impl"], eng["moe_impl"], eng["compute_dtype"]) == (
        128, 5120, 256, "auto", "routed", "bfloat16")
    # a pool of 2.0 GB for the two attention layers: 4096 B a position
    assert 1.99e9 < eng["num_pages"] * 128 * 4096 < 2.01e9
    # the mix's own set, in an order that no seed changes (the driver's)
    a = ordered_schedule(7, mix, 45.0, 65536, 256)
    b = ordered_schedule(8, mix, 45.0, 65536, 256)
    plain = traffic.serve_schedule(7, mix, 45.0, 65536)
    lens = lambda s: (sorted(len(p) for _, p, _ in s), sorted(o for _, _, o in s))  # noqa: E731
    assert lens(a) == lens(b) == lens(plain)
    assert [o for _, _, o in a] == [o for _, _, o in b]
    assert ([-(-len(p) // 256) for _, p, _ in a]
            == [-(-len(p) // 256) for _, p, _ in b])
    assert [p for _, p, _ in a] != [p for _, p, _ in b]
    assert max(len(p) + o for _, p, o in a) <= eng["max_seq_len"]
    assert all(1 <= t < 65536 for _, p, _ in a[:3] for t in p)
    # five programs by doubling the bucket
    from fms_fsdp_tpu.serve.families.minicpm_sala import program_len

    lens = {program_len(-(-len(p) // 256) * 256, 256, 5120) for _, p, _ in a}
    assert lens == {256, 512, 1024, 2048, 4096}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(NEW_READERS)
    assert all(m["moves"] == "serve_tokens_per_s" for m in mine)


def test_readers_on_events_made_by_hand():
    """Each new reader on a trace made by hand (decode steps and prefills
    by scope, the costs from the configuration's file), and on another
    family's run, where each finds nothing and says so."""
    from benchmark import costs_lfm2 as costs
    from benchmark import harness
    from benchmark import program_scopes_lfm2 as scopes
    from benchmark.program_scopes_kexaone import KExaoneTrace

    with open(CONFIG) as f:
        c = json.load(f)
    ms = 1e6
    lt = KExaoneTrace(
        decode_steps=[
            {"attn_full": 0.9 * ms, "kv_write": 0.05 * ms, "qkv": 0.05 * ms,
             "conv_in": 0.4 * ms, "short_conv": 0.1 * ms, "conv_out": 0.1 * ms,
             "moe_experts": 13 * ms, "moe_router": 0.5 * ms,
             "moe_combine": 0.5 * ms, "dense_mlp": 0.4 * ms, "head": 0.5 * ms,
             "": 0.5 * ms}] * 3,
        prefills=[(1024, {"attn_full": 0.5 * ms, "moe_experts": 9 * ms,
                          "moe_group": 1 * ms, "moe_combine": 2 * ms,
                          "conv_in": 1 * ms, "dense_mlp": 6.5 * ms},
                   {"computed_tokens": 1024, "prompt_tokens": 900})])
    steps_log = [(0.0, 0.02, 128, 130_000, 0)] * 4
    run = types.SimpleNamespace(
        lfm2_trace=lt, config=c, trace_data=None,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        facts={"steps_log": steps_log, "moe_experts_touched": 500 * 500,
               "moe_steps": 500})

    def read(name):
        return harness.read_layer_metric(
            os.path.join(ROOT, "benchmark"), name, run)

    assert read("lfm2_decode_moe_ms") == pytest.approx(14.0)
    assert read("lfm2_decode_conv_ms") == pytest.approx(0.6)
    assert read("lfm2_decode_attn_ms") == pytest.approx(1.0)
    # 130k live positions x 2 attention layers x 2048 B over 819 GB/s =
    # 0.65 ms of the 0.9 ms under attn_full (the bytes bound it)
    ops, byts = costs.decode_attn_cost(c, 130_000)
    assert byts == 2 * 130_000 * 2048 and ops / 197e12 < byts / 819e9
    assert read("head64_decode_attn_roofline") == pytest.approx(
        100 * byts / 819e9 / 0.9e-3)
    want = 2 * 4 * (900 * 901 // 2) * 32 * 64
    assert costs.prefill_attn_ops(c, 900) == want
    assert read("head64_prefill_attn_roofline") == pytest.approx(
        100 * want / 197e12 / 0.5e-3)
    assert 0 < read("head64_prefill_attn_roofline") < 100
    assert read("lfm2_prefill_moe_share") == pytest.approx(60.0)
    # 500 of the 512 (layer, expert) pairs a step
    assert read("moe_experts_touched_share") == pytest.approx(100 * 500 / 512)
    assert read("lfm2_decode_roofline") is None  # no trace: no step's time
    # a run of this family without these scopes: nothing to read
    run.lfm2_trace = KExaoneTrace()
    run.facts = {"steps_log": steps_log}
    for name in NEW_READERS:
        assert read(name) is None, name
    # another family's run (its driver leaves no such counts, its
    # programs have no such scopes; the parent has no such family at all)
    with open(os.path.join(
            ROOT, "benchmark", "configs", "k-exaone-236b.1chip.json")) as f:
        other = json.load(f)
    run = types.SimpleNamespace(
        config=other, trace_data=None, rehearse=False, peaks=run.peaks,
        facts={"steps_log": steps_log}, family=None, cell_file={})
    for name in NEW_READERS:
        assert read(name) is None, name
    run.trace_data = object()  # and where it holds a trace
    assert scopes.scope_tables(run, {2048}) is None


def test_costs_at_the_published_sizes():
    from benchmark import costs_lfm2 as costs

    with open(CONFIG) as f:
        c = json.load(f)
    assert costs.layers(c) == (8, 2, 2, 8)
    assert costs.kv_row_bytes(c) == 2048 and costs.head_dim(c) == 64
    assert round(costs.conv_params(c) / 1e6, 2) == 16.78
    assert round(costs.attention_params(c) / 1e6, 2) == 10.49
    assert round(costs.expert_params(c) / 1e6, 3) == 9.437
    # 128 tokens of 4 choices in 64: none of the 64 is left untouched
    assert 63.9 < costs.expected_distinct_held(c, 128) < 64.0
    assert 55 < costs.expected_distinct_held(c, 32) < 57
    # 128 streams at 1k positions each: 10.53 GB of weights (every expert,
    # the whole tied head), 0.53 GB of pages, 17 MB of windows in and out
    need = costs.lfm2_decode_bytes(c, 128, 130_000)
    assert 11.0e9 < need < 11.1e9
    assert need < c["weight_bytes_bfloat16"] + 130_000 * 4096 + 20e6
    # what a step must move can never exceed what the chip holds for it
    assert costs.lfm2_decode_bytes(c, 1, 100) < 2.6e9


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
    """The decode program at the cell's 128 slots and the prefill program
    of the longest prompt (4096 positions), layers 0-9: both fit beside
    10.53 GB of weights, the attention layers' pool and the windows; the
    pools have a layer axis over the two attention layers alone, hold a
    position's 8 kv heads of 64 as four rows of 128 lanes (nothing
    padded) and are donated and updated in place; neither a pool nor a
    weight is copied or laid out again; the flash kernel runs at heads of
    64; the scope tables name every scope the readers ask for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness, weights
    from benchmark import program_scopes_lfm2 as scopes
    from benchmark.drivers.serve_hybrid import as_program_tree
    from fms_fsdp_tpu.obs.scopes import LFM2_SCOPES, scope_table
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families.kexaone import page_geometry
    from fms_fsdp_tpu.serve.families.lfm2 import (
        cache_bytes, decode_program, prefill_program, window_shape)

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
    weight_bytes = sum(x.size for x in jax.tree.leaves(params)) * 2
    assert weight_bytes == c["weight_bytes_bfloat16"]
    page, block_kv, max_pages, num_pages = page_geometry(cfg, scfg)
    assert (page, block_kv) == (128, 512) and max_pages * page == scfg.max_seq_len
    cost = cache_bytes(cfg, bf16)
    assert cost == {"per_token": 4096, "per_stream": 8 * 8192}
    pool_shape = (2, num_pages, page * 4, 128)
    pool_bytes = num_pages * page * cost["per_token"]
    window_bytes = scfg.max_batch * cost["per_stream"]
    assert window_bytes == 8 * 2**20
    assert weight_bytes + pool_bytes + window_bytes > 0.25 * 16e9
    B, top = scfg.max_batch, run.traffic["prompt_tokens"]["max"]
    decode = decode_program(cfg, scfg, page, block_kv, bf16).lower(
        params, {"z": S(window_shape(cfg, scfg), bf16)},
        {k: S(pool_shape, bf16) for k in ("k", "v")},
        S((B, max_pages), jnp.int32), S((B,), jnp.int32), S((B,), jnp.int32),
        S((2,), jnp.uint32)).compile()
    prefill = prefill_program(cfg, scfg, top, top, bf16).lower(
        params, S((1, top), jnp.int32), S((1,), jnp.int32)).compile()
    m = decode.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
    # no copy of a pool (2.0 GB where its last axis is a position's 512
    # values, 6.0 GB where it is one head of 64) or of a weight
    assert m.temp_size_in_bytes < 0.1e9
    assert m.argument_size_in_bytes < weight_bytes + pool_bytes + 0.05e9
    m = prefill.memory_analysis()  # pools and windows stand beside it
    assert m.temp_size_in_bytes < 0.5e9
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes + pool_bytes
            + window_bytes) < HBM
    dtext, ptext = decode.as_text(), prefill.as_text()
    assert ptext.startswith(f"HloModule jit__prefill_{top},")
    assert dtext.startswith("HloModule jit__step,")
    # the paged kernel once an attention layer; in the prefill two flash
    # calls an attention layer (the chunk's own block, the walk over
    # earlier ones) and three grouped matmuls an expert layer
    assert dtext.count("tpu_custom_call") == 2
    assert ptext.count("tpu_custom_call") == 2 * 2 + 3 * 8
    dims = ",".join(map(str, pool_shape))
    assert f"bf16[{dims}]" in dtext
    assert not re.search(r"= bf16\[%s\]\S* copy\(" % dims, dtext)
    for text in (dtext, ptext):  # no expert stack laid out again
        assert not re.search(
            r"= bf16\[64,(2048,1536|1536,2048)\]\S* (copy|transpose)\(", text)
    for compiled, want in (
            (decode, scopes.MOE[:1] + scopes.MOE[2:] + scopes.CONV
             + scopes.ATTN_DECODE[:4] + scopes.ATTN_DECODE[5:]
             + ("dense_mlp", "head", "norm", "embed", "sample")),
            (prefill, scopes.MOE + scopes.CONV + (
                "qkv", "kv_write", "attn_full", "attn_out", "dense_mlp",
                "head"))):
        table = scope_table(compiled.as_text(), LFM2_SCOPES)
        assert set(want) <= set(table.values()), set(want) - set(table.values())
