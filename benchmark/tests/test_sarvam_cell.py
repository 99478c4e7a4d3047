"""The sarvam cell: its rehearsal with the control failing, its
configuration against the catalog's row, its readers on events made by
hand, and its two programs compiled at published widths for a described
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
CELL = "sarvam-105b.serve-context-over"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "sarvam-105b.1chip.json")
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


def test_configuration_is_the_catalogs_row_cut_as_it_says():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "sarvam-105b")
    with open(CONFIG) as f:
        mine = json.load(f)
    assert mine["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if mine[k] != v)
    assert differs == sorted(mine["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert mine["published"] == {k: row["config"][k] for k in mine["reduced"]}
    # the guide's floors: a leading dense layer and at least four after
    # it, 8 routed experts, an eighth of the vocabulary; no width cut
    assert mine["num_hidden_layers"] - mine["first_k_dense_replace"] >= 4
    assert mine["num_experts"] >= 8
    assert mine["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert sorted(mine["assumed"]) == [
        "rotary_pairs", "router_scoring", "routing_groups", "serving_dtype",
        "use_qk_norm"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "sarvam-105b.1chip")
    assert entry["reduced"] == mine["reduced"]
    assert entry["source"] == row["source_url"]


def test_traffic_is_what_the_issue_states():
    from benchmark import traffic
    from benchmark.drivers.serve_sarvam import stratified_schedule

    def balanced_schedule(seed, mix, seconds, vocab):
        return stratified_schedule(seed, mix, seconds, vocab, 2048)

    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    mix = cell["traffic"]
    assert mix["queued_at_open"] == 64
    assert mix["prompt_tokens"] == {
        "median": 4096, "sigma": 0.6, "min": 1024, "max": 16384}
    assert mix["output_tokens"] == {
        "median": 160, "sigma": 0.7, "min": 32, "max": 512}
    eng = cell["engine"]
    assert (eng["max_batch"], eng["max_seq_len"], eng["prefill_bucket"],
            eng["moe_impl"], eng["compute_dtype"]) == (
        32, 16896, 2048, "routed", "bfloat16")
    a = balanced_schedule(7, mix, 45.0, 65536)
    b = balanced_schedule(8, mix, 45.0, 65536)
    plain = traffic.serve_schedule(7, mix, 45.0, 65536)
    lens = lambda s: (sorted(len(p) for _, p, _ in s), sorted(o for _, _, o in s))  # noqa: E731
    assert lens(a) == lens(b) == lens(plain)
    # the seed orders the members of a stratum; the strata stand where
    # they stood: the same buckets in the same places, other prompts
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]
    assert [o for _, _, o in a] != [o for _, _, o in b]
    assert ([-(-len(p) // 2048) for _, p, _ in a]
            == [-(-len(p) // 2048) for _, p, _ in b])
    assert a == balanced_schedule(7, mix, 45.0, 65536)
    assert max(len(p) + o for _, p, o in a) <= eng["max_seq_len"]
    assert all(1 <= t < 65536 for _, p, _ in a[:3] for t in p)
    # the pool, not the slots, bounds admission: the 32 longest requests
    # would need more pages than it has, the mean 32 far fewer
    per_page = 128
    pages = sorted(-(-(len(p) + o) // per_page) for _, p, o in a)
    assert sum(pages[-32:]) > eng["num_pages"] > 32 * sum(pages) / len(pages)


def test_prefill_modules_are_counted_with_the_done_span_that_follows():
    from benchmark.program_scopes_sarvam import pair_with_done_spans
    from benchmark.trace_reduce import Event

    def module(start, dur, n):
        return ("lines", Event(f"jit__prefill_{n}", start, dur), n)

    def done(start, computed, held):
        return Event("prefill.done", start, 0.0, {
            "rid": 1, "computed_tokens": computed, "moe_pairs_held": held,
            "moe_pairs_routed": computed * 40})

    mods = [module(100, 50, 2048), module(300, 80, 4096), module(600, 40, 2048)]
    spans = [
        Event("prefill", 90, 100),
        done(40, 2048, 1),  # of a prefill before the trace began
        done(160, 2048, 20000), done(390, 4096, 41000),
        # the third module's span fell after the trace's end
        Event("prefill.done", 700, 0.0, {"rid": 9, "computed_tokens": 1}),
    ]
    got = pair_with_done_spans(mods, spans)
    assert [(n, c["computed_tokens"], c["moe_pairs_held"])
            for _, _, n, c in got] == [(2048, 2048, 20000), (4096, 4096, 41000)]


def test_costs_at_the_published_sizes():
    from benchmark import costs_sarvam as costs

    with open(CONFIG) as f:
        c = json.load(f)
    assert costs.latent_bytes_per_token(c) == 6 * 1152
    assert round(costs.attention_params(c) / 1e6, 1) == 94.6
    assert round(costs.expert_params(c) / 1e6, 2) == 25.17
    # 32 streams at 5k tokens: 10.4-10.5 GB, most of it the held experts
    assert 10.3e9 < costs.sarvam_decode_bytes(c, 32, 160_000) < 10.6e9
    assert 27 < costs.expected_distinct_held(c, 32) < 29
    ops, byts = costs.mla_decode_attn_cost(c, 32, 160_000)
    assert 100 < ops / byts < 130  # about half the v5e's ridge of 240
    # a chunk of 2048 positions: a quarter of 2048 * 8 * 5 pairs, each
    # held expert of each MoE layer read once: the bytes bound it
    ops, byts = costs.moe_grouped_cost(c, 2048 * 8 * 5 // 4, 1)
    assert 8.0e9 < byts < 8.5e9 and ops / 197e12 < byts / 819e9


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


def _shapes(text):
    return {
        tuple(int(d) for d in dims.split(","))
        for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]", text)}


def test_programs_compile_at_published_widths_and_fit(topo, monkeypatch):
    """The decode program at the cell's 32 slots and the prefill program
    of the longest prompt (16384 tokens), 1 + 5 layers: both fit beside
    10.92 GB of weights and the 2.4 GB pool; the pool is one array of 576
    values a position and layer in 640 lanes, donated and updated in
    place (no temporary of its size); no array of expanded keys or values for
    a whole cache and no dense-over-experts array exists in either; the
    prefill's experts and attention are Mosaic kernels, and its scope
    table names every scope the readers ask for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness, weights
    from benchmark import program_scopes_sarvam as scopes
    from fms_fsdp_tpu.models.sarvam import pool_width
    from fms_fsdp_tpu.obs.scopes import SARVAM_SCOPES, scope_table
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families.sarvam import (
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
    params = weights.unflatten({
        p: S(s["shape"], bf16)
        for p, s in run.reference.param_spec(c).items()})
    weight_bytes = sum(x.size for x in jax.tree.leaves(params)) * 2
    assert 10.9e9 < weight_bytes < 10.95e9
    page, max_pages, num_pages = page_geometry(cfg, scfg)
    # 576 values a position and layer, in whole rows of 128 lanes
    assert (cfg.latent_dim, pool_width(cfg)) == (576, 640)
    pool_shape = (6, num_pages, page, 640)
    pool_bytes = 2 * 6 * num_pages * page * 640
    assert 2.0e9 < pool_bytes <= 2.41e9
    assert weight_bytes + pool_bytes > 11e9  # what the fullest device holds
    B, top = scfg.max_batch, run.traffic["prompt_tokens"]["max"]
    decode = decode_program(cfg, scfg, page, bf16).lower(
        params, {"latent": S(pool_shape, bf16)}, S((B, max_pages), jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.int32), S((2,), jnp.uint32)).compile()
    prefill = prefill_program(cfg, scfg, top, top, bf16).lower(
        params, S((1, top), jnp.int32), S((1,), jnp.int32)).compile()
    m = decode.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
    assert m.temp_size_in_bytes < 1.5e9 < pool_bytes  # no copy of the pool
    m = prefill.memory_analysis()  # the pool stands beside it
    assert m.argument_size_in_bytes + m.temp_size_in_bytes + pool_bytes < HBM
    N = cfg.nheads
    for name, compiled in (("decode", decode), ("prefill", prefill)):
        text = compiled.as_text()
        shapes = _shapes(text)
        for s in shapes:
            n = 1
            for d in s:
                n *= d
            # expanded keys or values of a whole cache: positions x heads
            # x a head's width, for as many positions as a stream may hold
            assert not (len(s) >= 3 and s[-2:] in ((N, 192), (N, 128), (N, 256))
                        and n >= scfg.max_seq_len * N * 128), (name, s)
            # dense over experts: (tokens, experts, width)
            assert not (len(s) >= 3 and s[-2] in (32, 128)
                        and s[-1] in (2048, 4096) and n >= 2048 * 32 * 2048
                        and s[-3] >= 2048), (name, s)
    text = prefill.as_text()
    # three grouped matmuls a MoE layer (one scan body) and two flash
    # calls (the chunk's own block, the walk over earlier ones) for the
    # dense layer and for the scan body
    assert text.count("tpu_custom_call") == 3 + 2 * 2
    assert text.startswith(f"HloModule jit__prefill_{top},")
    assert decode.as_text().startswith("HloModule jit__step,")
    assert decode.as_text().count("tpu_custom_call") == 2  # the paged kernel
    for compiled, want in (
            # (no ``latent_gather``: on the chip the paged kernel reads the
            # pages where they lie, and its time is ``attn``'s)
            (decode, tuple(s for s in scopes.ATTN_DECODE if s != "latent_gather")
             + scopes.MOE_DECODE + ("mlp", "norm", "embed", "lm_head", "sample")),
            (prefill, scopes.ATTN_PREFILL + scopes.MOE_GROUPED
             + ("moe_router", "moe_shared", "mlp", "norm", "embed", "lm_head"))):
        found = set(scope_table(compiled.as_text(), SARVAM_SCOPES).values())
        assert set(want) <= found, sorted(set(want) - found)
