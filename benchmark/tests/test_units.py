"""The yardstick's own arithmetic: costs, traffic, weights, the trace
reduction on a small recorded trace, the manifest's form."""

import json
import os

import numpy as np
import pytest

from benchmark import costs, traffic, trace_reduce
from benchmark.trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# -- costs against hand counts ----------------------------------------------


def test_costs_mixtral_decode_bytes_hand_count():
    c = _config("mixtral-8x7b.1chip")
    assert c["num_hidden_layers"] == 3
    weights = (
        3 * (4096 * 128 * 80 + 4096 * 8)  # attention + router, each layer
        + 24 * 3 * 4096 * 14336  # 8 distinct experts in each of 3 layers
        + 4096 * 32000  # head
        + 8 * 4096  # embedding rows of 8 tokens
    )
    kv = 2 * 3 * 1000 * 8 * 128
    got = costs.mixtral_decode_bytes(c, 8, [8, 8, 8], 1000)
    assert got == 2 * weights + 2 * kv == 8_982_069_248


# -- traffic ----------------------------------------------------------------


@pytest.fixture(scope="module")
def chat_mix():
    with open(os.path.join(BENCH, "workloads", "mixtral-8x7b.serve-chat-over.json")) as f:
        return json.load(f)["traffic"]


def test_serve_schedule_reproducible_and_clipped(chat_mix):
    a = traffic.serve_schedule(2**31 + 77, chat_mix, 40.0, 32000)
    b = traffic.serve_schedule(2**31 + 77, chat_mix, 40.0, 32000)
    assert a == b
    at_open = chat_mix["queued_at_open"]
    assert len(a) == at_open + round(chat_mix["rate_per_s"] * 40.0)
    p, o = chat_mix["prompt_tokens"], chat_mix["output_tokens"]
    assert [t for t, _, _ in a[:at_open]] == [0.0] * at_open
    assert a[at_open][0] > 0.0
    assert all(x[0] <= y[0] for x, y in zip(a, a[1:]))
    assert a[-1][0] < 40.0
    for _, ids, new in a:
        assert p["min"] <= len(ids) <= p["max"]
        assert o["min"] <= new <= o["max"]
        assert min(ids) >= 1 and max(ids) < 32000


def test_serve_schedule_same_set_every_seed_in_another_order(chat_mix):
    a = traffic.serve_schedule(1, chat_mix, 40.0, 32000)
    b = traffic.serve_schedule(2, chat_mix, 40.0, 32000)
    for pick in (lambda x: len(x[1]), lambda x: x[2]):
        assert sorted(map(pick, a)) == sorted(map(pick, b))
        assert list(map(pick, a)) != list(map(pick, b))
    gaps = lambda s: np.round(np.diff([t for t, _, _ in s]), 9)
    assert sorted(gaps(a)) == sorted(gaps(b)) and list(gaps(a)) != list(gaps(b))
    assert a[-1][0] == pytest.approx(b[-1][0])
    assert a[0][1] != b[0][1]


def test_the_set_is_the_mid_quantiles_of_the_stated_laws(chat_mix):
    n = 4001
    x = traffic.lognormal_lengths(n, dict(
        chat_mix["output_tokens"], min=1, max=10**6))
    assert x[n // 2] == chat_mix["output_tokens"]["median"]
    assert abs(np.std(np.log(x)) - chat_mix["output_tokens"]["sigma"]) < 0.01
    g = traffic.exponential_gaps(n, 0.7)
    assert abs(g.mean() - 1 / 0.7) < 0.01 and abs(np.median(g) - np.log(2) / 0.7) < 1e-3


# -- weights ------------------------------------------------------------------


def test_weights_leaf_alone_equals_leaf_in_tree():
    import jax
    import jax.numpy as jnp

    from benchmark import weights
    from benchmark.reference import mixtral

    c = dict(_config("mixtral-8x7b.1chip"), hidden_size=64, intermediate_size=96,
             vocab_size=128, num_attention_heads=2, num_key_value_heads=1,
             num_hidden_layers=2)
    spec = mixtral.param_spec(c)
    key = weights.seed_key(2**31 + 9)
    tree = jax.jit(lambda k: weights.make_tree(k, spec, jnp.bfloat16))(key)
    one = weights.make_leaf(key, "layers/w2", spec["layers/w2"], jnp.bfloat16, 1)
    assert bool(jnp.all(one == tree["layers"]["w2"][1]))
    assert abs(float(jnp.std(tree["lm_head"].astype(jnp.float32))) - 0.02) < 2e-3
    assert weights.unflatten({"a/b": 1, "a/c": 2, "d": 3}) == {
        "a": {"b": 1, "c": 2}, "d": 3}


# -- trace reduction ------------------------------------------------------------


def test_union_and_gaps_on_hand_made_events():
    ev = [Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 5)]
    assert trace_reduce.union_ns(ev) == 20
    assert trace_reduce.gaps(ev, 0, 40) == [(15, 30), (35, 40)]
    assert trace_reduce.time_by_name(ev + [Event("a", 50, 1)]) == {
        "a": 11, "b": 10, "c": 5}
    trace = Trace(
        {"/device:TPU:0": {"XLA Ops": ev}},
        [Event("wait", 14, 12), Event("feed", 27, 2), Event("late", 36, 10)])
    assert trace_reduce.busy_and_window_s(trace) == (20 / 1e9, 35 / 1e9)
    assert trace_reduce.idle_gaps_by_span(trace) == [["wait", 15 / 1e9]]


@pytest.fixture(scope="module")
def recorded():
    """One step of a Bamba training program cut from a trace taken on a
    TPU v5e in PR 24 (the cell it came from was taken out again; to the
    reduction a trace is a trace): its device operations of 100 us and
    more, its program, the benchmark's host spans around it."""
    return trace_reduce.load(
        os.path.join(HERE, "data", "recorded_train_step.textproto"))


def test_recorded_trace_busy_idle_and_names(recorded):
    ops = trace_reduce.device_ops(recorded)["/device:TPU:0"]
    assert len(ops) == 127
    # names are instruction names, the HLO text is kept beside them
    assert all(" = " not in e.name and not e.name.startswith("%") for e in ops)
    assert all(e.stats["hlo_text"].startswith("%" + e.name) for e in ops)
    busy, window = trace_reduce.busy_and_window_s(recorded)
    # brute force on a 1 us grid
    lo = min(e.start_ns for e in ops)
    grid = np.zeros(int((max(e.end_ns for e in ops) - lo) / 1e3) + 1, bool)
    for e in ops:
        grid[int((e.start_ns - lo) / 1e3):int((e.end_ns - lo) / 1e3)] = True
    assert abs(busy - grid.sum() / 1e6) < 3e-4
    assert 0.27 < busy < window < 0.29
    top = trace_reduce.top_device_ops(recorded, 3)
    assert [n for n, _ in top] == ["fusion.315", "multiply_reduce_fusion", "fusion.626"]
    by = trace_reduce.time_by_name(ops)
    assert abs(by["flash_attention_fwd.1"] / 1e6 - 2.909) < 0.01
    kernels = [e for e in ops if 'custom_call_target="tpu_custom_call"'
               in e.stats["hlo_text"]]
    assert {e.name for e in kernels} == {
        "flash_attention_fwd.1", "flash_attention_bwd.2", "flash_attention_bwd.3"}


def test_recorded_trace_gap_goes_to_the_host_span_over_it(recorded):
    assert [s.name for s in recorded.spans] == [
        "data_wait", "step_dispatch", "wait_device"]
    gaps = trace_reduce.idle_gaps_by_span(recorded)
    # the loop was blocked on the device while the device's short
    # operations (cut from this file) ran
    assert gaps[0][0] == "wait_device" and gaps[0][1] > 0.001


# -- the manifest -----------------------------------------------------------------


def test_manifest_names_files_that_exist_and_agree():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cfgs = {c["name"]: c for c in m["configs"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in body["published"], key
        for key in ("hidden_size", "intermediate_size"):
            assert key not in c["reduced"]
    for w in m["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "workloads", w["name"] + ".json"))
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= 1
    for x in m["per_layer"]:
        assert x["moves"] in e2e
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", x["name"] + ".py")), x["name"]
        for w in x.get("workloads", []):
            assert w in {y["name"] for y in m["workloads"]}
    # the contract's character sets and lengths
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in m[group]]
        assert len(names) == len(set(names))
        assert all(name.match(n) for n in names), names
    for w in m["workloads"]:
        assert name.match(w["traffic"]) and name.match(w["config"])
    for x in m["end_to_end"] + m["per_layer"]:
        assert unit.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert x["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= x["bound"] <= 0.1 and x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert 1 <= len(x["layer"]) <= 200
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


# -- the reference's blocks of rows ---------------------------------------------


def test_reference_attention_in_blocks_that_do_not_divide():
    """A checked sequence is padded to a multiple of 256, the query block
    is 1024: 1280 rows have to work (they did not, once)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mixtral

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    S = 320
    q = jax.random.normal(kq, (1, S, 4, 16))
    k = jax.random.normal(kk, (1, S, 2, 16))
    v = jax.random.normal(kv, (1, S, 2, 16))
    whole = mixtral.causal_gqa(q, k, v, block=S)
    assert jnp.allclose(mixtral.causal_gqa(q, k, v, block=256), whole, atol=1e-5)


# -- readers of the benchmark's own spans ------------------------------------


def test_prefill_readers_on_hand_made_steps_without_a_trace():
    """Three decode-only steps of 0.1 s and two that also prefilled (300
    and 700 prompt tokens, 0.04 s and 0.08 s beyond a decode step) in a
    window of 2 s; a third prefill ends after the window and is left out.
    No trace at all: these readers may not depend on what the profiler's
    last seconds happen to hold."""
    import types

    from benchmark import harness

    log = [(0.0, 0.1, 8, 100, 0), (0.1, 0.24, 8, 100, 300), (0.24, 0.34, 8, 100, 0),
           (0.34, 0.52, 8, 100, 700), (0.52, 0.62, 8, 100, 0), (1.9, 2.3, 8, 100, 500)]
    run = types.SimpleNamespace(
        facts={"steps_log": log, "window_s": 2.0}, trace_data=None)
    here = os.path.join(ROOT, "benchmark")
    per_1k = harness.read_layer_metric(here, "prefill_ms_per_1k_tokens", run)
    assert per_1k == pytest.approx(1e3 * (0.04 + 0.08) / 1.0)
    share = harness.read_layer_metric(here, "prefill_stall_share", run)
    assert share == pytest.approx(100 * (0.04 + 0.08) / 2.0)
