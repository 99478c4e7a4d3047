"""The readers of the engine's step log (``benchmark/step_log.py`` and
the six per-layer metrics on it, and ``moe_slabs_per_layer_chunk``): the
join and its whole-window rule, each reader on records made by hand with
the hand count beside it, and the traced rehearsal of a cell, which has
to list the six names."""

import os
import subprocess
import sys
import types

import pytest

from benchmark import harness, program_trace, step_log
from benchmark.trace_reduce import Event

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NEW = ("admit_starved_share", "admit_pool_bound_share", "kv_pages_peak_share",
       "engine_step_max_ms.decode", "prefill_share_of_window",
       "prefill_ms_per_1k_computed")
T0 = 5000.0  # the window's start on the engine's clock
WINDOW_S = 10.0


def record(step, t, wall_ms, **fields):
    """A record as ``serve/step.log`` carries it: a decode step of a full
    engine of 4 slots unless ``fields`` say otherwise."""
    rec = dict(
        step=step, t=T0 + t, wall_us=1e3 * wall_ms, prefill_us=0.0,
        queued=3, busy=4, admitted=0, busy_after_admit=4,
        admit_stopped="no_slot", padded_tokens=0, computed_tokens=0, built=0,
        live=4, kv_tokens=100, tokens=4, pages_in_use=10, slow=0,
        slots=4, pages_total=40)
    rec.update(fields)
    return rec


# the window by hand: (record, the driver's (start, end) of that call)
HAND = [
    # two warm-up steps before the window: the driver logged none
    (record(1, -3.0, 900, built=1, computed_tokens=64, prefill_us=8e5), None),
    (record(2, -2.0, 20), None),
    # a full engine decoding: 20 ms
    (record(3, 0.001, 20), (0.0, 0.0211)),
    # a prefill of 2048 computed positions: 500 ms, 450 of them prefill
    (record(4, 0.022, 500, admitted=1, admit_stopped="budget",
            padded_tokens=2048, computed_tokens=2048, prefill_us=450e3,
            pages_in_use=26), (0.0215, 0.5225)),
    # the head of the queue does not fit the pool: 30 ms
    (record(5, 0.523, 30, busy=3, busy_after_admit=3,
            admit_stopped="no_pages", live=3, pages_in_use=30),
     (0.5228, 0.5535)),
    # nobody queued and a slot free: 25 ms the engine could have used
    (record(6, 0.554, 25, queued=0, busy=3, busy_after_admit=3,
            admit_stopped="queue_empty", live=3), (0.5538, 0.5795)),
    # the last stream's last token: nothing queued, and nothing live after
    (record(7, 0.580, 15, queued=0, busy=1, busy_after_admit=1,
            admit_stopped="queue_empty", live=1), (0.5798, 0.5955)),
    # 2.0 s later a request came: the gap was the caller's wait
    (record(8, 2.595, 300, queued=1, busy=0, admitted=1, busy_after_admit=1,
            admit_stopped="budget", padded_tokens=1024, computed_tokens=1024,
            prefill_us=250e3, live=1, pages_in_use=8), (2.5948, 2.8955)),
    # a decode step that stood still: 2.5 s
    (record(9, 2.896, 2500, queued=0, busy=1, busy_after_admit=1,
            admit_stopped="queue_empty", live=1), (2.8958, 5.3965)),
    # the call that straddles the close started inside the window
    (record(10, 9.99, 40, queued=0, busy=1, busy_after_admit=1,
            admit_stopped="queue_empty", live=1), (9.9898, 10.0301)),
]


def spans_of(recs):
    """The records as the replay writes them: newest first, some twice (a
    second session), among other spans."""
    out = [Event("step", 1e6, 1e6, {"step": 10})]
    for i, rec in enumerate(reversed(recs)):
        out.append(Event("step.log", 2e6 + i, 0.0, dict(rec)))
    if recs:
        out.append(Event("step.log", 9e6, 0.0, dict(recs[len(recs) // 2])))
    out.append(
        Event("prefill.done", 9.5e6, 0.0, {"rid": 1, "computed_tokens": 8}))
    return out


def fake_run(recs=None, log=None):
    run = types.SimpleNamespace(
        facts={
            "window": (T0, T0 + WINDOW_S), "window_s": WINDOW_S,
            "steps_log": [
                (*se, 1, 10, 0) for _, se in HAND if se
            ] if log is None else log},
        rehearse=True)
    pt = program_trace.ProgramTrace(
        spans_of([r for r, _ in HAND] if recs is None else recs))
    run.program_trace = pt
    return run


def read(name, run):
    return harness.read_layer_metric(BENCH, name, run)


# -- the join -------------------------------------------------------------------


def test_one_record_per_step_of_the_window():
    recs = step_log.records(fake_run())
    assert [r["step"] for r in recs] == [3, 4, 5, 6, 7, 8, 9, 10]


def test_a_dropped_record_gives_no_number():
    """A whole window or no number: with one record missing every reader
    on the log returns ``None``."""
    run = fake_run(recs=[r for r, _ in HAND if r["step"] != 6])
    assert step_log.records(run) is None
    assert [read(name, run) for name in NEW] == [None] * len(NEW)


def test_a_step_the_driver_did_not_log_gives_no_number():
    log = [(s, e, 1, 10, 0) for r, se in HAND if se and r["step"] != 6
           for s, e in [se]]
    assert step_log.records(fake_run(log=log)) is None


def test_a_program_without_the_span_gives_no_number():
    run = fake_run(recs=[])
    assert step_log.records(run) is None
    assert all(read(name, run) is None for name in NEW)
    run.program_trace = None
    run.trace_data = None  # no trace at all: program_trace.of gives None
    del run.step_log_records
    assert step_log.records(run) is None


# -- each reader, the hand count beside it ----------------------------------------


def test_admit_starved_share():
    # steps 6, 7, 9 and 10 stopped at an empty queue with a slot free:
    # 25 + 15 + 2500 + 40 ms; step 7 left nothing live or queued and step
    # 8 came 2.595 - (0.580 + 0.015) = 2.0 s later
    assert read("admit_starved_share", fake_run()) == pytest.approx(
        100.0 * (0.025 + 0.015 + 2.5 + 0.040 + 2.0) / WINDOW_S)


def test_admit_starved_share_of_a_full_engine_is_zero():
    recs = [record(i, 0.02 * i, 19) for i in range(1, 6)]
    log = [(0.02 * i - 0.0005, 0.02 * i + 0.0195, 4, 100, 0)
           for i in range(1, 6)]
    assert read("admit_starved_share", fake_run(recs, log)) == 0.0


def test_admit_pool_bound_share():
    assert read("admit_pool_bound_share", fake_run()) == pytest.approx(
        100.0 * 0.030 / WINDOW_S)


def test_kv_pages_peak_share():
    assert read("kv_pages_peak_share", fake_run()) == pytest.approx(
        100.0 * 30 / 40)
    # a family with no pages has no share
    recs = [record(3, 0.001, 20, pages_total=0, pages_in_use=0)]
    assert read("kv_pages_peak_share",
                fake_run(recs, [(0.0, 0.0211, 1, 1, 0)])) is None


def test_engine_step_max_ms_decode():
    # the longest step that computed no prefill position and decoded
    assert read("engine_step_max_ms.decode", fake_run()) == pytest.approx(2500)


def test_prefill_share_of_window():
    assert read("prefill_share_of_window", fake_run()) == pytest.approx(
        100.0 * (0.450 + 0.250) / WINDOW_S)


def test_prefill_ms_per_1k_computed():
    assert read("prefill_ms_per_1k_computed", fake_run()) == pytest.approx(
        (450 + 250) / ((2048 + 1024) / 1e3))


def test_moe_slabs_per_layer_chunk():
    """Two traced prefills of a program with 5 MoE layers and chunks of
    2048: 16384 computed positions took 42 trips where 40 is one a layer
    and chunk, 2048 took 5."""
    run = fake_run()
    run.config = {"family": "sarvam"}
    run.family = types.SimpleNamespace(
        model_config=lambda c: types.SimpleNamespace(n_moe_layers=5))
    run.program_trace.spans = [
        Event("prefill", 1e6, 9e6, {"rid": 7, "padded_tokens": 16384}),
        Event("prefill.done", 9e6, 0.0, {
            "rid": 7, "computed_tokens": 16384, "moe_slabs": 42}),
        Event("prefill", 2e7, 9e6, {"rid": 8, "padded_tokens": 2048}),
        Event("prefill.done", 2.8e7, 0.0, {
            "rid": 8, "computed_tokens": 2048, "moe_slabs": 5}),
        # a handoff import computes nothing and counts for nothing
        Event("prefill.done", 3e7, 0.0, {"rid": 9, "computed_tokens": 0}),
    ]
    assert read("moe_slabs_per_layer_chunk", run) == pytest.approx(47 / 45)
    run.program_trace.spans = run.program_trace.spans[-1:]
    assert read("moe_slabs_per_layer_chunk", run) is None


# -- the manifest and the traced rehearsal ------------------------------------------


def test_the_manifest_lists_the_new_metrics_with_their_files():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for name in NEW:
        m = by_name[name]
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "program_span", "serving engine", "serve_tokens_per_s", "lower")
        assert m["workloads"] == cells
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    slabs = by_name["moe_slabs_per_layer_chunk"]
    assert slabs["workloads"] == [
        "sarvam-105b.serve-context-over", "k-exaone-236b.serve-mixed-over"]
    assert slabs["layer"] == "family adapter and decode step"
    # appended behind what PR 35 left (32 entries), in this order; a later
    # PR appends behind them
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[32:39] == list(NEW) + ["moe_slabs_per_layer_chunk"]


def test_traced_rehearsal_reads_the_step_log():
    """The whole way on the CPU: the engine's ring, the replay into the
    rehearsal's session, the trace file, the join with the driver's log
    of the whole window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mixtral-8x7b.serve-chat-over", "--seed", "2147483701", "--seconds",
         "1.5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    (listed,) = [l for l in p.stdout.splitlines()
                 if l.startswith("rehearsal read per-layer metrics:")]
    for name in NEW:
        assert f"'{name}'" in listed, p.stdout[-3000:]
    assert "moe_slabs_per_layer_chunk" not in listed  # not this cell's
    (joined,) = [l for l in p.stdout.splitlines()
                 if l.startswith("step log:")]
    assert joined.endswith("joined"), joined
