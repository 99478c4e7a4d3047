"""The readers of the program's own spans and scopes
(``benchmark/program_trace.py`` and the six per-layer metrics on it), on
events made by hand, each with the hand count beside it; and the traced
rehearsal of the cell, which has to list ``engine_host_ms.decode``."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import harness, program_trace, trace_reduce
from benchmark.trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MS = 1e6  # ns
NEW = ("engine_host_ms.decode", "idle_outside_engine_share", "decode_attn_ms",
       "decode_moe_gather_ms", "decode_moe_experts_ms", "decode_unscoped_share")


def span(name, start_ms, dur_ms, **stats):
    return Event(name, start_ms * MS, dur_ms * MS, stats)


def decode_step(start_ms, wait_ms, step):
    """A ``step`` span of 10 ms that decodes: 1 ms before the decode,
    ``decode.dispatch`` 1 ms, ``decode.wait``, ``decode.commit`` to the
    end of ``decode`` at 9.5 ms, ``publish`` 0.5 ms."""
    t = start_ms
    return [
        span("step", t, 10, step=step),
        span("expire", t + 0.1, 0.1, step=step),
        span("admit", t + 0.3, 0.2, step=step),
        span("admit.done", t + 0.5, 0.0, step=step, admitted=0),
        span("grow", t + 0.6, 0.1, step=step),
        span("decode", t + 1, 8.5, step=step, live=8),
        span("decode.table", t + 1.1, 0.1, uploaded=0),
        span("decode.dispatch", t + 1.5, 1),
        span("decode.wait", t + 2.5, wait_ms),
        span("decode.commit", t + 2.5 + wait_ms, 9.5 - 2.5 - wait_ms, step=step),
        span("publish", t + 9.5, 0.5, step=step),
    ]


def prefill_step(start_ms, step):
    """A ``step`` of 30 ms whose ``admit`` holds a ``prefill`` of 18 ms."""
    t = start_ms
    return [
        span("step", t, 30, step=step),
        span("admit", t + 1, 20, step=step),
        span("prefill", t + 1.5, 18, step=step, rid=7, prompt_tokens=300),
        span("prefill.dispatch", t + 2, 15, rid=7, built=0),
        span("prefill.sample", t + 18, 1, step=step, rid=7),
        span("decode", t + 22, 7.5, step=step, live=8),
        span("decode.wait", t + 23, 6),
    ]


SPANS = sorted(
    decode_step(0, 6.0, 1) + [span("submit", 10.2, 0.3, prompt_tokens=40)]
    + prefill_step(11, 2) + decode_step(42, 5.0, 3) + decode_step(53, 6.5, 4),
    key=lambda e: (e.start_ns, -e.dur_ns))


# -- host spans -----------------------------------------------------------------


def test_host_ms_is_the_step_less_its_wait_over_decode_only_steps():
    # steps 1, 3, 4 decode only: 10 - 6, 10 - 5, 10 - 6.5; step 2 holds a
    # prefill and is left out (30 - 6 = 24 would be its own)
    got = program_trace.host_ms_of_decode_steps(SPANS)
    assert got == pytest.approx([4.0, 5.0, 3.5])
    assert program_trace.median(got) == pytest.approx(4.0)


def test_a_step_with_a_chunk_of_a_prefill_is_not_a_decode_step():
    spans = decode_step(0, 6.0, 1) + [span("prefill_chunk", 0.5, 0.3, rid=3)]
    assert program_trace.host_ms_of_decode_steps(spans) == []


def test_innermost_segments_give_each_instant_to_one_span():
    segs = program_trace.innermost_segments(decode_step(0, 6.0, 1))
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    own = {}
    for lo, hi, name in segs:
        own[name] = own.get(name, 0.0) + (hi - lo) / MS
    # decode 8.5 ms less table 0.1, dispatch 1, wait 6, commit 1 = 0.4;
    # step 10 less expire 0.1, admit 0.2, grow 0.1, decode 8.5, publish 0.5
    assert own["decode"] == pytest.approx(0.4)
    assert own["step"] == pytest.approx(0.6)
    assert own["decode.wait"] == pytest.approx(6.0)
    assert "admit.done" not in own
    assert sum(own.values()) == pytest.approx(10.0)


def test_idle_goes_to_the_innermost_span_or_outside():
    idle = [
        (1.2 * MS, 2.0 * MS),    # decode.table 1.2-1.2 (0), decode 0.3, dispatch 0.5
        (8.4 * MS, 8.6 * MS),    # decode.wait up to 8.5, decode.commit after
        (9.8 * MS, 10.3 * MS),   # publish 0.2, outside 0.2, submit 0.1
        (40.9 * MS, 42.5 * MS),  # step 2 0.1, outside 1, step 3 0.1, expire 0.1, step 3 0.1, admit 0.2
    ]
    by, total = program_trace.idle_by_innermost_span(SPANS, idle)
    assert total == pytest.approx((0.8 + 0.2 + 0.5 + 1.6) / 1e3)
    assert by["decode.dispatch"] == pytest.approx(0.5e-3)
    assert by["decode"] == pytest.approx(0.3e-3)
    assert by["decode.wait"] == pytest.approx(0.1e-3)
    assert by["decode.commit"] == pytest.approx(0.1e-3)
    assert by["publish"] == pytest.approx(0.2e-3)
    assert by["submit"] == pytest.approx(0.1e-3)
    assert by["(outside)"] == pytest.approx(1.2e-3)
    assert by["expire"] == pytest.approx(0.1e-3)
    assert by["admit"] == pytest.approx(0.2e-3)
    assert by["step"] == pytest.approx(0.1e-3 + 0.1e-3 + 0.1e-3)
    assert sum(by.values()) == pytest.approx(total)


def _device_trace():
    """One device. Operations from -5 ms (before the first step span: a
    step that was under way when the profiler started) to 63 ms, with
    four idle gaps after 0: 1.0-2.5 (inside step 1), 10.0-11.0 (0.3 in
    ``submit``, 0.7 outside), 41.0-42.0 (outside), 62.5-63.0 (step 4)."""
    busy = [(-5, 1.0), (2.5, 10.0), (11.0, 41.0), (42.0, 62.5), (63.0, 64.0)]
    ops = [Event(f"fusion.{i}", lo * MS, (hi - lo) * MS)
           for i, (lo, hi) in enumerate(busy)]
    return Trace({"/device:TPU:0": {trace_reduce.OPS_LINE: ops}}, [])


def test_device_idle_is_taken_between_the_first_and_the_last_step_span():
    idle = program_trace.device_idle(_device_trace(), SPANS)
    # the stretch before the first step span (-5 to 0) and after the last
    # (63 on) are left out
    assert [(lo / MS, hi / MS) for lo, hi in idle] == [
        (1.0, 2.5), (10.0, 11.0), (41.0, 42.0), (62.5, 63.0)]


def test_outside_share_is_the_idle_time_under_no_step_or_submit():
    pt = program_trace.ProgramTrace(SPANS)
    pt.idle_by_span, pt.idle_s = program_trace.idle_by_innermost_span(
        SPANS, program_trace.device_idle(_device_trace(), SPANS))
    # idle 1.5 + 1.0 + 1.0 + 0.5 = 4.0 ms; outside 0.7 + 1.0 = 1.7 ms
    assert pt.idle_s == pytest.approx(4.0e-3)
    assert program_trace.outside_share(pt) == pytest.approx(100 * 1.7 / 4.0)
    # no spans (the parent of the PR that added them): nothing to read
    bare = program_trace.ProgramTrace([])
    bare.idle_by_span, bare.idle_s = {"(outside)": 4e-3}, 4e-3
    assert program_trace.outside_share(bare) is None


# -- the decode program by scope --------------------------------------------------

SCOPES = {
    "fusion.1": "qkv", "fusion.2": "kv_gather", "fusion.3": "attn",
    "gather.4": "moe_gather", "fusion.5": "moe_experts",
    "fusion.6": "moe_router", "slice.7": "layers", "copy.8": "",
    "fusion.9": "lm_head",
}


def _decode_trace():
    """Two executions of ``jit__step`` (0-100 and 200-300 ms) and one
    prefill module between them. In each step a ``while`` wrapper spans
    the events of its body, which are listed too."""
    def step(t, gather_ms):
        evs = [
            ("while.1", t + 1, 90),           # the wrapper: must not count
            ("fusion.1", t + 1, 2), ("fusion.2", t + 3, 3),
            ("fusion.3", t + 6, 1), ("gather.4", t + 7, gather_ms),
            ("fusion.5", t + 50, 20), ("fusion.6", t + 70, 1),
            ("slice.7", t + 71, 15), ("copy.8", t + 86, 4),
            ("unknown.77", t + 90, 1),        # not in the table: unscoped
            ("fusion.9", t + 92, 1),
        ]
        return [Event(n, s * MS, d * MS) for n, s, d in evs]

    ops = step(0, 40) + [Event("fusion.1", 150 * MS, 30 * MS)] + step(200, 42)
    mods = [
        Event("jit__step(123)", 0, 100 * MS),
        Event("jit__unknown(5)", 140 * MS, 50 * MS),
        Event("jit__step(123)", 200 * MS, 100 * MS)]
    return Trace({"/device:TPU:0": {
        trace_reduce.OPS_LINE: ops, trace_reduce.MODULES_LINE: mods}}, [])


def _program_trace(spans=SPANS):
    pt = program_trace.ProgramTrace(spans)
    pt.decode_steps, pt.joined_share = program_trace.decode_time_by_scope(
        _decode_trace(), SCOPES)
    pt.idle_by_span, pt.idle_s = program_trace.idle_by_innermost_span(
        spans, program_trace.device_idle(_device_trace(), spans))
    return pt


def test_decode_time_by_scope_leaves_wrappers_and_other_modules_out():
    steps, joined = program_trace.decode_time_by_scope(_decode_trace(), SCOPES)
    assert len(steps) == 2
    first = {k: v / MS for k, v in steps[0].items()}
    assert first == {
        "qkv": 2, "kv_gather": 3, "attn": 1, "moe_gather": 40,
        "moe_experts": 20, "moe_router": 1, "layers": 15, "": 5, "lm_head": 1}
    # 88 ms of operations in a 100 ms module; the while's 90 ms not added,
    # the prefill module's fusion.1 (30 ms) not counted as qkv
    assert sum(first.values()) == 88
    # ten events a step, nine of them known to the table
    assert joined == pytest.approx(18 / 20)


@pytest.mark.parametrize("name,want", [
    ("engine_host_ms.decode", 4.0),                # median of 4.0, 5.0, 3.5
    ("idle_outside_engine_share", 42.5),           # 1.7 of 4.0 ms
    ("decode_attn_ms", 6.0),                       # 2 + 3 + 1
    ("decode_moe_gather_ms", 42.0),                # upper median of 40, 42
    ("decode_moe_experts_ms", 21.0),               # 20 + 1
    # copy.8 + unknown.77 of 88 ms and of 90 ms: the upper median
    ("decode_unscoped_share", 100 * 5 / 88),
])
def test_reader_on_hand_made_events(name, want):
    run = types.SimpleNamespace(program_trace=_program_trace())
    assert harness.read_layer_metric(BENCH, name, run) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_a_window_without_a_prefill_still_gives_the_metric(name):
    spans = decode_step(0, 6.0, 1) + decode_step(11, 5.0, 2)
    run = types.SimpleNamespace(program_trace=_program_trace(spans))
    assert harness.read_layer_metric(BENCH, name, run) is not None


@pytest.mark.parametrize("name", NEW)
def test_no_trace_or_a_program_without_spans_and_scopes_gives_none(name):
    """An untraced run; and the parent of the PR that added the spans:
    its trace holds device events and no ``serve/`` span, and it has no
    ``decode_program`` to build a table from."""
    run = types.SimpleNamespace(trace_data=None, facts={})
    assert harness.read_layer_metric(BENCH, name, run) is None
    bare = program_trace.ProgramTrace([])
    bare.idle_by_span, bare.idle_s = {"(outside)": 4e-3}, 4e-3
    run = types.SimpleNamespace(program_trace=bare)
    assert harness.read_layer_metric(BENCH, name, run) is None


def test_a_program_without_scope_table_builds_none(monkeypatch):
    import fms_fsdp_tpu.obs.scopes as scopes

    monkeypatch.delattr(scopes, "scope_table")
    run = types.SimpleNamespace(config={"family": "mixtral"})
    assert program_trace.decode_scope_table(run) is None


def test_the_manifest_lists_the_six_for_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    assert [m["name"] for m in manifest["per_layer"][-6:]] == list(NEW)
    for m in mine:
        assert m["workloads"] == ["mixtral-8x7b.serve-chat-over"]
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))


def test_traced_rehearsal_reads_the_engine_host_time():
    """On the CPU there is no device plane, so the four device readers
    and the idle share read nothing; the program's spans are there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mixtral-8x7b.serve-chat-over", "--seed", "2147483700", "--seconds",
         "1.5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    (read,) = [l for l in p.stdout.splitlines()
               if l.startswith("rehearsal read per-layer metrics:")]
    assert "'engine_host_ms.decode'" in read, p.stdout[-3000:]
    assert "decode_attn_ms" not in read and "idle_outside" not in read
    # a CPU's times are not printed under the chip's names
    assert "program spans:" not in p.stdout
