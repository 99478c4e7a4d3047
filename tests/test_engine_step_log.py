"""The engine's step log (obs/spans.py::StepLog, serve/engine.py): one
record per ``step()`` on every path, timed on the host by the same
``span()`` calls that write the profiler's spans, why admission stopped,
the slow-step line, and the replay of the ring into a profiler session.

Tiny engines on the CPU; what is pinned is what
``benchmark/step_log.py`` and docs/observability.md "The step log" rely
on.
"""

import glob
import json
import logging
import os
import time

import jax
import pytest

from fms_fsdp_tpu.models.configs import LlamaConfig
from fms_fsdp_tpu.models.llama import init_llama_params
from fms_fsdp_tpu.models.speculator import (
    SpeculatorConfig,
    init_speculator_params,
    save_speculator,
)
from fms_fsdp_tpu.obs import spans
from fms_fsdp_tpu.obs.spans import ADMIT_STOPPED, FIELDS, PREFIX, StepLog
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine

TINY = LlamaConfig(
    src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
    max_expected_seq_len=256,
)
BASE = dict(
    max_batch=2, max_seq_len=256, page_size=8, prefill_bucket=8,
    attn_impl="reference", compute_dtype="float32",
)
# (prompt length, max_new_tokens): three requests over two slots
REQUESTS = ((5, 4), (9, 3), (12, 5))
# the phases of a step that do not overlap: children of ``serve/step``
PHASES = ("expire_us", "admit_us", "grow_us", "decode_us", "publish_us")


@pytest.fixture(scope="module")
def params():
    return init_llama_params(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    scfg = SpeculatorConfig(
        emb_dim=TINY.emb_dim, inner_dim=32,
        vocab_size=TINY.src_vocab_size, n_predict=3)
    path = str(tmp_path_factory.mktemp("spec") / "speculator.pkl")
    save_speculator(
        path, init_speculator_params(jax.random.PRNGKey(7), scfg), scfg)
    return path


def engine(params, **kw):
    return ServingEngine(params, TINY, ServeConfig(**{**BASE, **kw}), seed=3)


def submit(eng, requests=REQUESTS):
    return [eng.submit([1 + (i + j) % 100 for j in range(p)], new)
            for i, (p, new) in enumerate(requests)]


def serve(eng, requests=REQUESTS):
    reqs = submit(eng, requests)
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    return reqs


def handoff_engine(params):
    """A decode-role engine that resumes what a prefill-role engine
    packed: its admissions import pages and run no prefill program."""
    pe = engine(params, role="prefill")
    wires = [r.handoff_out for r in serve(pe)]
    de = engine(params, role="decode")
    reqs = [de.submit_handoff(w) for w in wires]
    de.run()
    assert all(r.state == "finished" for r in reqs)
    return de


PATHS = {
    "plain": lambda p, spec: _served(engine(p)),
    "speculative": lambda p, spec: _served(
        engine(p, speculator_path=spec, page_size=16)),
    "chunked": lambda p, spec: _served(engine(p, prefill_chunk_tokens=8)),
    "handoff_import": lambda p, spec: handoff_engine(p),
}


def _served(eng):
    serve(eng)
    return eng


@pytest.fixture(scope="module", params=sorted(PATHS))
def served(request, params, spec_path):
    return request.param, PATHS[request.param](params, spec_path)


# -- (a) one record per step(), on every path ---------------------------------


def test_one_record_per_step(served):
    _, eng = served
    log = list(eng.step_log)
    assert [r["step"] for r in log] == list(range(1, eng.iterations + 1))
    assert all(set(r) <= set(FIELDS) for r in log)
    assert sorted(r["t"] for r in log) == [r["t"] for r in log]


def test_phases_sum_to_no_more_than_the_step(served):
    _, eng = served
    for r in eng.step_log:
        assert 0 < sum(r[p] for p in PHASES) <= r["wall_us"], r
        # a child's time lies inside its parent's
        assert r["prefill_us"] <= r["wall_us"]
        assert (r["prefill_dispatch_us"] + r["prefill_write_us"]
                <= r["prefill_us"])
        assert r["table_us"] + r["dispatch_us"] <= r["wall_us"]


def test_the_adapters_spans_land_in_the_engines_record(served):
    """``decode.dispatch``, ``decode.wait``, ``prefill.dispatch`` and
    ``prefill.write_pages`` are entered by the adapter, which is handed
    nothing: they find the record of the step that is open."""
    path, eng = served
    log = list(eng.step_log)
    decoded = [r for r in log if r["live"]]
    assert decoded and all(r["dispatch_us"] > 0 for r in decoded)
    assert sum(r["wait_us"] for r in log) > 0
    prefilled = [r for r in log if r["computed_tokens"]]
    if path == "handoff_import":
        assert not prefilled and not any(r["padded_tokens"] for r in log)
        assert sum(r["admitted"] for r in log) == len(REQUESTS)
        assert all(r["prefill_us"] > 0 for r in log if r["admitted"])
    else:
        assert prefilled
        assert all(r["prefill_dispatch_us"] > 0 for r in prefilled)
        assert sum(r["prefill_write_us"] for r in log) > 0
        assert sum(r["prefill_sample_us"] for r in log) > 0


def test_the_records_counts_are_the_registrys(served):
    _, eng = served
    log, reg = list(eng.step_log), eng.registry
    for field, counter in (
            ("padded_tokens", "serve.prefill_padded_tokens"),
            ("computed_tokens", "serve.prefill_computed_tokens"),
            ("built", "serve.prefill_programs_built"),
            ("live", "serve.decode_live_slots"),
            ("tokens", "serve.decode_tokens")):
        assert sum(r[field] for r in log) == reg.counter(counter).value, field
    for why in ADMIT_STOPPED:
        assert reg.counter(f"serve.admit_stopped.{why}").value == sum(
            r["admit_stopped"] == why for r in log), why
    assert reg.gauge("serve.kv_pages_peak").value == max(
        r["pages_in_use"] for r in log) > 0
    # entry counts: the first step met everything queued and nothing live
    assert (log[0]["queued"], log[0]["busy"]) == (len(REQUESTS), 0)
    assert all(r["busy_after_admit"] >= r["admitted"] - 1 for r in log)


def test_two_engines_stepped_in_turn_do_not_mix(params):
    a, b = engine(params), engine(params, prefill_chunk_tokens=8)
    ra, rb = submit(a), submit(b, REQUESTS[:1])
    while a.has_work() or b.has_work():
        for eng in (a, b):
            if eng.has_work():
                eng.step()
    for eng, reqs in ((a, ra), (b, rb)):
        log = list(eng.step_log)
        assert [r["step"] for r in log] == list(range(1, eng.iterations + 1))
        assert sum(r["admitted"] for r in log) == len(reqs)
        assert sum(r["tokens"] for r in log) == sum(
            len(r.generated) - 1 for r in reqs)
    assert spans._open.rec is None


def test_a_span_outside_a_step_is_not_recorded(params):
    eng = engine(params)
    submit(eng)  # serve/submit: no step is open
    assert len(eng.step_log) == 0 and spans._open.rec is None
    eng.step()
    eng.drain()  # collects the step in flight outside any step()
    assert len(eng.step_log) == 1
    # the tokens of that collect are the counter's, not a record's
    assert eng.registry.counter("serve.decode_tokens").value == 1
    assert sum(r["tokens"] for r in eng.step_log) == 0


# -- (b) why admission stopped ------------------------------------------------


def _stopped(eng):
    return [r["admit_stopped"] for r in eng.step_log]


@pytest.mark.parametrize("why", ADMIT_STOPPED)
def test_admit_stopped(params, why):
    if why == "queue_empty":
        eng = engine(params)
        submit(eng, REQUESTS[:1])
        eng.step()  # admits the one request: the budget ends the loop
        eng.step()  # nobody queued
        assert _stopped(eng) == ["budget", "queue_empty"]
        assert eng.step_log[-1]["busy_after_admit"] == 1
    elif why == "budget":
        eng = engine(params, max_prefill_per_step=2)
        submit(eng)
        eng.step()
        rec = eng.step_log[-1]
        assert (rec["admit_stopped"], rec["admitted"]) == ("budget", 2)
    elif why == "no_slot":
        eng = engine(params, max_prefill_per_step=4)
        submit(eng)
        eng.step()
        rec = eng.step_log[-1]
        assert rec["admit_stopped"] == "no_slot"
        assert (rec["admitted"], rec["busy_after_admit"]) == (2, 2)
    elif why == "no_pages":
        # 5 pages of 8 tokens: a 24-token prompt takes 4 with its first
        # token's, and the next one's 2 do not fit
        eng = engine(params, num_pages=7, max_prefill_per_step=4)
        submit(eng, ((24, 8), (9, 3)))
        eng.step()
        rec = eng.step_log[-1]
        assert rec["admit_stopped"] == "no_pages"
        assert (rec["admitted"], rec["busy_after_admit"]) == (1, 1)
        eng.run()
        assert "no_pages" not in _stopped(eng)[-2:]
    else:
        eng = engine(params)
        reqs = submit(eng, REQUESTS[:1])
        eng.step()
        eng.drain()
        eng.run()
        assert reqs[0].state == "finished"
        assert set(_stopped(eng)[1:]) == {"draining"}
    assert eng.registry.counter(f"serve.admit_stopped.{why}").value == (
        _stopped(eng).count(why)) > 0


# -- (c) the ring -------------------------------------------------------------


def test_the_ring_is_bounded(params):
    eng = engine(params)
    eng.step_log = StepLog(capacity=4)
    serve(eng)
    assert eng.iterations > 4 and len(eng.step_log) == 4
    assert [r["step"] for r in eng.step_log] == list(
        range(eng.iterations - 3, eng.iterations + 1))
    assert eng.step_log.closed == eng.iterations
    assert StepLog().ring.maxlen == StepLog.CAPACITY == 8192


def test_tokens_per_s_is_tokens_over_the_rings_time(params):
    tick = iter(range(10**6))
    eng = ServingEngine(
        params, TINY, ServeConfig(**BASE), clock=lambda: float(next(tick)))
    assert eng.serving_stats()["tokens_per_s"] == 0.0
    serve(eng)
    log = list(eng.step_log)
    tokens = sum(r["tokens"] for r in log[:-1])
    assert tokens > 0
    assert eng.serving_stats()["tokens_per_s"] == pytest.approx(
        tokens / (log[-1]["t"] - log[0]["t"]))
    assert "serve.tokens_per_s" not in eng.registry.snapshot()


# -- (d) a slow step writes itself out ----------------------------------------


def _slow_lines(caplog):
    return [json.loads(r.getMessage()) for r in caplog.records
            if r.name == "fms_fsdp_tpu.serve" and r.levelno == logging.WARNING]


def test_a_step_that_stands_still_is_logged_once(params, caplog, monkeypatch):
    monkeypatch.setattr(StepLog, "SLOW_S", 0.1)
    caplog.set_level(logging.WARNING, logger="fms_fsdp_tpu.serve")
    eng = engine(params)
    submit(eng, ((5, 40),))
    for _ in range(6):
        eng.step()  # the first builds; the rest are what a step takes
    assert _slow_lines(caplog) == []
    program = eng.adapter._decode_fn

    def stands_still(*args):
        time.sleep(0.8)
        return program(*args)

    eng.adapter._decode_fn = stands_still
    eng.step()
    eng.adapter._decode_fn = program
    eng.run()
    (line,) = _slow_lines(caplog)
    assert line["slow_step"]["step"] == 7 and line["slow_step"]["slow"] == 1
    assert line["slow_step"]["dispatch_us"] > 0.8e6
    assert line["slow_step"]["wall_us"] > 0.8e6
    assert [r["step"] for r in line["before"]] == [4, 5, 6]
    # a CPU gives no memory figures and the line says so
    assert line["memory"] == "the device gives no memory_stats"
    assert eng.registry.counter("serve.steps_slow").value == 1
    assert [r["step"] for r in eng.step_log if r["slow"]] == [7]


def test_a_build_and_a_first_prefill_of_its_size_are_not_logged(
        params, caplog, monkeypatch):
    """A step that built a program is a compile; a long prefill with no
    earlier one of its size to be held against is what such a prefill
    takes. The next of that size, three times as long, is logged."""
    monkeypatch.setattr(StepLog, "SLOW_S", 0.1)
    caplog.set_level(logging.WARNING, logger="fms_fsdp_tpu.serve")
    eng = engine(params)
    call = eng.adapter._call_prefill
    naps = iter((0.5, 0.3, 1.2))

    def long_prefill(*args):
        time.sleep(next(naps))
        return call(*args)

    eng.adapter._call_prefill = long_prefill
    for i in range(3):  # the same bucket thrice: built, first, slow
        submit(eng, ((12, 3),))
        eng.run()
    prefills = [r for r in eng.step_log if r["computed_tokens"]]
    assert [r["built"] for r in prefills] == [1, 0, 0]
    assert all(r["wall_us"] > 0.1e6 for r in prefills)
    assert [r["slow"] for r in prefills] == [0, 0, 1]
    (line,) = _slow_lines(caplog)
    assert line["slow_step"]["step"] == prefills[2]["step"]
    assert line["slow_step"]["prefill_dispatch_us"] > 1.2e6


# -- (e) the replay into a profiler session -----------------------------------


def _session(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(trace_dir), profiler_options=opts)


def _read(trace_dir):
    """-> ([(start, stats) of each ``serve/step.log``], [start of each
    ``serve/step``]) of the one trace in ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = [
        e for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX)]
    logs = sorted((e.start_ns, dict(e.stats)) for e in events
                  if e.name == PREFIX + "step.log")
    return logs, sorted(e.start_ns for e in events if e.name == PREFIX + "step")


@pytest.fixture(scope="module")
def replayed(params, tmp_path_factory):
    """200 steps with no session, then two sessions of three steps each,
    which hand over 64 earlier records a step (64 to 256 in the program,
    by the room the step's wait for the device leaves: the tiny engine's
    streams are too short for that many steps)."""
    eng = engine(params)
    assert (StepLog.REPLAY_FLOOR, StepLog.REPLAY) == (64, 256)
    eng.step_log.REPLAY = 64
    submit(eng, ((5, 245),))
    for _ in range(200):
        eng.step()
    out = []
    for name in ("first", "second"):
        d = tmp_path_factory.mktemp(name)
        with _session(d):
            for _ in range(3):
                eng.step()
        out.append(_read(str(d)))
        eng.step()  # a step between two sessions: the engine sees the end
    return eng, out


@pytest.mark.parametrize("session", (0, 1))
def test_a_session_is_handed_the_steps_before_it(replayed, session):
    eng, read = replayed
    logs, steps = read[session]
    first = 201 + 4 * session  # the session's first step
    given = [s["step"] for _, s in logs]
    assert len(set(given)) == len(given)  # none twice
    # its own three live and 64 earlier ones a step, newest first
    assert sorted(given) == list(range(first - 3 * 64, first + 3))
    # after each step span at most 64 + 1
    edges = steps[1:] + [float("inf")]
    per_step = [sum(lo < t <= hi for t, _ in logs)
                for lo, hi in zip(steps, edges)]
    assert per_step == [65, 65, 65]
    assert [s["step"] for _, s in logs[:3]] == [first, first - 1, first - 2]
    # the ring's own numbers, and the two facts of the engine
    ring = {r["step"]: r for r in eng.step_log}
    for _, stats in logs:
        rec = ring[stats["step"]]
        assert stats["slots"] == 2 and stats["pages_total"] > 0
        assert {k: stats[k] for k in rec} == pytest.approx(rec)


@pytest.mark.parametrize("wait_us,handed", [
    (0.0, 64), (3000.0, 64), (7200.0, 120), (12300.0, 205), (20000.0, 256)])
def test_a_step_hands_over_what_its_wait_leaves_room_for(wait_us, handed):
    """A step that waited 7.2 ms for the device (the Jamba cell's) hands a
    session 120 earlier records, one that waited 12.3 ms (Mixtral's) 205;
    never fewer than 64 nor more than 256."""
    log = StepLog()
    for i in range(400):
        rec = log.open(i, float(i), 0, 0)
        rec[spans.SLOT["wait_us"]] = wait_us
        log.close(rec)
    first = log.unwritten()
    assert [r["step"] for r in first] == list(range(399, 398 - handed, -1))
    rec = log.open(400, 400.0, 0, 0)
    rec[spans.SLOT["wait_us"]] = wait_us
    log.close(rec)
    second = log.unwritten()
    assert second[0]["step"] == 400 and second[1]["step"] == 398 - handed
    assert spans._open.rec is None


def test_no_session_nothing_is_handed(params):
    eng = engine(params)
    serve(eng)
    assert eng.step_log._given is None and len(eng.step_log) == eng.iterations


# -- (f) the blocks the ragged paged decode kernel walks ----------------------


@pytest.mark.parametrize("attn,rows", [("kernel", 1), ("reference", 0)])
def test_attn_blocks_of_the_dense_family(params, walked_blocks, attn, rows):
    """llama's pages through the kernel: blocks of ``block_kv`` positions
    (the tuning table's; a page at this size), ``seq_len // block + 1`` a
    live stream; the gauge is the grid of every block a slot could hold.
    Gathered pages walk no kernel: 0."""
    eng = engine(params, attn_impl=attn, max_batch=3, max_prefill_per_step=3)
    assert "attn_blocks" in FIELDS
    assert eng.adapter.block_kv == 8
    walked_blocks(eng, (5, 9, 12), 6, 3 * (256 // 8), rows)
