"""Observability subsystem suite (fms_fsdp_tpu/obs/, docs/observability.md):
registry semantics, phase-timer math under a fake clock, goodput folding
in resilience skipped steps, JSONL/CSV sink schema round-trips, the
heartbeat contract, the schema-version digest guard, and an e2e CPU
smoke asserting a tiny fault-injected run writes a parseable
metrics.jsonl whose goodput reflects the skipped step — while the
ref-exact print report stays byte-identical in shape."""

import json
import os

import pytest

from fms_fsdp_tpu.obs.observer import Observer, build_observer
from fms_fsdp_tpu.obs.registry import MetricRegistry
from fms_fsdp_tpu.obs.schema import (
    SCHEMA_DIGESTS,
    SCHEMA_VERSION,
    schema_digest,
    validate_record,
)
from fms_fsdp_tpu.obs.sinks import (
    CSVSink,
    Heartbeat,
    JSONLSink,
    TrackerSink,
    build_sinks,
    read_heartbeat,
)
from fms_fsdp_tpu.obs.timing import GoodputTracker, PhaseTimer

TINY_OVERRIDES = {
    "LlamaConfig.nlayers": 2,
    "LlamaConfig.emb_dim": 64,
    "LlamaConfig.nheads": 4,
    "LlamaConfig.kvheads": 2,
    "LlamaConfig.src_vocab_size": 256,
    "LlamaConfig.multiple_of": 16,
    "LlamaConfig.max_expected_seq_len": 64,
}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


# ---- registry --------------------------------------------------------------


def test_registry_counter_cumulative_and_window():
    reg = MetricRegistry()
    reg.counter("c").add(2)
    reg.counter("c").add(3)
    snap = reg.snapshot()
    assert snap["c"] == 5 and snap["c_window"] == 5
    reg.counter("c").add(1)
    snap = reg.snapshot()
    assert snap["c"] == 6 and snap["c_window"] == 1
    # idempotent identity: counter(name) returns the same cell
    assert reg.counter("c") is reg.counter("c")


def test_registry_gauge_ewma_hist():
    reg = MetricRegistry()
    reg.gauge("g").set(7.5)
    reg.ewma("e", alpha=0.5).update(1.0)
    reg.ewma("e").update(3.0)
    for v in [1.0, 2.0, 3.0, 4.0]:
        reg.hist("h").record(v)
    snap = reg.snapshot()
    assert snap["g"] == 7.5
    assert snap["e"] == pytest.approx(2.0)  # 0.5*3 + 0.5*1
    assert snap["h_mean"] == pytest.approx(2.5)
    assert snap["h_max"] == 4.0
    # window cleared: next snapshot has no h stats
    assert "h_mean" not in reg.snapshot()
    # empty registry snapshots cleanly
    assert MetricRegistry().snapshot() == {}


# ---- phase timer (fake clock) ----------------------------------------------


def test_phase_timer_attribution_and_other():
    clk = FakeClock()
    t = PhaseTimer(clock=clk)
    with t.phase("data_wait"):
        clk.tick(2.0)
    with t.phase("compute"):
        clk.tick(5.0)
    clk.tick(3.0)  # unattributed -> other
    w = t.window()
    assert w["data_wait"] == pytest.approx(2.0)
    assert w["compute"] == pytest.approx(5.0)
    assert w["checkpoint"] == 0.0
    assert w["other"] == pytest.approx(3.0)
    assert w["wall"] == pytest.approx(10.0)
    # window reset: a fresh window starts from zero
    clk.tick(1.0)
    w2 = t.window()
    assert w2["compute"] == 0.0 and w2["wall"] == pytest.approx(1.0)


def test_phase_timer_nested_inner_wins():
    clk = FakeClock()
    t = PhaseTimer(clock=clk)
    with t.phase("compute"):
        clk.tick(1.0)
        with t.phase("checkpoint"):
            clk.tick(10.0)
        clk.tick(2.0)
    w = t.window()
    assert w["compute"] == pytest.approx(3.0)
    assert w["checkpoint"] == pytest.approx(10.0)
    assert w["wall"] == pytest.approx(13.0)


def test_phase_timer_record_direct():
    t = PhaseTimer(clock=FakeClock())
    t.record("data_wait", 1.25)
    assert t.window()["data_wait"] == pytest.approx(1.25)


# ---- goodput ---------------------------------------------------------------


def test_goodput_clean_window():
    g = GoodputTracker()
    win, overall = g.update(
        {"wall": 10.0, "compute": 8.0}, steps=4, skipped_steps=0
    )
    assert win == pytest.approx(0.8)
    assert overall == pytest.approx(0.8)


def test_goodput_folds_skipped_steps():
    g = GoodputTracker()
    # 4 steps, 1 skipped: only 3/4 of the compute time was productive
    win, _ = g.update({"wall": 10.0, "compute": 8.0}, steps=4, skipped_steps=1)
    assert win == pytest.approx(8.0 * 0.75 / 10.0)
    # cumulative: a later clean window lifts the overall number
    _, overall = g.update(
        {"wall": 10.0, "compute": 8.0}, steps=4, skipped_steps=0
    )
    assert overall == pytest.approx((6.0 + 8.0) / 20.0)


def test_goodput_zero_wall_no_crash():
    win, overall = GoodputTracker().update(
        {"wall": 0.0, "compute": 0.0}, steps=1
    )
    assert win == 0.0 and overall == 0.0


# ---- schema ----------------------------------------------------------------


def test_schema_digest_pins_version():
    """Changing SCHEMA_FIELDS without bumping SCHEMA_VERSION fails here
    (and in CI). To evolve the schema: bump the version, pin the new
    digest (printed below), document in docs/observability.md."""
    assert SCHEMA_VERSION in SCHEMA_DIGESTS, "pin a digest for this version"
    assert schema_digest() == SCHEMA_DIGESTS[SCHEMA_VERSION], (
        f"metric schema changed without a version bump; new digest: "
        f"{schema_digest()}"
    )


def test_validate_record_catches_violations():
    good = _observer_record()
    assert validate_record(good) == []
    bad = dict(good)
    bad.pop("goodput")
    assert any("goodput" in e for e in validate_record(bad))
    bad = dict(good, loss="high")
    assert any("loss" in e for e in validate_record(bad))
    bad = dict(good, surprise=1)
    assert any("surprise" in e for e in validate_record(bad))
    bad = dict(good, schema_version=SCHEMA_VERSION + 1)
    assert any("schema_version" in e for e in validate_record(bad))


def _observer_record(**kw):
    obs = Observer(clock=FakeClock(), strict_schema=True)
    args = dict(
        loss=2.5,
        tokens_per_sec_per_chip=1000.0,
        skipped_steps_total=0,
        skipped_steps_window=0,
    )
    args.update(kw)
    return obs.report(10, 4, **args)


def test_quant_modes_land_in_record():
    """schema v4: the step's quantization modes ride every record; a
    perf record must state the numerics that produced it. Built from
    config via build_observer, null when unset."""
    rec = _observer_record()
    assert rec["quantized_matmuls"] is None
    assert rec["quantized_reduce"] is None

    from fms_fsdp_tpu.obs import build_observer

    class Cfg:
        obs_dir = ""
        obs_sinks = ""
        kernel_tuning = "auto"
        quantized_matmuls = "int8_dgrad"
        quantized_reduce = "fp8_delayed"
        seq_length = 64

    obs = build_observer(Cfg(), rank=0, clock=FakeClock())
    rec = obs.report(
        10,
        4,
        loss=2.5,
        tokens_per_sec_per_chip=1000.0,
        skipped_steps_total=0,
        skipped_steps_window=0,
    )
    assert rec["quantized_matmuls"] == "int8_dgrad"
    assert rec["quantized_reduce"] == "fp8_delayed"
    assert validate_record(rec) == []


def test_checkpoint_stats_provider_feeds_record():
    """schema v2: the async checkpoint manager's stats provider fills
    checkpoint_bg_s / checkpoint_in_flight; without a provider both
    default to zero (plain synchronous Checkpointer)."""
    rec = _observer_record()
    assert rec["checkpoint_bg_s"] == 0.0
    assert rec["checkpoint_in_flight"] == 0

    obs = Observer(clock=FakeClock(), strict_schema=True)
    obs.attach_checkpoint_stats(lambda: {"bg_s": 3.5, "in_flight": 1})
    rec = obs.report(
        10,
        4,
        loss=2.5,
        tokens_per_sec_per_chip=1000.0,
        skipped_steps_total=0,
        skipped_steps_window=0,
    )
    assert rec["checkpoint_bg_s"] == pytest.approx(3.5)
    assert rec["checkpoint_in_flight"] == 1
    assert validate_record(rec) == []


# ---- observer --------------------------------------------------------------


def test_observer_report_derives_mfu_and_goodput():
    clk = FakeClock()
    obs = Observer(
        clock=clk,
        flops_per_token=100.0,
        hfu_flops_per_token=120.0,
        peak_flops=1e6,
        strict_schema=True,
    )
    with obs.phase("compute"):
        clk.tick(8.0)
    clk.tick(2.0)
    rec = obs.report(
        5,
        4,
        loss=2.0,
        tokens_per_sec_per_chip=5000.0,
        skipped_steps_total=1,
        skipped_steps_window=1,
    )
    assert validate_record(rec) == []
    assert rec["mfu"] == pytest.approx(0.5)
    assert rec["hfu"] == pytest.approx(0.6)
    assert rec["goodput"] == pytest.approx(8.0 * 0.75 / 10.0)
    assert rec["wall_s"] == pytest.approx(10.0)
    assert rec["skipped_steps"] == 1


def test_observer_wrap_data_iter_times_waits():
    clk = FakeClock()
    obs = Observer(clock=clk)

    def gen():
        for i in range(3):
            clk.tick(1.0)  # "the pipeline is slow"
            yield i

    assert list(obs.wrap_data_iter(gen())) == [0, 1, 2]
    assert obs.timer.window()["data_wait"] == pytest.approx(3.0)


def test_observer_registry_lands_in_extra(tmp_path):
    obs = Observer(
        sinks=[JSONLSink(str(tmp_path / "m.jsonl"))], clock=FakeClock()
    )
    obs.registry.counter("feed.batches").add(7)
    obs.report(
        1, 1, loss=1.0, tokens_per_sec_per_chip=1.0,
        skipped_steps_total=0, skipped_steps_window=0,
    )
    rec = json.loads((tmp_path / "m.jsonl").read_text())
    assert rec["extra"]["feed.batches"] == 7


def test_observer_nonfinite_window_emits_null_not_nan(tmp_path):
    """A fully-poisoned window (NaN loss/gnorm) must serialize as null —
    a bare NaN token would make the JSONL line unparseable by strict
    parsers exactly in the fault window the record exists to capture."""
    obs = Observer(
        sinks=[JSONLSink(str(tmp_path / "m.jsonl"))],
        clock=FakeClock(),
        strict_schema=True,
    )
    obs.registry.gauge("bad").set(float("inf"))
    rec = obs.report(
        2, 2,
        loss=float("nan"),
        grad_norm=float("nan"),
        tokens_per_sec_per_chip=100.0,
        skipped_steps_total=2,
        skipped_steps_window=2,
    )
    assert rec["loss"] is None and rec["grad_norm"] is None
    assert rec["extra"]["bad"] is None
    line = (tmp_path / "m.jsonl").read_text()
    assert "NaN" not in line and "Infinity" not in line
    parsed = json.loads(line)  # strict parse round-trips
    assert validate_record(parsed) == []
    assert parsed["skipped_steps_window"] == 2


# ---- sinks -----------------------------------------------------------------


def test_jsonl_sink_roundtrip_validates(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    sink = JSONLSink(path)
    for step in (2, 4):
        sink.emit(_observer_record())
    sink.close()
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    for ln in lines:
        assert validate_record(json.loads(ln)) == []


def test_csv_sink_columns_and_append(tmp_path):
    path = str(tmp_path / "metrics.csv")
    sink = CSVSink(path)
    sink.emit(_observer_record())
    sink.emit(_observer_record())
    sink.close()
    lines = open(path).read().splitlines()
    assert lines[0].startswith("schema_version,step,")
    assert "extra" not in lines[0]
    assert len(lines) == 3
    # append after reopen: no duplicate header
    sink2 = CSVSink(path)
    sink2.emit(_observer_record())
    sink2.close()
    assert len(open(path).read().splitlines()) == 4


def test_tracker_sink_emits_legacy_keys():
    logged = []
    TrackerSink(lambda d, step: logged.append((d, step))).emit(
        _observer_record()
    )
    (payload, step), = logged
    assert step == 10
    # the exact key names the pre-obs loop logged (dashboards key on them)
    for key in (
        "learning rate", "loss", "gradient norm", "token seen",
        "current throughput (token per chip per sec)",
        "overall throughput (token per chip per sec)",
        "chip reserved memory", "chip allocated memory", "skipped batches",
    ):
        assert key in payload, key


def test_tracker_sink_disables_on_backend_error():
    """A raising tracker backend (finished wandb run, aim db error) must
    disable the sink, never propagate into the hot loop."""
    calls = []

    def flaky(d, step):
        calls.append(step)
        raise RuntimeError("wandb run finished")

    sink = TrackerSink(flaky)
    sink.emit(_observer_record())  # must not raise
    assert sink._broken
    sink.emit(_observer_record())  # disabled: backend not called again
    assert len(calls) == 1


def test_heartbeat_contract(tmp_path):
    path = str(tmp_path / "hb" / "heartbeat.json")
    Heartbeat(path).beat(42, 1234.5, 0.875)
    hb = read_heartbeat(path)
    assert hb == {
        "step": 42,
        "time_unix": 1234.5,
        "goodput": 0.875,
        "schema_version": SCHEMA_VERSION,
    }
    assert read_heartbeat(str(tmp_path / "nope.json")) is None


def test_sink_io_error_disables_not_raises(tmp_path, monkeypatch):
    sink = JSONLSink(str(tmp_path / "m.jsonl"))
    sink.emit(_observer_record())

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(sink._f, "write", boom)
    sink.emit(_observer_record())  # must not raise
    assert sink._broken
    sink.emit(_observer_record())  # still silent


def test_build_sinks_unknown_name_raises(tmp_path):
    with pytest.raises(ValueError, match="unknown obs sink"):
        build_sinks(str(tmp_path), ["jsonl", "speedometer"])
    # jsonl/csv need a dir; tracker needs a fn — silently absent otherwise
    assert build_sinks("", ["jsonl", "csv", "tracker"]) == []


def test_build_observer_rank_gating(tmp_path):
    from fms_fsdp_tpu.config import TrainConfig

    cfg = TrainConfig(obs_dir=str(tmp_path / "obs"), obs_sinks="jsonl,csv")
    obs0 = build_observer(cfg, rank=0)
    obs1 = build_observer(cfg, rank=1)
    assert len(obs0.sinks) == 2 and obs0.heartbeat is not None
    assert obs1.sinks == [] and obs1.heartbeat is None


def test_build_observer_flops_model():
    from fms_fsdp_tpu.config import TrainConfig
    from fms_fsdp_tpu.utils.config_utils import get_model_config

    model_cfg = get_model_config("llama3_194m_4k")
    cfg = TrainConfig(
        seq_length=128,
        fsdp_activation_checkpointing=True,
        selective_checkpointing=0.5,
    )
    obs = build_observer(cfg, rank=0, model_cfg=model_cfg)
    assert obs.flops_per_token
    # no TPU, no peak: a CPU record carries no MFU against a chip's peak
    assert obs.peak_flops is None
    assert obs.device["platform"] == "cpu" and obs.device["count"] == 8
    # HFU numerator counts the recompute: strictly above the MFU one
    assert obs.hfu_flops_per_token > obs.flops_per_token


def test_device_feed_finite_loader_terminates():
    """A finite loader behind a prefetching DeviceFeed must end the
    consumer's iteration (sentinel on clean exhaustion), not leave it
    blocked in q.get() forever — and the feed counters land in the
    registry."""
    import numpy as np

    from fms_fsdp_tpu.data.device_feed import DeviceFeed
    from fms_fsdp_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(sharding_strategy="fsdp"))
    reg = MetricRegistry()
    loader = iter([np.zeros((2, 8), np.int32)] * 3)
    feed = DeviceFeed(loader, mesh, prefetch=2, registry=reg)
    batches = list(feed)  # hangs without the StopIteration sentinel
    assert len(batches) == 3
    assert reg.snapshot()["feed.batches"] == 3


# ---- watchdog x heartbeat --------------------------------------------------


def test_watchdog_stall_report_quotes_heartbeat(tmp_path):
    """A stalled run's watchdog post-mortem includes the last heartbeat
    (how far the run got, how healthy it was) before exiting 2 — and
    every report line carries the host's process index (passed in at
    construction, never fetched from jax on the wedged-process path) so
    merged multi-host logs attribute WHICH host's stacks follow."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hb_path = str(tmp_path / "heartbeat.json")
    script = (
        "import time, sys\n"
        "sys.path.insert(0, %r)\n"
        "from fms_fsdp_tpu.obs.sinks import Heartbeat\n"
        "from fms_fsdp_tpu.resilience.guards import StepWatchdog\n"
        "Heartbeat(%r).beat(123, 99.0, 0.5)\n"
        "w = StepWatchdog(0.5, heartbeat_path=%r, process_index=3).start()\n"
        "w.beat()\n"
        "time.sleep(30)\n"
    ) % (repo, hb_path, hb_path)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 2, (proc.returncode, proc.stderr[-1000:])
    assert "step watchdog [proc 3]: no training progress" in proc.stderr, (
        proc.stderr[-1000:]
    )
    assert "step watchdog [proc 3]: last heartbeat" in proc.stderr, (
        proc.stderr[-1000:]
    )
    assert "'step': 123" in proc.stderr, proc.stderr[-1000:]


# ---- hot-loop accounting (drives _train_loop with fakes) -------------------


class _CaptureSink:
    def __init__(self):
        self.records = []

    def emit(self, rec):
        self.records.append(rec)

    def close(self):
        pass


class _FakeCheckpointer:
    observer = None

    def __init__(self):
        self.saves = []

    def save(self, step, state, dataloader=None, reason="interval", **md):
        self.saves.append((step, reason, md))

    def finalize(self):
        pass


def _drive_loop(
    num_steps,
    report_interval,
    nonfinite_steps=(),
    start_step=0,
    step_sleep=0.0,
    checkpoint_interval=10**9,
):
    """Run the real _train_loop over a fake step_fn/loader/checkpointer;
    metrics are host floats so the report-time device_get is a no-op."""
    import time as _time

    from fms_fsdp_tpu.config import TrainConfig
    from fms_fsdp_tpu.utils.train_utils import _train_loop

    cfg = TrainConfig(
        num_steps=num_steps,
        report_interval=report_interval,
        checkpoint_interval=checkpoint_interval,
        batch_size=2,
        seq_length=8,
        step_timeout_s=0,
    )
    cap = _CaptureSink()
    obs = Observer(sinks=[cap])
    ck = _FakeCheckpointer()

    def step_fn(state, batch):
        if step_sleep:
            _time.sleep(step_sleep)
        i = state["i"] + 1
        bad = i in nonfinite_steps
        return dict(state, i=i), {
            "loss": float("nan") if bad else 2.0 + i * 0.01,
            "gnorm": float("nan") if bad else 1.0,
            "lr": 0.1,
            "nonfinite": 1.0 if bad else 0.0,
        }

    loss = _train_loop(
        cfg,
        {"i": start_step},
        step_fn,
        0,
        iter(int, 1),  # infinite stream of dummy batches
        None,
        ck,
        start_step,
        0,
        obs,
        1,
    )
    return loss, cap.records, ck


def test_train_loop_partial_window_rates_use_true_step_count():
    """A resume's first report window is partial (len(fetched) <
    report_interval): the record's step_time_s / throughput must divide
    by the TRUE step count, not the configured interval — else a resume
    inflates the persistent throughput/MFU record 2x here."""
    per_step = 0.05
    loss, records, _ = _drive_loop(
        num_steps=4, report_interval=4, start_step=2, step_sleep=per_step
    )
    assert [r["step"] for r in records] == [4]
    rec = records[0]
    # two steps of >= 50ms each: a report_interval divisor would halve it
    assert rec["step_time_s"] >= per_step * 0.9, rec["step_time_s"]
    # rate and step time stay algebraically consistent with batch tokens
    assert rec["tokens_per_sec_per_chip"] * rec["step_time_s"] == pytest.approx(
        2 * 8
    )


def test_train_loop_drains_tail_window_on_exit():
    """num_steps lands mid-report-window: the tail steps' non-finite
    flags must still reach the guard (skipped_steps in the final record)
    and the final save's metadata — not vanish with the undrained
    window."""
    loss, records, ck = _drive_loop(
        num_steps=6, report_interval=4, nonfinite_steps={6}
    )
    assert [r["step"] for r in records] == [4, 6]
    tail = records[-1]
    assert tail["skipped_steps_window"] == 1
    assert tail["skipped_steps"] == 1
    # the drained window still carries its clean step's loss
    assert tail["loss"] == pytest.approx(2.0 + 5 * 0.01)
    # the final save's metadata records the guard's totals
    steps = [s for s in ck.saves if s[1] == "final"]
    assert steps and steps[-1][2]["skipped_steps"] == 1
    # exact tokens at the save step, not the last report's stale figure
    assert steps[-1][2]["tokens_seen"] == 6 * 2 * 8


def test_train_loop_poisoned_window_carries_last_clean_loss(capsys):
    """Every step of a window non-finite: the window is reported as
    poisoned — the record's loss is null (never NaN into sinks), the
    print stream carries the last clean loss, and the returned loss is
    the carried one."""
    loss, records, _ = _drive_loop(
        num_steps=4, report_interval=2, nonfinite_steps={3, 4}
    )
    out = capsys.readouterr().out
    assert "report window poisoned: all 2 step(s) non-finite" in out
    clean, poisoned = records
    assert clean["loss"] is not None
    assert poisoned["loss"] is None
    assert poisoned["grad_norm"] is None
    assert poisoned["skipped_steps_window"] == 2
    assert poisoned["extra"].get("window_poisoned") == 1
    # carried: the last clean window's mean, also the returned loss
    assert loss == pytest.approx(clean["loss"])


# ---- e2e CPU smoke ---------------------------------------------------------


@pytest.mark.slow
def test_e2e_metrics_jsonl_with_injected_skip(tmp_path, capsys):
    """Tiny fault-injected llama run: every metrics.jsonl line validates
    against the documented schema, carries loss / tokens-per-sec / MFU /
    data-wait fraction / goodput, the skipped step depresses its
    window's goodput, the heartbeat tracks the last step, and the
    ref-exact print lines keep their exact shape."""
    import main_training_llama

    obs_dir = tmp_path / "obs"
    main_training_llama.main(
        use_dummy_dataset=True,
        num_steps=6,
        seq_length=32,
        batch_size=2,
        report_interval=2,
        checkpoint_interval=100,
        vocab_size=256,
        sharding_strategy="fsdp",
        attention_kernel="xla",
        ckpt_save_path=str(tmp_path),
        ckpt_load_path=str(tmp_path),
        obs_dir=str(obs_dir),
        obs_sinks="jsonl,csv",
        obs_strict_schema=True,
        faults="nan_loss:step=2:count=1",
        **TINY_OVERRIDES,
    )
    out = capsys.readouterr().out

    records = [
        json.loads(ln)
        for ln in (obs_dir / "metrics.jsonl").read_text().splitlines()
    ]
    assert len(records) == 3  # 6 steps / report_interval 2
    for rec in records:
        assert validate_record(rec) == [], rec
        for field in (
            "loss", "tokens_per_sec_per_chip",
            "data_wait_frac", "goodput",
        ):
            assert rec[field] is not None
        # v16: every record names its device; off a TPU there is no peak
        # to divide by, so MFU is null rather than a share of a v5e
        assert rec["device_platform"] == "cpu"
        assert rec["device_kind"] and rec["device_count"] == 8
        assert rec["mfu"] is None and rec["hfu"] is None
    # the injected NaN batch (device step counter 2 -> trainer step 3,
    # the second report window) is folded into that window's accounting
    assert records[0]["skipped_steps_window"] == 0
    assert records[1]["skipped_steps_window"] == 1
    assert records[1]["skipped_steps"] == 1
    assert records[-1]["skipped_steps"] == 1
    # goodput < 1 and consistent with its own phase decomposition: the
    # skipped step halves the window's productive compute time
    w = records[1]
    assert 0.0 <= w["goodput"] <= 1.0
    expected = (w["compute_s"] * (2 - 1) / 2) / w["wall_s"]
    assert w["goodput"] == pytest.approx(expected, rel=1e-6)
    clean = records[0]
    assert clean["goodput"] == pytest.approx(
        clean["compute_s"] / clean["wall_s"], rel=1e-6
    )

    # heartbeat tracks the last report step
    hb = read_heartbeat(str(obs_dir / "heartbeat.json"))
    assert hb["step"] == 6
    assert hb["goodput"] == pytest.approx(records[-1]["goodput"])

    # CSV summary has header + one row per report
    assert len((obs_dir / "metrics.csv").read_text().splitlines()) == 4

    # ref-exact print report: same labels, same order, every window
    labels = [
        "step:", "loss:", "LR:", "tokens seen:", "gradient norm:",
        "reserved memory:", "allocated memory:", "current step time:",
        "overall step time:", "current token per chip per sec:",
        "overall token per chip per sec:", "overall token per day:",
    ]
    printed = [
        ln for ln in out.splitlines()
        if any(ln.startswith(lbl) for lbl in labels)
    ]
    assert len(printed) == 3 * len(labels), out[-2000:]
    assert "skipped batches: 1" in out
    # no obs chatter leaked into the report stream: no line *starts*
    # with an unknown label (the obs layer prints nothing of its own)
    known = tuple(labels) + (
        "-->", "Sharding strategy", "Constructing", "Datasets", "No valid",
        "Training for", "skipped batches:", "Checkpoint saved",
        "model_save_time",
    )
    for ln in out.splitlines():
        if ln.strip():
            assert ln.startswith(known), f"unexpected output line: {ln!r}"


@pytest.mark.slow
def test_e2e_observer_absent_obs_dir_writes_nothing(tmp_path, capsys):
    """Default config (obs_dir=""): no metrics files appear anywhere and
    the run is byte-compatible with the pre-obs loop."""
    import main_training_llama

    main_training_llama.main(
        use_dummy_dataset=True,
        num_steps=2,
        seq_length=32,
        batch_size=2,
        report_interval=2,
        checkpoint_interval=100,
        vocab_size=256,
        sharding_strategy="fsdp",
        attention_kernel="xla",
        ckpt_save_path=str(tmp_path),
        ckpt_load_path=str(tmp_path),
        **TINY_OVERRIDES,
    )
    capsys.readouterr()
    found = [
        os.path.join(r, f)
        for r, _, fs in os.walk(tmp_path)
        for f in fs
        if f in ("metrics.jsonl", "metrics.csv", "heartbeat.json")
    ]
    assert found == []


def test_v5_collective_split_defaults_zero():
    """schema v5: single-slice runs (no probe attached) report 0.0 for
    both collective-split fields — and the record still validates."""
    rec = _observer_record()
    assert rec["ici_collective_s"] == 0.0
    assert rec["dcn_collective_s"] == 0.0
    assert validate_record(rec) == []


def test_collective_probe_fills_v5_split():
    """On a multi-slice mesh the report-cadence probe (obs/collectives)
    times a real within-slice and a real cross-slice reduction into the
    v5 fields; on a single-slice mesh no probe exists at all."""
    from fms_fsdp_tpu.obs.collectives import make_collective_split_probe
    from fms_fsdp_tpu.parallel.mesh import MeshConfig, build_mesh

    single = build_mesh(MeshConfig(sharding_strategy="fsdp"))
    obs = Observer(strict_schema=True)
    assert make_collective_split_probe(single, obs.timer) is None

    mesh = build_mesh(MeshConfig(sharding_strategy="fsdp", num_slices=2))
    probe = make_collective_split_probe(mesh, obs.timer)
    assert probe is not None
    obs.attach_collective_probe(probe)
    rec = obs.report(
        10,
        4,
        loss=2.5,
        tokens_per_sec_per_chip=1000.0,
        skipped_steps_total=0,
        skipped_steps_window=0,
    )
    assert rec["ici_collective_s"] > 0.0, rec
    assert rec["dcn_collective_s"] > 0.0, rec
    assert validate_record(rec) == []
