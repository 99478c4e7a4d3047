"""Host-side training loop, distributed setup, profiler, and trackers.

The loop keeps the reference's observable behavior
(ref:fms_fsdp/utils/train_utils.py:21-180): report cadence and metric
names/semantics (loss, LR, gradient norm, tokens seen, memory,
current/overall tokens-per-chip-per-sec, tokens-per-day), checkpoint
cadence, resume semantics. TPU differences:

- fwd/loss/bwd/clip/update is ONE jitted ``step_fn``; metric scalars stay
  on device and are fetched only at report time, so the host never forces a
  sync inside the hot window (XLA dispatch stays ahead of the device);
- no explicit all_reduce of stats: loss/gnorm come out of the step already
  globally reduced (jit over global arrays);
- memory stats come from ``device.memory_stats()`` instead of CUDA.
"""

import os
import signal
import time
from contextlib import nullcontext as _nullctx
from dataclasses import asdict

import jax


def setup():
    """Join the multi-host JAX world (NCCL-process-group analog,
    ref:train_utils.py:183-184). Initializes on any multi-host signal:
    an explicit coordinator, a multi-worker TPU pod env, or NUM_PROCESSES.
    No-op on single-host runs (Orbax's multi-process commit protocol is
    only needed — and only engaged — when process_count > 1).

    Every entry point comes through here first, so this is also where
    JAX's persistent compilation cache is placed
    (utils/compile_cache.py)."""
    from fms_fsdp_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    multihost = (
        os.environ.get("COORDINATOR_ADDRESS")
        or int(os.environ.get("NUM_PROCESSES", "1")) > 1
        or len([h for h in hostnames.split(",") if h.strip()]) > 1
        or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
        # Slurm launch (scripts/train.slurm): jax.distributed auto-detects
        # the coordinator/process-index from the Slurm env. SLURM_PROCID
        # gates on actually being inside an srun step — a bare `python`
        # inside a multi-task allocation inherits SLURM_NTASKS but is a
        # single process and must stay single-host.
        or (
            "SLURM_PROCID" in os.environ
            and int(os.environ.get("SLURM_NTASKS", "1")) > 1
        )
    )
    if multihost:
        coord = os.environ.get("COORDINATOR_ADDRESS")
        if coord and "NUM_PROCESSES" in os.environ:
            # explicit env-driven init (torch env:// analog: MASTER_ADDR/
            # WORLD_SIZE/RANK -> COORDINATOR_ADDRESS/NUM_PROCESSES/
            # PROCESS_ID). jax's argless auto-detect only covers managed
            # launchers (Slurm/OMPI/TPU pods/K8s) — a hand-launched or
            # custom-orchestrated world must pass the triple explicitly.
            if os.environ.get("JAX_PLATFORMS", "") == "cpu":
                # cross-process collectives on CPU need a real backend;
                # gloo is the XLA:CPU implementation (tested by
                # tests/test_multiprocess.py on a 2-process world)
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo"
                )
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(os.environ["NUM_PROCESSES"]),
                process_id=int(os.environ["PROCESS_ID"]),
            )
        else:
            jax.distributed.initialize()
    # slice-aware init (docs/train_details.md "Multi-slice"): surface
    # the detected fault domain once the world is up — slice index/count
    # come from device attributes on real multislice hardware, the
    # MEGASCALE env on older stacks, or the FMS_SIM_SLICES gloo
    # simulation knob in tests (parallel/mesh.py). Purely informational
    # here; the mesh builder and train loop re-derive the same facts.
    try:
        from fms_fsdp_tpu.parallel.mesh import process_slice_context

        n_slices, slice_idx = process_slice_context()
        if n_slices > 1:
            print(
                f"--> multi-slice world: slice {slice_idx} of {n_slices} "
                f"(process {jax.process_index()} of {jax.process_count()})"
            )
    except Exception:  # noqa: BLE001 — a detection hiccup must not block init
        pass


def setup_environ_flags():
    """Fail-loudly flags (ref:train_utils.py:187-189 analog)."""
    os.environ.setdefault("JAX_TRACEBACK_FILTERING", "off")


class DeliberateAbort(RuntimeError):
    """An abort the train loop raised ON PURPOSE (anomaly guard).

    The multi-slice exception classifier must not hold these for a
    liveness verdict: a whole-world deliberate abort would otherwise
    wait out slice_timeout_s on every rank and — with the other slice's
    processes already gone — be re-reported as a lost slice, sending the
    operator to a fault-domain restart for what is really a data/NaN
    problem. (Transport errors from a genuinely dead slice arrive as
    XlaRuntimeError/etc., never as this type.)"""


def get_tracker(cfg, rank: int):
    """Optional wandb/aim tracker (ref:train_utils.py:34-73). Returns a
    log_fn(dict, step) or None."""
    if not cfg.tracker:
        return None
    if cfg.tracker not in ["wandb", "aim"]:
        raise ValueError(f"tracker {cfg.tracker} not supported.")
    if rank != 0:
        return None
    if cfg.tracker == "wandb":
        try:
            import wandb
        except ImportError:
            raise ImportError("tracker is set to wandb but wandb is not installed.")
        print("--> wandb is enabled!")
        wandb.init(
            project=cfg.tracker_project_name,
            dir=cfg.tracker_dir,
            resume="allow",
            id=cfg.tracker_run_id,
        )
        wandb.config = asdict(cfg)
        return wandb.log
    try:
        from aim import Run
    except ImportError:
        raise ImportError("tracker is set to aim but aim is not installed.")
    print("--> aim is enabled!")
    run = Run(
        experiment=cfg.tracker_project_name,
        repo=cfg.tracker_dir,
        run_hash=cfg.tracker_run_id,
    )
    run["hparams"] = asdict(cfg)
    return run.track


class WindowedProfiler:
    """jax.profiler trace with the reference's windowing — skip ``wait``
    steps, ``warmup`` more, capture ``active`` steps, once
    (ref:train_utils.py:256-271: wait=1, warmup=2, active=3, repeat=1),
    writing a TensorBoard-compatible XPlane trace to ``logdir``."""

    def __init__(self, logdir="profile_traces", wait=1, warmup=2, active=3):
        self.logdir = logdir
        self.start_at = wait + warmup
        self.stop_at = wait + warmup + active
        self.count = 0
        self._running = False

    def step(self):
        self.count += 1
        if self.count == self.start_at and not self._running:
            jax.profiler.start_trace(self.logdir)
            self._running = True
        elif self.count == self.stop_at and self._running:
            jax.profiler.stop_trace()
            self._running = False

    def close(self):
        """Finalize a trace left open by an early loop exit — an unflushed
        XPlane buffer writes no usable profile."""
        if self._running:
            jax.profiler.stop_trace()
            self._running = False


def get_profiler(cfg, rank: int):
    if not cfg.use_profiler:
        return None
    if cfg.profiler_rank0_only and rank != 0:
        return None
    return WindowedProfiler()


def _memory_stats():
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0), stats.get("bytes_in_use", 0)


class PreemptionGuard:
    """SIGTERM -> checkpoint at the next step boundary, then exit clean.

    TPU capacity is commonly preemptible (spot/queued resources send
    SIGTERM with a grace window before teardown); the reference's story
    is restart-based resume from the last *interval* checkpoint, which
    loses up to checkpoint_interval steps. The guard converts the grace
    window into an up-to-date checkpoint.

    Multi-host note: the Orbax save is collective, so every process must
    enter it at the same step. ``poll()`` makes the trigger itself
    collective: each boundary, every rank contributes its local flag to a
    tiny jitted global max over all devices, and the boundary's decision
    reads the collective result dispatched one boundary earlier — so a
    rank that never received SIGTERM (delivery straddling a boundary, or
    a scheduler that signals only one rank) still saves at the same step
    as the rank that did. The one-boundary pipeline delay keeps the fetch
    non-blocking in steady state (the collective finished during the
    step) at the cost of saving one step after the signal — well inside
    any real grace window. Single-process worlds skip the collective
    entirely and see the flag at the boundary it arrived.
    """

    def __init__(self):
        self.triggered = False
        self._prev = None
        self._dispatch = None
        self._inflight = None

    def install(self):
        def handler(signum, frame):
            self.triggered = True
            if self._prev not in (None, signal.SIG_DFL, signal.SIG_IGN):
                self._prev(signum, frame)

        try:
            self._prev = signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # not the main thread (tests, embedded use): no-op
        return self

    def _make_dispatch(self):
        import numpy as np
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(jax.devices()), ("all",))
        sharding = NamedSharding(mesh, PartitionSpec("all"))
        n_local = len(jax.local_devices())
        _max = jax.jit(jnp.max)

        def dispatch(flag: bool):
            local = np.full((n_local,), 1 if flag else 0, dtype=np.int32)
            garr = jax.make_array_from_process_local_data(sharding, local)
            return _max(garr)

        return dispatch

    def poll(self) -> bool:
        """Call exactly once per step boundary on every rank. Returns the
        globally-agreed flag (identical on all ranks at the same step)."""
        if jax.process_count() == 1:
            return self.triggered
        if self._dispatch is None:
            self._dispatch = self._make_dispatch()
        agreed = bool(self._inflight) if self._inflight is not None else False
        self._inflight = self._dispatch(self.triggered)
        return agreed


def _mix_record(observer, dataloader):
    """Per-corpus data-mix accounting for the report record (obs schema
    v7 ``data_mix``): drains the SamplingDataset's buffered lifecycle
    events into the registry (data.corpus_quarantined / corpus_rearmed
    counters) and reads realized-vs-target token shares from the live
    loader. None when the run carries no mixing layer (dummy data,
    process-mode workers)."""
    from fms_fsdp_tpu.data.loader import loader_mix_stats
    from fms_fsdp_tpu.data.streaming import drain_mix_events

    for name, n in drain_mix_events().items():
        if n:
            observer.registry.counter(f"data.{name}").add(n)
    mix = loader_mix_stats(dataloader) if dataloader is not None else None
    if mix is None:
        return None
    total = sum(mix["tokens"].values())
    record = {}
    for corpus, tokens in mix["tokens"].items():
        observer.registry.gauge(f"data.mix.{corpus}.tokens_seen").set(tokens)
        record[f"{corpus}.tokens_seen"] = tokens
        record[f"{corpus}.target_share"] = round(
            mix["weights"].get(corpus, 0.0), 6
        )
        record[f"{corpus}.realized_share"] = (
            round(tokens / total, 6) if total else 0.0
        )
        record[f"{corpus}.quarantined"] = (
            1 if corpus in mix["quarantined"] else 0
        )
    return record


def train(
    cfg,
    state,
    step_fn,
    rank,
    train_loader,
    profiler,
    checkpointer,
    start_step,
    tokens_seen,
    dataloader=None,
    model_cfg=None,
    observer=None,
):
    """Run the hot loop to cfg.num_steps. Returns the final reported loss.

    ``dataloader`` is the stateful loader behind ``train_loader`` (which
    is typically a rebatch/DeviceFeed iterator over it): when provided,
    interval/final/preemption checkpoints persist the live loader state
    into the same ``step_N_ckp`` dir as the model, so a resume continues
    the data stream instead of relying on the loader's own auto-save
    clock (which can drift from trainer steps).

    ``observer`` (obs/) carries the metrics registry, phase timing, and
    sinks; built here from ``cfg`` (and ``model_cfg``, for the MFU FLOPs
    model) when the entry point didn't pass one. The legacy wandb/aim
    tracker attaches to it as one sink among several."""
    tracker_fn = get_tracker(cfg, rank)
    from fms_fsdp_tpu.obs import build_observer
    from fms_fsdp_tpu.obs.sinks import TrackerSink

    if observer is None:
        observer = build_observer(
            cfg, rank, model_cfg=model_cfg, tracker_fn=tracker_fn
        )
    elif tracker_fn is not None:
        observer.sinks.append(TrackerSink(tracker_fn))

    world_size = (
        jax.device_count()
        // max(1, getattr(cfg, "tensor_parallel_size", 1))
        // max(1, getattr(cfg, "context_parallel_size", 1))
    )

    try:
        train_loss = _train_loop(
            cfg,
            state,
            step_fn,
            rank,
            train_loader,
            profiler,
            checkpointer,
            start_step,
            tokens_seen,
            observer,
            world_size,
            dataloader,
        )
    finally:
        if profiler:
            profiler.close()
        try:
            # mandatory on loop exit/preemption (ckpt/manager.py):
            # joins the in-flight background writer so the final save
            # is never torn by process exit, and surfaces any writer
            # error the loop hadn't hit yet (no-op on the synchronous
            # Checkpointer)
            checkpointer.finalize()
        finally:
            observer.close()
    return train_loss


def _train_loop(
    cfg,
    state,
    step_fn,
    rank,
    train_loader,
    profiler,
    checkpointer,
    start_step,
    tokens_seen,
    observer,
    world_size,
    dataloader=None,
):
    from fms_fsdp_tpu.parallel.mesh import process_slice_context
    from fms_fsdp_tpu.resilience import divergence as _divergence
    from fms_fsdp_tpu.resilience import scrub as _scrub
    from fms_fsdp_tpu.resilience.divergence import StateDivergenceError
    from fms_fsdp_tpu.resilience.faults import fire_fault
    from fms_fsdp_tpu.resilience.guards import AnomalyGuard, StepWatchdog
    from fms_fsdp_tpu.resilience.integrity import drain_integrity_events
    from fms_fsdp_tpu.resilience.slices import (
        SliceHealthMonitor,
        SliceLostError,
    )
    from fms_fsdp_tpu.train.step import wrap_step_fn

    window = []
    train_loss = -1.0
    g_norm = -1.0
    start = time.time()
    loop_start = time.time()
    batch_idx = start_step
    preemption = PreemptionGuard().install()
    guard = AnomalyGuard(
        max_consecutive=max(1, getattr(cfg, "anomaly_max_consecutive", 8))
    )
    # multi-slice fault domains (docs/resilience.md): slice context for
    # guard tagging + the slice health monitor; (1, 0) on single-slice
    # worlds, where every slice-aware path below is inert
    n_slices, slice_idx = process_slice_context(cfg)
    slice_tag = f"[proc {rank} slice {slice_idx}] " if n_slices > 1 else ""
    watchdog = None
    timeout_s = float(getattr(cfg, "step_timeout_s", 0.0) or 0.0)
    if timeout_s > 0:
        hb = observer.heartbeat.path if observer.heartbeat else None
        # rank (== jax.process_index() in the entries) is passed in so a
        # multi-host stall report names its host without the wedged
        # process having to touch jax from the watchdog thread; the
        # slice index rides along on multi-slice worlds so stall triage
        # names the fault domain directly
        watchdog = StepWatchdog(
            timeout_s,
            heartbeat_path=hb,
            process_index=rank,
            slice_index=slice_idx if n_slices > 1 else None,
        ).start()
    monitor = None
    if n_slices > 1:
        hb_dir = str(getattr(cfg, "slice_heartbeat_dir", "") or "")
        if not hb_dir and getattr(cfg, "obs_dir", ""):
            hb_dir = os.path.join(cfg.obs_dir, "slice_health")
        slice_timeout = float(getattr(cfg, "slice_timeout_s", 0.0) or 0.0)
        if hb_dir and slice_timeout > 0:
            monitor = SliceHealthMonitor(
                hb_dir, n_slices, slice_idx, rank, slice_timeout
            ).start()

    # phase instrumentation: data_wait at the loop's next(), compute at
    # step dispatch + the report-time fetch, checkpoint inside save()
    train_loader = observer.wrap_data_iter(train_loader)
    step_fn = wrap_step_fn(step_fn, observer.timer)
    checkpointer.observer = observer

    # state-integrity layer (docs/checkpointing.md "State integrity"):
    # the background scrubber re-verifies committed checkpoints across
    # all tiers at scrub_interval_steps cadence (rank 0 — sidecars on
    # shared storage need a single writer), and the cross-replica
    # divergence compare runs at report boundaries every
    # divergence_check_interval steps on multi-process worlds
    scrubber = None
    scrub_interval = int(getattr(cfg, "scrub_interval_steps", 0) or 0)
    if scrub_interval > 0 and rank == 0:
        roots = _scrub.scrub_roots(checkpointer)
        if roots:
            scrubber = _scrub.CheckpointScrubber(roots, scrub_interval)
    divergence_interval = int(
        getattr(cfg, "divergence_check_interval", 0) or 0
    )
    if jax.process_count() == 1:
        divergence_interval = 0  # nothing to compare against
    last_divergence_check = start_step

    def _integrity_stats():
        # drained at report cadence on the main thread: the scrubber
        # thread and every verify buffered into integrity's event
        # window; detections become registry counters so they land in
        # this record's extras (obs schema v8)
        ev = drain_integrity_events()
        if ev.get("shard_corrupt_detected"):
            observer.registry.counter(
                "integrity.shard_corrupt_detected"
            ).add(int(ev["shard_corrupt_detected"]))
        return {
            "verify_s": float(ev.get("verify_s", 0.0)),
            "scrub_verified": _scrub.total_verified(),
            "divergence_checks": _divergence.total_checks(),
        }

    observer.attach_integrity_stats(_integrity_stats)

    def global_tokens(step):
        """Tokens seen through ``step``, exact at any step — checkpoint
        metadata must not reuse the last report's stale figure when a
        preemption/final save lands mid-report-window."""
        return tokens_seen + (
            (step - start_step) * world_size * cfg.batch_size * cfg.seq_length
        )

    def flush_window(step, drain=False):
        """Fetch + report the pending metric window (no-op when empty).

        Called at every report boundary AND (``drain=True``) when the
        loop exits mid-window (preemption, final step, exhausted
        loader): the tail steps' non-finite flags must reach
        ``guard.observe`` — otherwise the final record under-counts
        skipped_steps_total and a bad streak spanning the exit is
        invisible — and the tail's metrics must land in one last record
        before the final save stamps the guard's totals into checkpoint
        metadata. Boundary prints keep the reference's fixed
        report_interval divisor (ref parity, even for a resume's partial
        first window); drain windows are new output with no reference
        counterpart, so their printed rates use the true step count —
        the exit lines an operator reads must not inflate throughput by
        report_interval/len(window)."""
        nonlocal window, start, train_loss, g_norm
        if not window:
            return
        # one host sync per report interval. This device_get is where a
        # stuck collective actually manifests (the loop only
        # dispatches), so the watchdog timeout must cover a FULL report
        # window of steps — see the step_timeout_s sizing note in
        # config/training.py.
        with observer.phase("compute"):
            fetched = jax.device_get(window)
        if watchdog:
            watchdog.beat()
        window = []
        # anomaly accounting: per-step non-finite flags in step order
        # (updates for flagged steps were already skipped on device);
        # report means over the clean steps only so one NaN doesn't
        # poison the whole window's loss
        flags = [float(m.pop("nonfinite", 0.0)) for m in fetched]
        window_skips = guard.observe(flags)
        good = [m for m, f in zip(fetched, flags) if not f]
        # a fully-poisoned window (every step non-finite) has no finite
        # loss to state: carry the last clean loss/gnorm instead of
        # averaging NaN into the print stream, and mark the record
        # (loss=null in sinks, window_poisoned in extra) — skipped_
        # steps_window == steps tells the story
        poisoned = not good
        if not poisoned:
            train_loss = float(sum(m["loss"] for m in good) / len(good))
            g_norm = float(sum(m["gnorm"] for m in good) / len(good))
        current_lr = float(fetched[-1]["lr"])
        # any extra model-family metrics (e.g. MoE moe_drop_frac)
        extra_metrics = (
            {}
            if poisoned
            else {
                k: float(sum(m[k] for m in good) / len(good))
                for k in good[-1]
                if k not in ("loss", "gnorm", "lr")
            }
        )
        elapsed_time = time.time() - loop_start
        new_tokens_seen = (
            (step - start_step) * world_size * cfg.batch_size * cfg.seq_length
        )
        total_tokens_seen = tokens_seen + new_tokens_seen
        window_wall = time.time() - start
        current_step_time = window_wall / (
            len(fetched) if drain else cfg.report_interval
        )
        overall_step_time = elapsed_time / max(1, step - start_step)
        current_throughput = int(
            cfg.batch_size * cfg.seq_length / current_step_time
        )
        overall_throughput = int(
            cfg.batch_size * cfg.seq_length / overall_step_time
        )
        reserved_mem, allocated_mem = _memory_stats()
        if rank == 0:
            if poisoned:
                print(
                    f"report window poisoned: all {len(fetched)} step(s) "
                    f"non-finite; carrying last clean loss"
                )
            print("step:", step)
            print("loss:", train_loss)
            print("LR:", current_lr)
            print("tokens seen:", total_tokens_seen)
            print("gradient norm:", g_norm)
            print("reserved memory:", reserved_mem)
            print("allocated memory:", allocated_mem)
            print("current step time:", current_step_time)
            print("overall step time:", overall_step_time)
            print("current token per chip per sec:", current_throughput)
            print("overall token per chip per sec:", overall_throughput)
            print(
                "overall token per day:",
                int(new_tokens_seen / elapsed_time * 3600 * 24),
            )
            if guard.skipped_batches:
                print("skipped batches:", guard.skipped_batches)
            for k, v in extra_metrics.items():
                print(f"{k}:", v)
        # structured record: every sink (JSONL/CSV file sinks, the
        # legacy wandb/aim tracker adapter), goodput/MFU derivation, and
        # the heartbeat hang off this one call; non-zero ranks run it
        # too (no sinks — it closes their phase window so timing stays
        # rank-consistent). Rates are derived from the window's TRUE
        # step count (a resume's first window and an exit-drain window
        # are partial — len(fetched) < report_interval — and the printed
        # per-interval numbers inherit the reference's fixed divisor) so
        # the persistent record never inflates throughput/MFU.
        window_steps = max(1, len(fetched))
        obs_step_time = max(1e-9, window_wall) / window_steps
        record_extra = dict(extra_metrics)
        if poisoned:
            record_extra["window_poisoned"] = 1
        data_mix = _mix_record(observer, dataloader)
        observer.report(
            step,
            len(fetched),
            loss=float("nan") if poisoned else train_loss,
            grad_norm=float("nan") if poisoned else g_norm,
            learning_rate=current_lr,
            tokens_seen=total_tokens_seen,
            tokens_per_sec_per_chip=(
                cfg.batch_size * cfg.seq_length / obs_step_time
            ),
            tokens_per_sec_per_chip_overall=overall_throughput,
            step_time_s=obs_step_time,
            skipped_steps_total=guard.skipped_batches,
            skipped_steps_window=window_skips,
            memory_reserved_bytes=reserved_mem,
            memory_allocated_bytes=allocated_mem,
            data_mix=data_mix,
            extra=record_extra,
        )
        start = time.time()

    try:
        for batch_idx, batch in enumerate(train_loader, start=start_step + 1):
            if batch_idx > cfg.num_steps:
                batch_idx -= 1  # this batch was never trained on
                break
            if watchdog:
                watchdog.beat()
            if monitor:
                monitor.beat(batch_idx)
            # slice-scoped fault sites (resilience/faults.py): kill every
            # process of one fault domain at the step boundary, or park a
            # rank in a wedged cross-slice reduce — the failures the
            # SliceHealthMonitor must detect/classify
            kill = fire_fault("slice_kill", step=batch_idx, slice=slice_idx)
            if kill is not None:
                from fms_fsdp_tpu.resilience.exits import EXIT_CODES

                os._exit(int(kill.get("code", EXIT_CODES["injected_kill"])))
            stall = fire_fault(
                "dcn_reduce_stall", step=batch_idx, slice=slice_idx
            )
            if stall is not None:
                time.sleep(float(stall.get("seconds", 3600)))
            sdc = fire_fault("sdc_grad_flip", step=batch_idx, proc=rank)
            if sdc is not None:
                # injected silent data corruption: perturb THIS
                # process's replica of one param leaf, host-side (zero
                # compiled-program changes — see divergence.inject_sdc).
                # Nothing here reports it: the cross-replica fingerprint
                # compare at the next report boundary must DISCOVER it.
                state, leaf_key = _divergence.inject_sdc(
                    state, float(sdc.get("scale", 1.5))
                )
                print(
                    f"sdc_grad_flip fault: scaled local shards of "
                    f"{leaf_key} by {float(sdc.get('scale', 1.5))} on "
                    f"proc {rank} at step {batch_idx}"
                )
            state, metrics = step_fn(state, batch)
            window.append(metrics)

            if profiler:
                profiler.step()

            if batch_idx % cfg.report_interval == 0:
                if _divergence.divergence_due(
                    batch_idx, last_divergence_check, divergence_interval
                ):
                    # cross-replica fingerprint compare (one tiny
                    # allgather, every rank at the same boundary),
                    # BEFORE the window flush: loss/gnorm are the LAST
                    # flushed window's post-reduce scalars — replicated
                    # values that must be bit-identical on every
                    # process — and the whole-state checksum proves the
                    # dcn-replicated LIVE state still agrees.
                    # Disagreement raises StateDivergenceError ->
                    # classified state_divergence exit; the supervisor
                    # relaunches under the verified-resume rule. No
                    # checkpoint is saved on this path: the live state
                    # is suspect.
                    last_divergence_check = batch_idx
                    try:
                        _divergence.check_divergence(
                            state,
                            train_loss,
                            g_norm,
                            batch_idx,
                            cfg,
                            observer.registry,
                        )
                    except StateDivergenceError:
                        # the pending window (and with it the
                        # integrity.divergence_detected counter the
                        # check just bumped) must reach one final
                        # record before the classified abort — the
                        # exit path never reports again
                        flush_window(batch_idx, drain=True)
                        raise
                flush_window(batch_idx)

                if scrubber is not None:
                    # cadence check only; the sweep itself runs on a
                    # daemon thread and self-throttles to one in flight
                    scrubber.maybe_scrub(batch_idx)

                if guard.should_abort():
                    # a poisoned data region or true divergence: skipping
                    # forever would silently train on nothing. Save a
                    # final checkpoint (params are the last good ones —
                    # flagged updates never landed) and abort loudly.
                    with watchdog.paused() if watchdog else _nullctx():
                        checkpointer.save(
                            batch_idx,
                            state,
                            dataloader,
                            reason="abort",
                            tokens_seen=global_tokens(batch_idx),
                            skipped_steps=guard.skipped_batches,
                        )
                    raise DeliberateAbort(
                        f"{slice_tag}anomaly guard: {guard.consecutive} "
                        f"consecutive non-finite steps (threshold "
                        f"{guard.max_consecutive}); checkpoint saved at "
                        f"step {batch_idx}, aborting"
                    )

            preempt_now = preemption.poll()
            # tier-aware cadence when the checkpointer is the async
            # manager (a fast local tier can be due between durable
            # intervals); plain Checkpointer keeps the single interval
            interval_due = (
                checkpointer.save_due(batch_idx)
                if hasattr(checkpointer, "save_due")
                else batch_idx % cfg.checkpoint_interval == 0
            )
            if interval_due or batch_idx == cfg.num_steps or preempt_now:
                reason = (
                    "preempt"
                    if preempt_now
                    else ("final" if batch_idx == cfg.num_steps else "interval")
                )
                if reason != "interval":
                    # the loop is about to exit: drain the pending
                    # window first so the guard's totals (stamped into
                    # the save's metadata below) and the final record
                    # cover the tail steps
                    flush_window(batch_idx, drain=True)
                # the watchdog deadline is sized for step windows; a
                # healthy multi-minute Orbax save must not trip it, so
                # the watchdog is suspended (and re-armed) around it.
                # (Async saves only block for the snapshot here; the
                # storage write runs on the background writer.)
                with watchdog.paused() if watchdog else _nullctx():
                    checkpointer.save(
                        batch_idx,
                        state,
                        dataloader,
                        reason=reason,
                        tokens_seen=global_tokens(batch_idx),
                        skipped_steps=guard.skipped_batches,
                    )
            if preempt_now:
                if rank == 0:
                    print(
                        f"preemption signal received: checkpoint saved at "
                        f"step {batch_idx}, exiting clean"
                    )
                break

        # exhausted loader (finite stream) or num_steps overrun: drain
        # whatever the last report window left pending (no-op when the
        # exit landed on a report/save boundary)
        flush_window(batch_idx, drain=True)
        if guard.should_abort() and rank == 0:
            print(
                f"WARNING: {slice_tag}run exited with {guard.consecutive} "
                f"consecutive non-finite steps still streaking"
            )
    except Exception as e:
        # DCN-collective timeout classifier (resilience/slices.py): a
        # dead slice can surface on the survivors as a transport ERROR
        # from the cross-slice collective rather than a hang. Hold the
        # exception until the liveness verdict is in, and re-raise it
        # classified — "slice K lost, restart at world minus one fault
        # domain" — instead of the raw transport traceback. Unrelated
        # failures (no slice went silent) re-raise untouched, and the
        # loop's own deliberate aborts skip the wait entirely (a
        # whole-world abort must not be re-badged as a slice loss) —
        # as does a divergence detection, which every rank raises from
        # the same collective compare (a whole-world classified abort,
        # not a dead fault domain).
        if monitor is not None and not isinstance(
            e, (DeliberateAbort, StateDivergenceError)
        ):
            dead = monitor.wait_classify()
            if dead is not None:
                # typed (resilience/slices.py) so the entry points'
                # classified-exit wrapper exits with the slice_loss
                # registry code — the same code the monitor thread's
                # direct os._exit path uses
                raise SliceLostError(monitor.describe_loss(dead)) from e
        raise
    finally:
        if watchdog:
            watchdog.stop()
        if monitor:
            monitor.stop()
        if scrubber is not None:
            scrubber.stop()

    return train_loss
