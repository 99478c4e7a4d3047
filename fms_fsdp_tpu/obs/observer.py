"""The Observer facade the train loops drive.

One object owns the registry, phase timer, goodput tracker, sinks, and
heartbeat. The hot loop touches it in exactly three ways:

- ``wrap_data_iter(it)`` — times each ``next()`` as ``data_wait``;
- ``phase(name)`` — context manager around step dispatch / metric fetch
  (``compute``) and checkpoint saves (``checkpoint``);
- ``report(...)`` — once per report interval: folds the phase window,
  skipped-step counts, and MFU/HFU into a schema-validated record and
  fans it out to every sink plus the heartbeat.

Ranks other than 0 get the same timer/registry (phases are cheap and
keeping them armed avoids rank-divergent control flow) but no sinks —
only rank 0 writes files or talks to trackers.
"""

import logging
import math
import time
from typing import Callable, Dict, Iterable, Iterator, List, Optional

from fms_fsdp_tpu.obs.registry import MetricRegistry
from fms_fsdp_tpu.obs.schema import SCHEMA_VERSION, validate_record
from fms_fsdp_tpu.obs.sinks import Heartbeat, Sink, build_sinks
from fms_fsdp_tpu.obs.timing import GoodputTracker, PhaseTimer

logger = logging.getLogger(__name__)


def _nonfinite(v) -> bool:
    return isinstance(v, float) and not math.isfinite(v)


class Observer:
    def __init__(
        self,
        sinks: Optional[List[Sink]] = None,
        heartbeat: Optional[Heartbeat] = None,
        flops_per_token: Optional[float] = None,
        hfu_flops_per_token: Optional[float] = None,
        peak_flops: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
        strict_schema: bool = False,
        kernel_tuning: Optional[str] = None,
        quantized_matmuls: Optional[str] = None,
        quantized_reduce: Optional[str] = None,
        restarts: int = 0,
        restart_downtime_s: float = 0.0,
        device: Optional[Dict] = None,
    ):
        self.registry = MetricRegistry()
        # the device every record of this run was measured on (v16
        # fields): {"platform", "kind", "count"} as jax reports them
        self.device = dict(device or {})
        # the kernel-tuning mode this run's step was built under (v3
        # schema field); resolved tiles arrive via the registry
        # (tune.lookup.attach_registry) as kernel.tune.* extras
        self.kernel_tuning = kernel_tuning
        # the quantization modes the step was built under (v4 fields):
        # a perf record must state the numerics that produced it
        self.quantized_matmuls = quantized_matmuls
        self.quantized_reduce = quantized_reduce
        self.timer = PhaseTimer(clock=clock)
        # supervisor restart accounting (schema v6): how many times this
        # run has been auto-relaunched, and the cumulative downtime —
        # pre-charged into the goodput wall clock so a faulted run's
        # goodput_overall is strictly below the fault-free run's
        self.restarts = int(restarts)
        self.restart_downtime_s = float(restart_downtime_s)
        self.goodput = GoodputTracker(
            restart_downtime_s=self.restart_downtime_s
        )
        self.sinks = sinks or []
        self.heartbeat = heartbeat
        self.flops_per_token = flops_per_token
        self.hfu_flops_per_token = hfu_flops_per_token
        self.peak_flops = peak_flops
        self.strict_schema = strict_schema
        self.last_record: Optional[Dict] = None
        self._schema_warned = False
        # set by the async checkpoint manager (ckpt/manager.py) when the
        # loop attaches this observer to it: a callable draining the
        # background-write window ({bg_s, in_flight}) for the record's
        # checkpoint_bg_s / checkpoint_in_flight fields
        self._ckpt_stats: Optional[Callable[[], Dict]] = None
        # set by the entry on multi-slice meshes (obs/collectives.py):
        # the report-cadence probe whose timings fill the v5
        # ici_collective_s / dcn_collective_s split; None (single-slice)
        # leaves both fields 0.0
        self._collective_probe: Optional[Callable[[], None]] = None
        # set by the train loop when the state-integrity layer is armed
        # (utils/train_utils.py): a callable draining the verification
        # window for the v8 integrity_verify_s / scrub_verified /
        # divergence_checks fields; absent -> 0 / 0 / 0.0
        self._integrity_stats: Optional[Callable[[], Dict]] = None
        # set by the entry when the step was built with the DCN-overlap
        # schedule (parallel/overlap.py plan_summary()): bucket count +
        # bytes, consumed by the v10 dcn_overlap_frac estimate; None
        # (overlap off / single-slice) keeps the field 0.0
        self._overlap_schedule: Optional[Dict] = None

    def attach_checkpoint_stats(self, fn: Callable[[], Dict]) -> None:
        self._ckpt_stats = fn

    def attach_integrity_stats(self, fn: Callable[[], Dict]) -> None:
        self._integrity_stats = fn

    def attach_collective_probe(self, fn: Optional[Callable[[], None]]) -> None:
        self._collective_probe = fn

    def attach_overlap_schedule(self, schedule: Optional[Dict]) -> None:
        self._overlap_schedule = dict(schedule) if schedule else None

    def _overlap_frac(self, window: Dict) -> float:
        """Estimate the fraction of the window's DCN collective time the
        bucket schedule hides under backward compute.

        With K buckets, only the first bucket's reduce has nothing to
        overlap with (the backward for later buckets runs under it), so
        the structurally exposed time is ~d/K plus whatever total DCN
        time exceeds the backward compute available to hide it (taken as
        2/3 of the window's compute — backward's share of fwd+bwd).
        Clamped to [0, 1]; 0.0 without a schedule or probe signal. An
        estimate for trend lines, not a bytes-accurate profile — the
        XPlane profiler owns exactness."""
        if not self._overlap_schedule:
            return 0.0
        d = float(window.get("dcn_collective", 0.0))
        if d <= 0.0:
            return 0.0
        k = max(1, int(self._overlap_schedule.get("buckets", 1)))
        c = float(window.get("compute", 0.0)) * (2.0 / 3.0)
        exposed = d / k + max(0.0, d - d / k - c)
        return max(0.0, min(1.0, 1.0 - exposed / d))

    # -- hot-loop hooks ----------------------------------------------------

    def phase(self, name: str):
        return self.timer.phase(name)

    def wrap_data_iter(self, it: Iterable) -> Iterator:
        """Yield from ``it`` with each ``next()`` timed as data_wait."""
        it = iter(it)
        while True:
            try:
                with self.timer.phase("data_wait"):
                    item = next(it)
            except StopIteration:
                return
            yield item

    # -- report-cadence ----------------------------------------------------

    def report(
        self,
        step: int,
        steps_in_window: int,
        *,
        loss: float,
        tokens_per_sec_per_chip: float,
        skipped_steps_total: int = 0,
        skipped_steps_window: int = 0,
        grad_norm: Optional[float] = None,
        learning_rate: Optional[float] = None,
        tokens_seen: Optional[int] = None,
        tokens_per_sec_per_chip_overall: Optional[float] = None,
        step_time_s: Optional[float] = None,
        memory_reserved_bytes: Optional[int] = None,
        memory_allocated_bytes: Optional[int] = None,
        data_mix: Optional[Dict[str, float]] = None,
        serving: Optional[Dict[str, float]] = None,
        serving_fleet: Optional[Dict[str, float]] = None,
        extra: Optional[Dict[str, float]] = None,
    ) -> Dict:
        """Close the phase window, derive goodput/MFU, emit to sinks.

        Returns the record (also kept as ``last_record`` for tests and
        callers that want the derived numbers)."""
        if self._collective_probe is not None:
            # inside the closing window, before it is folded: the
            # probe's seconds belong to the record they attribute.
            # Collective — every rank reports at the same step, so the
            # probe stays rank-consistent.
            self._collective_probe()
        window = self.timer.window()
        goodput_w, goodput_all = self.goodput.update(
            window, steps_in_window, skipped_steps_window
        )
        mfu = hfu = None
        if self.flops_per_token and self.peak_flops:
            achieved = tokens_per_sec_per_chip * self.flops_per_token
            mfu = achieved / self.peak_flops
            if self.hfu_flops_per_token:
                hfu = (
                    tokens_per_sec_per_chip
                    * self.hfu_flops_per_token
                    / self.peak_flops
                )
        # checkpoint stats BEFORE the registry snapshot: the provider
        # (ckpt/manager.py obs_stats) flushes the writer thread's
        # committed-save counters into the registry here on the main
        # thread, so they land in THIS record's extras
        ckpt_stats = self._ckpt_stats() if self._ckpt_stats else {}
        # integrity stats BEFORE the snapshot too: the provider drains
        # the scrubber/verify event buffer into the registry counters
        # (integrity.shard_corrupt_detected) so detections land in THIS
        # record's extras
        integ = self._integrity_stats() if self._integrity_stats else {}
        extras = dict(self.registry.snapshot())
        if extra:
            extras.update(extra)
        wall = window["wall"]
        record = {
            "schema_version": SCHEMA_VERSION,
            "step": int(step),
            "time_unix": time.time(),
            "loss": float(loss),
            "grad_norm": None if grad_norm is None else float(grad_norm),
            "learning_rate": (
                None if learning_rate is None else float(learning_rate)
            ),
            "tokens_seen": None if tokens_seen is None else int(tokens_seen),
            "tokens_per_sec_per_chip": float(tokens_per_sec_per_chip),
            "tokens_per_sec_per_chip_overall": (
                None
                if tokens_per_sec_per_chip_overall is None
                else float(tokens_per_sec_per_chip_overall)
            ),
            "step_time_s": (
                None if step_time_s is None else float(step_time_s)
            ),
            "mfu": mfu,
            "hfu": hfu,
            "data_wait_s": window["data_wait"],
            "data_wait_frac": (
                window["data_wait"] / wall if wall > 0 else 0.0
            ),
            "compute_s": window["compute"],
            # blocking time at the step boundary only (the snapshot,
            # under the async manager); the storage-write remainder is
            # checkpoint_bg_s, off the critical path
            "checkpoint_s": window["checkpoint"],
            "checkpoint_bg_s": float(ckpt_stats.get("bg_s", 0.0)),
            "checkpoint_in_flight": int(ckpt_stats.get("in_flight", 0)),
            # v5: the multi-slice collective split (obs/collectives.py
            # probe; 0.0 without one — single-slice runs)
            "ici_collective_s": window.get("ici_collective", 0.0),
            "dcn_collective_s": window.get("dcn_collective", 0.0),
            # v10: estimated hidden fraction of the DCN time above under
            # the bucketed overlap schedule (0.0 when overlap is off)
            "dcn_overlap_frac": self._overlap_frac(window),
            # v8: state-integrity accounting (scrub + divergence layer;
            # 0 / 0 / 0.0 when the layer is not armed)
            "integrity_verify_s": float(integ.get("verify_s", 0.0)),
            "scrub_verified": int(integ.get("scrub_verified", 0)),
            "divergence_checks": int(integ.get("divergence_checks", 0)),
            "wall_s": wall,
            "goodput": goodput_w,
            "goodput_overall": goodput_all,
            "skipped_steps": int(skipped_steps_total),
            "skipped_steps_window": int(skipped_steps_window),
            # v6: supervisor restart accounting (restart ledger)
            "restarts": self.restarts,
            "restart_downtime_s": self.restart_downtime_s,
            # v7: per-corpus data-mix accounting ("<corpus>.<stat>"
            # flat map); None when the run has no live mixing layer
            "data_mix": dict(data_mix) if data_mix else None,
            # v9: serving-engine headline map
            # (ServingEngine.serving_stats()); None on training runs
            "serving": dict(serving) if serving else None,
            # v11: fleet-router headline map (FleetRouter.stats());
            # None on training runs and single-engine serving
            "serving_fleet": (
                dict(serving_fleet) if serving_fleet else None
            ),
            "kernel_tuning": self.kernel_tuning,
            "quantized_matmuls": self.quantized_matmuls,
            "quantized_reduce": self.quantized_reduce,
            "memory_reserved_bytes": (
                None
                if memory_reserved_bytes is None
                else int(memory_reserved_bytes)
            ),
            "memory_allocated_bytes": (
                None
                if memory_allocated_bytes is None
                else int(memory_allocated_bytes)
            ),
            "device_platform": self.device.get("platform"),
            "device_kind": self.device.get("kind"),
            "device_count": self.device.get("count"),
            "extra": extras,
        }
        # non-finite scalars become null: a NaN loss (fully-poisoned
        # window) serialized bare would make the JSONL line unparseable
        # by strict parsers exactly when the post-mortem matters most
        record = {
            k: (None if _nonfinite(v) else v) for k, v in record.items()
        }
        record["extra"] = {
            k: (None if _nonfinite(v) else v) for k, v in extras.items()
        }
        errs = validate_record(record)
        if errs:
            if self.strict_schema:
                raise ValueError(f"metrics record violates schema: {errs}")
            if not self._schema_warned:
                # warn once (not per report): downstream consumers are
                # about to choke on this stream and the operator needs
                # a signal, but a per-report warning would flood logs
                self._schema_warned = True
                logger.warning(
                    "metrics record violates schema (emitting anyway; "
                    "set obs_strict_schema=True to raise): %s", errs
                )
        self.last_record = record
        for sink in self.sinks:
            sink.emit(record)
        if self.heartbeat:
            self.heartbeat.beat(step, record["time_unix"], goodput_w)
        return record

    def close(self) -> None:
        for sink in self.sinks:
            sink.close()


def build_observer(
    cfg,
    rank: int,
    model_cfg=None,
    tracker_fn: Optional[Callable] = None,
    clock: Callable[[], float] = time.monotonic,
) -> Observer:
    """Build the Observer from TrainConfig knobs (docs/observability.md).

    File sinks and the heartbeat attach only on rank 0 and only when
    ``cfg.obs_dir`` is set; the tracker sink attaches whenever a live
    ``tracker_fn`` exists (rank 0 by construction — ``get_tracker``
    returns None elsewhere). MFU/HFU need ``model_cfg`` for the FLOPs
    model; without it they are emitted as null.
    """
    import os

    obs_dir = getattr(cfg, "obs_dir", "") or ""
    names = [
        s for s in (getattr(cfg, "obs_sinks", "jsonl") or "").split(",") if s
    ]
    # the legacy tracker rides as a sink whenever configured, even if the
    # user's obs_sinks list predates the tracker sink name
    if tracker_fn is not None and "tracker" not in [n.strip() for n in names]:
        names.append("tracker")
    sinks = build_sinks(obs_dir if rank == 0 else "", names, tracker_fn)
    heartbeat = None
    if rank == 0 and obs_dir and getattr(cfg, "obs_heartbeat", True):
        heartbeat = Heartbeat(os.path.join(obs_dir, "heartbeat.json"))

    flops = hfu_flops = peak = None
    if model_cfg is not None:
        from fms_fsdp_tpu.parallel.ac import selective_ac_mask
        from fms_fsdp_tpu.utils.flops import (
            peak_flops_per_chip,
            train_flops_per_token,
        )

        seq_len = cfg.seq_length
        flops = train_flops_per_token(model_cfg, seq_len)
        ac_actual = 0.0
        if getattr(cfg, "fsdp_activation_checkpointing", False):
            n_layers = getattr(model_cfg, "nlayers", None) or getattr(
                model_cfg, "n_layer", 1
            )
            mask = selective_ac_mask(n_layers, cfg.selective_checkpointing)
            ac_actual = (sum(mask) / n_layers) if mask else 0.0
        hfu_flops = train_flops_per_token(
            model_cfg, seq_len, ac_fraction=ac_actual
        )
        peak = peak_flops_per_chip(getattr(cfg, "obs_chip_hint", "") or "")

    # self-healing supervisor accounting (schema v6): when relaunched by
    # resilience/supervisor.py, the restart ledger (FMS_RESTART_LEDGER,
    # written before each launch) carries how many restarts preceded
    # this incarnation and their cumulative downtime — folded into every
    # record and charged against goodput. Unsupervised runs: 0 / 0.0.
    from fms_fsdp_tpu.resilience.exits import read_restart_ledger

    ledger = read_restart_ledger() or {}
    restarts = int(ledger.get("restarts", 0) or 0)
    restart_downtime_s = float(ledger.get("restart_downtime_s", 0.0) or 0.0)

    from fms_fsdp_tpu.utils.flops import device_info

    obs = Observer(
        device=device_info(),
        sinks=sinks,
        heartbeat=heartbeat,
        flops_per_token=flops,
        hfu_flops_per_token=hfu_flops,
        peak_flops=peak,
        clock=clock,
        strict_schema=bool(getattr(cfg, "obs_strict_schema", False)),
        kernel_tuning=getattr(cfg, "kernel_tuning", None),
        quantized_matmuls=getattr(cfg, "quantized_matmuls", None),
        quantized_reduce=getattr(cfg, "quantized_reduce", None),
        restarts=restarts,
        restart_downtime_s=restart_downtime_s,
    )
    # resolved kernel tiles (kernel.tune.* gauges) land in this
    # observer's registry from the trace-time lookup — attach before the
    # first step build so nothing is lost (already-recorded choices are
    # replayed on attach regardless)
    from fms_fsdp_tpu.tune.lookup import attach_registry

    attach_registry(obs.registry)
    return obs
