"""The versioned metrics-record schema shared by every sink.

One record is emitted per report step. ``SCHEMA_FIELDS`` is the
contract: field name -> (type tag, required). Changing the field set or
a type WITHOUT bumping ``SCHEMA_VERSION`` fails CI: the pinned digest in
``SCHEMA_DIGESTS`` no longer matches (tests/test_obs.py::
test_schema_digest_pins_version). To evolve the schema: edit
``SCHEMA_FIELDS``, bump ``SCHEMA_VERSION``, add the new digest (printed
by the failing test), and document the change in docs/observability.md.

Type tags: ``int`` / ``float`` (``null`` allowed only where required is
False) / ``str`` / ``map`` (flat str->number dict).
"""

import hashlib
import json
import numbers
from typing import Any, Dict, List

SCHEMA_VERSION = 16

# name -> (type, required)
SCHEMA_FIELDS = {
    "schema_version": ("int", True),
    "step": ("int", True),
    "time_unix": ("float", True),
    # nullable: a fully-poisoned report window (every step flagged
    # non-finite) has no finite loss to state — null, never bare NaN,
    # keeps each line strict-JSON parseable exactly when the post-mortem
    # matters most; skipped_steps_window == steps tells the story
    "loss": ("float", False),
    "grad_norm": ("float", False),
    "learning_rate": ("float", False),
    "tokens_seen": ("int", False),
    "tokens_per_sec_per_chip": ("float", True),
    "tokens_per_sec_per_chip_overall": ("float", False),
    "step_time_s": ("float", False),
    "mfu": ("float", False),
    "hfu": ("float", False),
    "data_wait_s": ("float", True),
    "data_wait_frac": ("float", True),
    "compute_s": ("float", True),
    # v2: checkpoint_s is the step-boundary BLOCKING time only (the
    # device→host snapshot under the async manager; the whole save when
    # running synchronously)...
    "checkpoint_s": ("float", True),
    # ...and checkpoint_bg_s is the background writer-thread wall time
    # that landed in this window (off the critical path), with
    # checkpoint_in_flight flagging a save still committing at report
    # time. Per-tier save counts and bytes ride in ``extra``
    # (checkpoint.saves.<tier>, checkpoint.bytes).
    "checkpoint_bg_s": ("float", True),
    "checkpoint_in_flight": ("int", True),
    # v5: collective time split by transport tier (docs/observability.md
    # "Multi-slice collective split"). On a multi-slice mesh the report-
    # cadence collective probe (obs/collectives.py) times one tiny
    # within-slice reduce (ICI) and one cross-slice reduce (DCN) per
    # window, so cross-slice overhead — the HSDP scaling tax — is
    # attributable per record. Single-slice runs report 0.0 for both
    # (no probe is traced; the train step's HLO stays untouched).
    "ici_collective_s": ("float", True),
    "dcn_collective_s": ("float", True),
    # v10: estimated fraction of the window's DCN collective time hidden
    # under backward compute by the bucketed overlap schedule
    # (parallel/overlap.py; docs/observability.md "DCN overlap"). Derived
    # from the probe's dcn_collective_s, the resolved bucket count, and
    # the window's compute time — 0.0 when overlap is off, the mesh is
    # single-slice, or no probe ran this window.
    "dcn_overlap_frac": ("float", True),
    "wall_s": ("float", True),
    "goodput": ("float", True),
    "goodput_overall": ("float", False),
    "skipped_steps": ("int", True),
    "skipped_steps_window": ("int", True),
    # v7: multi-corpus data-mix accounting (docs/dataloader.md
    # "Multi-corpus mixing"). Flat map keyed "<corpus>.<stat>" with
    # stats tokens_seen / target_share / realized_share / quarantined
    # (0|1) per corpus, filled at report cadence from the live loader's
    # SamplingDataset layer — realized-vs-target share drift and a
    # degraded (quarantined) mix are first-class record facts. Absent
    # (null) on dummy-data runs and in worker_mode="process" (the
    # parent's pipeline copies don't advance). The corpus lifecycle
    # counters (data.corpus_quarantined / data.corpus_rearmed) and
    # data.mix.<corpus>.tokens_seen gauges additionally ride in
    # ``extra``.
    "data_mix": ("map", False),
    # v8: state-integrity accounting (docs/checkpointing.md "State
    # integrity"). integrity_verify_s is the window's wall seconds spent
    # in manifest verification (scrubber sweeps + restore-walk
    # verifies, drained from the background event buffer);
    # scrub_verified is the cumulative count of checkpoints this
    # process has confirmed content-verified (fresh hash or matching
    # cached verdict); divergence_checks is the cumulative count of
    # cross-replica fingerprint compares performed
    # (resilience/divergence.py). Detections ride in ``extra`` as the
    # integrity.shard_corrupt_detected / integrity.divergence_detected
    # counters. Runs without the integrity layer armed report 0 / 0 /
    # 0.0.
    "integrity_verify_s": ("float", True),
    "scrub_verified": ("int", True),
    "divergence_checks": ("int", True),
    # v9: serving-engine accounting (docs/serving.md). Flat map with
    # the serving headline stats: tokens_per_s (decode throughput),
    # ttft_s (mean time-to-first-token of the window), queue_depth,
    # kv_pages_in_use, requests_completed / evicted / expired, and
    # p99_latency_s — filled from ServingEngine.serving_stats() when a
    # serving loop drives the observer. The full serve.* counter/gauge
    # set (serve.decode_tokens, serve.kv_defrag_moves, ...) rides in
    # ``extra`` via the registry snapshot as usual. Absent (null) on
    # training runs.
    # v12: the map gains ``family`` — the engine's model family as a
    # numeric code (0=llama 1=mamba 2=mixtral; serve/families/
    # FAMILY_CODES — the map is flat str->number, so the name travels
    # as its code) — and ``state_bytes_per_stream``, the decode-state
    # slab bytes one stream holds (mamba's constant-memory headline;
    # 0.0 for families whose whole decode state is paged KV).
    # v13: the map gains the disaggregation + layout fields (docs/
    # observability.md "v13"): ``role`` (serve/disagg ROLE_CODES:
    # 0=unified 1=prefill 2=decode), ``serve_layout`` (100*tp + fsdp,
    # 0 = single-chip; parallel/sharding.py::serve_layout_code),
    # ``handoff_bytes`` (cumulative PageHandoff wire bytes packed +
    # imported) and ``handoff_s`` (wall seconds packing/scattering).
    # v15: the map gains ``drained`` (1.0 once the engine stopped
    # admitting — a draining/preempted replica is visibly winding down
    # in its last heartbeats' stats).
    # v14: the map gains the raw-speed fields (docs/observability.md
    # "v14"): ``spec_accept_rate`` (accepted draft tokens over offered
    # — 0.0 when speculative serving is off), ``spec_draft_tokens``
    # (draft tokens per verify step; 0 = non-speculative),
    # ``prefill_chunks`` (cumulative chunked-prefill slices advanced;
    # 0 = whole-prompt prefill) and ``paged_kernel_impl`` (0 =
    # reference gather, 1 = paged-attention kernel with one full-width
    # page per cell, 2 = kernel with multi-page cells and/or native
    # quantized page reads).
    "serving": ("map", False),
    # v11: serving-fleet accounting (docs/serving.md "Fleet
    # resilience"). Flat map from FleetRouter.stats(): replicas /
    # replicas_live, availability (replica-seconds live over owed —
    # the restart ledger folded into one number), restarts,
    # stalls_detected, request outcome counts (admitted / completed /
    # expired / failed / requeued / rejected), duplicates_dropped
    # (exactly-once dedup hits), completion_rate, p99_latency_s under
    # churn. Absent (null) on training runs and single-engine serving.
    # v15: the map gains the streaming-transport + drain counters
    # (docs/observability.md "v15"): ``handoff_retries`` (transfers
    # that needed >= 1 chunk retransmit), ``chunks_resent`` (total
    # retransmitted chunks, router side), ``transfers_resumed``
    # (transfers that continued past an interruption — journal-seeded
    # resume or in-flight retransmit) and ``drain_migrations`` (live
    # streams migrated off a preempted replica with zero recompute).
    "serving_fleet": ("map", False),
    # v6: self-healing supervisor accounting (docs/resilience.md
    # "Self-healing supervisor"). The relaunched run reads the
    # supervisor's restart ledger (FMS_RESTART_LEDGER) at observer
    # build: ``restarts`` is how many times this run has been
    # auto-relaunched and ``restart_downtime_s`` the cumulative
    # death-to-relaunch wall time — charged against goodput (the
    # GoodputTracker's wall clock starts that far behind), so a faulted
    # run's goodput_overall is strictly below the fault-free run's.
    # Unsupervised runs report 0 / 0.0.
    "restarts": ("int", True),
    "restart_downtime_s": ("float", True),
    # v3: the kernel-tuning mode the run's step was built under
    # ("auto" | "off" | a table path). The per-kernel resolved tiles ride
    # in ``extra`` as kernel.tune.* gauges (flash block_q/block_k/kvgrid,
    # ssd chunk, ce chunk, exact/nearest/default/pinned/off counters, and
    # the block-degradation counter) — a run's perf record states which
    # tiles produced it (flash gauges reflect post-divisibility-halving
    # values; "pinned" = the call site or a non-default config value
    # named the tile explicitly while tuning was on).
    "kernel_tuning": ("str", False),
    # v4: the quantization modes the run's step was built under — the
    # GEMM path ("none" | "int8" | "int8_dgrad" | "fp8" | "fp8_dgrad",
    # ops/quant.py) and the gradient-reduction wire format ("none" |
    # "int8" | "fp8" | "fp8_delayed", parallel/sharding.py). A perf
    # record must state the numerics that produced it; the tuner's
    # resolved flash quant family additionally rides in ``extra`` as
    # kernel.tune.flash.quant_code (0=none 1=int8 2=fp8).
    "quantized_matmuls": ("str", False),
    "quantized_reduce": ("str", False),
    "memory_reserved_bytes": ("int", False),
    "memory_allocated_bytes": ("int", False),
    # v16: the device the record was measured on, as jax reports it
    # (``jax.devices()[0].platform`` / ``.device_kind`` /
    # ``len(jax.devices())``) — a throughput or utilization figure is
    # only readable beside the hardware that produced it, and a CPU
    # record must never pass for a chip's. Filled by build_observer;
    # null on a hand-built Observer given no device.
    "device_platform": ("str", False),
    "device_kind": ("str", False),
    "device_count": ("int", False),
    "extra": ("map", False),
}

# Digest of the canonical field serialization for each published
# version. A mismatch for the CURRENT version means the schema changed
# without a version bump.
SCHEMA_DIGESTS = {
    1: "01cf2035086946667a852893e38535f44bd340e20871a10be2d6f4103cd62f90",
    # v2: + checkpoint_bg_s / checkpoint_in_flight (async checkpoint
    # manager: blocking-snapshot vs background-write split)
    2: "6fe196571d7fdf02da2dc0060f5151ddbcee7fae5275ad45277c0bce95be49c8",
    # v3: + kernel_tuning (autotuner mode; resolved tiles ride in extra
    # as kernel.tune.* gauges)
    3: "f040074f56e65a7aef0e33bb7281fd38b6f1941115ee5e862412962b5f5c2a84",
    # v4: + quantized_matmuls / quantized_reduce (the step's GEMM and
    # gradient-reduce quantization modes; the tuner's flash quant family
    # rides in extra as kernel.tune.flash.quant_code)
    4: "488f2ccf06394fbc05445c7134628520fef64de1cd61a1bd6bf44000bd1ee66e",
    # v5: + ici_collective_s / dcn_collective_s (the multi-slice
    # collective split measured by the report-cadence probe)
    5: "5b3a957aa5736c7bce67ed7650ee3f5dc6fc322bc1edb85409dcc4653eddb011",
    # v6: + restarts / restart_downtime_s (self-healing supervisor:
    # restart-ledger accounting, downtime charged against goodput)
    6: "beafaf1c7f6338ad6693fe16ce1b2c4403c5447e3135e12b3776d5494864b8ce",
    # v7: + data_mix (per-corpus tokens_seen / target vs realized share /
    # quarantined flag from the weighted multi-corpus mixing layer)
    7: "fed0cc09460e2c7da58cf4519e40e8d4e0ff6c25874b65fbd9d0e7f44ff83af9",
    # v8: + integrity_verify_s / scrub_verified / divergence_checks
    # (state-integrity layer: manifest verification time, scrub-verified
    # checkpoint count, cross-replica fingerprint compares)
    8: "96ce592c9a1e990018a24d93757370679c594bfac64269b225cd2ff635ee4a3e",
    # v9: + serving (serving-engine headline map: tokens_per_s, ttft_s,
    # queue_depth, kv_pages_in_use, request outcome counts,
    # p99_latency_s — docs/serving.md)
    9: "178c0ec2d1d31834a0ae939d0df6e734ce66665f0dfccb662ab97dcc5fcc4e12",
    # v10: + dcn_overlap_frac (estimated hidden fraction of the window's
    # DCN collective time under the bucketed overlap schedule —
    # parallel/overlap.py, docs/observability.md "DCN overlap")
    10: "864cdd64b4d6f3fa3dd7e24c3e0a18f42ae118f56965c32fbfb2f0a847f7287a",
    # v11: + serving_fleet (fleet router headline map: replica
    # availability from the restart ledger, restarts, stalls, request
    # outcome counts, exactly-once dedup hits, p99 under churn —
    # docs/serving.md "Fleet resilience")
    11: "3fa631fc73a3499c0515780e834069bd2874861a64e3bab5bd14770fdb45d513",
    # v12: serving map gains family (numeric code via
    # serve/families.FAMILY_CODES) + state_bytes_per_stream (constant
    # decode-slab bytes; the field set itself is unchanged)
    12: "30df6d1be6e3214a083627b8cbb8a765d7c7e51aef6bdf4eca8fe469d13e5881",
    # v13: serving map gains role (disagg ROLE_CODES), serve_layout
    # (100*tp + fsdp layout code), handoff_bytes and handoff_s (the
    # PageHandoff wire traffic; field set itself unchanged), and the
    # serving_fleet map gains prefill_replicas / requests_handed_off /
    # handoff_bytes
    13: "598cbb44447e0667b8655a5b06dc569b2e00b33f748561f2d2ec6d365600418d",
    # v14: serving map gains spec_accept_rate / spec_draft_tokens
    # (speculative serving), prefill_chunks (chunked prefill) and
    # paged_kernel_impl (the kernel generation engaged); the field set
    # itself is unchanged
    14: "2f8909a62cde9d1cdfd1d4153c219e37d8f16b8011a7f3dca7feeb5ebb2a567a",
    # v15: serving map gains drained (engine stopped admitting — the
    # drain/preempt wind-down flag); serving_fleet map gains
    # handoff_retries / chunks_resent / transfers_resumed /
    # drain_migrations (streaming state-transfer transport +
    # drain-and-migrate preemption); the field set itself is unchanged
    15: "72f5816eded0eb4caa3a834f60eb0dc10db1a31772699bf81af6c0c40665b38a",
    # v16: + device_platform / device_kind / device_count (the device
    # the record was measured on, as jax reports it)
    16: "d10795827bb448b3bf4e2d4cbd05193ce67ef19ffbc7916d66f2f50d58ba8c77",
}


def schema_digest() -> str:
    canon = json.dumps(
        {"version": SCHEMA_VERSION, "fields": SCHEMA_FIELDS},
        sort_keys=True,
        separators=(",", ":"),
    )
    return hashlib.sha256(canon.encode()).hexdigest()


def _type_ok(tag: str, v: Any) -> bool:
    if tag == "int":
        return isinstance(v, numbers.Integral) and not isinstance(v, bool)
    if tag == "float":
        return isinstance(v, numbers.Real) and not isinstance(v, bool)
    if tag == "str":
        return isinstance(v, str)
    if tag == "map":
        return isinstance(v, dict) and all(
            isinstance(k, str)
            and (v[k] is None or isinstance(v[k], numbers.Real))
            for k in v
        )
    return False


def validate_record(rec: Dict[str, Any]) -> List[str]:
    """Return a list of violations (empty = valid). Checks: required
    fields present and non-null, all present fields well-typed, no
    fields outside the schema, version matches."""
    errs = []
    if rec.get("schema_version") != SCHEMA_VERSION:
        errs.append(
            f"schema_version {rec.get('schema_version')!r} != {SCHEMA_VERSION}"
        )
    for name, (tag, required) in SCHEMA_FIELDS.items():
        if name not in rec or rec[name] is None:
            if required:
                errs.append(f"missing required field {name!r}")
            continue
        if not _type_ok(tag, rec[name]):
            errs.append(f"field {name!r}={rec[name]!r} is not a {tag}")
    for name in rec:
        if name not in SCHEMA_FIELDS:
            errs.append(f"unknown field {name!r} (bump SCHEMA_VERSION?)")
    return errs
