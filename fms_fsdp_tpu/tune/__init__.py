"""Kernel autotuning: measured block/tile selection with a persistent
per-chip tuning table.

Three layers (docs/performance.md "Autotuning"):

- :mod:`candidates` — enumerate legal tile configs per kernel and prune
  statically against a per-chip VMEM budget (the same residency math the
  kernels document; no device, no timing);
- :mod:`table` — the schema-versioned JSON tuning table committed
  in-repo (KERNEL_TUNING.json), keyed by
  (kernel, shape signature, dtype, chip kind);
- :mod:`lookup` — trace-time resolution wired into
  ops/{flash_attention,ssd,fused_ce} and the serving engine's paged
  decode (resolve_paged_decode, answered once at engine build): exact
  table match first, nearest signature next, today's static defaults
  last. Pure table + cost model — the lookup path never times anything,
  so tier-1 CPU runs are fully deterministic.

The on-device sweep that fills the table is scripts/autotune_kernels.py.
"""

from fms_fsdp_tpu.tune.lookup import (  # noqa: F401
    attach_registry,
    choices,
    configure_kernel_tuning,
    resolve_ce_chunk,
    resolve_flash,
    resolve_paged_decode,
    resolve_ssd_chunk,
)
from fms_fsdp_tpu.tune.table import (  # noqa: F401
    TUNING_SCHEMA_VERSION,
    TuningTable,
    default_table_path,
    validate_table,
)
