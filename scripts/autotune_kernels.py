"""On-device kernel autotune sweep -> KERNEL_TUNING.json.

For every (kernel, shape signature, dtype) in the bench-derived suite,
enumerate the legal tile candidates (fms_fsdp_tpu/tune/candidates.py —
divisibility + static VMEM pruning, no device needed), time the
survivors on the attached chip (fwd+bwd, proper warmup and
``block_until_ready``), and write the winners into the schema-versioned
tuning table the trace-time lookup reads
(fms_fsdp_tpu/tune/{table,lookup}.py).

Robustness contract mirrors aot_lower_kernels.py: the parent
never imports jax; every candidate times in its own ``--measure``
subprocess under a watchdog, so one Mosaic hang or OOM yields an error
entry instead of killing the sweep. Measured entries replace
cost-model-seeded ones; a failed candidate simply never wins.

Modes:
    python scripts/autotune_kernels.py              # full on-chip sweep
    python scripts/autotune_kernels.py --dry-run    # candidate gen +
        VMEM pruning only: pure host arithmetic, no jax import, runs on
        any CI box (exercised by tests/test_tune.py and pytest.yml)
    python scripts/autotune_kernels.py --lookup-only [--chip v5e]
        # resolve the whole suite through the committed table (exact /
        # nearest / default per entry) without timing anything
    python scripts/autotune_kernels.py --seed-cost-model [--chip v5e]
        # (re)seed table entries from the cost model without a chip —
        # never overwrites measured entries

Env: AUTOTUNE_CANDIDATE_TIMEOUT_S (default 420), AUTOTUNE_STEPS,
AUTOTUNE_REPS, FMS_TUNE_CHIP (chip key override for the table).
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from fms_fsdp_tpu.tune import candidates as cand  # noqa: E402  (pure host code)
from fms_fsdp_tpu.tune.table import (  # noqa: E402
    TuningTable,
    default_table_path,
    validate_table,
)

CANDIDATE_TIMEOUT_S = int(os.environ.get("AUTOTUNE_CANDIDATE_TIMEOUT_S", "420"))
STEPS = int(os.environ.get("AUTOTUNE_STEPS", "10"))
REPS = int(os.environ.get("AUTOTUNE_REPS", "3"))

# The sweep suite: the kernel signatures of the training and serving
# shapes the table answers, in the training dtype. Keyed exactly as the
# trace-time lookup keys them, so a sweep win is a guaranteed exact hit.
SUITE = [
    # flash: llama2_7b headline (32q/32kv heads, head 128, seq 4096)
    ("flash_attention",
     {"batch": 2, "nq": 32, "nkv": 32, "seq_q": 4096, "seq_k": 4096,
      "head": 128},
     "bfloat16"),
    # flash: llama3_194m_4k (8 MHA heads)
    ("flash_attention",
     {"batch": 4, "nq": 8, "nkv": 8, "seq_q": 4096, "seq_k": 4096,
      "head": 128},
     "bfloat16"),
    # flash: the 16k / 32k long-context rows (kv-streamed territory)
    ("flash_attention",
     {"batch": 1, "nq": 8, "nkv": 8, "seq_q": 16384, "seq_k": 16384,
      "head": 128},
     "bfloat16"),
    ("flash_attention",
     {"batch": 1, "nq": 8, "nkv": 8, "seq_q": 32768, "seq_k": 32768,
      "head": 128},
     "bfloat16"),
    # SSD: mamba_9.8b head geometry (128 heads x P=64, N=128, 1 group)
    ("ssd",
     {"batch": 2, "seq": 4096, "heads": 128, "headdim": 64, "groups": 1,
      "dstate": 128},
     "bfloat16"),
    ("ssd",
     {"batch": 1, "seq": 16384, "heads": 128, "headdim": 64, "groups": 1,
      "dstate": 128},
     "bfloat16"),
    # fused CE: 7B-shaped head (d 4096, 32k vocab) and the 194m head
    # (d 1024, 128k vocab) the long-context rows run
    ("fused_ce", {"d_model": 4096, "vocab": 32000}, "bfloat16"),
    ("fused_ce", {"d_model": 1024, "vocab": 128256}, "bfloat16"),
    # paged decode (serving): 7B-shaped GQA decode batch and the
    # high-throughput small-model shape
    ("paged_decode",
     {"batch": 8, "nq": 32, "nkv": 8, "head": 128, "max_seq": 4096},
     "bfloat16"),
    ("paged_decode",
     {"batch": 16, "nq": 8, "nkv": 8, "head": 128, "max_seq": 2048},
     "bfloat16"),
    # dcn_bucket (parallel/overlap.py): the bucketed cross-slice gradient
    # reduction schedule. grad_mb = the grad tree's total wire MB —
    # 7B at bf16 wire (~13.4GB), the 194m-shaped model (~372MB), and the
    # 7B again on a 4-slice world at the 1-byte fp8 wire. leaves matches
    # the scan-stacked llama param tree (11 top-level leaves).
    ("dcn_bucket",
     {"grad_mb": 13344, "leaves": 11, "slices": 2, "wire_bytes": 2},
     "bfloat16"),
    ("dcn_bucket",
     {"grad_mb": 372, "leaves": 11, "slices": 2, "wire_bytes": 2},
     "bfloat16"),
    ("dcn_bucket",
     {"grad_mb": 6672, "leaves": 11, "slices": 4, "wire_bytes": 1},
     "bfloat16"),
]


def suite_candidates(chip: str):
    """[(kernel, sig, dtype, [candidate, ...]), ...] — pure host work."""
    out = []
    for kernel, sig, dtype in SUITE:
        gen = cand.CANDIDATES[kernel]
        out.append((kernel, sig, dtype, gen(sig, dtype, chip)))
    return out


def _default_config(kernel: str) -> dict:
    if kernel == "flash_attention":
        return {
            "family": None,
            "block_q": cand.FLASH_DEFAULT_BLOCK_Q,
            "block_k": cand.FLASH_DEFAULT_BLOCK_K,
        }
    if kernel == "ssd":
        return {"chunk": cand.SSD_DEFAULT_CHUNK}
    if kernel == "paged_decode":
        return {
            "page_size": cand.PAGED_DEFAULT_PAGE_SIZE,
            "block_kv": cand.PAGED_DEFAULT_BLOCK_KV,
        }
    if kernel == "dcn_bucket":
        return {"bucket_mb": cand.DCN_BUCKET_DEFAULT_MB}
    return {"chunk": cand.CE_DEFAULT_CHUNK}


def _cost_model_pick(kernel: str, sig: dict, cands: list, dtype: str,
                     chip: str) -> dict:
    """Chipless seed: prefer the static default when it survived
    pruning (it is the measured-in-anger configuration the shipped
    kernels were sized around), else the largest legal tile — bigger
    tiles amortize more loop overhead per DMA under the budget.
    dcn_bucket candidates carry a modeled exposed-latency cost instead
    of a VMEM footprint, so there the cheapest candidate wins."""
    if kernel == "dcn_bucket":
        if not cands:
            return _default_config(kernel)
        best = min(cands, key=lambda c: c.get("cost_us", float("inf")))
        return _strip(best)
    default = _default_config(kernel)
    if kernel == "paged_decode":
        # keep the measured-in-anger page size, but take the widest
        # legal block_kv at it: the v2 kernel fetches block_kv//page_size
        # pages per grid step, and more positions per cell amortize the
        # per-step overhead (tie-break the cost model can price blind)
        at_ps = [c for c in cands
                 if c.get("page_size") == default["page_size"]]
        if at_ps:
            best = max(at_ps, key=lambda c: c["block_kv"])
            return _strip(best)
        return default
    for c in cands:
        if all(c.get(k) == v for k, v in default.items() if k != "family"):
            d = dict(default)
            if kernel == "flash_attention":
                d["family"] = (
                    "resident" if sig["seq_k"] <= cand.resident_max_seq(
                        sig["head"], dtype, chip) else "kvgrid"
                )
            return d
    if not cands:
        return default
    best = max(cands, key=lambda c: c.get("vmem_bytes",
                                          c.get("working_set_bytes", 0)))
    return {k: v for k, v in best.items()
            if k not in ("vmem_bytes", "working_set_bytes")}


# -- child: time one candidate ----------------------------------------------


def _measure_child(spec_json: str):
    spec = json.loads(spec_json)
    kernel, sig, dtype, config = (
        spec["kernel"], spec["sig"], spec["dtype"], spec["config"],
    )
    import jax
    import jax.numpy as jnp

    # pin everything: the candidate under test must be exactly what
    # runs, never a table resolution of it
    from fms_fsdp_tpu.tune.lookup import configure_kernel_tuning

    configure_kernel_tuning("off")
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

    if kernel == "flash_attention":
        from fms_fsdp_tpu.ops.flash_attention import flash_attention

        b, nq, nkv, sq, sk, h = (
            sig["batch"], sig["nq"], sig["nkv"], sig["seq_q"],
            sig["seq_k"], sig["head"],
        )
        q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, nq, h), dt)
        kv = jax.random.normal(jax.random.PRNGKey(1), (b, sk, nkv, h), dt)

        def loss(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=True,
                    block_q=config["block_q"], block_k=config["block_k"],
                    variant=config.get("family"),
                    # quant candidates must time the path production
                    # runs: the q/k wire round-trip + kernel, not the
                    # bare unquantized kernel
                    quant=config.get("quant"),
                ).astype(jnp.float32)
            )

        f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        args = (q, kv, kv)
    elif kernel == "ssd":
        from fms_fsdp_tpu.ops.ssd import ssd_scan

        b, s, hh, p, g, n = (
            sig["batch"], sig["seq"], sig["heads"], sig["headdim"],
            sig["groups"], sig["dstate"],
        )
        x = jax.random.normal(jax.random.PRNGKey(0), (b, s, hh, p), dt)
        dts = jax.nn.softplus(
            jax.random.normal(jax.random.PRNGKey(1), (b, s, hh))
        )
        A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (hh,)))
        Bm = jax.random.normal(jax.random.PRNGKey(3), (b, s, g, n), dt)
        Cm = jax.random.normal(jax.random.PRNGKey(4), (b, s, g, n), dt)

        def loss(x, Bm, Cm):
            return jnp.sum(
                ssd_scan(
                    x, dts, A, Bm, Cm, chunk_size=config["chunk"]
                ).astype(jnp.float32)
            )

        f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        args = (x, Bm, Cm)
    elif kernel == "paged_decode":
        from fms_fsdp_tpu.ops.paged_attention import paged_attention_kernel

        b, nq, nkv, h, max_seq = (
            sig["batch"], sig["nq"], sig["nkv"], sig["head"],
            sig["max_seq"],
        )
        ps = config["page_size"]
        maxp = max_seq // ps
        # pool sized for the batch at capacity; sequential page tables
        # with rows at ~3/4 capacity (the ragged steady state)
        pool = b * maxp + 2
        kp = jax.random.normal(jax.random.PRNGKey(0), (pool, ps, nkv, h), dt)
        vp = jax.random.normal(jax.random.PRNGKey(1), (pool, ps, nkv, h), dt)
        q = jax.random.normal(jax.random.PRNGKey(2), (b, nq, h), dt)
        import numpy as np

        table = np.arange(2, 2 + b * maxp, dtype=np.int32).reshape(b, maxp)
        lens = np.full((b,), (3 * max_seq) // 4, np.int32)

        bkv = int(config.get("block_kv", ps))
        f = jax.jit(
            lambda q, kp, vp, t, l: paged_attention_kernel(
                q, kp, vp, t, l, block_kv=bkv
            )
        )
        args = (q, kp, vp, jnp.asarray(table), jnp.asarray(lens))
    elif kernel == "dcn_bucket":
        # time the SCHEDULE, not a kernel: K sequential bucket-sized
        # all-reduces over every attached device (on a multi-slice host
        # that path crosses the DCN; single-slice sweeps measure the
        # interconnect they have). Payload per reduce = one bucket's
        # wire bytes in fp32 elements, K = ceil(grad_mb / bucket_mb) —
        # the same arithmetic the cost model prices.
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = np.array(jax.devices())
        mesh = Mesh(devs, ("x",))
        bucket_mb = int(config["bucket_mb"])
        total_mb = int(sig["grad_mb"])
        k_buckets = max(1, -(-total_mb // bucket_mb))
        nbytes = min(bucket_mb, total_mb) * 1024 * 1024
        n = max(1, nbytes // 4)
        x = jax.device_put(
            jnp.ones((len(devs), n), jnp.float32),
            NamedSharding(mesh, P("x")),
        )
        reduce_fn = jax.jit(
            lambda a: jnp.sum(a, axis=0),
            out_shardings=NamedSharding(mesh, P()),
        )

        def f(a, _k=k_buckets):
            out = None
            for _ in range(_k):
                out = reduce_fn(a)
            return out

        args = (x,)
    else:  # fused_ce
        from fms_fsdp_tpu.ops.fused_ce import fused_linear_cross_entropy

        d, v = sig["d_model"], sig["vocab"]
        toks = 8192  # one bench-row step's worth of tokens (bs*seq scale)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, toks, d), dt)
        w = jax.random.normal(jax.random.PRNGKey(1), (d, v), dt)
        labels = jax.random.randint(
            jax.random.PRNGKey(2), (1, toks), 0, v, dtype=jnp.int32
        )

        def loss(x, w):
            return fused_linear_cross_entropy(x, w, labels, config["chunk"])

        f = jax.jit(jax.grad(loss, argnums=(0, 1)))
        args = (x, w)

    # warmup/compile, then best-of-REPS amortized timing
    out = f(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = f(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / STEPS)
    print("AUTOTUNE_JSON:" + json.dumps({"ms": best * 1e3}))


# -- parent ------------------------------------------------------------------


def _detect_chip() -> str:
    """Chip key via a probe subprocess (the parent never imports jax)."""
    code = (
        "from fms_fsdp_tpu.tune.lookup import chip_kind;"
        "print('CHIP:' + chip_kind())"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, timeout=240, text=True, cwd=REPO,
        )
        for line in (proc.stdout or "").splitlines():
            if line.startswith("CHIP:"):
                return line.split(":", 1)[1].strip()
    except subprocess.TimeoutExpired:
        pass
    return "unknown"


def _time_candidate(kernel, sig, dtype, config):
    spec = json.dumps(
        {"kernel": kernel, "sig": sig, "dtype": dtype, "config": config}
    )
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--measure", spec],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=CANDIDATE_TIMEOUT_S, text=True, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        return None, f"timeout after {CANDIDATE_TIMEOUT_S}s"
    for line in (proc.stdout or "").splitlines():
        if line.startswith("AUTOTUNE_JSON:"):
            try:
                return json.loads(line[len("AUTOTUNE_JSON:"):])["ms"], None
            except (json.JSONDecodeError, KeyError):
                break
    tail = " | ".join((proc.stdout or "").strip().splitlines()[-3:])
    return None, f"rc={proc.returncode}: {tail}"[:300]


def _strip(config: dict) -> dict:
    return {k: v for k, v in config.items()
            if k not in ("vmem_bytes", "working_set_bytes", "cost_us")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="candidate generation + VMEM pruning only")
    ap.add_argument("--lookup-only", action="store_true",
                    help="resolve the suite through the table, no timing")
    ap.add_argument("--seed-cost-model", action="store_true",
                    help="write cost-model picks for entries lacking "
                         "measured data")
    ap.add_argument("--chip", default=os.environ.get("FMS_TUNE_CHIP", ""),
                    help="chip key for the table (default: detect)")
    ap.add_argument("--table", default=default_table_path())
    ap.add_argument("--measure", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.measure:
        _measure_child(args.measure)
        return

    chip = args.chip or ("v5e" if args.dry_run else _detect_chip())

    if args.dry_run:
        report = []
        for kernel, sig, dtype, cands in suite_candidates(chip):
            report.append(
                {
                    "kernel": kernel, "signature": sig, "dtype": dtype,
                    "chip": chip, "legal_candidates": len(cands),
                    "candidates": cands,
                    "cost_model_pick": _cost_model_pick(
                        kernel, sig, cands, dtype, chip
                    ),
                }
            )
        doc = {"mode": "dry_run", "chip": chip, "suite": report}
        if os.path.exists(args.table):
            with open(args.table) as f:
                doc["table_violations"] = validate_table(json.load(f))
        print(json.dumps(doc, indent=1))
        return

    if args.lookup_only:
        from fms_fsdp_tpu.tune.lookup import (
            configure_kernel_tuning,
            resolve_ce_chunk,
            resolve_dcn_bucket,
            resolve_flash,
            resolve_paged_decode,
            resolve_ssd_chunk,
            choices,
        )

        configure_kernel_tuning("auto", args.table, chip=chip)
        resolved = []
        for kernel, sig, dtype in SUITE:
            if kernel == "flash_attention":
                bq, bk, fam, qnt, how = resolve_flash(
                    (sig["batch"], sig["seq_q"], sig["nq"], sig["head"]),
                    (sig["batch"], sig["seq_k"], sig["nkv"], sig["head"]),
                    dtype, chip=chip,
                )
                r = {"block_q": bq, "block_k": bk, "family": fam,
                     "quant": qnt, "how": how}
            elif kernel == "ssd":
                L = resolve_ssd_chunk(
                    (sig["batch"], sig["seq"], sig["heads"],
                     sig["headdim"]),
                    sig["groups"], sig["dstate"], dtype,
                    requested=cand.SSD_DEFAULT_CHUNK, chip=chip,
                )
                r = {"chunk": L, "how": choices()["ssd"]["how"]}
            elif kernel == "paged_decode":
                ps, bkv, how = resolve_paged_decode(
                    sig["batch"], sig["nq"], sig["nkv"], sig["head"],
                    sig["max_seq"], dtype, chip=chip,
                )
                r = {"page_size": ps, "block_kv": bkv, "how": how}
            elif kernel == "dcn_bucket":
                mb = resolve_dcn_bucket(
                    sig["grad_mb"], sig["leaves"], sig["slices"],
                    sig["wire_bytes"], requested=0, chip=chip,
                )
                r = {"bucket_mb": mb,
                     "how": choices()["dcn_bucket"]["how"]}
            else:
                c = resolve_ce_chunk(
                    sig["d_model"], sig["vocab"], dtype,
                    requested=cand.CE_DEFAULT_CHUNK, chip=chip,
                )
                r = {"chunk": c, "how": choices()["ce"]["how"]}
            resolved.append(
                {"kernel": kernel, "signature": sig, "resolved": r}
            )
        print(json.dumps(
            {"mode": "lookup_only", "chip": chip, "resolved": resolved},
            indent=1,
        ))
        return

    # write modes: load (or create) the table
    try:
        table = TuningTable.load(args.table)
    except (OSError, ValueError):
        table = TuningTable(path=args.table)

    if args.seed_cost_model:
        for kernel, sig, dtype, cands in suite_candidates(chip):
            pick = _cost_model_pick(kernel, sig, cands, dtype, chip)
            table.add(kernel, chip, dtype, sig, pick, source="cost_model")
        table.save(args.table)
        print(json.dumps({"mode": "seed_cost_model", "chip": chip,
                          "entries": len(table.doc["entries"])}))
        return

    # full sweep
    results = []
    for kernel, sig, dtype, cands in suite_candidates(chip):
        timed = []
        for config in cands:
            config = _strip(config)
            ms, err = _time_candidate(kernel, sig, dtype, config)
            status = f"{ms:.3f}ms" if ms is not None else f"ERR {err}"
            print(f"[tune] {kernel} {sig} {config}: {status}", flush=True)
            timed.append({"config": config, "ms": ms, "error": err})
        ok = [t for t in timed if t["ms"] is not None]
        if ok:
            win = min(ok, key=lambda t: t["ms"])
            table.add(kernel, chip, dtype, sig, win["config"],
                      source="measured", measured_ms=round(win["ms"], 4))
        results.append(
            {"kernel": kernel, "signature": sig, "timed": timed,
             "winner": (win["config"] if ok else None)}
        )
    table.save(args.table)
    print(json.dumps(
        {"mode": "sweep", "chip": chip, "table": args.table,
         "swept": len(results),
         "winners": sum(1 for r in results if r["winner"])},
    ))


if __name__ == "__main__":
    main()
