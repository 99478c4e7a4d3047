"""Candidate tile configs per kernel + the static VMEM cost model.

Everything here is pure host arithmetic over python ints — no jax, no
device, no clock — so candidate generation and pruning run identically
on a chipless CI host and inside the trace-time lookup path.

The cost model reuses the residency math the kernels themselves
document:

- flash resident family (ops/flash_attention.py): the whole per-head kv
  stream lives in VMEM (k+v forward, k+v for dq) — ~``2 * S * H *
  dtype_bytes`` per operand, double-buffered by Mosaic because the
  block index map changes across grid cells; q/o/do/stat blocks ride
  alongside. This is the "~8 * S * H bytes" note above MAX_KERNEL_SEQ,
  and the model reproduces that 8k bf16 cap exactly
  (tests/test_tune.py::test_cost_model_matches_resident_cap).
- flash kvgrid family: O(block) residency — q/k/v/o blocks plus the
  fp32 (block_q, head) online-softmax scratch; independent of S.
- dk/dv kernel (shared by both families): kv blocks resident across the
  (group, q-block) sweep plus two fp32 (block_k, head) scratch
  accumulators.
- SSD fused kernel (ops/ssd.py): (L, L) fp32 C@B^T scratch, the
  per-group-member (R, N, P) fp32 carried state, and the L-row operand
  blocks.
- fused CE (ops/fused_ce.py): an XLA scan, not a Pallas kernel — the
  constraint is the fp32 (chunk, V) logits tile (one live in fwd, two in
  bwd: p and d_logits), budgeted against HBM headroom rather than VMEM.
- paged decode (ops/paged_attention.py): one (block_kv * Nkv, H) k and v
  block in each of the two buffers the kernel fills by hand, the (Nq, H)
  q/o blocks, and the fp32 online-softmax scratch — O(block) residency
  like the kvgrid family, plus the scalar-prefetched page table in SMEM.
"""

from typing import Dict, List, Optional

# Per-core VMEM budget by chip kind. ~16 MiB/core is the working figure
# the shipped kernels were sized against (the resident flash family's 8k
# bf16 sequence cap lands exactly at this budget); chips we have not
# measured inherit the conservative default.
CHIP_VMEM_BYTES: Dict[str, int] = {
    "v4": 16 << 20,
    "v5e": 16 << 20,
    "v5p": 16 << 20,
    "v6e": 16 << 20,
    "cpu": 16 << 20,  # interpret mode runs the same block algebra
}
DEFAULT_VMEM_BYTES = 16 << 20

# HBM headroom budget for the fused-CE logits tile (the tile competes
# with params/activations for the 16 GB chip). 8 GiB is calibrated
# against measured reality: the 128k-vocab long-context bench rows run
# chunk=4096 (a ~4.2 GiB fp32 tile pair) on a 16 GB v5e, so the budget
# must admit it; 8192 at 128k vocab (~8.4 GiB) is where a full train
# step stops fitting.
CE_HBM_BUDGET_BYTES = 8 << 30

DTYPE_BYTES = {
    "bfloat16": 2,
    "float16": 2,
    "float32": 4,
    "int8": 1,
}

# Mosaic double-buffers grid-streamed blocks (the next cell's DMA runs
# behind the current cell's compute).
_DB = 2

# Today's static defaults — the last link of the fallback chain, and the
# values `kernel_tuning="off"` must reproduce bit-identically.
FLASH_DEFAULT_BLOCK_Q = 512
FLASH_DEFAULT_BLOCK_K = 512
SSD_DEFAULT_CHUNK = 256
CE_DEFAULT_CHUNK = 4096

_BLOCK_CHOICES = (128, 256, 512, 1024, 2048)
_SSD_CHUNK_CHOICES = (128, 256, 512)
_CE_CHUNK_CHOICES = (1024, 2048, 4096, 8192, 16384)

# Quantized flash family: the k stream (with q, the operands of the
# score GEMM — v is never quantized) rides in a 1-byte wire format,
# cutting the resident family's k+v residency 1.5x vs bf16 and lifting
# its sequence cap past 16k. None = today's unquantized kernels.
_FLASH_QUANT_CHOICES = (None, "int8", "fp8")


def dtype_bytes(dtype: str) -> int:
    return DTYPE_BYTES.get(str(dtype), 4)


def vmem_budget(chip: str) -> int:
    return CHIP_VMEM_BYTES.get(chip, DEFAULT_VMEM_BYTES)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def flash_sig(q_shape, k_shape) -> Dict[str, int]:
    """Shape signature of one attention call, (B, S, N, H) layout."""
    b, sq, nq, h = q_shape
    _, sk, nkv, _ = k_shape
    return {
        "batch": int(b),
        "nq": int(nq),
        "nkv": int(nkv),
        "seq_q": int(sq),
        "seq_k": int(sk),
        "head": int(h),
    }


def _flash_fwd_resident_bytes(sig, db, bq, kv_db):
    h, sk = sig["head"], sig["seq_k"]
    kv = sk * h * (kv_db + db) * _DB  # k (wire width) + v, whole stream
    q_o = 2 * bq * h * db * _DB  # q in + o out blocks
    lse = bq * 4 * _DB
    acc = bq * h * 4 + 2 * bq * 4  # fp32 acc + running max/denominator
    return kv + q_o + lse + acc


def _flash_fwd_kvgrid_bytes(sig, db, bq, bk, kv_db):
    h = sig["head"]
    kv = bk * h * (kv_db + db) * _DB
    q_o = 2 * bq * h * db * _DB
    lse = bq * 4 * _DB
    scratch = bq * h * 4 + 2 * bq * 4  # VMEM scratch: acc, m, l
    return kv + q_o + lse + scratch


def _flash_dq_resident_bytes(sig, db, bq, kv_db):
    h, sk = sig["head"], sig["seq_k"]
    kv = sk * h * (kv_db + db) * _DB
    blocks = 3 * bq * h * db * _DB  # q, do in + dq out
    stats = 2 * bq * 4 * _DB  # lse, delta
    acc = bq * h * 4  # fori-loop fp32 dq accumulator
    return kv + blocks + stats + acc


def _flash_dq_kvgrid_bytes(sig, db, bq, bk, kv_db):
    h = sig["head"]
    kv = bk * h * (kv_db + db) * _DB
    blocks = 3 * bq * h * db * _DB
    stats = 2 * bq * 4 * _DB
    scratch = bq * h * 4
    return kv + blocks + stats + scratch


def _flash_dkv_bytes(sig, db, bq, bk, kv_db):
    # shared by both families: kv blocks resident across the (g, qi)
    # sweep, q/do streamed, two fp32 scratch accumulators
    h = sig["head"]
    kv_blocks = bk * h * (kv_db + db) * _DB
    dkv_out = 2 * bk * h * 4 * _DB  # fp32 outputs
    q_do = 2 * bq * h * db * _DB
    stats = 2 * bq * 4 * _DB
    scratch = 2 * bk * h * 4
    return kv_blocks + dkv_out + q_do + stats + scratch


def flash_vmem_bytes(family: str, sig: Dict[str, int], dtype: str,
                     block_q: int, block_k: int,
                     quant: Optional[str] = None) -> int:
    """Worst-case per-core VMEM over the kernels a training step runs
    (fwd + dq + dkv) for one family/tile choice. ``quant`` ("int8" /
    "fp8") prices the k stream at its 1-byte wire width — v stays
    full-width (only q/k ride the wire, ops/flash_attention.py). The
    per-block scale vectors are O(block) fp32, noise against the
    O(block*head) operands."""
    db = dtype_bytes(dtype)
    kv_db = 1 if quant else db
    if family == "resident":
        fwd = _flash_fwd_resident_bytes(sig, db, block_q, kv_db)
        dq = _flash_dq_resident_bytes(sig, db, block_q, kv_db)
    else:
        fwd = _flash_fwd_kvgrid_bytes(sig, db, block_q, block_k, kv_db)
        dq = _flash_dq_kvgrid_bytes(sig, db, block_q, block_k, kv_db)
    dkv = _flash_dkv_bytes(sig, db, block_q, block_k, kv_db)
    return max(fwd, dq, dkv)


def _legal_block(seq: int, b: int) -> bool:
    return b <= seq and seq % b == 0


def flash_candidates(sig: Dict[str, int], dtype: str, chip: str) -> List[Dict]:
    """Legal (family, block_q, block_k) configs under the VMEM budget,
    smallest-footprint last so the sweep can time cheap ones first."""
    budget = vmem_budget(chip)
    out = []
    for family in ("resident", "kvgrid"):
        for quant in _FLASH_QUANT_CHOICES:
            for bq in _BLOCK_CHOICES:
                if not _legal_block(sig["seq_q"], bq):
                    continue
                for bk in _BLOCK_CHOICES:
                    if not _legal_block(sig["seq_k"], bk):
                        continue
                    vmem = flash_vmem_bytes(family, sig, dtype, bq, bk, quant)
                    if vmem > budget:
                        continue
                    c = {
                        "family": family,
                        "block_q": bq,
                        "block_k": bk,
                        "vmem_bytes": vmem,
                    }
                    if quant:
                        c["quant"] = quant
                    out.append(c)
    return out


def flash_config_legal(config: Dict, sig: Dict[str, int], dtype: str,
                       chip: str) -> bool:
    """Is a table entry's config runnable for this exact shape on this
    chip? (Nearest-signature fallbacks must re-check: a block that
    divided the neighbor's sequence may not divide ours, and a resident
    pick near the cap may not fit a longer sequence.)"""
    family = config.get("family")
    bq = config.get("block_q", FLASH_DEFAULT_BLOCK_Q)
    bk = config.get("block_k", FLASH_DEFAULT_BLOCK_K)
    quant = config.get("quant")
    if family not in (None, "resident", "kvgrid"):
        return False
    if quant not in _FLASH_QUANT_CHOICES:
        return False
    if not isinstance(bq, int) or not isinstance(bk, int):
        return False
    if not (_legal_block(sig["seq_q"], bq) and _legal_block(sig["seq_k"], bk)):
        return False
    fam = family or "resident"
    return flash_vmem_bytes(fam, sig, dtype, bq, bk, quant) <= vmem_budget(chip)


def resident_max_seq(head: int, dtype: str, chip: str,
                     block_q: int = FLASH_DEFAULT_BLOCK_Q) -> int:
    """Largest power-of-two seq_k the resident family fits under the
    chip's VMEM budget — the cost-model restatement of MAX_KERNEL_SEQ."""
    s = 256
    while True:
        sig = {"batch": 1, "nq": 1, "nkv": 1, "seq_q": s * 2,
               "seq_k": s * 2, "head": head}
        if flash_vmem_bytes("resident", sig, dtype, block_q,
                            FLASH_DEFAULT_BLOCK_K) > vmem_budget(chip):
            return s
        s *= 2


# ---------------------------------------------------------------------------
# SSD (Mamba2 chunked scan)
# ---------------------------------------------------------------------------


def ssd_sig(x_shape, groups: int, dstate: int) -> Dict[str, int]:
    """x (B, S, H, P); groups/dstate from the B/C projections."""
    b, s, h, p = x_shape
    return {
        "batch": int(b),
        "seq": int(s),
        "heads": int(h),
        "headdim": int(p),
        "groups": int(groups),
        "dstate": int(dstate),
    }


def ssd_vmem_bytes(sig: Dict[str, int], dtype: str, chunk: int) -> int:
    """Fused-kernel residency for chunk length L: the (L, L) fp32
    C@B^T scratch, the (R, N, P) fp32 carried state, and the L-row
    operand/output blocks (ops/ssd.py::_fused_kernel)."""
    db = dtype_bytes(dtype)
    L = chunk
    p, n = sig["headdim"], sig["dstate"]
    r = max(1, sig["heads"] // max(1, sig["groups"]))
    cb = L * L * 4
    state = r * n * p * 4
    x_blk = L * p * db * _DB
    bc_blk = 2 * L * n * db * _DB
    rows = 2 * L * 4 * _DB  # cum + dt (1, L) fp32 rows
    y_out = L * p * 4 * _DB  # fp32 output block
    return cb + state + x_blk + bc_blk + rows + y_out


def ssd_candidates(sig: Dict[str, int], dtype: str, chip: str) -> List[Dict]:
    budget = vmem_budget(chip)
    out = []
    for L in _SSD_CHUNK_CHOICES:
        if L > sig["seq"] or sig["seq"] % L != 0:
            continue
        vmem = ssd_vmem_bytes(sig, dtype, L)
        if vmem > budget:
            continue
        out.append({"chunk": L, "vmem_bytes": vmem})
    return out


def ssd_config_legal(config: Dict, sig: Dict[str, int], dtype: str,
                     chip: str) -> bool:
    L = config.get("chunk")
    if not isinstance(L, int) or L <= 0:
        return False
    if L > sig["seq"] or sig["seq"] % L != 0:
        return False
    return ssd_vmem_bytes(sig, dtype, L) <= vmem_budget(chip)


# ---------------------------------------------------------------------------
# fused CE (chunked lm-head + cross-entropy)
# ---------------------------------------------------------------------------


def ce_sig(d_model: int, vocab: int) -> Dict[str, int]:
    return {"d_model": int(d_model), "vocab": int(vocab)}


def ce_working_set_bytes(sig: Dict[str, int], dtype: str, chunk: int) -> int:
    """Live-tile bytes of one bwd scan step: the fp32 (chunk, V) p and
    d_logits tiles plus the (chunk, D) x tile (ops/fused_ce.py)."""
    db = dtype_bytes(dtype)
    return 2 * chunk * sig["vocab"] * 4 + chunk * sig["d_model"] * db


def ce_candidates(sig: Dict[str, int], dtype: str, chip: str) -> List[Dict]:
    del chip  # the CE tile is HBM-budgeted, not VMEM-budgeted
    out = []
    for c in _CE_CHUNK_CHOICES:
        ws = ce_working_set_bytes(sig, dtype, c)
        if ws > CE_HBM_BUDGET_BYTES:
            continue
        out.append({"chunk": c, "working_set_bytes": ws})
    return out


def ce_config_legal(config: Dict, sig: Dict[str, int], dtype: str,
                    chip: str) -> bool:
    del chip
    c = config.get("chunk")
    if not isinstance(c, int) or c <= 0:
        return False
    return ce_working_set_bytes(sig, dtype, c) <= CE_HBM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# paged decode (serving: ragged paged-attention, ops/paged_attention.py)
# ---------------------------------------------------------------------------

PAGED_DEFAULT_PAGE_SIZE = 64
PAGED_DEFAULT_BLOCK_KV = 64

_PAGE_SIZE_CHOICES = (16, 32, 64, 128, 256)
_BLOCK_KV_MULTIPLES = (1, 2, 4)


def paged_decode_sig(batch: int, nq: int, nkv: int, head: int,
                     max_seq: int) -> Dict[str, int]:
    """Shape signature of one serving decode step: the ragged batch
    width, head geometry, and the per-sequence cache capacity the page
    table spans (max_pages * page_size)."""
    return {
        "batch": int(batch),
        "nq": int(nq),
        "nkv": int(nkv),
        "head": int(head),
        "max_seq": int(max_seq),
    }


def paged_decode_vmem_bytes(sig: Dict[str, int], dtype: str,
                            page_size: int, block_kv: int) -> int:
    """Per-core residency of one (batch row, kv block) cell of the
    decode kernel: k+v blocks of ``block_kv`` positions across every kv
    head (double-buffered — the next block's DMA runs behind the current
    block's compute), the (Nq, H) q/o blocks, the fp32 online-softmax
    scratch, one page's fp32 (Nq, page_size * Nkv) score and
    probability tiles, and the row's page-table slice in SMEM (4 bytes
    per page, counted for honesty though it never threatens the
    budget)."""
    db = dtype_bytes(dtype)
    h, nq, nkv = sig["head"], sig["nq"], max(1, sig["nkv"])
    kv = 2 * block_kv * nkv * h * db * _DB
    q_o = 2 * nq * h * db * _DB
    scratch = nq * h * 4 + 2 * nq * 4  # fp32 acc + m/l
    scores = 2 * nq * page_size * nkv * 4
    table = 4 * (sig["max_seq"] // max(1, page_size))
    return kv + q_o + scratch + scores + table


def paged_decode_candidates(sig: Dict[str, int], dtype: str,
                            chip: str) -> List[Dict]:
    """Legal (page_size, block_kv) tiles under the VMEM budget. The
    kernel fetches ``block_kv // page_size`` pool pages a block of a
    stream's walk, so enumeration covers block_kv multiples of page_size
    — more positions a block amortize the per-block overhead at the price
    of a wider VMEM buffer."""
    budget = vmem_budget(chip)
    out = []
    for ps in _PAGE_SIZE_CHOICES:
        if ps > sig["max_seq"] or sig["max_seq"] % ps != 0:
            continue
        for mult in _BLOCK_KV_MULTIPLES:
            bkv = ps * mult
            if bkv > sig["max_seq"]:
                continue
            vmem = paged_decode_vmem_bytes(sig, dtype, ps, bkv)
            if vmem > budget:
                continue
            out.append(
                {"page_size": ps, "block_kv": bkv, "vmem_bytes": vmem}
            )
    return out


def paged_decode_config_legal(config: Dict, sig: Dict[str, int], dtype: str,
                              chip: str) -> bool:
    ps = config.get("page_size")
    bkv = config.get("block_kv", ps)
    if not isinstance(ps, int) or ps <= 0:
        return False
    if not isinstance(bkv, int) or bkv <= 0 or bkv % ps != 0:
        return False
    if ps > sig["max_seq"] or sig["max_seq"] % ps != 0:
        return False
    return paged_decode_vmem_bytes(sig, dtype, ps, bkv) <= vmem_budget(chip)


# ---------------------------------------------------------------------------
# dcn_bucket (bucketed cross-slice gradient reduction, parallel/overlap.py)
# ---------------------------------------------------------------------------

DCN_BUCKET_DEFAULT_MB = 32

_DCN_BUCKET_MB_CHOICES = (4, 8, 16, 32, 64, 128)

# Per-chip-pair DCN characteristics for the bytes-on-wire cost model:
# effective per-chip cross-slice bandwidth (bytes/s) and the per-collective
# launch/rendezvous latency. Working figures from the multi-slice scaling
# guidance the dcn axis was sized against; chips we have not measured
# inherit the conservative default.
CHIP_DCN_BANDWIDTH: Dict[str, float] = {
    "v4": 25e9,
    "v5e": 12.5e9,
    "v5p": 50e9,
    "v6e": 25e9,
}
DEFAULT_DCN_BANDWIDTH = 12.5e9
DCN_COLLECTIVE_LATENCY_S = 50e-6


def dcn_bucket_sig(grad_mb: int, leaves: int, slices: int,
                   wire_bytes: int) -> Dict[str, int]:
    """Signature of one gradient-reduction schedule: total wire MB of
    the grad tree (rounded up), its leaf count, the slice count, and the
    wire width (1 for the fp8/int8 reduce formats, 2 for bf16)."""
    return {
        "grad_mb": max(1, int(grad_mb)),
        "leaves": int(leaves),
        "slices": int(slices),
        "wire_bytes": int(wire_bytes),
    }


def dcn_bucket_cost_s(sig: Dict[str, int], bucket_mb: int,
                      chip: str) -> float:
    """Exposed-latency estimate for one bucket size: K buckets pay K
    collective launches, and the LAST bucket's wire time cannot hide
    under any remaining backward compute (2x for the ring all-reduce's
    reduce+broadcast halves across slices). Minimizing trades launch
    count (favors big buckets) against the exposed tail (favors small
    ones)."""
    bw = CHIP_DCN_BANDWIDTH.get(chip, DEFAULT_DCN_BANDWIDTH)
    total = sig["grad_mb"] << 20
    bucket = max(1, int(bucket_mb)) << 20
    k = max(1, -(-total // bucket))  # ceil
    tail_bytes = min(bucket, total)
    hops = 2 * (sig["slices"] - 1) / max(1, sig["slices"])
    return k * DCN_COLLECTIVE_LATENCY_S + tail_bytes * hops / bw


def dcn_bucket_candidates(sig: Dict[str, int], dtype: str,
                          chip: str) -> List[Dict]:
    """Legal bucket sizes with their modeled exposed cost. A candidate
    larger than the grad tree collapses to one bucket — legal (it is
    exactly the unsplit schedule) but only the smallest such size is
    kept, so the sweep never times duplicates."""
    del dtype  # the wire width is part of the signature
    out = []
    seen_single = False
    for mb in _DCN_BUCKET_MB_CHOICES:
        if mb >= sig["grad_mb"]:
            if seen_single:
                continue
            seen_single = True
        out.append({
            "bucket_mb": mb,
            "cost_us": round(dcn_bucket_cost_s(sig, mb, chip) * 1e6, 3),
        })
    return out


def dcn_bucket_config_legal(config: Dict, sig: Dict[str, int], dtype: str,
                            chip: str) -> bool:
    del sig, dtype, chip  # any positive size buckets any tree
    mb = config.get("bucket_mb")
    return isinstance(mb, int) and not isinstance(mb, bool) and mb > 0


LEGALITY = {
    "flash_attention": flash_config_legal,
    "ssd": ssd_config_legal,
    "fused_ce": ce_config_legal,
    "paged_decode": paged_decode_config_legal,
    "dcn_bucket": dcn_bucket_config_legal,
}

CANDIDATES = {
    "flash_attention": flash_candidates,
    "ssd": ssd_candidates,
    "fused_ce": ce_candidates,
    "paged_decode": paged_decode_candidates,
    "dcn_bucket": dcn_bucket_candidates,
}


def config_legal(kernel: str, config: Dict, sig: Dict[str, int], dtype: str,
                 chip: str) -> bool:
    fn = LEGALITY.get(kernel)
    return bool(fn and fn(config, sig, dtype, chip))
