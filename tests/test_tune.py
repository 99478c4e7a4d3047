"""Kernel autotuner: table round trip, lookup fallback chain, VMEM cost
model vs the kernels' own residency math, config/env precedence, CPU
determinism, bit-identical "off" behavior and the _pick_block
degradation signal."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from fms_fsdp_tpu.obs.registry import MetricRegistry
from fms_fsdp_tpu.tune import candidates as cand
from fms_fsdp_tpu.tune import lookup
from fms_fsdp_tpu.tune.table import (
    TUNING_SCHEMA_VERSION,
    TuningTable,
    default_table_path,
    validate_table,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FLASH_SIG = {"batch": 1, "nq": 4, "nkv": 2, "seq_q": 512, "seq_k": 512,
             "head": 128}


@pytest.fixture(autouse=True)
def _reset_tuning():
    """Every test starts from the import-time default and leaves no
    forcing behind (the same no-inheritance rule the step build has)."""
    lookup.configure_kernel_tuning(None)
    lookup.attach_registry(None)
    yield
    lookup.configure_kernel_tuning(None)
    lookup.attach_registry(None)


def _table_with(tmp_path, entries):
    t = TuningTable(path=str(tmp_path / "table.json"))
    for kernel, chip, dtype, sig, config in entries:
        t.add(kernel, chip, dtype, sig, config, source="measured",
              measured_ms=1.0)
    return t.save()


# ---------------------------------------------------------------------------
# table round trip + fallback chain
# ---------------------------------------------------------------------------


def test_table_round_trip_exact_nearest_default(tmp_path):
    path = _table_with(
        tmp_path,
        [
            ("flash_attention", "v5e", "bfloat16", FLASH_SIG,
             {"family": "kvgrid", "block_q": 256, "block_k": 128}),
        ],
    )
    t = TuningTable.load(path)
    # exact
    config, how = t.lookup("flash_attention", "v5e", "bfloat16", FLASH_SIG)
    assert how == "exact" and config["block_q"] == 256

    # nearest: same keys, different values
    near = dict(FLASH_SIG, seq_q=1024, seq_k=1024)
    config, how = t.lookup("flash_attention", "v5e", "bfloat16", near)
    assert how == "nearest" and config["block_k"] == 128

    # default: wrong chip / dtype / kernel all miss
    for k, c, d in [
        ("flash_attention", "v4", "bfloat16"),
        ("flash_attention", "v5e", "float32"),
        ("ssd", "v5e", "bfloat16"),
    ]:
        config, how = t.lookup(k, c, d, FLASH_SIG)
        assert config is None and how is None


def test_table_nearest_prefers_closer_signature(tmp_path):
    far = dict(FLASH_SIG, seq_q=8192, seq_k=8192)
    close = dict(FLASH_SIG, seq_q=1024, seq_k=1024)
    path = _table_with(
        tmp_path,
        [
            ("flash_attention", "v5e", "bfloat16", far,
             {"family": "resident", "block_q": 1024, "block_k": 1024}),
            ("flash_attention", "v5e", "bfloat16", close,
             {"family": "resident", "block_q": 256, "block_k": 256}),
        ],
    )
    t = TuningTable.load(path)
    config, how = t.lookup(
        "flash_attention", "v5e", "bfloat16", dict(FLASH_SIG, seq_q=2048,
                                                   seq_k=2048)
    )
    assert how == "nearest" and config["block_q"] == 256


def test_table_validation_catches_garbage():
    assert validate_table({"schema_version": 999, "entries": []})
    assert validate_table({"schema_version": TUNING_SCHEMA_VERSION})
    errs = validate_table(
        {
            "schema_version": TUNING_SCHEMA_VERSION,
            "entries": [{"kernel": "nope"}],
        }
    )
    assert any("unknown kernel" in e for e in errs)
    assert any("missing" in e for e in errs)


def test_committed_table_is_valid_and_serves_bench_shapes():
    """The in-repo table must validate AND answer the bench signatures
    exactly — the acceptance contract for kernel_tuning="auto"."""
    with open(default_table_path()) as f:
        doc = json.load(f)
    assert validate_table(doc) == []
    t = TuningTable.load(default_table_path())
    # headline flash shape (llama2-7b-shaped row)
    config, how = t.lookup(
        "flash_attention", "v5e", "bfloat16",
        {"batch": 2, "nq": 32, "nkv": 32, "seq_q": 4096, "seq_k": 4096,
         "head": 128},
    )
    assert how == "exact" and config["block_q"] >= 128
    # SSD (mamba_9.8b-shaped row)
    config, how = t.lookup(
        "ssd", "v5e", "bfloat16",
        {"batch": 2, "seq": 4096, "heads": 128, "headdim": 64,
         "groups": 1, "dstate": 128},
    )
    assert how == "exact" and config["chunk"] > 0
    # fused CE (7B head)
    config, how = t.lookup(
        "fused_ce", "v5e", "bfloat16", {"d_model": 4096, "vocab": 32000}
    )
    assert how == "exact" and config["chunk"] > 0
    # paged decode (the 7B-shaped serving signature)
    config, how = t.lookup(
        "paged_decode", "v5e", "bfloat16",
        {"batch": 8, "nq": 32, "nkv": 8, "head": 128, "max_seq": 4096},
    )
    assert how == "exact" and config["page_size"] > 0
    assert config["block_kv"] % config["page_size"] == 0
    # dcn_bucket (the 7B-shaped bf16-wire reduction schedule)
    config, how = t.lookup(
        "dcn_bucket", "v5e", "bfloat16",
        {"grad_mb": 13344, "leaves": 11, "slices": 2, "wire_bytes": 2},
    )
    assert how == "exact" and config["bucket_mb"] > 0


def test_measured_entry_not_clobbered_by_cost_model(tmp_path):
    t = TuningTable(path=str(tmp_path / "t.json"))
    t.add("ssd", "v5e", "bfloat16", {"seq": 4096}, {"chunk": 512},
          source="measured", measured_ms=2.0)
    t.add("ssd", "v5e", "bfloat16", {"seq": 4096}, {"chunk": 128},
          source="cost_model")
    config, _ = t.lookup("ssd", "v5e", "bfloat16", {"seq": 4096})
    assert config["chunk"] == 512  # measured wins
    t.add("ssd", "v5e", "bfloat16", {"seq": 4096}, {"chunk": 256},
          source="measured", measured_ms=1.0)
    config, _ = t.lookup("ssd", "v5e", "bfloat16", {"seq": 4096})
    assert config["chunk"] == 256  # newer measurement replaces


# ---------------------------------------------------------------------------
# VMEM cost model vs the kernels' residency math
# ---------------------------------------------------------------------------


def test_cost_model_matches_resident_cap():
    """The resident family's budgeted max sequence must equal the
    kernels' documented MAX_KERNEL_SEQ for the shipped bf16/head-128
    geometry — the cost model and the kernel family switch must agree."""
    from fms_fsdp_tpu.ops.flash_attention import MAX_KERNEL_SEQ

    assert cand.resident_max_seq(128, "bfloat16", "v5e") == MAX_KERNEL_SEQ


def test_flash_candidates_prune_resident_past_cap():
    sig16k = {"batch": 1, "nq": 8, "nkv": 8, "seq_q": 16384,
              "seq_k": 16384, "head": 128}
    cands16k = cand.flash_candidates(sig16k, "bfloat16", "v5e")
    fams = {c["family"] for c in cands16k if not c.get("quant")}
    assert fams == {"kvgrid"}  # bf16 resident cannot fit 16k in VMEM
    # the quantized family's 1-byte kv stream is exactly what lifts the
    # resident cap past 16k — the candidate set must reflect it
    assert {"resident", "kvgrid"} == {
        c["family"] for c in cands16k if c.get("quant")
    }
    sig4k = dict(sig16k, seq_q=4096, seq_k=4096)
    fams = {c["family"] for c in
            cand.flash_candidates(sig4k, "bfloat16", "v5e")}
    assert fams == {"resident", "kvgrid"}


def test_flash_quant_candidates_enumerated_and_cheaper():
    """Every block choice is enumerated across the quant axis (None /
    int8 / fp8), and the quantized kv stream prices below bf16 for the
    same family/tiles."""
    sig = {"batch": 1, "nq": 8, "nkv": 8, "seq_q": 4096, "seq_k": 4096,
           "head": 128}
    cands = cand.flash_candidates(sig, "bfloat16", "v5e")
    quants = {c.get("quant") for c in cands}
    assert quants == {None, "int8", "fp8"}
    bf16 = cand.flash_vmem_bytes("resident", sig, "bfloat16", 512, 512)
    q8 = cand.flash_vmem_bytes("resident", sig, "bfloat16", 512, 512,
                               quant="int8")
    assert q8 < bf16
    # legality check accepts a quant-carrying config and rejects junk
    assert cand.flash_config_legal(
        {"family": "resident", "block_q": 512, "block_k": 512,
         "quant": "int8"}, sig, "bfloat16", "v5e")
    assert not cand.flash_config_legal(
        {"family": "resident", "block_q": 512, "block_k": 512,
         "quant": "int4"}, sig, "bfloat16", "v5e")


def test_kvgrid_footprint_independent_of_seq():
    a = cand.flash_vmem_bytes(
        "kvgrid", {"batch": 1, "nq": 8, "nkv": 8, "seq_q": 4096,
                   "seq_k": 4096, "head": 128}, "bfloat16", 512, 512)
    b = cand.flash_vmem_bytes(
        "kvgrid", {"batch": 1, "nq": 8, "nkv": 8, "seq_q": 32768,
                   "seq_k": 32768, "head": 128}, "bfloat16", 512, 512)
    assert a == b


def test_ssd_candidates_divide_sequence():
    sig = {"batch": 2, "seq": 4096, "heads": 128, "headdim": 64,
           "groups": 1, "dstate": 128}
    cands = cand.ssd_candidates(sig, "bfloat16", "v5e")
    assert cands and all(sig["seq"] % c["chunk"] == 0 for c in cands)
    # the shipped default must always survive pruning for bench shapes
    assert any(c["chunk"] == cand.SSD_DEFAULT_CHUNK for c in cands)


def test_ce_budget_admits_shipped_configs():
    # the 128k-vocab long-context rows run chunk=4096 on chip today; the
    # cost model must not prune a configuration known to fit
    assert cand.ce_config_legal(
        {"chunk": 4096}, {"d_model": 1024, "vocab": 128256}, "bfloat16",
        "v5e",
    )


def test_illegal_table_config_falls_back_to_default(tmp_path):
    # table says block_q=1024 for a seq-512 shape: illegal (1024 > 512
    # after divisibility) -> defaults, not a crash
    path = _table_with(
        tmp_path,
        [("flash_attention", "v5e", "bfloat16", FLASH_SIG,
          {"family": "resident", "block_q": 1024, "block_k": 384})],
    )
    lookup.configure_kernel_tuning("auto", path, chip="v5e")
    bq, bk, fam, qnt, how = lookup.resolve_flash(
        (1, 512, 4, 128), (1, 512, 2, 128), "bfloat16")
    assert (bq, bk) == (cand.FLASH_DEFAULT_BLOCK_Q,
                        cand.FLASH_DEFAULT_BLOCK_K)
    assert how == "default"


# ---------------------------------------------------------------------------
# lookup resolution: modes, precedence, determinism
# ---------------------------------------------------------------------------


def test_resolve_flash_auto_vs_off(tmp_path):
    path = _table_with(
        tmp_path,
        [("flash_attention", "v5e", "bfloat16", FLASH_SIG,
          {"family": "kvgrid", "block_q": 256, "block_k": 128})],
    )
    lookup.configure_kernel_tuning("auto", path, chip="v5e")
    bq, bk, fam, qnt, how = lookup.resolve_flash(
        (1, 512, 4, 128), (1, 512, 2, 128), "bfloat16")
    assert (bq, bk, fam, qnt, how) == (256, 128, "kvgrid", None, "exact")

    lookup.configure_kernel_tuning("off")
    bq, bk, fam, qnt, how = lookup.resolve_flash(
        (1, 512, 4, 128), (1, 512, 2, 128), "bfloat16")
    assert (bq, bk, fam, qnt, how) == (512, 512, None, None, "off")


def test_resolve_flash_explicit_blocks_pinned(tmp_path):
    path = _table_with(
        tmp_path,
        [("flash_attention", "v5e", "bfloat16", FLASH_SIG,
          {"family": "kvgrid", "block_q": 256, "block_k": 128})],
    )
    lookup.configure_kernel_tuning("auto", path, chip="v5e")
    bq, bk, fam, qnt, how = lookup.resolve_flash(
        (1, 512, 4, 128), (1, 512, 2, 128), "bfloat16",
        requested_q=128, requested_k=256)
    assert (bq, bk) == (128, 256)  # caller wins over the table
    assert how == "pinned"  # never labeled "off" while the mode is auto


def test_resolve_ssd_and_ce_chunks(tmp_path):
    ssd_sig = {"batch": 1, "seq": 1024, "heads": 4, "headdim": 64,
               "groups": 2, "dstate": 32}
    path = _table_with(
        tmp_path,
        [
            ("ssd", "v5e", "float32", ssd_sig, {"chunk": 128}),
            ("fused_ce", "v5e", "float32",
             {"d_model": 64, "vocab": 512}, {"chunk": 2048}),
        ],
    )
    lookup.configure_kernel_tuning("auto", path, chip="v5e")
    L = lookup.resolve_ssd_chunk((1, 1024, 4, 64), 2, 32, "float32",
                                 requested=256)
    assert L == 128
    c = lookup.resolve_ce_chunk(64, 512, "float32", requested=4096)
    assert c == 2048
    # a NON-default requested value is an explicit operator choice and
    # pins even under auto (forcing one knob must not require
    # kernel_tuning="off")
    assert lookup.resolve_ssd_chunk((1, 1024, 4, 64), 2, 32, "float32",
                                    requested=512) == 512
    assert lookup.choices()["ssd"]["how"] == "pinned"
    assert lookup.resolve_ce_chunk(64, 512, "float32",
                                   requested=1024) == 1024
    assert lookup.choices()["ce"]["how"] == "pinned"
    # off: requested wins
    lookup.configure_kernel_tuning("off")
    assert lookup.resolve_ssd_chunk((1, 1024, 4, 64), 2, 32, "float32",
                                    requested=256) == 256
    assert lookup.resolve_ce_chunk(64, 512, "float32",
                                   requested=4096) == 4096


def test_resolve_dcn_bucket_contract(tmp_path):
    """resolve_dcn_bucket follows the shared resolver contract: a
    nonzero TrainConfig.dcn_bucket_mb pins, the table answers exact,
    and a tableless host falls back to the cost model's cheapest
    candidate — never a blind constant."""
    sig = cand.dcn_bucket_sig(1024, 11, 2, 2)
    path = _table_with(
        tmp_path,
        [("dcn_bucket", "v5e", "bfloat16", sig, {"bucket_mb": 64})],
    )
    lookup.configure_kernel_tuning("auto", path, chip="v5e")
    assert lookup.resolve_dcn_bucket(1024, 11, 2, 2, chip="v5e") == 64
    assert lookup.choices()["dcn_bucket"]["how"] == "exact"
    # nonzero requested = explicit operator choice, pins under auto
    assert lookup.resolve_dcn_bucket(1024, 11, 2, 2, requested=8,
                                     chip="v5e") == 8
    assert lookup.choices()["dcn_bucket"]["how"] == "pinned"
    # no dcn_bucket entry in the table: the cost model picks the
    # cheapest modeled size instead of a blind constant
    other = TuningTable(path=str(tmp_path / "other.json"))
    other.add("ssd", "v5e", "bfloat16", {"seq": 4096}, {"chunk": 256},
              source="measured", measured_ms=1.0)
    lookup.configure_kernel_tuning("auto", other.save(), chip="v5e")
    mb = lookup.resolve_dcn_bucket(1024, 11, 2, 2, chip="v5e")
    cands = cand.dcn_bucket_candidates(sig, "bfloat16", "v5e")
    assert mb == min(cands, key=lambda c: c["cost_us"])["bucket_mb"]
    # off: requested (or the static default) wins, no table consulted
    lookup.configure_kernel_tuning("off")
    assert lookup.resolve_dcn_bucket(1024, 11, 2, 2, requested=16) == 16
    assert lookup.resolve_dcn_bucket(
        1024, 11, 2, 2) == cand.DCN_BUCKET_DEFAULT_MB
    assert lookup.choices()["dcn_bucket"]["how"] == "off"


def test_dcn_bucket_measured_never_clobbered(tmp_path):
    """A measured dcn_bucket winner survives cost-model reseeding —
    the same keep_measured discipline every kernel entry has."""
    sig = cand.dcn_bucket_sig(2048, 11, 2, 2)
    t = TuningTable(path=str(tmp_path / "t.json"))
    t.add("dcn_bucket", "v5e", "bfloat16", sig, {"bucket_mb": 32},
          source="measured", measured_ms=4.2)
    t.add("dcn_bucket", "v5e", "bfloat16", sig, {"bucket_mb": 128},
          source="cost_model")
    config, _ = t.lookup("dcn_bucket", "v5e", "bfloat16", sig)
    assert config["bucket_mb"] == 32


def test_dcn_bucket_candidates_cost_model_shape():
    """Candidate enumeration: every size carries a modeled cost, sizes
    at or past the grad total collapse to one bucket and only the
    smallest such size survives (no duplicate timings), and the cost
    model charges more slices a longer ring."""
    sig = cand.dcn_bucket_sig(48, 11, 2, 2)
    cands = cand.dcn_bucket_candidates(sig, "bfloat16", "v5e")
    assert all(c["cost_us"] > 0 for c in cands)
    single = [c["bucket_mb"] for c in cands if c["bucket_mb"] >= 48]
    assert single == [64]  # 64 kept, 128 pruned as a duplicate schedule
    four = cand.dcn_bucket_cost_s(cand.dcn_bucket_sig(48, 11, 4, 2), 16,
                                  "v5e")
    two = cand.dcn_bucket_cost_s(sig, 16, "v5e")
    assert four > two


def test_configure_precedence_env_vs_config(monkeypatch, tmp_path):
    """configure(None) restores the env default; an explicit configure
    beats it; a path-valued mode implies auto against that table."""
    path = _table_with(
        tmp_path,
        [("fused_ce", "v5e", "float32", {"d_model": 8, "vocab": 128},
          {"chunk": 1024})],
    )
    monkeypatch.setattr(lookup, "_ENV_MODE", "off")
    monkeypatch.setattr(lookup, "_ENV_TABLE", None)
    lookup.configure_kernel_tuning(None)
    assert lookup.tuning_mode() == "off"
    lookup.configure_kernel_tuning(path, chip="v5e")  # path => auto
    assert lookup.tuning_mode() == "auto"
    assert lookup.resolve_ce_chunk(8, 128, "float32", requested=4096) == 1024
    with pytest.raises(ValueError):
        lookup.configure_kernel_tuning("warp-speed")


def test_explicit_bad_table_path_fails_loud(tmp_path):
    """An operator-named table that cannot load must raise (a run
    labeled tuned-against-a-table it never read is the mislabeled-
    benchmark class); the committed default stays fallback-soft."""
    with pytest.raises(ValueError):
        lookup.configure_kernel_tuning(
            "auto", str(tmp_path / "missing.json"), chip="v5e"
        )
    bad = tmp_path / "corrupt.json"
    bad.write_text("{not json")
    with pytest.raises(ValueError):
        lookup.configure_kernel_tuning(str(bad), chip="v5e")


def test_configure_invalidates_table_cache(tmp_path):
    """A table regenerated at the same path is re-read by the next
    configure (next step build), not served stale from the cache."""
    path = _table_with(
        tmp_path,
        [("fused_ce", "v5e", "float32", {"d_model": 8, "vocab": 128},
          {"chunk": 1024})],
    )
    lookup.configure_kernel_tuning("auto", path, chip="v5e")
    assert lookup.resolve_ce_chunk(8, 128, "float32", requested=4096) == 1024
    t = TuningTable.load(path)
    t.add("fused_ce", "v5e", "float32", {"d_model": 8, "vocab": 128},
          {"chunk": 2048}, source="measured", measured_ms=0.5)
    t.save(path)
    lookup.configure_kernel_tuning("auto", path, chip="v5e")
    assert lookup.resolve_ce_chunk(8, 128, "float32", requested=4096) == 2048


def test_lookup_deterministic_and_clock_free(tmp_path):
    """Same inputs -> same answer, twice, and the lookup modules never
    touch a clock (no time import anywhere in the lookup path)."""
    import fms_fsdp_tpu.tune.candidates as cmod
    import fms_fsdp_tpu.tune.lookup as lmod
    import fms_fsdp_tpu.tune.table as tmod

    for mod in (lmod, tmod, cmod):
        assert "time" not in dir(mod), f"{mod.__name__} imports time"
        src_file = mod.__file__
        with open(src_file) as f:
            src = f.read()
        assert "import time" not in src and "perf_counter" not in src, (
            f"{mod.__name__} reads the clock"
        )
    lookup.configure_kernel_tuning("auto", chip="v5e")
    r1 = lookup.resolve_flash((2, 4096, 32, 128), (2, 4096, 32, 128),
                              "bfloat16")
    r2 = lookup.resolve_flash((2, 4096, 32, 128), (2, 4096, 32, 128),
                              "bfloat16")
    assert r1 == r2


def test_committed_table_resolves_bench_shapes_via_lookup_api():
    """kernel_tuning="auto" + the committed table: the bench-shape tile
    choices come from the table (exact), per the acceptance criteria."""
    lookup.configure_kernel_tuning("auto", chip="v5e")
    bq, bk, fam, qnt, how = lookup.resolve_flash(
        (2, 4096, 32, 128), (2, 4096, 32, 128), "bfloat16")
    assert how == "exact" and fam in ("resident", "kvgrid")
    # the committed table carries no quant entries: stock runs must
    # never silently select the quantized family
    assert qnt is None
    L = lookup.resolve_ssd_chunk((2, 4096, 128, 64), 1, 128, "bfloat16",
                                 requested=256)
    assert lookup.choices()["ssd"]["how"] == "exact" and 4096 % L == 0
    c = lookup.resolve_ce_chunk(4096, 32000, "bfloat16", requested=4096)
    assert lookup.choices()["ce"]["how"] == "exact" and c > 0


# ---------------------------------------------------------------------------
# kernel integration: bit-identical off, tuned engagement, gauges
# ---------------------------------------------------------------------------


def test_flash_off_bit_identical_to_static_defaults():
    from fms_fsdp_tpu.ops.flash_attention import flash_attention

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 4, 128),
                          jnp.float32)
    lookup.configure_kernel_tuning("off")
    off = flash_attention(q, q, q, interpret=True)
    pinned = flash_attention(q, q, q, interpret=True, block_q=512,
                             block_k=512)
    assert jnp.array_equal(off, pinned)


def test_flash_tuned_blocks_engage_and_match(tmp_path):
    from fms_fsdp_tpu.ops.flash_attention import flash_attention

    path = _table_with(
        tmp_path,
        [("flash_attention", "cpu", "float32", FLASH_SIG,
          {"family": "resident", "block_q": 128, "block_k": 256})],
    )
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 4, 128),
                          jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 512, 2, 128),
                          jnp.float32)
    lookup.configure_kernel_tuning("auto", path, chip="cpu")
    out = flash_attention(q, k, k, interpret=True)
    ch = lookup.choices()["flash"]
    assert (ch["block_q"], ch["block_k"], ch["how"]) == (128, 256, "exact")
    lookup.configure_kernel_tuning("off")
    ref = flash_attention(q, k, k, interpret=True)
    assert jnp.allclose(out, ref, atol=2e-5)


def test_flash_quant_family_from_table_engages(tmp_path):
    """A table entry carrying ``quant`` turns on the kv wire format:
    the output differs bitwise from the unquantized kernel (the
    round-trip is lossy) but stays within quantization tolerance, and
    the resolved mode lands in choices() + the quant_code gauge."""
    from fms_fsdp_tpu.ops.flash_attention import flash_attention

    path = _table_with(
        tmp_path,
        [("flash_attention", "cpu", "float32", FLASH_SIG,
          {"family": "resident", "block_q": 256, "block_k": 256,
           "quant": "int8"})],
    )
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 4, 128),
                          jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 512, 2, 128),
                          jnp.float32)
    reg = MetricRegistry()
    lookup.configure_kernel_tuning("auto", path, chip="cpu")
    lookup.attach_registry(reg)
    out = flash_attention(q, k, k, interpret=True)
    ch = lookup.choices()["flash"]
    assert (ch["quant"], ch["quant_code"], ch["how"]) == ("int8", 1, "exact")
    assert reg.snapshot()["kernel.tune.flash.quant_code"] == 1
    lookup.configure_kernel_tuning("off")
    ref = flash_attention(q, k, k, interpret=True)
    assert lookup.choices()["flash"]["quant_code"] == 0
    assert not jnp.array_equal(out, ref)  # the wire format engaged
    # int8 per-row q/k round-trip: scores shift by O(1/127) per operand
    assert jnp.allclose(out, ref, atol=0.05), float(
        jnp.max(jnp.abs(out - ref))
    )


def test_flash_quant_family_gradients_flow(tmp_path):
    """The straight-through wire round-trip must keep flash_attention
    differentiable: grads are finite and close to the unquantized
    kernel's (the STE passes cotangents through unchanged)."""
    from fms_fsdp_tpu.ops.flash_attention import flash_attention

    path = _table_with(
        tmp_path,
        [("flash_attention", "cpu", "float32", FLASH_SIG,
          {"family": "resident", "block_q": 256, "block_k": 256,
           "quant": "fp8"})],
    )
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 4, 128),
                          jnp.float32)
    lookup.configure_kernel_tuning("auto", path, chip="cpu")

    def loss(q):
        return flash_attention(q, q, q, interpret=True).sum()

    g_q = jax.grad(loss)(q)
    assert bool(jnp.all(jnp.isfinite(g_q)))
    lookup.configure_kernel_tuning("off")
    g_r = jax.grad(loss)(q)
    rel = float(jnp.linalg.norm(g_q - g_r) / jnp.linalg.norm(g_r))
    assert rel < 0.1, rel


def test_flash_quant_resident_past_cap_executes_kvgrid(tmp_path):
    """The cost model legalizes quantized resident past the bf16 8k cap
    (1-byte kv stream), but today's SIMULATED execution runs the
    full-width unquantized kernel — a table entry claiming resident at
    16k must execute as kvgrid (and the record must say so), not launch
    a bf16 resident kernel past its VMEM cap."""
    from fms_fsdp_tpu.ops.flash_attention import (
        MAX_KERNEL_SEQ,
        flash_attention,
    )

    seq = 2 * MAX_KERNEL_SEQ
    sig = {"batch": 1, "nq": 2, "nkv": 2, "seq_q": seq, "seq_k": seq,
           "head": 128}
    # the candidate really is cost-model legal on v5e...
    assert cand.flash_config_legal(
        {"family": "resident", "block_q": 512, "block_k": 512,
         "quant": "int8"}, sig, "bfloat16", "v5e")
    path = _table_with(
        tmp_path,
        [("flash_attention", "cpu", "bfloat16", sig,
          {"family": "resident", "block_q": 512, "block_k": 512,
           "quant": "int8"})],
    )
    lookup.configure_kernel_tuning("auto", path, chip="cpu")
    q = jax.ShapeDtypeStruct((1, seq, 2, 128), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, interpret=True), q, q, q
    )
    ch = lookup.choices()["flash"]
    # ...but what ran is the kv-streamed family, quant wire engaged
    assert ch["quant"] == "int8" and ch["kvgrid"] == 1


def test_ssd_tuned_chunk_engages_and_matches(tmp_path):
    from fms_fsdp_tpu.ops.ssd import ssd_scan

    sig = {"batch": 1, "seq": 512, "heads": 4, "headdim": 64,
           "groups": 2, "dstate": 32}
    path = _table_with(
        tmp_path, [("ssd", "cpu", "float32", sig, {"chunk": 128})]
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 4, 64))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (1, 512, 4)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (4,)))
    B = jax.random.normal(jax.random.PRNGKey(3), (1, 512, 2, 32))
    lookup.configure_kernel_tuning("auto", path, chip="cpu")
    y_tuned = ssd_scan(x, dt, A, B, B, chunk_size=256)
    assert lookup.choices()["ssd"] == {"chunk": 128, "how": "exact",
                                       "seq": 512}
    lookup.configure_kernel_tuning("off")
    y_off = ssd_scan(x, dt, A, B, B, chunk_size=256)
    # a different chunk length changes fp32 accumulation order, not the
    # math — compare at accumulation-noise tolerance
    assert jnp.allclose(y_tuned, y_off, rtol=1e-4, atol=1e-3)


def test_choices_land_in_registry_as_gauges(tmp_path):
    reg = MetricRegistry()
    lookup.configure_kernel_tuning("auto", chip="v5e")
    lookup.resolve_flash((2, 4096, 32, 128), (2, 4096, 32, 128),
                         "bfloat16")
    lookup.attach_registry(reg)  # late attach replays recorded choices
    snap = reg.snapshot()
    assert snap["kernel.tune.flash.block_q"] > 0
    assert "kernel.tune.flash.kvgrid" in snap
    lookup.resolve_ce_chunk(4096, 32000, "bfloat16", requested=4096)
    snap = reg.snapshot()
    assert snap["kernel.tune.ce.chunk"] > 0
    assert snap.get("kernel.tune.exact", 0) >= 1


def test_step_build_resolves_tuning_from_config(tmp_path):
    """make_train_step configures tuning from its own cfg each build —
    a later "off" build must not inherit the earlier table forcing."""
    from fms_fsdp_tpu.config import TrainConfig
    from fms_fsdp_tpu.models.configs import LlamaConfig
    from fms_fsdp_tpu.parallel.mesh import MeshConfig, build_mesh
    from fms_fsdp_tpu.train.step import make_optimizer, make_train_step

    model_cfg = LlamaConfig(
        src_vocab_size=128, emb_dim=64, nheads=2, nlayers=1,
        max_expected_seq_len=64,
    )
    mesh = build_mesh(MeshConfig(sharding_strategy="fsdp"))
    for mode, want in (("auto", "auto"), ("off", "off")):
        cfg = TrainConfig(
            batch_size=1, seq_length=64, fused_loss=True,
            kernel_tuning=mode, sharding_strategy="fsdp",
        )
        make_train_step(model_cfg, cfg, mesh, make_optimizer(cfg))
        assert lookup.tuning_mode() == want


# ---------------------------------------------------------------------------
# _pick_block degradation signal
# ---------------------------------------------------------------------------


def test_pick_block_degradation_logged():
    from fms_fsdp_tpu.ops.flash_attention import _pick_block

    reg = MetricRegistry()
    lookup.attach_registry(reg)
    # 2944 @ 512: halves 512 -> 256 -> 128 (2944 = 23 * 128) — below
    # half the request, must signal
    assert _pick_block(2944, 512, kind="q") == 128
    snap = reg.snapshot()
    assert snap["kernel.tune.block_degraded"] == 1
    assert snap["kernel.tune.block_degraded_q"] == 128
    # a clean divide must NOT signal
    assert _pick_block(4096, 512, kind="q") == 512
    assert reg.snapshot()["kernel.tune.block_degraded"] == 1
    # one halving (to exactly half) is quiet too: 768 = 256 * 3
    assert _pick_block(768, 512, kind="k") == 256
    assert reg.snapshot()["kernel.tune.block_degraded"] == 1


def test_flash_record_states_post_halving_blocks():
    """The recorded gauges state the tiles that actually ran: a
    non-power-of-two sequence halves the resolved request inside
    flash_attention, and the record follows."""
    from fms_fsdp_tpu.ops.flash_attention import flash_attention

    reg = MetricRegistry()
    lookup.configure_kernel_tuning("off")
    lookup.attach_registry(reg)
    # seq 640 = 128 * 5: default 512 doesn't divide it, so _pick_block
    # halves 512 -> 256 -> 128 before the kernel launches
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 640, 2, 128),
                          jnp.float32)
    flash_attention(q, q, q, interpret=True)
    ch = lookup.choices()["flash"]
    assert ch["block_q"] == 128 and ch["block_k"] == 128
    snap = reg.snapshot()
    assert snap["kernel.tune.flash.block_q"] == 128


def test_flash_record_states_seq_rule_family():
    """When resolve_flash returns fam=None (tuning off, or no table
    hit), the family is decided inside the op by the MAX_KERNEL_SEQ
    rule — the record must state the family that actually runs, not
    kvgrid=0. eval_shape traces flash_attention (the record is written
    at trace time) without executing the long-sequence kernel."""
    from fms_fsdp_tpu.ops.flash_attention import (
        MAX_KERNEL_SEQ,
        flash_attention,
    )

    reg = MetricRegistry()
    lookup.configure_kernel_tuning("off")
    lookup.attach_registry(reg)
    seq = 2 * MAX_KERNEL_SEQ  # past the resident cap: kvgrid runs
    q = jax.ShapeDtypeStruct((1, seq, 2, 128), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, interpret=True), q, q, q
    )
    ch = lookup.choices()["flash"]
    assert ch["how"] == "off" and ch["kvgrid"] == 1
    assert reg.snapshot()["kernel.tune.flash.kvgrid"] == 1
    # and below the cap the resident family is recorded
    q = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, interpret=True), q, q, q
    )
    assert lookup.choices()["flash"]["kvgrid"] == 0
    assert reg.snapshot()["kernel.tune.flash.kvgrid"] == 0


# ---------------------------------------------------------------------------
# autotune script: dry-run + lookup-only (no TPU, no timing)
# ---------------------------------------------------------------------------


def test_autotune_dry_run_candidates_and_pruning():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import autotune_kernels as ak
    finally:
        sys.path.pop(0)
    suite = ak.suite_candidates("v5e")
    assert len(suite) == len(ak.SUITE)
    by_kernel = {}
    for kernel, sig, dtype, cands in suite:
        assert cands, f"no legal candidates for {kernel} {sig}"
        by_kernel.setdefault(kernel, 0)
        by_kernel[kernel] += len(cands)
        pick = ak._cost_model_pick(kernel, sig, cands, dtype, "v5e")
        assert pick  # a pick always exists
        if kernel == "flash_attention" and sig["seq_k"] > 8192:
            # past the bf16 resident cap every UNQUANTIZED candidate is
            # kv-streamed; quantized kv (1-byte stream) may stay resident
            assert all(
                c["family"] == "kvgrid"
                for c in cands
                if not c.get("quant")
            )
    assert set(by_kernel) == {
        "flash_attention", "ssd", "fused_ce", "paged_decode", "dcn_bucket"
    }


@pytest.mark.slow
def test_autotune_script_dry_run_subprocess():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "autotune_kernels.py"), "--dry-run"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["mode"] == "dry_run"
    assert doc.get("table_violations") == []
    assert all(s["legal_candidates"] > 0 for s in doc["suite"])
