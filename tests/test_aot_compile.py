"""Deviceless compiles of the main path's kernels for a described TPU v5e.

Interpret mode hides what the chip's compiler refuses (a slice not
aligned to the tiling, a block that takes one head out of the minor
tile, too much VMEM), so every kernel the trainer and the server run
on the chip is compiled here at its real geometry against a v5e:2x2
topology description — no chip needed, about two seconds each. Nothing
executes: a pass says the kernel compiles, not that it is right or fast.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# describing a topology takes no chip: do not queue behind another
# process's libtpu lock file
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu on this host
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A deviceless TPU executable is written to the persistent cache
    but cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("nq,nkv", [(32, 32), (8, 2)])
def test_flash_fwd_bwd_compiles(v5e, nq, nkv):
    from fms_fsdp_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    q = _sds(v5e, (1, 4096, nq, 128), jnp.bfloat16)
    kv = _sds(v5e, (1, 4096, nkv, 128), jnp.bfloat16)
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)


# llama3_1.8b decode geometry under ServeConfig(max_batch=8,
# max_seq_len=2048): 16 query / 8 kv heads of 128
_B, _NQ, _NKV, _HD, _MAX_SEQ = 8, 16, 8, 128, 2048


@pytest.mark.parametrize(
    "page_size,pages_per_block,store",
    [
        (16, 1, jnp.bfloat16),
        (16, 4, jnp.bfloat16),
        (128, 1, jnp.bfloat16),
        (128, 4, jnp.bfloat16),
        (16, 1, jnp.int8),
        (16, 4, jnp.int8),
    ],
)
def test_paged_attention_compiles(v5e, page_size, pages_per_block, store):
    from fms_fsdp_tpu.ops.paged_attention import paged_attention_kernel

    maxp = _MAX_SEQ // page_size
    pool = _B * maxp + 2

    pages = _sds(v5e, (pool, page_size, _NKV, _HD), store)
    args = [
        _sds(v5e, (_B, _NQ, _HD), jnp.bfloat16),
        pages,
        pages,
        _sds(v5e, (_B, maxp), jnp.int32),
        _sds(v5e, (_B,), jnp.int32),
    ]
    if store == jnp.int8:
        args += [_sds(v5e, (pool, page_size, _NKV, 1), jnp.float32)] * 2

    def fn(q, k, v, table, lens, k_scales=None, v_scales=None):
        return paged_attention_kernel(
            q, k, v, table, lens, k_scales=k_scales, v_scales=v_scales,
            block_kv=pages_per_block * page_size, interpret=False,
        )

    _compile(fn, *args)


@pytest.mark.parametrize("causal", [True, False], ids=["diagonal", "earlier"])
def test_flash_forward_compiles_at_heads_of_64(v5e, causal):
    """The lfm2 prefill's two partials of a chunk of 2048 (its own block
    under the mask, an earlier block whole), 32 query heads on 8 kv heads
    of 64 as published: blocks of 64 lanes, with the log-sum-exp that the
    merge of the partials needs. Forward only: ``supports`` says so."""
    from fms_fsdp_tpu.ops.flash_attention import flash_attention, supports

    q = _sds(v5e, (1, 2048, 32, 64), jnp.bfloat16)
    kv = _sds(v5e, (1, 2048, 8, 64), jnp.bfloat16)
    assert supports(q.shape, kv.shape, forward_only=True)
    assert not supports(q.shape, kv.shape)
    _compile(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, return_lse=True, interpret=False),
        q, kv, kv)


def test_packed_pages_attention_compiles(v5e):
    """The lfm2 decode step's attention at the cell's sizes: 128 slots, 32
    query heads of 64, the two attention layers' pools seen as one run of
    pages of 512 rows of 128 lanes (128 positions of 8 kv heads of 64, two
    heads a row), cells of 512 positions."""
    from fms_fsdp_tpu.ops.paged_attention import (
        packed_pages_attention_kernel,
        packed_row_width,
        tile_rows,
    )

    assert (packed_row_width(8, 64), tile_rows(8, 64)) == (128, 4)
    pages = _sds(v5e, (2 * 3816, 128 * 4, 128), jnp.bfloat16)
    _compile(
        lambda q, k, v, table, lens: packed_pages_attention_kernel(
            q, k, v, table, lens, nkv=8, block_kv=512, interpret=False),
        _sds(v5e, (128, 32, 64), jnp.bfloat16), pages, pages,
        _sds(v5e, (128, 40), jnp.int32), _sds(v5e, (128,), jnp.int32))


@pytest.mark.parametrize("kv_len,segment", [
    (65536, None), (16384, None), (262144, None), (65536, 16384)],
    ids=["64k", "16k", "256k_in_two_segments", "64k_in_four_segments"])
def test_gathered_blocks_attention_compiles(v5e, kv_len, segment):
    """The minicpm_sala prefill's kernel over each query's list of free
    blocks, at the published sizes: a chunk of 2048 queries, 2 kv heads of
    16 query heads of 128, 31 free blocks of 64 a query, one kv head's
    keys and values of the context resident in vector memory (32 MB at
    65536 positions; 131072 positions a segment where the context is
    longer)."""
    from fms_fsdp_tpu.ops import paged_attention as P

    c, nkv, g, hd, w = 2048, 2, 16, 128, 31
    if segment is None and kv_len > 131072:
        assert P.RESIDENT_KV_BYTES // (2 * hd * 2) == 131072

    def attend(q, kb, vb, free, n, upto):
        return P.gathered_blocks_attention(
            q, kb, vb, free, n, upto[0], block_size=64, segment=segment,
            interpret=False)

    kv = _sds(v5e, (1, kv_len, nkv, hd), jnp.bfloat16)
    compiled = _compile(
        attend, _sds(v5e, (1, c, nkv, g, hd), jnp.bfloat16), kv, kv,
        _sds(v5e, (1, nkv, c, w), jnp.int32), _sds(v5e, (1, nkv, c), jnp.int32),
        _sds(v5e, (1,), jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize(
    "layers,slots", [(9, 128), (1, 16)], ids=["phi4flash", "jamba"])
def test_scan_step_kernel_compiles_in_place(v5e, layers, slots):
    """The one-position selective scan over the phi4flash cell's stacked
    slab and over one Jamba layer's state, donated: Mosaic takes the
    strided reads of a (slots * 16, 128) block, nine layers are one
    lowering, the slab comes back as the buffer it went in as and nothing
    of a layer's size stands beside it."""
    from fms_fsdp_tpu.ops.selective_scan import selective_scan_step_kernel

    N, C = 16, 5120

    def steps(u, dt, A, B, Cm, D, slab, live):
        y = 0.0
        for layer in range(layers):
            out, slab = selective_scan_step_kernel(
                u, dt, A, B, Cm, D, slab, layer, live)
            y = y + out
        return y, slab

    f32 = jnp.float32
    row, col = _sds(v5e, (slots, C), f32), _sds(v5e, (slots, N), f32)
    lowered = jax.jit(steps, donate_argnums=(6,)).lower(
        row, row, _sds(v5e, (N, C), f32), col, col, _sds(v5e, (C,), f32),
        _sds(v5e, (layers, slots, N, C), f32),
        _sds(v5e, (slots,), jnp.bool_))
    # the layer is an operand: every layer's call is the one lowering
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 4 * layers * slots * N * C
    assert m.temp_size_in_bytes < 4 * slots * N * C
    assert compiled.as_text().count("tpu_custom_call") == layers


def test_ssd_fused_fwd_bwd_compiles(v5e, monkeypatch):
    from fms_fsdp_tpu.ops import pallas_mode
    from fms_fsdp_tpu.ops.ssd import ssd_scan

    monkeypatch.setattr(pallas_mode, "interpret_default", lambda: False)
    # mamba_9.8b head geometry: 128 heads x 64, d_state 128, one group
    b, s, heads, p, g, n = 1, 4096, 128, 64, 1, 128

    def loss(x, dt, A, Bm, Cm, D):
        y = ssd_scan(x, dt, A, Bm, Cm, D, kernel="pallas")
        return jnp.sum(y.astype(jnp.float32))

    _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5)),
        _sds(v5e, (b, s, heads, p), jnp.bfloat16),
        _sds(v5e, (b, s, heads), jnp.float32),
        _sds(v5e, (heads,), jnp.float32),
        _sds(v5e, (b, s, g, n), jnp.bfloat16),
        _sds(v5e, (b, s, g, n), jnp.bfloat16),
        _sds(v5e, (heads,), jnp.float32),
    )


# a whole layer's stacked expert tensor, (E, D, H) or (E, H, D), made by
# an instruction of its own: what the layer scan's slice or a gather of
# the routed experts would copy out. Inside a fused computation the same
# shape is the dot's operand read in place, and a parameter or a tuple
# element of it is no copy.
_WHOLE_LAYER = r"= bf16\[8,(?:4096,14336|14336,4096)\]"


def _whole_layer_copies(text):
    import re

    found, fused = [], False
    for line in text.splitlines():
        if line.endswith("{") and " = " not in line:
            fused = line.lstrip("%").startswith("fused_computation")
        elif (
            not fused
            and re.search(_WHOLE_LAYER, line)
            and " parameter(" not in line
            and " get-tuple-element(" not in line
        ):
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("slots,form", [(8, "all_experts"), (1, "per_pair")])
def test_mixtral_routed_decode_reads_expert_weights_in_place(v5e, slots, form):
    """The routed decode program at Mixtral-8x7B's published widths (3
    layers, ``slots`` x 2048 positions, page 64, bfloat16): every expert
    weight is read where it lies in the stacked (L, E, ...) arrays. The
    gather-then-einsum it replaces peaked at 14.47 GB with 7.16 GB of
    temporaries, 337 loops and eight whole-layer copies a layer; one
    layer's w1 alone is 0.94 GB, so temporaries under 1 GB hold no copy
    of one. Plain XLA: a Pallas kernel would put the checkout's path into
    the program's cache key."""
    from fms_fsdp_tpu.models.configs import MixtralConfig
    from fms_fsdp_tpu.models.mixtral import init_mixtral_params, routed_moe_form
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families.mixtral import (
        decode_program,
        page_geometry,
    )

    layers = 3
    cfg = MixtralConfig(
        src_vocab_size=32000, emb_dim=4096, nheads=32, kvheads=8,
        nlayers=layers, hidden_dim=14336, num_experts=8, top_k=2,
        max_expected_seq_len=32768, rope_theta=1e6,
    )
    scfg = ServeConfig(
        max_batch=slots, max_seq_len=2048, page_size=64,
        prefill_bucket=256, compute_dtype="bfloat16",
    )
    assert routed_moe_form(slots * cfg.top_k, cfg.num_experts) == form
    page, _, _, max_pages, num_pages = page_geometry(cfg, scfg)
    params = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(
            lambda k: init_mixtral_params(k, cfg, jnp.bfloat16),
            jax.random.PRNGKey(0),
        ),
    )
    pool = _sds(
        v5e,
        (layers, num_pages, page, cfg.n_kv_heads, cfg.head_dim),
        jnp.bfloat16,
    )
    compiled = decode_program(cfg, scfg, page, jnp.bfloat16).lower(
        params,
        {"k": pool, "v": pool},
        _sds(v5e, (slots, max_pages), jnp.int32),
        _sds(v5e, (slots,), jnp.int32),
        _sds(v5e, (slots,), jnp.int32),
        _sds(v5e, (2,), jnp.uint32),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.startswith("HloModule jit__step,")
    assert "tpu_custom_call" not in text
    assert text.count(" while(") <= 4
    assert _whole_layer_copies(text) == []
    assert mem.temp_size_in_bytes < 1e9
    assert mem.peak_memory_in_bytes < 10.5e9


def test_hybrid_prefill_of_the_longest_bucket_is_one_loop_over_chunks(
    v5e, monkeypatch
):
    """The prefill program of 8192 tokens at AI21-Jamba2-3B's published
    widths (28 layers, bfloat16): one ``while`` at its top, over chunks
    of ``PREFILL_CHUNK`` positions, with the layer stack inside it (26
    selective scans, and per attention layer a causal flash call for the
    chunk's own block and a non-causal one in the walk over earlier
    blocks). Recorded: peak 6.25 GB, temporaries 0.24 GB beside 6.06 GB
    of weights; the whole-sequence program it replaces peaked at 8.64 GB
    with 2.86 GB of temporaries, and a (8192, 5120) float32 array alone
    is 0.17 GB."""
    import json

    from fms_fsdp_tpu.models.mamba import init_mamba_params, prefill_chunk
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families import load_model_config
    from fms_fsdp_tpu.serve.families.mamba import prefill_program

    # the program picks its kernels by the backend it finds: say "tpu",
    # as the chip will
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(
            here, "..", "benchmark", "configs", "jamba2-3b.1chip.json")) as f:
        cfg = load_model_config(json.load(f))
    scfg = ServeConfig(
        max_batch=16, max_seq_len=9216, prefill_bucket=2048,
        attn_impl="auto", compute_dtype="bfloat16",
    )
    p_pad = 8192
    params = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(
            lambda k: init_mamba_params(k, cfg, jnp.bfloat16),
            jax.random.PRNGKey(0),
        ),
    )
    compiled = prefill_program(cfg, scfg, p_pad, p_pad, jnp.bfloat16).lower(
        params, _sds(v5e, (1, p_pad), jnp.int32), _sds(v5e, (1,), jnp.int32)
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.startswith(f"HloModule jit__prefill_{p_pad},")
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    entry = lines[start : lines.index("}", start)]
    assert sum(" while(" in l for l in entry) == 1
    assert text.count(" while(") == 3  # and the two attention layers' walks
    assert text.count("tpu_custom_call") == 26 + 2 * 2
    # not higher than the whole-sequence program's, and no array of the
    # bucket's positions by a width of the model anywhere
    assert mem.peak_memory_in_bytes < 6.5e9 < 8.64e9
    assert mem.temp_size_in_bytes < 0.5e9 < 2.86e9
    c = prefill_chunk(p_pad)
    # (the MLP's w2 is itself (8192, 2560): look for the row's leading 1)
    for width in (cfg.d_model, cfg.d_inner, cfg.d_intermediate):
        assert f"[1,{p_pad},{width}]" not in text, width
    assert f"[1,{c},{cfg.d_inner}]" in text  # the chunk's are there


def _moe_row_counts(text, width):
    """Leading sizes of the arrays ``width`` wide that an instruction
    under a ``moe_*`` scope of a compiled prefill program makes or
    takes: the rows moved around the grouped product."""
    import re

    rows = set()
    for line in text.splitlines():
        if re.search(r'op_name="[^"]*/moe_(group|experts|combine)/', line):
            rows.update(
                int(n) for n in re.findall(r"\[(\d+),%d\]" % width, line))
    return rows


def test_sarvam_programs_keep_one_latent_pool_and_no_dense_expert_array(
    v5e, monkeypatch
):
    """The decode program (32 slots) and the prefill program of 16384
    tokens of the sarvam cell at published widths, 1 + 5 layers, 32 of
    128 experts held, bfloat16. Recorded: decode peak 13.43 GB with 0.10
    GB of temporaries beside 10.92 GB of weights and the 2.40 GB pool
    (2441 pages of 128 positions), which is donated, written and read by
    the paged kernel in place (with the pool's entry at 576
    and not 640 lanes the compiler laid it out pages-minor and the step
    held 2.67 GB of temporaries: models/sarvam.py::pool_width); prefill
    peak 12.18 GB with 1.24 GB of temporaries since PR 32 (the flash
    kernel at the published widths, keys 192 and values 128; padded to
    256 the same compile gives 12.28 and 1.37; the pool stands beside
    it: 14.58 GB of the 16.9), 1.25 GB since PR 35 (a slab of the sorted
    pairs' rows around the grouped product where all 16384 pairs' were).
    Neither holds keys or values expanded for
    a whole cache, nor an array of (tokens, experts, width), and no
    operand of the flash call is 256 wide."""
    import json
    import re

    from fms_fsdp_tpu.models.moe_held import grouped_slab
    from fms_fsdp_tpu.models.sarvam import (
        init_sarvam_params,
        pool_width,
        prefill_chunk,
    )
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families import load_model_config
    from fms_fsdp_tpu.serve.families.sarvam import (
        decode_program,
        page_geometry,
        prefill_program,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(
            here, "..", "benchmark", "configs", "sarvam-105b.1chip.json")) as f:
        cfg = load_model_config(json.load(f))
    scfg = ServeConfig(
        max_batch=32, max_seq_len=16896, num_pages=2443,
        prefill_bucket=2048, attn_impl="auto", moe_impl="routed",
        compute_dtype="bfloat16",
    )
    bf16 = jnp.bfloat16
    params = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(
            lambda k: init_sarvam_params(k, cfg, bf16), jax.random.PRNGKey(0)
        ),
    )
    page, max_pages, num_pages = page_geometry(cfg, scfg)
    assert (cfg.latent_dim, pool_width(cfg)) == (576, 640)
    assert (page, max_pages) == (128, 132)
    pool = (cfg.nlayers, num_pages, page, 640)
    pool_bytes = 2 * 6 * num_pages * page * 640
    B, top = 32, 16384
    decode = decode_program(cfg, scfg, page, bf16).lower(
        params, {"latent": _sds(v5e, pool, bf16)},
        _sds(v5e, (B, max_pages), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.int32), _sds(v5e, (2,), jnp.uint32),
    ).compile()
    prefill = prefill_program(cfg, scfg, top, top, bf16).lower(
        params, _sds(v5e, (1, top), jnp.int32), _sds(v5e, (1,), jnp.int32)
    ).compile()
    dm, pm = decode.memory_analysis(), prefill.memory_analysis()
    assert dm.peak_memory_in_bytes < 13.6e9 and dm.temp_size_in_bytes < 0.3e9
    assert pm.peak_memory_in_bytes < 12.25e9 < 12.28e9
    assert pm.temp_size_in_bytes < 1.3e9 < 1.37e9
    assert pm.peak_memory_in_bytes + pool_bytes < 15.75 * 2**30
    dtext, ptext = decode.as_text(), prefill.as_text()
    assert dtext.startswith("HloModule jit__step,")
    assert ptext.startswith(f"HloModule jit__prefill_{top},")
    # the pool is one array, an argument aliased to a result, never copied
    assert f"bf16[{','.join(map(str, pool))}]" in dtext
    assert not re.search(
        r"= bf16\[%s\]\S* copy\(" % ",".join(map(str, pool)), dtext)
    # the ragged paged latent kernel, in the dense layer and the scan
    assert dtext.count("tpu_custom_call") == 2
    # prefill: a flash call for the chunk's own block and one in the walk
    # over earlier blocks (dense layer, and the scan's body), and two
    # grouped matmuls in the scan's body (three until PR 44: gate and up
    # are one pass over the rows since, ops/grouped_matmul.py)
    assert ptext.count("tpu_custom_call") == 2 * 2 + 2
    N, c = cfg.nheads, prefill_chunk(top)
    # the flash calls take queries and keys 192 wide and values 128 wide
    # in the kernel's (B, N, c, H) layout, and nothing padded to 256
    for width, there in ((cfg.q_head_dim, True), (cfg.v_head_dim, True),
                         (256, False)):
        assert (f"bf16[1,{N},{c},{width}]" in ptext) is there, width
    for text in (dtext, ptext):
        for dims in set(re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]", text)):
            s = tuple(int(d) for d in dims.split(","))
            n = 1
            for d in s:
                n *= d
            # keys or values expanded for as many positions as a stream
            # may hold: (..., positions, heads, a head's width)
            assert not (len(s) >= 3 and s[-2] == N and s[-1] in (128, 192, 256)
                        and n >= scfg.max_seq_len * N * 128), s
            # dense over experts: (tokens, experts, width)
            assert not (len(s) >= 3 and s[-2] in (32, 128)
                        and s[-1] in (2048, 4096) and s[-3] >= c), s
    # around the grouped product the rows of one slab of the chunk's
    # sorted pairs (models/moe_held.py::grouped_slab: 1.5 x 32 / 128 of
    # them), never those of all its 16384 (token, choice) pairs (PR 35:
    # five arrays of 134 MB a layer and chunk went; the program's
    # temporaries read 1.254 GB where they read 1.237)
    slab = grouped_slab(cfg, c * cfg.top_k)
    assert (slab, c * cfg.top_k) == (6144, 16384)
    for width in (cfg.emb_dim, 2048):
        rows = _moe_row_counts(ptext, width)
        assert slab in rows and c * cfg.top_k not in rows, (width, rows)
    assert pm.temp_size_in_bytes < 1.26e9


def test_kexaone_programs_fit_beside_a_cache_of_each_kind_at_eight_layers(
    v5e, monkeypatch
):
    """The decode program (32 slots) and the prefill program of 16384
    tokens of the kexaone cell at published widths, layers 0-7 (two
    periods LLLG, the first layer dense), 16 of 128 experts held,
    bfloat16: the compile that decides 8 layers or 5. Recorded (PR 33):
    decode peak 13.78 GB with 0.007 GB of temporaries beside 11.96 GB of
    weights, the 1.60 GB pools of the **two full layers alone** and 0.10
    GB of rings for the six window layers, all donated and written in
    place; prefill peak 12.92 GB with 0.98 GB of temporaries, 14.6 GB
    beside pools and rings of the 16.9 the compiler has: 8 layers are
    kept (since PR 35 12.60 and 0.75 GB: a slab of the sorted pairs'
    rows around the grouped product where all 16384 pairs' were). With
    the products of W_q asked for by head (the reshape in front of the
    QK-norm's sum) the compiler laid W_q out by head first:
    a transposed copy of 100 MB a layer in every decode step, 0.61 GB of
    temporaries there and 2.46 GB in the prefill (16.0 GB beside the
    pools): models/kexaone.py::_qkv ends the products before the
    reshape. No operand is a layer's slice of a pool, no weight is
    copied, and nothing holds keys or values for a whole context in a
    window layer."""
    import json
    import re

    from fms_fsdp_tpu.models.kexaone import init_kexaone_params, prefill_chunk
    from fms_fsdp_tpu.models.moe_held import grouped_slab
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families import load_model_config
    from fms_fsdp_tpu.serve.families.kexaone import (
        cache_bytes,
        decode_program,
        page_geometry,
        prefill_program,
        ring_shape,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(
            here, "..", "benchmark", "configs",
            "k-exaone-236b.1chip.json")) as f:
        cfg = load_model_config(json.load(f))
    with open(os.path.join(
            here, "..", "benchmark", "workloads",
            "k-exaone-236b.serve-mixed-over.json")) as f:
        scfg = ServeConfig(**json.load(f)["engine"])
    assert (scfg.max_batch, scfg.prefill_bucket) == (32, 2048)
    assert cfg.nlayers == 8 and cfg.held == (0, 16)
    bf16 = jnp.bfloat16
    params = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(
            lambda k: init_kexaone_params(k, cfg, bf16), jax.random.PRNGKey(0)
        ),
    )
    page, block_kv, max_pages, num_pages = page_geometry(cfg, scfg)
    assert (page, block_kv) == (128, 512)
    cost = cache_bytes(cfg, bf16)
    assert cost == {"per_token": 8192, "per_stream": 3145728}
    pool = (2, num_pages, page, 8, 128)  # the full layers alone
    pool_bytes = num_pages * page * cost["per_token"]
    ring = ring_shape(cfg, scfg)
    assert ring == (6, 32, 128, 8, 128)  # whatever max_seq_len is
    ring_bytes = 32 * cost["per_stream"]
    B, top = 32, 16384
    decode = decode_program(cfg, scfg, page, block_kv, bf16).lower(
        params, {k: _sds(v5e, ring, bf16) for k in ("k", "v")},
        {k: _sds(v5e, pool, bf16) for k in ("k", "v")},
        _sds(v5e, (B, max_pages), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.int32), _sds(v5e, (2,), jnp.uint32),
    ).compile()
    prefill = prefill_program(cfg, scfg, top, top, bf16).lower(
        params, _sds(v5e, (1, top), jnp.int32), _sds(v5e, (1,), jnp.int32)
    ).compile()
    dm, pm = decode.memory_analysis(), prefill.memory_analysis()
    hbm = 15.75 * 2**30
    weights = cfg.n_params() * 2
    assert dm.temp_size_in_bytes < 0.05e9
    assert dm.peak_memory_in_bytes < weights + pool_bytes + ring_bytes + 0.2e9
    assert dm.peak_memory_in_bytes < hbm
    assert pm.temp_size_in_bytes < 1.1e9 < 2.46e9
    assert pm.peak_memory_in_bytes < 13.0e9
    assert pm.peak_memory_in_bytes + pool_bytes + ring_bytes < hbm - 1.5e9
    dtext, ptext = decode.as_text(), prefill.as_text()
    assert dtext.startswith("HloModule jit__step,")
    assert ptext.startswith(f"HloModule jit__prefill_{top},")
    # pools and rings are arguments aliased to results, never copied
    for shape in (pool, ring):
        dims = ",".join(map(str, shape))
        assert f"bf16[{dims}]" in dtext
        assert not re.search(r"= bf16\[%s\]\S* copy\(" % dims, dtext)
    # the ragged paged kernel once a full layer; in the prefill a windowed
    # flash call a window layer, two flash calls a full layer (the chunk's
    # own block, the walk over earlier ones), two grouped matmuls a
    # sparse layer (three until PR 44: gate and up are one pass over the
    # rows since; PR 38: the windowed call's cell holds a KV group's
    # query heads, scores keys down; still one call a window layer, and
    # the program's temporaries read 0.7536 GB where they read 0.7545)
    assert dtext.count("tpu_custom_call") == 2
    assert ptext.count("tpu_custom_call") == 6 + 2 * 2 + 2 * 7
    c = prefill_chunk(top)
    for text in (dtext, ptext):
        # no weight laid out again
        assert not re.search(
            r"= bf16\[(8192,6144|6144,8192|1024,6144)\]\S* copy\(", text)
        for dims in set(re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]", text)):
            s = tuple(int(d) for d in dims.split(","))
            n = 1
            for d in s:
                n *= d
            # dense over experts: (tokens, experts, width)
            assert not (len(s) >= 3 and s[-2] in (16, 128)
                        and s[-1] in (2048, 6144) and s[-3] >= c), s
    # around the grouped product the rows of one slab of the chunk's
    # sorted pairs (1.5 x 16 / 128 of them), never those of all its 16384
    # (token, choice) pairs (PR 35: five arrays of 201 MB a layer and
    # chunk went, and with them 0.23 GB of the program's temporaries)
    slab = grouped_slab(cfg, c * cfg.top_k)
    assert (slab, c * cfg.top_k) == (3072, 16384)
    for width in (cfg.emb_dim, 2048):
        rows = _moe_row_counts(ptext, width)
        assert slab in rows and c * cfg.top_k not in rows, (width, rows)
    assert pm.temp_size_in_bytes < 0.76e9 < 0.98e9
    assert pm.peak_memory_in_bytes < 12.7e9
    # the prompt's keys and values for the pages: the two full layers'
    assert f"bf16[2,1,{top},8,128]" in ptext
    assert f"bf16[6,1,{top},8,128]" not in ptext
    assert "bf16[6,1,128,8,128]" in ptext  # the rings handed over


def test_phi4flash_programs_fit_beside_ring_slab_and_one_layers_pool(
    v5e, monkeypatch
):
    """The decode program (128 slots) and the prefill program of 4096
    positions of the phi4flash cell at published widths, all 32 layers,
    bfloat16. Recorded (PR 46): decode peak 13.25 GB with 0.44 GB of
    temporaries beside 7.71 GB of weights, 2.68 GB of rings, 0.41 GB of
    slabs and the 2.0 GB pool of **one layer**, all donated and written in
    place; prefill peak 7.87 GB with 0.15 GB of temporaries, 13.0 GB
    beside rings, slabs and pool of the 16.9 the compiler has: the pool
    stands as the issue sized it. With the rings held by head, (8, 128,
    512, 10, 128), the chip pads a position's ten rows to sixteen and
    copies both rings whole every step (4.0 GB of temporaries, 16.3 GB in
    all: refused); as rows of 128 lanes read by the ragged paged kernel
    they are arguments aliased to results. 16 Mosaic calls a decode step:
    the paged kernel over a slot's ring a window layer, over the one
    layer's pages for the full layer and each cross layer; since PR 48 nine
    more, the one-position scan kernel a Mamba layer over the stacked slab
    in place (decode peak 12.97 GB with 0.043 GB of temporaries: the stack's
    copy and a layer's new state have no buffer of their own); 17 a
    prefill: the windowed flash kernel a window
    layer, the scan kernel a Mamba layer."""
    import json
    import re

    from fms_fsdp_tpu.models.phi4flash import init_phi4flash_params
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families import load_model_config
    from fms_fsdp_tpu.serve.families.phi4flash import (
        cache_bytes,
        decode_program,
        page_geometry,
        pool_row,
        prefill_program,
        state_shapes,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(
            here, "..", "benchmark", "configs",
            "phi-4-mini-flash.1chip.json")) as f:
        cfg = load_model_config(json.load(f))
    with open(os.path.join(
            here, "..", "benchmark", "workloads",
            "phi-4-mini-flash.serve-reasoning-over.json")) as f:
        scfg = ServeConfig(**json.load(f)["engine"])
    assert (scfg.max_batch, scfg.prefill_bucket) == (128, 256)
    bf16 = jnp.bfloat16
    params = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(
            lambda k: init_phi4flash_params(k, cfg, bf16),
            jax.random.PRNGKey(0),
        ),
    )
    page, block_kv, max_pages, num_pages = page_geometry(cfg, scfg)
    assert (page, block_kv) == (128, 512)
    cost = cache_bytes(cfg, bf16)
    assert cost["per_token"] == 5120
    B, top = 128, 4096
    shapes = state_shapes(cfg, B, bf16)
    ring = shapes["ring_k"][0]
    assert ring == (8, 128, 5120, 128)  # rows of 128 lanes, nothing padded
    pool = (1, num_pages) + pool_row(cfg, page)
    assert pool == (1, num_pages, 1280, 128)
    held = B * cost["per_stream"] + num_pages * page * cost["per_token"]
    decode = decode_program(cfg, scfg, page, block_kv, bf16).lower(
        params, {k: _sds(v5e, *s) for k, s in shapes.items()},
        {k: _sds(v5e, pool, bf16) for k in ("k", "v")},
        _sds(v5e, (B, max_pages), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.int32), _sds(v5e, (2,), jnp.uint32),
    ).compile()
    prefill = prefill_program(cfg, scfg, top, top, bf16).lower(
        params, _sds(v5e, (1, top), jnp.int32), _sds(v5e, (1,), jnp.int32)
    ).compile()
    dm, pm = decode.memory_analysis(), prefill.memory_analysis()
    hbm = 15.75 * 2**30
    weights = cfg.n_params() * 2
    assert dm.temp_size_in_bytes < 0.1e9
    assert dm.peak_memory_in_bytes < weights + held + 0.2e9 < hbm
    assert pm.temp_size_in_bytes < 0.2e9
    assert pm.peak_memory_in_bytes + held < hbm - 2.0e9
    dtext, ptext = decode.as_text(), prefill.as_text()
    assert dtext.startswith("HloModule jit__step,")
    assert ptext.startswith(f"HloModule jit__prefill_{top},")
    # pool and rings are arguments aliased to results, never copied
    for shape in (pool, ring):
        dims = ",".join(map(str, shape))
        assert f"bf16[{dims}]" in dtext
        assert not re.search(r"= bf16\[%s\]\S* copy\(" % dims, dtext)
    # the nine scan states are stepped where they lie in the stacked slab
    # (PR 48): no copy, slice or write-back of a layer's 42 MB or of the
    # stack beside the kernel's nine calls
    slab = ",".join(map(str, shapes["ssd"][0]))
    assert f"f32[{slab}]" in dtext
    assert not re.search(
        r"= f32\[(%s|%s)\]\S* (copy|dynamic-update-slice|select)\("
        % (slab, slab.split(",", 1)[1]), dtext)
    assert dtext.count("tpu_custom_call") == 8 + 8 + 9
    assert ptext.count("tpu_custom_call") == 8 + 9
    # the second half of the stack meets one row a prompt: no product of
    # a gated memory unit's width over a chunk of rows that is not a
    # Mamba layer's in_proj (9 layers of two) or its out_proj's operand
    assert "bf16[1,4096,5120]" not in ptext  # nothing d_inner wide a prompt
    # the one layer's keys and values for the pages, the state handed over
    assert f"bf16[1,1,{top * 10},128]" in ptext
    assert "bf16[8,1,5120,128]" in ptext and "f32[9,1,16,5120]" in ptext


def test_lfm2_decode_program_walks_its_pages_and_copies_no_pool(
    v5e, monkeypatch
):
    """The decode program of the lfm2 cell (128 slots, layers 0-9, all 64
    experts, bfloat16) with the ragged paged kernel a cell a stream: the
    two attention layers' pools, (2, pages, 512, 128), are arguments
    aliased to results and read where they lie, two Mosaic calls a step.
    A pool is 1.0 GB: temporaries under 0.1 GB mean that neither the
    hand-made copies of the kernel nor the view of two layers as one run
    of pages made the compiler lay one out again."""
    import json
    import re

    from fms_fsdp_tpu.models.lfm2 import init_lfm2_params
    from fms_fsdp_tpu.serve.engine import ServeConfig
    from fms_fsdp_tpu.serve.families import load_model_config
    from fms_fsdp_tpu.serve.families.lfm2 import (
        decode_program,
        page_geometry,
        window_shape,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(
            here, "..", "benchmark", "configs",
            "lfm2-24b-a2b.1chip.json")) as f:
        cfg = load_model_config(json.load(f))
    with open(os.path.join(
            here, "..", "benchmark", "workloads",
            "lfm2-24b-a2b.serve-turns-over.json")) as f:
        scfg = ServeConfig(**json.load(f)["engine"])
    bf16 = jnp.bfloat16
    params = jax.tree.map(
        lambda a: _sds(v5e, a.shape, a.dtype),
        jax.eval_shape(
            lambda k: init_lfm2_params(k, cfg, bf16), jax.random.PRNGKey(0)
        ),
    )
    page, block_kv, max_pages, num_pages = page_geometry(cfg, scfg)
    assert (page, block_kv, scfg.max_batch) == (128, 512, 128)
    B = scfg.max_batch
    pool = (2, num_pages, page * 4, 128)
    decode = decode_program(cfg, scfg, page, block_kv, bf16).lower(
        params, {"z": _sds(v5e, window_shape(cfg, scfg), bf16)},
        {k: _sds(v5e, pool, bf16) for k in ("k", "v")},
        _sds(v5e, (B, max_pages), jnp.int32), _sds(v5e, (B,), jnp.int32),
        _sds(v5e, (B,), jnp.int32), _sds(v5e, (2,), jnp.uint32),
    ).compile()
    m = decode.memory_analysis()
    assert m.temp_size_in_bytes < 0.1e9
    text = decode.as_text()
    assert text.startswith("HloModule jit__step,")
    dims = ",".join(map(str, pool))
    assert f"bf16[{dims}]" in text
    assert not re.search(r"= bf16\[%s\]\S* copy\(" % dims, text)


def test_sala_decode_kernel_call_compiles_at_32_slots_of_2_kv_heads(
    v5e, monkeypatch
):
    """The sala decode step's attention at the cell's sizes: 32 slots of 2
    kv heads are 64 rows of the ragged kernel, 16 query heads a row over
    pages of 64 positions of one head, each row's table its 128 chosen
    pages and blocks of 512 positions; the pools of the two sparse
    layers' four kv heads (1.6 GB each) are read where they lie."""
    from fms_fsdp_tpu.ops.paged_attention import chosen_pages_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, nkv, g, hd, page, pages = 32, 2, 16, 128, 64, 24576
    pool = _sds(v5e, (4 * pages, page, 1, hd), jnp.bfloat16)

    def attend(q, k, v, table, lens, blocks, n, first):
        return chosen_pages_attention(
            q, k, v, table, lens, blocks, n, first_page=first, block_kv=512)

    compiled = _compile(
        attend,
        _sds(v5e, (slots, nkv, g, hd), jnp.bfloat16), pool, pool,
        _sds(v5e, (slots, 65536 // page), jnp.int32),
        _sds(v5e, (slots,), jnp.int32),
        _sds(v5e, (slots, nkv, 128), jnp.int32),
        _sds(v5e, (slots, nkv), jnp.int32), _sds(v5e, (nkv,), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9
