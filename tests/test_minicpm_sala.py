"""The minicpm_sala family (block-sparse attention layers that choose
their context through a cache of compressed keys, beside lightning
linear-attention layers that keep a state a head) against the plain
float32 reference ``benchmark/reference/minicpm_sala.py``, at a small size
that keeps every ratio: a slice ``sparse, lightning, lightning, sparse``,
4 query heads on 2 kv heads of 16, compressed keys over 8 positions every
4, blocks of 16, 6 of them chosen (the first and the newest 2 always),
dense up to 64 positions, seeded weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import minicpm_sala as reference
from fms_fsdp_tpu.models import minicpm_sala as M
from fms_fsdp_tpu.models.configs import (
    SalaConfig,
    SalaSparseConfig,
    minicpm_sala_config,
)
from fms_fsdp_tpu.ops import lightning_attention as L
from fms_fsdp_tpu.ops import paged_attention as P
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import (
    FAMILY_CODES,
    check_params_family,
    family_of,
    load_model_config,
)
from fms_fsdp_tpu.serve.families import minicpm_sala as A
from fms_fsdp_tpu.serve.scheduler import RequestRejected

SPARSE = {"kernel_size": 8, "kernel_stride": 4, "block_size": 16, "topk": 6,
          "init_blocks": 1, "window_size": 32, "dense_len": 64}
TINY = {
    "model_type": "minicpm_sala",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "intermediate_size": 128, "lightning_nh": 4, "lightning_nkv": 4,
    "lightning_head_dim": 16, "lightning_use_rope": True,
    "lightning_scale": "1/sqrt(d)", "attn_use_rope": False, "qk_norm": True,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 16, "vocab_size": 256, "hidden_act": "silu",
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "published": {"num_hidden_layers": 32},
    "sparse_config": SPARSE,
}
LIMIT = 0.035  # of the bfloat16 test: sound under it, float8 over it
CHUNK, BUCKET = 48, 64  # a chunk's edge falls inside a window of 8 every 4


@pytest.fixture(autouse=True)
def _small_loops(monkeypatch):
    """Chunks small enough that a test prompt takes several trips of the
    prefill's loop, some wholly dense, some that choose."""
    monkeypatch.setattr(M, "PREFILL_CHUNK", CHUNK)
    monkeypatch.setattr(M, "SELECT_TILE", 16)
    monkeypatch.setattr(L, "CHUNK", 16)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tree(c=TINY, seed=3, dtype=jnp.float32):
    return weights.make_tree(
        weights.seed_key(seed), reference.param_spec(c), dtype)


_REF = {}


def _ref_logits(tree, tokens, c=TINY):
    """The reference's logits at every position of ``tokens``: padded to
    256 (causal: what follows changes nothing), one program a config."""
    key = repr(sorted(c["sparse_config"].items()))
    if key not in _REF:
        _REF[key] = jax.jit(lambda tr, toks: reference.forward(tr, toks, c))
    row = list(tokens) + [0] * (256 - len(tokens))
    out = _REF[key](tree, jnp.asarray([row], jnp.int32))[0]
    return np.asarray(out)[: len(tokens)]


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# the config and the tree
# ---------------------------------------------------------------------------


def test_load_model_config_on_the_published_keys():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "minicpm-sala-9b.1chip.json")
    with open(path) as f:
        c = json.load(f)
    cfg = load_model_config(c)
    assert isinstance(cfg, SalaConfig) and family_of(cfg) == "minicpm_sala"
    assert FAMILY_CODES["minicpm_sala"] == 5
    assert (cfg.emb_dim, cfg.nheads, cfg.kvheads, cfg.head_dim) == (
        4096, 32, 2, 128)
    assert (cfg.lightning_nh, cfg.lightning_head_dim) == (32, 128)
    assert (cfg.hidden_dim, cfg.src_vocab_size) == (16384, 73448)
    assert cfg.nlayers == 8 and cfg.depth_published == 32
    assert [cfg.kind(i) for i in range(8)] == (
        ["sparse"] + ["lightning"] * 6 + ["sparse"])
    assert c["mixer_types"] == c["published"]["mixer_types"][9:17]
    # 1.4 / sqrt(32) whatever depth is kept; logits over 4096 / 256
    assert cfg.residual_gain == pytest.approx(1.4 / 32**0.5)
    assert cfg.logit_divisor == 16 and cfg.scale_emb == 12
    assert cfg.sparse == SalaSparseConfig(32, 16, 64, 64, 1, 2048, 8192)
    assert cfg.sparse.list_blocks == 128 and cfg.sparse.per_block == 4
    # the hand count of the issue: 285.2M, 253.8M, 2821M held
    assert round(cfg.layer_params("lightning") / 1e6, 1) == 285.2
    assert round(cfg.layer_params("sparse") / 1e6, 1) == 253.8
    assert round(cfg.n_params() / 1e6) == 2821
    # asked for what is not built: refused by the key's name
    for key, value in (("attn_use_rope", True), ("lightning_nkv", 8),
                       ("use_output_gate", False), ("mup_denominator", 8)):
        with pytest.raises(ValueError, match=key):
            minicpm_sala_config({**c, key: value})
    with pytest.raises(ValueError, match="kernel_size"):
        minicpm_sala_config(
            {**c, "sparse_config": {**c["sparse_config"], "kernel_size": 48}})


def test_tree_is_the_programs_own():
    cfg = minicpm_sala_config(TINY)
    mine = jax.eval_shape(_tree)
    theirs = jax.eval_shape(
        lambda k: M.init_sala_params(k, cfg), jax.random.PRNGKey(0))
    weights.require_same_tree(mine, theirs, "minicpm_sala")
    check_params_family(mine, "minicpm_sala")
    with pytest.raises(ValueError, match="minicpm_sala"):
        check_params_family(mine, "llama")
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(mine))
    assert n == cfg.n_params()


# ---------------------------------------------------------------------------
# lightning attention: three forms, one arithmetic
# ---------------------------------------------------------------------------


def test_lightning_chunked_is_recurrent_is_one_position_updates():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 80, 4, 16)), jnp.float32)
               for _ in range(3))
    scale = 0.25
    want, s_want = L.lightning_recurrent(q, k, v, scale)
    # the equation itself, by hand: S_t = lam S_{t-1} + k v^T, o = q^T S
    lam = np.exp(-(2.0 ** (-8.0 * np.arange(1, 5) / 4)))
    S = np.zeros((2, 4, 16, 16))
    for t in range(80):
        S = lam[None, :, None, None] * S + np.einsum(
            "bhk,bhv->bhkv", np.asarray(k[:, t]), np.asarray(v[:, t]))
        o = np.einsum("bhk,bhkv->bhv", np.asarray(q[:, t]), S) * scale
        assert np.allclose(o, want[:, t], atol=1e-4)
    got, s_got = L.lightning_chunked(q, k, v, scale)
    assert np.allclose(got, want, atol=1e-4)
    assert np.allclose(s_got, s_want, atol=1e-4)
    # from a carried state, and a row that ends inside the chunk
    a, s_a = L.lightning_chunked(q[:, :48], k[:, :48], v[:, :48], scale)
    live = jnp.arange(32)[None, :] < jnp.asarray([32, 20])[:, None]
    b, s_b = L.lightning_chunked(
        q[:, 48:], k[:, 48:], v[:, 48:], scale, s_a, live)
    assert np.allclose(b[0], want[0, 48:], atol=1e-4)
    assert np.allclose(b[1, :20], want[1, 48:68], atol=1e-4)
    assert np.allclose(s_b[0], s_want[0], atol=1e-4)
    _, s_68 = L.lightning_recurrent(q[1:, :68], k[1:, :68], v[1:, :68], scale)
    assert np.allclose(s_b[1], s_68[0], atol=1e-4)
    # one position at a time from the state the chunks left
    s = s_a
    for t in range(48, 80):
        o, s = L.lightning_step(q[:, t], k[:, t], v[:, t], s, scale)
        assert np.allclose(o, want[:, t], atol=1e-4)
    assert np.allclose(s, s_want, atol=1e-4)


# ---------------------------------------------------------------------------
# the choice of blocks
# ---------------------------------------------------------------------------


def _qk(S, seed=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, S, 2, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S, 2, 16)), jnp.float32)
    return q, k


def test_chosen_blocks_are_the_references_past_topk_blocks():
    """400 positions: 25 blocks of 16 where 6 are chosen. The mask of
    every position, and the decode step's list of one."""
    sp, S = SalaSparseConfig(**SPARSE), 400
    q, k = _qk(S)
    t = jnp.arange(S, dtype=jnp.int32)
    want = np.asarray(reference.choose_blocks(
        q, reference.compress(k, SPARSE), t, S // 16, SPARSE))
    kc = M._pad_rows(P.compress_keys(k, sp), S // 4)
    key, exists, dense = P.block_keys(q, kc, t[None], sp)
    got = np.asarray(P.chosen_mask(key, exists, dense, sp))
    assert (got == want).all()
    per_query = got.sum(-1)[0, 0]
    assert (per_query[64:] == np.minimum(t[64:] // 16 + 1, 6)).all()
    assert (per_query[:64] == t[:64] // 16 + 1).all()  # dense: every block
    # beyond the always-chosen blocks the two kv heads choose differently
    assert (got[0, 0] != got[0, 1]).any()
    for pos in (63, 64, 200, 399):
        blocks, n = P.chosen_list(key[:, :, pos:pos + 1], dense[:, pos:pos + 1], sp)
        for h in range(2):
            listed = np.asarray(blocks[0, h, : int(n[0, h])])
            assert (listed == np.flatnonzero(want[0, h, pos])).all()
            assert listed[-1] == pos // 16  # the query's own block is last


def test_sparse_is_dense_on_a_context_of_fewer_blocks():
    """Up to ``topk`` blocks every block is chosen, whatever ``dense_len``
    says: the sparse layer's attention is plain causal attention."""
    c = {**TINY, "sparse_config": {**SPARSE, "dense_len": 16}}
    full = {**TINY, "sparse_config": {**SPARSE, "dense_len": 4096}}
    tree = _tree()
    tokens = np.random.default_rng(2).integers(1, 256, size=128).tolist()
    a, b = _ref_logits(tree, tokens, c), _ref_logits(tree, tokens, full)
    assert np.abs(a[:96] - b[:96]).max() < 1e-5  # 6 blocks of 16
    assert np.abs(a[96:] - b[96:]).max() > 1e-3  # then blocks are left out
    got = jax.jit(lambda tr, toks: M.sala_forward(
        tr, toks, minicpm_sala_config(c), compute_dtype=jnp.float32))(
        tree, jnp.asarray([tokens], jnp.int32))[0]
    assert _gap(np.asarray(got), a) < 2e-5


def _chunk(sparse, kv_len, start, c, nkv=2, g=2, seed=5, ties=False):
    """A chunk's queries at ``start`` and buffers written up to its end,
    with the compressed keys of what is written (``ties``: keys of zeros,
    so every block scores alike)."""
    sp = SalaSparseConfig(**sparse)
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, c, nkv, g, 16)), jnp.float32)
    kb, vb = (rng.normal(size=(1, kv_len, nkv, 16)) for _ in range(2))
    kb[:, start + c:], vb[:, start + c:] = 0, 0
    if ties:
        kb[:] = 0
    kb, vb = jnp.asarray(kb, jnp.float32), jnp.asarray(vb, jnp.float32)
    kc = M._pad_rows(P.compress_keys(kb, sp), kv_len // sp.kernel_stride)
    t = jnp.broadcast_to(start + jnp.arange(c, dtype=jnp.int32), (1, c))
    return sp, q, kb, vb, kc, t


# (sparse sizes, kv_len, start, chunk, kv heads, query heads a kv head,
# bytes of resident keys and values or None for the module's)
CHOSEN_CHUNKS = {
    "deep_in_a_context": (SPARSE, 512, 448, 32, 2, 2, None),
    "first_past_dense_len": (SPARSE, 256, 64, 32, 2, 2, None),
    # 12 chosen of which 9 free, where blocks 4 and 5 have 2 and 3 to give
    "fewer_free_blocks_than_the_list": (
        {**SPARSE, "topk": 12}, 256, 64, 32, 2, 2, None),
    # one block a chunk and band tile: its first and last query
    "a_blocks_first_and_last_query": (SPARSE, 256, 160, 16, 2, 2, None),
    "one_kv_head": (SPARSE, 256, 192, 32, 1, 4, None),
    # 128 positions of float32 keys and values a segment: two segments
    "lists_cut_across_two_segments": (
        {**SPARSE, "topk": 10}, 256, 192, 32, 2, 2, 2 * 128 * 16 * 4),
    # a chunk longer than the window: free blocks inside the chunk itself
    "free_blocks_inside_the_chunk": (SPARSE, 256, 128, 128, 2, 2, None),
}


@pytest.mark.parametrize("case", list(CHOSEN_CHUNKS))
def test_chosen_chunk_attention_is_the_masked_attention(case, monkeypatch):
    """The band of forced blocks and the kernel over each query's list
    (interpret mode) against one masked product under ``chosen_mask``'s
    mask, float32."""
    sparse, kv_len, start, c, nkv, g, resident = CHOSEN_CHUNKS[case]
    if resident:
        monkeypatch.setattr(P, "RESIDENT_KV_BYTES", resident)
    monkeypatch.setattr(M, "BAND_TILE", 16)
    sp, q, kb, vb, kc, t = _chunk(sparse, kv_len, start, c, nkv, g)
    chosen = np.asarray(M._choose(q, kc, t, sp))  # (1, Nkv, c, nb)
    pos = np.arange(kv_len)
    mask = chosen[..., pos // sp.block_size] & (
        pos[None, :] <= np.asarray(t)[0][:, None])
    want, _ = M._masked_attention(q, kb, vb, jnp.asarray(mask))
    free, n = M._select_chunk(q, kc, t, sp, lists=True)
    width = min(sp.topk, kv_len // sp.block_size) - 3
    assert free.shape == (1, nkv, c, width)
    exist = np.asarray(t)[0] // sp.block_size + 1
    assert (np.asarray(n)[0] == np.clip(exist - 3, 0, width)).all()
    if case == "fewer_free_blocks_than_the_list":
        assert int(n.max()) < width
    if case == "free_blocks_inside_the_chunk":
        assert int(free.max()) * sp.block_size >= start
    got = M._chosen_chunk_attention(q, kb, vb, free, n, jnp.int32(start), sp)
    assert got.shape == want.shape == (1, c, nkv * g, 16)
    gap = np.abs(np.asarray(got) - np.asarray(want)).max(axis=(0, 2, 3))
    assert gap.max() < 2e-6, (int(gap.argmax()), gap.max())
    # and the free half alone, against the mask less the forced blocks
    own = exist - 1
    blocks = np.arange(kv_len // sp.block_size)
    forced = (blocks[None] < sp.init_blocks) | (
        (blocks[None] <= own[:, None])
        & (blocks[None] > own[:, None] - sp.window_blocks))
    rest = jnp.asarray((chosen & ~forced)[..., pos // sp.block_size])
    want, lse = M._masked_attention(q, kb, vb, rest)
    got, got_lse = P.gathered_blocks_attention(
        q, kb, vb, free, n, start + c, block_size=sp.block_size)
    some = np.asarray(n)[0, 0] > 0  # an empty list: no weight in the merge
    assert np.abs(np.asarray(got - want))[:, some].max() < 2e-6
    assert np.abs(np.asarray(got_lse - lse))[:, some].max() < 2e-6
    assert (np.asarray(got_lse)[:, ~some] < -1e29).all()


@pytest.mark.parametrize("ties", [False, True], ids=["ranked", "ties"])
def test_free_lists_and_forced_blocks_are_the_masks_rows(ties):
    """``_select_chunk``'s lists against ``chosen_mask``'s rows: the
    first block, the band and the listed blocks are the mask, block for
    block; where every block scores alike the list is the lowest free
    indices, as ``chosen_mask`` breaks its ties."""
    sparse = {**SPARSE, "topk": 8}
    sp, q, kb, vb, kc, t = _chunk(sparse, 512, 320, 64, ties=ties)
    mask = np.asarray(M._select_chunk(q, kc, t, sp, lists=False))
    free, n = map(np.asarray, M._select_chunk(q, kc, t, sp, lists=True))
    assert free.shape == (1, 2, 64, 5) and (n == 5).all()
    for h in range(2):
        for i in range(64):
            own = (320 + i) // 16
            listed = {0, own - 1, own, *free[0, h, i].tolist()}
            assert listed == set(np.flatnonzero(mask[0, h, i]).tolist())
            assert len(listed) == 8
            if ties:
                assert free[0, h, i].tolist() == [1, 2, 3, 4, 5]
    if not ties:
        assert (free[0, 0] != free[0, 1]).any()  # a kv head's own choice


def test_prefill_attn_form_follows_the_chunks(monkeypatch):
    cfg = minicpm_sala_config(TINY)
    assert M.chunk_forms(192, cfg) == ["dense", "masked", "chosen", "chosen"]
    assert M.prefill_attn_form(cfg, "xla", 192) == (
        "einsum+masked_blocks+chosen_blocks")
    assert M.prefill_attn_form(cfg, "xla", 48) == "einsum"
    assert M.prefill_attn_form(cfg, "xla", 96) == "einsum+masked_blocks"
    # the published sizes: the chunk divides dense_len, no chunk holds both
    big = minicpm_sala_config({**TINY, "head_dim": 128, "sparse_config": {}})
    assert cfg.sparse != big.sparse == SalaSparseConfig()
    monkeypatch.setattr(M, "PREFILL_CHUNK", 2048)  # the module's own
    assert M.prefill_chunk(65536, big) == 2048
    assert M.chunk_forms(65536, big) == ["dense"] * 4 + ["chosen"] * 28
    assert M.prefill_attn_form(big, "pallas", 65536) == "flash+chosen_blocks"
    assert M.prefill_attn_form(big, "pallas", 8192) == "flash"
    # no block to choose freely: the band is all, the walk stays
    monkeypatch.setattr(M, "PREFILL_CHUNK", CHUNK)
    none = minicpm_sala_config(
        {**TINY, "sparse_config": {**SPARSE, "topk": 3}})
    assert M.chunk_forms(192, none) == ["dense"] + ["masked"] * 3


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lightning", ["recurrent", "chunked"])
def test_full_forward_agrees_with_the_reference(lightning):
    tree, cfg = _tree(), minicpm_sala_config(TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 256), 1, 256)
    want = jax.jit(lambda tr, toks: reference.forward(tr, toks, TINY))(
        tree, tokens)
    got = jax.jit(lambda tr, toks: M.sala_forward(
        tr, toks, cfg, compute_dtype=jnp.float32, lightning=lightning))(
        tree, tokens)
    assert _gap(np.asarray(got), np.asarray(want)) < 2e-5


@pytest.mark.parametrize("chunk,forms", [
    (48, ["dense", "masked", "chosen", "chosen"]),
    (32, ["dense"] * 2 + ["chosen"] * 4),
], ids=["a_chunk_holds_both_kinds", "every_chunk_past_dense_len_chosen"])
def test_prefill_in_chunks_is_the_forward(chunk, forms, monkeypatch):
    """Rows that end inside a chunk, on a chunk's edge and inside a
    compression window; chunks of 48 cut every window of 8 that starts 4
    before an edge, and one of them holds positions up to ``dense_len``
    and positions past it (the masked walk); with chunks of 32 every
    chunk past ``dense_len`` takes the band and the kernel. What is
    handed over is what a decode step reads."""
    monkeypatch.setattr(M, "PREFILL_CHUNK", chunk)
    tree, cfg = _tree(), minicpm_sala_config(TINY)
    sp = cfg.sparse
    lengths = [190, 144, 67]
    tokens = jax.random.randint(jax.random.PRNGKey(4), (3, 192), 1, 256)
    assert M.prefill_chunk(192, cfg) == chunk
    assert M.chunk_forms(192, cfg) == forms
    logits, kv, state, counts = M.sala_prefill(
        tree, tokens, jnp.asarray(lengths, jnp.int32), cfg,
        compute_dtype=jnp.float32, attn_impl="xla")
    want = reference.forward(tree, tokens, TINY)
    for b, n in enumerate(lengths):
        assert _gap(np.asarray(logits[b]), np.asarray(want[b, n - 1])) < 2e-5
    assert kv["k"].shape == (4, 3, 192, 1, 16) and kv["kc"].shape == (4, 3, 48, 16)
    assert state["S"].shape == (2, 3, 4, 16, 16)
    assert tuple(map(int, counts)) == tuple(
        sum(x) for x in zip(*(
            M.prefill_choices(n, cfg) + (M.prefill_multiplied(n, 192, cfg),)
            for n in lengths)))
    assert M.prefill_choices(67, cfg) == (3, 3 * 5, 3 * 5)
    assert M.prefill_positions(67, 192, cfg) == 96
    # positions 64-66: the walk of the chunk 48-95 multiplies its 6
    # blocks; a chosen chunk the first block, the band of 2 with a tile's
    # corner (a tile is the chunk's 2 blocks) and the 2 free blocks left
    assert M.prefill_multiplied(67, 192, cfg) == (
        3 * 6 if chunk == 48 else 3 * (1 + 1 + 2 + 2))
    chose, chosen, context, multiplied = map(int, counts)
    assert chosen <= multiplied < context
    # the index rows: the mean of each whole window, zeros past the end
    for b, n in enumerate(lengths):
        k = kv["k"][:2, b, :, 0]  # the first sparse layer's two heads
        whole = (n - sp.kernel_size) // sp.kernel_stride + 1
        # 7 straddles position 32, 11 and 12 position 48
        for j in (0, 7, 11, 12, whole - 1):
            mean = k[:, 4 * j: 4 * j + 8].mean(axis=1)
            assert np.allclose(kv["kc"][:2, b, j], mean, atol=1e-5)
        assert not np.asarray(kv["kc"][:, b, whole:]).any()
        assert not np.asarray(kv["k"][:, b, n:]).any()


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _engine(tree, cfg, dtype="float32", **kw):
    scfg = ServeConfig(**{
        "max_batch": 2, "max_seq_len": 256, "compute_dtype": dtype,
        "attn_impl": "reference", "prefill_bucket": BUCKET,
        "max_prefill_per_step": 2, **kw})
    return ServingEngine(tree, cfg, scfg)


def _serve_capturing(eng, prompts, max_new):
    """-> per request, the logits row of every served position, read
    where the adapter hands them to the engine (tests/test_sarvam.py)."""
    rows = {}
    prefill, decode = eng.adapter.prefill, eng.adapter.decode_dispatch

    def capture_prefill(rid, slot, prompt):
        row = prefill(rid, slot, prompt)
        rows[rid] = [np.asarray(row, np.float32)]
        return row

    def capture_decode(slot_rids, lens, tokens, key, fresh, **kw):
        live = [(slot, rid) for slot, rid in enumerate(slot_rids)
                if rid is not None and lens[slot] > 0]
        toks, logits = decode(slot_rids, lens, tokens, key, fresh, **kw)
        step = np.asarray(logits, np.float32)
        for slot, rid in live:
            rows[rid].append(step[slot])
        return toks, logits

    eng.adapter.prefill = capture_prefill
    eng.adapter.decode_dispatch = capture_decode
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run()
    return reqs, [np.stack(rows[r.rid][: len(r.generated)]) for r in reqs]


@pytest.mark.parametrize("attn,bucket", [
    ("reference", BUCKET), ("kernel", BUCKET), ("reference", 48)],
    ids=["reference", "kernel", "a_chunk_holds_both_kinds"])
def test_engine_agrees_with_the_reference_on_logits_float32(
        attn, bucket, tmp_path):
    """Three requests on two slots (a slot's state is left and taken over
    while the other decodes): a prompt past ``dense_len`` prefilled in
    chunks and decoded through chosen pages, one that crosses
    ``dense_len`` while it decodes (50 + 20 positions over 64), a short
    one. Every served position's logits against the reference's full
    forward. With the bucket of 64 the programs' chunks are 32 and every
    chunk past ``dense_len`` takes the band and the kernel; with the
    bucket of 48 they are 48 and the chunk 48-95 takes the masked walk.
    The prefill spans say which and what the products touched."""
    from tests.test_serve_spans import named, read_spans

    cfg, tree = minicpm_sala_config(TINY), _tree()
    eng = _engine(tree, cfg, attn_impl=attn, prefill_bucket=bucket)
    assert eng.adapter.attn_impl == attn and eng.family == "minicpm_sala"
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (150, 50, 5)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        reqs, rows = _serve_capturing(eng, prompts, 20)
    for prompt, req, got in zip(prompts, reqs, rows):
        assert req.state == "finished" and len(req.generated) == 20
        want = _ref_logits(tree, prompt + req.generated[:-1])
        assert _gap(got, want[len(prompt) - 1:]) < 3e-5
        assert (want[len(prompt) - 1:].argmax(-1) == req.generated).all()
    count = eng.registry.counter
    # programs by doubling of the bucket: 64 and 256 (for 192), not 192;
    # both tile into chunks of 32, and the loop stops at the prompt's end
    lens = {64: (256, 64, 64), 48: (192, 96, 48)}[bucket]
    assert sorted(k[0] for k in eng.adapter._prefill_cache) == sorted(set(lens))
    computed = sum(M.prefill_positions(len(p), n, cfg)
                   for p, n in zip(prompts, lens))
    assert computed == {64: 160 + 64 + 32, 48: 192 + 96 + 48}[bucket]
    assert count("serve.prefill_computed_tokens").value == computed
    assert count("serve.prefill_state_writes").value == 3
    chose, blocks, context = M.prefill_choices(150, cfg)
    assert chose == 150 - 64
    assert count("serve.sparse_chose_tokens").value == chose
    assert count("serve.sparse_chosen_blocks").value == blocks
    assert count("serve.sparse_context_blocks").value == context
    # the products touched the chosen blocks and the band's corners (a
    # tile of 16 queries has none), or every block where a chunk walks
    multiplied = M.prefill_multiplied(150, lens[0], cfg)
    assert count("serve.sparse_multiplied_blocks").value == multiplied
    if bucket == BUCKET:
        assert M.chunk_forms(256, cfg)[:3] == ["dense", "dense", "chosen"]
        # the band tile's second block beside the chosen ones
        assert multiplied == blocks + chose < context
    else:
        assert M.chunk_forms(192, cfg)[1] == "masked"
        assert blocks < multiplied
    spans = read_spans(str(tmp_path))
    forms = {s.stats["rid"]: s.stats["attn_form"]
             for s in named(spans, "prefill.dispatch")}
    assert [forms[r.rid] for r in reqs] == {
        64: ["einsum+chosen_blocks", "einsum", "einsum"],
        48: ["einsum+masked_blocks+chosen_blocks", "einsum+masked_blocks",
             "einsum"]}[bucket]
    done = {s.stats["rid"]: s.stats for s in named(spans, "prefill.done")}
    assert [done[r.rid]["multiplied_blocks"] for r in reqs] == [
        multiplied, 0, 0]
    assert done[reqs[0].rid]["chosen_blocks"] == blocks
    assert done[reqs[0].rid]["context_blocks"] == context
    # decode steps whose stream stood past dense_len: all 19 of the long
    # one's and the crossing one's from position 64 on (lens 64 .. 68)
    assert count("serve.sparse_decode_chose").value == 19 + 5
    gauges = eng.registry.gauge
    assert gauges("serve.sparse_layers").value == 2
    assert gauges("serve.lightning_layers").value == 2
    # 2 kv heads of 16, K and V, float32: 256 B a layer and position; one
    # compressed key every 4 positions; a float32 (4, 16, 16) state
    assert gauges("serve.kv_bytes_per_token").value == 2 * 256
    assert gauges("serve.index_bytes_per_token").value == 2 * 128 // 4
    assert gauges("serve.lightning_state_bytes_per_stream").value == 2 * 4096
    pools = eng.adapter.cache.pools
    assert pools["k"].shape[0] == 4 and pools["k"].shape[2:] == (16, 1, 16)
    assert pools["kc"].shape == pools["k"].shape[:2] + (4, 16)
    assert eng.adapter._state["S"].shape == (2, 2, 4, 16, 16)
    assert eng.adapter._state["S"].dtype == jnp.float32


def test_bfloat16_serving_is_within_a_tolerance_that_float8_fails():
    from benchmark.drivers.serve import through_fp8

    def gap(control):
        tree = _tree(dtype=jnp.bfloat16)
        tree32 = jax.tree.map(lambda w: w.astype(jnp.float32), tree)
        if control:
            tree = jax.tree.map(through_fp8, tree)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (90,)]
        reqs, rows = _serve_capturing(
            _engine(tree, minicpm_sala_config(TINY), "bfloat16", max_batch=1),
            prompts, 8)
        d, s = [], []
        for prompt, req, got in zip(prompts, reqs, rows):
            want = _ref_logits(tree32, prompt + req.generated[:-1])
            want = want[len(prompt) - 1:]
            d.append(np.abs(got - want).ravel())
            s.append(want.std())
        return float(np.mean(np.concatenate(d)) / np.mean(s))

    sound, control = gap(False), gap(True)
    print("bf16 gap", sound, "float8 control", control)
    assert sound < LIMIT < control



def test_admission_reckons_with_the_sparse_layers_pool_alone():
    """Four slots and a pool that holds two long streams: the third long
    request waits for pages while a slot stands empty, a request the pool
    could never hold is rejected at the door, greedy tokens are those of
    an engine with room for all; what a stream holds in lightning layers
    does not depend on its context."""
    cfg, tree = minicpm_sala_config(TINY), _tree()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (120, 116, 100, 7)]
    roomy = _engine(tree, cfg, max_batch=4)
    want = [roomy.submit(p, 10) for p in prompts]
    roomy.run()
    # 128 + 10 positions a long stream -> 9 pages of 16; 20 hold two
    tight = _engine(tree, cfg, max_batch=4, num_pages=2 + 20)
    reqs = [tight.submit(p, 10) for p in prompts]
    most_live, in_use = 0, set()
    state_bytes = tight.adapter._state["S"].nbytes
    while tight.has_work():
        tight.step()
        most_live = max(most_live, sum(r is not None for r in tight._slots))
        in_use.add(tight.adapter.pages_in_use)
        assert tight.adapter.pages_in_use <= 20
        assert tight.adapter._state["S"].nbytes == state_bytes
    assert most_live <= 3  # never all four: pages, not slots, held one back
    assert tight.adapter.cache.failed_allocs == 0
    for a, b in zip(want, reqs):
        assert b.state == "finished" and a.generated == b.generated
    small = _engine(tree, cfg, num_pages=2 + 8)
    with pytest.raises(RequestRejected, match="sparse-attention pages"):
        small.submit(list(range(1, 121)), 30)
    long = _engine(tree, cfg, max_seq_len=1024)
    assert (long.adapter.state_bytes_per_stream
            == small.adapter.state_bytes_per_stream == 2 * 4096)
    assert state_bytes == 4 * 2 * 4096
    assert A.cache_bytes(cfg, jnp.bfloat16) == {
        "per_token": 2 * 128, "index_per_token": 2 * 64 // 4,
        "per_stream": 2 * 4096}


def test_refusals_name_what_is_not_built():
    cfg, tree = minicpm_sala_config(TINY), _tree()
    for kw, word in (({"kv_quant": "int8"}, "full-width"),
                     ({"serve_layout": "tp=2"}, "one chip"),
                     ({"speculator_path": "/x"}, "llama-only"),
                     ({"role": "prefill"}, "handoff"),
                     ({"prefill_chunk_tokens": 32}, "between decode steps"),
                     ({"page_size": 8}, "page_size=8"),
                     ({"prefill_bucket": 40}, "whole blocks")):
        with pytest.raises(ValueError, match=word):
            _engine(tree, cfg, **kw)
    assert not _engine(tree, cfg).adapter.supports_handoff
    c = {**TINY, "num_hidden_layers": 2, "mixer_types": ["lightning-attn"] * 2}
    with pytest.raises(ValueError, match="without one of them"):
        _engine(_tree(c), minicpm_sala_config(c))
    with pytest.raises(ValueError, match="minicpm_sala"):
        load_model_config({"family": "nope"})


@pytest.mark.parametrize("attn,rows", [("kernel", 2), ("reference", 0)])
def test_step_record_counts_the_blocks_the_kernel_walks(
    attn, rows, walked_blocks, monkeypatch
):
    """A row of the kernel is a (stream, kv head) and its length the
    chosen positions: every block up to the query's own while it is dense
    (``t + 1 <= 64`` here), ``topk`` (6) pages of 16 past that; blocks of
    two pages. The grid the kernel had: rows x the list's six pages in
    blocks of two."""
    from fms_fsdp_tpu.serve import families

    monkeypatch.setattr(families, "DECODE_BLOCK_TOKENS", 32)
    cfg = minicpm_sala_config(TINY)
    eng = _engine(_tree(), cfg, max_batch=3, max_prefill_per_step=3,
                  attn_impl=attn)
    assert cfg.kvheads == 2 and cfg.sparse.list_blocks == 6
    assert (eng.adapter.page_size, eng.adapter.block_kv) == (16, 32)

    def chosen(t):
        n = t // 16 + 1
        return (min(n, 6) - 1) * 16 + t % 16 if t + 1 > 64 else t

    by_hand = walked_blocks(eng, (5, 60, 130), 6, 3 * 2 * 3, rows, chosen)
    if rows:  # 5: one block; 60: two; 130: chosen 5 * 16 + 2 = 82, three
        assert by_hand[0] == 2 * (1 + 2 + 3)
