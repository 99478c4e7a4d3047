"""The kexaone family (window and full attention layers with a cache of
each kind, a sigmoid-routed mixture of many small experts beside a shared
one, a leading dense layer, the held share of the experts) against the
plain float32 reference ``benchmark/reference/kexaone.py``, at a small
size that keeps every ratio: two periods ``LLLG LLLG`` with the first
layer dense, 4 query heads on 2 kv heads, a window of 8 that prompts and
decodes overrun several times, 16 experts top-4 with a bias that changes
the choice, seeded weights.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import kexaone as reference
from fms_fsdp_tpu.models import kexaone as M
from fms_fsdp_tpu.models import moe_held as H
from fms_fsdp_tpu.models.configs import KExaoneConfig, kexaone_config
from fms_fsdp_tpu.ops.flash_attention import flash_attention
from fms_fsdp_tpu.ops.paged_attention import (
    paged_attention_kernel,
    paged_attention_reference,
)
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import (
    check_params_family,
    family_of,
    load_model_config,
)
from fms_fsdp_tpu.serve.families import kexaone as A
from fms_fsdp_tpu.serve.scheduler import RequestRejected

LLLG = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {
    "model_type": "exaone_moe",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 8,
    "layer_types": LLLG * 2, "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "first_k_dense_replace": 1, "sliding_window": 8,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 16, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "vocab_size": 256,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_nextn_predict_layers": 0,
}
# one chip's share of four: experts 4-7 of 16
SHARE = {**TINY, "num_experts": 4, "published": {"num_experts": 16},
         "first_expert_held": 4}
# one period of it, for the tests that serve in bfloat16 (slow on a CPU)
SHARE4 = {**SHARE, "num_hidden_layers": 4, "layer_types": LLLG,
          "mlp_layer_types": ["dense"] + ["sparse"] * 3}
CHUNK, BUCKET = 16, 32


@pytest.fixture(autouse=True)
def _small_loops(monkeypatch):
    """Chunks small enough that a test prompt takes several trips of the
    prefill's loop (and is longer than a chunk and than the window)."""
    monkeypatch.setattr(M, "PREFILL_CHUNK", CHUNK)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tree(c, seed=3, dtype=jnp.float32):
    return weights.make_tree(
        weights.seed_key(seed), reference.param_spec(c), dtype)


def _ref_logits(tree, c, tokens):
    return np.asarray(
        reference.forward(tree, jnp.asarray([tokens], jnp.int32), c)[0])


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# the config and the tree
# ---------------------------------------------------------------------------


def test_load_model_config_on_the_published_keys():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs",
                           "k-exaone-236b.1chip.json")) as f:
        file = json.load(f)
    cfg = load_model_config(file)
    assert family_of(cfg) == "kexaone"
    assert (cfg.emb_dim, cfg.nheads, cfg.kvheads, cfg.head_dim,
            cfg.sliding_window, cfg.rope_theta, cfg.norm_eps) == (
        6144, 64, 8, 128, 128, 1e6, 1e-5)
    assert (cfg.moe_hidden_dim, cfg.hidden_dim, cfg.top_k, cfg.num_experts,
            cfg.routed_scaling_factor, cfg.num_shared_experts) == (
        2048, 18432, 8, 128, 2.5, 1)
    assert cfg.held == (0, 16) and cfg.src_vocab_size == 19200
    # two whole periods, the first layer dense
    assert cfg.stacks == {
        "sliding_dense": [0], "sliding_sparse": [1, 2, 4, 5, 6],
        "full_sparse": [3, 7]}
    assert cfg.window_layers == (0, 1, 2, 4, 5, 6)
    assert cfg.full_layers == (3, 7) and cfg.n_moe_layers == 7
    assert cfg.n_params() == 5979349888  # 11.96 GB in bfloat16
    # a stream's cache by layer kind, at the published widths
    assert A.cache_bytes(cfg, jnp.bfloat16) == {
        "per_token": 8192, "per_stream": 6 * 128 * 4096}
    # the published file itself is the whole model: 236B, and asks for
    # the multi-token-prediction module, which is refused by name
    whole = {k: v for k, v in file.items()
             if k not in ("published", "first_expert_held", "family")}
    whole.update(num_hidden_layers=48, num_experts=128, vocab_size=153600,
                 layer_types=LLLG * 12,
                 mlp_layer_types=["dense"] + ["sparse"] * 47)
    cfg = load_model_config(whole)
    assert cfg.held == (0, 128) and cfg.nlayers == 48
    assert (len(cfg.window_layers), len(cfg.full_layers)) == (36, 12)
    assert 236e9 < cfg.n_params() < 237e9
    with pytest.raises(ValueError, match="multi-token-prediction"):
        load_model_config({**whole, "num_nextn_predict_layers": 1})
    with pytest.raises(ValueError, match="no range"):
        KExaoneConfig(experts_held=(120, 16), layer_types=LLLG * 12,
                      mlp_layer_types=("sparse",) * 48)
    with pytest.raises(ValueError, match="layer_types"):
        KExaoneConfig(nlayers=2, layer_types=("sliding_attention",),
                      mlp_layer_types=("sparse",) * 2)


def test_tree_is_the_programs_own():
    for c in (TINY, SHARE):
        cfg = kexaone_config(c)
        mine = jax.eval_shape(lambda: _tree(c))
        theirs = jax.eval_shape(
            lambda k: M.init_kexaone_params(k, cfg), jax.random.PRNGKey(0))
        weights.require_same_tree(mine, theirs, "kexaone")
        check_params_family(theirs, "kexaone")
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(theirs))
        assert n == cfg.n_params()
    with pytest.raises(ValueError, match="mismatch"):
        check_params_family(theirs, "sarvam")


# ---------------------------------------------------------------------------
# the held experts' layer: the code sarvam runs
# ---------------------------------------------------------------------------


def _moe_layer_by_token_loop(h, layer, cfg):
    """Each row through each of its chosen experts that is held, one
    product at a time: what every routed form has to equal."""
    idx, w = H._router(h, layer, cfg)
    first, held = cfg.held
    out = np.zeros(h.shape, np.float32)
    for t in range(h.shape[0]):
        for e, wt in zip(np.asarray(idx[t]), np.asarray(w[t])):
            if first <= e < first + held:
                out[t] += float(wt) * np.asarray(H._swiglu(
                    h[t], layer["w1"][e - first], layer["w3"][e - first],
                    layer["w2"][e - first]))
    return out


@pytest.mark.parametrize("c", [TINY, SHARE], ids=["whole", "share"])
def test_routing_skewed_onto_one_held_expert_drops_no_pair(c):
    cfg, tree = kexaone_config(c), _tree(c)
    layer = jax.tree.map(lambda a: a[1], tree["sliding_sparse"])
    # a bias that sends every row to the first held expert
    bias = layer["gate_bias"].at[cfg.held[0]].add(5.0)
    layer = dict(layer, gate_bias=bias)
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    want = _moe_layer_by_token_loop(h, layer, cfg)
    y, n, *_ = H._moe_grouped(h, layer, cfg)
    idx, _ = H._router(h, layer, cfg)
    here = (idx >= cfg.held[0]) & (idx < sum(cfg.held))
    assert int(n) == int(here.sum()) >= 40  # every row's pair on it counted
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    tok = H._moe_token(h[:, None], layer, cfg, "routed")[:, 0]
    np.testing.assert_allclose(np.asarray(tok), want, atol=2e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight shares of an eighth of the experts each, the shared expert
    counted once, equal the uncut layer: in the program (all three forms
    of the held part) and in the reference."""
    whole = kexaone_config(TINY)
    tree = _tree(TINY)
    layer = jax.tree.map(lambda a: a[0], tree["full_sparse"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64))
    want = H._moe_dense_held(h, layer, whole) + H._shared(h, layer)
    ref_whole = reference.moe(h, layer, TINY)
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(ref_whole), atol=2e-5)

    sums = {"dense": 0, "grouped": 0, "token": 0, "reference": 0}
    n_pairs = 0
    for first in range(0, 16, 2):
        c = {**TINY, "num_experts": 2, "published": {"num_experts": 16},
             "first_expert_held": first}
        cfg = kexaone_config(c)
        part = dict(layer, **{
            w: layer[w][first:first + 2] for w in ("w1", "w3", "w2")})
        assert cfg.held == (first, 2) and cfg.num_experts == 16
        sums["dense"] += H._moe_dense_held(h, part, cfg)
        y, n, *_ = H._moe_grouped(h[0], part, cfg)
        sums["grouped"] += y[None]
        n_pairs += int(n)
        sums["token"] += H._moe_token(
            jnp.moveaxis(h, 1, 0), part, cfg, "routed")[:, 0][None]
        sums["reference"] += reference.held_experts(h, part, c)
    assert n_pairs == 48 * 4  # every pair landed on exactly one share
    shared = H._shared(h, layer)
    for form, y in sums.items():
        np.testing.assert_allclose(
            np.asarray(y + shared), np.asarray(want), atol=3e-5, err_msg=form)


# 448 rows x 4 choices = 1792 pairs; a quarter of the experts is here, so
# a slab is 1.5 x a quarter of them in whole row tiles: 768, and three
# trips (the last one short, past the sorted pairs' end) take them all
SLAB_ROWS, SLAB = 448, 768


@pytest.mark.parametrize("poisoned", [False, True], ids=["clean", "nan-past"])
@pytest.mark.parametrize(
    "landed", [0, SLAB, SLAB + 1, SLAB_ROWS * 4],
    ids=["none", "one-slab", "one-slab-and-a-pair", "all"])
def test_grouped_prefill_takes_the_landed_pairs_a_slab_at_a_time(
        landed, poisoned, monkeypatch):
    """The first ``landed`` (token, choice) pairs of a chunk on the four
    held experts, the rest elsewhere: ``_moe_grouped`` equals the token
    loop, counts them, takes ``ceil(landed / slab)`` trips and counts
    the row tiles each trip's groups lie in. With every
    row that a grouped product did not visit poisoned, the result is the
    same to the bit: a slab's rows past its valid count are zeroed before
    any product reads them."""
    cfg, tree = kexaone_config(SHARE), _tree(SHARE)
    layer = jax.tree.map(lambda a: a[1], tree["sliding_sparse"])
    first, held = cfg.held
    T, K = SLAB_ROWS, cfg.top_k
    assert H.grouped_slab(cfg, T * K) == SLAB
    assert H.grouped_slab(kexaone_config(TINY), T * K) == T * K  # all here
    rng = np.random.default_rng(landed)
    # choice k of a row: held expert k where the pair lands here (a row's
    # experts stay distinct), else one of the others
    here = (np.arange(T * K) < landed).reshape(T, K)
    away = (first + held + rng.integers(0, 16 - held, (T, K))) % 16
    idx = np.where(here, first + np.arange(K)[None, :], away)
    w = rng.uniform(0.1, 1.0, (T, K)).astype(np.float32)
    monkeypatch.setattr(
        H, "_router", lambda h, layer, cfg: (jnp.asarray(idx), jnp.asarray(w)))
    h = jax.random.normal(jax.random.PRNGKey(landed), (T, 64))
    want = np.zeros((T, 64), np.float32)
    for t, k in zip(*np.nonzero(here)):
        want[t] += w[t, k] * np.asarray(H._swiglu(
            h[t], layer["w1"][k], layer["w3"][k], layer["w2"][k]))

    run = jax.jit(lambda h: H._moe_grouped(h, layer, cfg))
    y, n, trips, met = run(h)
    assert (int(n), int(trips)) == (landed, -(-landed // SLAB))
    # sorted, the landed pairs are held expert 0's, then 1's...: of each
    # slab, the tiles of 128 rows that each group's rows lie in
    group = np.sort(np.nonzero(here)[1])
    by_hand = sum(
        len({(g, r // 128) for r, g in enumerate(group[lo:lo + SLAB])})
        for lo in range(0, landed, SLAB))
    assert H.grouped_tile_rows(cfg, T * K) == 128
    assert int(met) == by_hand
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    if poisoned:
        gmm = H._gmm

        def poisoned_gmm(x, stack, sizes, l):
            visited = jnp.arange(x.shape[0])[:, None] < jnp.sum(sizes)
            return jnp.where(visited, gmm(x, stack, sizes, l), jnp.nan)

        monkeypatch.setattr(H, "_gmm", poisoned_gmm)
        again = jax.jit(lambda h: H._moe_grouped(h, layer, cfg))(h)
        np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(y))


# ---------------------------------------------------------------------------
# the kernels at this family's shapes
# ---------------------------------------------------------------------------


def _banded(q, k, v, window):
    B, S, N, Hd = q.shape
    g = N // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqnh,bsnh->bnqs", q, k) * Hd**-0.5
    back = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    o = jnp.einsum("bnqs,bsnh->bqnh", jax.nn.softmax(s, axis=-1), v)
    return o, jnp.moveaxis(lse, 1, 2)[..., None]


@pytest.mark.parametrize("window,bq,bk,nq,nkv", [
    (37, 256, 128, 4, 2), (128, 256, 128, 4, 2), (128, 128, 128, 4, 2),
    (300, 128, 128, 4, 2), (129, 128, 256, 4, 2), (1, 128, 128, 4, 2),
    (4096, 256, 128, 4, 2),
    # the cell's real ratios: a KV head's eight query heads in one cell,
    # a head alone, a window wider than a Q block,
    # and the blocks the call picks for itself
    (128, 256, 128, 8, 1), (128, 256, 128, 2, 2), (300, 128, 128, 8, 1),
    (128, None, None, 8, 1), (128, None, None, 16, 2)],
    ids=["under-a-block", "a-block", "a-block-square", "over-a-block",
         "one-over", "self-only", "wider-than-all", "group-8", "group-1",
         "group-8-over-a-block", "group-8-own-blocks",
         "two-groups-of-8-own-blocks"])
def test_windowed_flash_forward_is_the_masked_einsum(window, bq, bk, nq, nkv):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 512, n, 128))
               for kk, n in zip(ks, (nq, nkv, nkv)))
    o, lse = flash_attention(
        q, k, v, window=window, block_q=bq, block_k=bk, interpret=True,
        return_lse=True)
    want_o, want_lse = _banded(q, k, v, window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want_lse), atol=1e-5)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("nq,nkv", [(64, 8), (8, 8)], ids=["group-8", "group-1"])
def test_windowed_flash_grid_has_a_cell_a_kv_head(nq, nkv):
    """The engagement check, off the chip: at the k-exaone chunk's shape
    the one Mosaic call's grid has the KV heads on its head axis and the
    group's query heads inside the cell's Q block; a K block and a V
    block (keys across) are named by the KV head alone; 192 cells a
    layer and chunk where a head a cell ran 1536."""
    B, S, H, window = 1, 2048, 128, 128
    q = jax.ShapeDtypeStruct((B, S, nq, H), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, nkv, H), jnp.bfloat16)
    (call,) = _pallas_calls(jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, window=window)
    )(q, kv, kv).jaxpr)
    mapping = call.params["grid_mapping"]
    blocks = [
        tuple(getattr(d, "block_size", d) for d in m.block_shape)
        for m in mapping.block_mappings]
    group = nq // nkv
    assert mapping.grid == (B, nkv, S // 256, 3)
    assert blocks == [
        (1, 1, group, 256, H), (1, 1, 128, H), (1, 1, H, 128),  # q, k, v
        (1, 1, group, 256, H), (1, 1, group, 1, 256)]  # o, lse


def test_windowed_flash_refuses_what_is_not_built_by_name():
    q = jnp.ones((1, 256, 2, 128))
    with pytest.raises(NotImplementedError, match="sliding window"):
        jax.grad(lambda x: flash_attention(
            x, q, q, window=8, interpret=True).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=8, causal=False, interpret=True)
    with pytest.raises(ValueError, match="one length"):
        flash_attention(q, q[:, :128], q[:, :128], window=8, interpret=True)


def test_paged_kernel_at_the_published_head_geometry():
    """64 query heads on 8 kv heads of 128, pages of one layer of a pool
    read through a table moved into that layer's part of it
    (``_pages_attend``), ragged lengths: the kernel equals the gather."""
    B, P, ps, maxp, L = 3, 12, 16, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, 64, 128))
    pools = {"k": jax.random.normal(ks[1], (L, P, ps, 8, 128)),
             "v": jax.random.normal(ks[2], (L, P, ps, 8, 128))}
    table = jnp.asarray([[2, 3, 4, 5], [6, 7, 0, 0], [8, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([50, 17, 3], jnp.int32)
    for lf in range(L):
        got = M._pages_attend(q, pools, lf, table, lens, True, 2 * ps)
        want = paged_attention_reference(
            q, pools["k"][lf], pools["v"][lf], table, lens)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5)
        ref = M._pages_attend(q, pools, lf, table, lens, False, None)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(want), atol=1e-6)
    one = paged_attention_kernel(
        q, pools["k"][1], pools["v"][1], table, lens, block_kv=2 * ps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(one), atol=1e-6)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c", [TINY, SHARE], ids=["whole", "share"])
def test_full_forward_agrees_with_the_reference(c):
    tree = _tree(c)
    tokens = np.random.default_rng(0).integers(1, 256, size=48).tolist()
    got = M.kexaone_forward(
        tree, jnp.asarray([tokens]), kexaone_config(c),
        compute_dtype=jnp.float32)[0]
    assert _gap(np.asarray(got), _ref_logits(tree, c, tokens)) < 1e-5


def test_a_window_layer_forgets_and_a_full_layer_does_not():
    """One layer of each kind alone: altering positions more than a
    window behind position t leaves a window layer's output at t as it
    was, to the bit, and moves a full layer's."""
    def logits(kind, tokens):
        c = {**TINY, "num_hidden_layers": 1, "layer_types": [kind],
             "mlp_layer_types": ["sparse"]}
        return np.asarray(M.kexaone_forward(
            _tree(c), jnp.asarray([tokens]), kexaone_config(c),
            compute_dtype=jnp.float32)[0])

    rng = np.random.default_rng(1)
    a = rng.integers(1, 256, size=40).tolist()
    b = rng.integers(1, 256, size=20).tolist() + a[20:]  # 0-19 altered
    # position 27 sees 20-27 under a window of 8: nothing that changed
    w_a, w_b = (logits("sliding_attention", t) for t in (a, b))
    assert (w_a[27:] == w_b[27:]).all() and (w_a[26] != w_b[26]).any()
    f_a, f_b = (logits("full_attention", t) for t in (a, b))
    assert np.abs(f_a[27:] - f_b[27:]).max() > 1e-3


# the flash kernels at a head of 128 (interpreted here): the windowed one
# over each chunk's band, the causal one over the full layers' blocks
WIDE = {**SHARE, "head_dim": 128, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 4,
        "layer_types": LLLG, "mlp_layer_types": ["dense"] + ["sparse"] * 3,
        "sliding_window": 128}


@pytest.mark.parametrize("attn_impl,c,chunk,lengths", [
    ("xla", SHARE, CHUNK, (45, 16, 7)), ("pallas", WIDE, 256, (600, 130))],
    ids=["einsum", "flash"])
def test_prefill_in_chunks_is_the_forward(
        attn_impl, c, chunk, lengths, monkeypatch):
    """Prompts longer than a chunk and than the window, one that fills
    its chunks and one shorter than the window: the last position's
    logits, the full layers' keys and values (zero past the length) and
    the window layers' rings are the whole-sequence forward's."""
    monkeypatch.setattr(M, "PREFILL_CHUNK", chunk)
    cfg, tree = kexaone_config(c), _tree(c)
    W, S = cfg.sliding_window, -(-max(lengths) // chunk) * chunk
    rng = np.random.default_rng(3)
    toks = np.zeros((len(lengths), S), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = rng.integers(1, 256, size=n)
    assert M.prefill_attn_form(cfg, attn_impl, S) == (
        "einsum" if attn_impl == "xla" else "flash_window+flash")
    logits, kv, ring, pairs, slabs, tiles = jax.jit(lambda p, t, l: M.kexaone_prefill(
        p, t, l, cfg, compute_dtype=jnp.float32, kv_len=S,
        attn_impl=attn_impl, moe_impl="routed"))(
        tree, jnp.asarray(toks), jnp.asarray(lengths, jnp.int32))
    assert kv["k"].shape == (len(cfg.full_layers), len(lengths), S,
                             cfg.kvheads, cfg.head_dim)
    assert ring["k"].shape == (len(cfg.window_layers), len(lengths), W,
                               cfg.kvheads, cfg.head_dim)
    assert int(pairs) > 0
    # the landed pairs of a layer and chunk fit one slab: a trip each
    assert int(slabs) == cfg.n_moe_layers * (S // chunk)
    assert int(tiles) >= int(slabs)  # a trip's product meets a row tile
    for i, n in enumerate(lengths):
        want = _ref_logits(tree, c, toks[i, :n].tolist())[-1]
        assert _gap(np.asarray(logits[i]), want) < 2e-5
        assert not np.asarray(kv["k"][:, i, n:]).any()
        assert np.asarray(kv["k"][:, i, :n]).all(axis=(-1, -2)).all()
    # a ring holds position t at t mod W: read back in order, it is the
    # keys of the prompt's last W positions as a prefill of the prompt
    # cut to its last position but one leaves them, shifted by one
    n = lengths[0]
    _, _, short, *_ = jax.jit(lambda p, t, l: M.kexaone_prefill(
        p, t, l, cfg, compute_dtype=jnp.float32, kv_len=S,
        attn_impl="xla", moe_impl="routed"))(
        tree, jnp.asarray(toks[:1]), jnp.asarray([n - 1], jnp.int32))
    for t in range(n - W, n - 1):
        np.testing.assert_allclose(
            np.asarray(ring["k"][:, 0, t % W]),
            np.asarray(short["k"][:, 0, t % W]), atol=1e-5)


# ---------------------------------------------------------------------------
# prefill then decode through the engine: rings and pages
# ---------------------------------------------------------------------------


def _engine(tree, cfg, dtype="float32", **kw):
    scfg = ServeConfig(**{
        "max_batch": 2, "max_seq_len": 128, "compute_dtype": dtype,
        "attn_impl": "reference", "prefill_bucket": BUCKET, "page_size": 8,
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


def test_a_prefill_counts_its_grouped_products_trips_beside_its_pairs(
        tmp_path):
    """``serve.moe_slabs`` and ``moe_slabs`` on the ``prefill.done`` span,
    beside ``moe_pairs_held`` (``HeldExpertsAdapter._count_prefill``, as
    for sarvam): the trips the grouped product's loop took, one a sparse
    layer and chunk here."""
    import glob

    from jax.profiler import ProfileData

    cfg = kexaone_config(SHARE4)
    eng = _engine(_tree(SHARE4), cfg, moe_impl="routed")
    prompt = np.random.default_rng(2).integers(1, 256, size=37).tolist()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.submit(prompt, 2)
        eng.run()
    want = cfg.n_moe_layers * -(-len(prompt) // CHUNK)
    count = eng.registry.counter
    assert count("serve.moe_slabs").value == want == 3 * 3
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    (done,) = [dict(e.stats) for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events
               if e.name == "serve/prefill.done"]
    assert done["moe_slabs"] == want
    assert done["moe_pairs_held"] == count("serve.moe_pairs_held").value > 0


ENGINES = [(SHARE, 2, "routed", "all_experts", "reference"),
           (SHARE, 2, "routed", "all_experts", "kernel"),
           (TINY, 1, "routed", "per_pair", "reference"),
           (SHARE, 2, "dense", "dense", "reference")]


@pytest.mark.parametrize(
    "c,slots,moe_impl,form,attn", ENGINES,
    ids=["share-all_experts", "share-kernel", "whole-per_pair", "dense"])
def test_engine_agrees_with_the_reference_on_logits_float32(
        c, slots, moe_impl, form, attn):
    """Prompts on, below and above a bucket edge, shorter and longer than
    the window, three requests on ``slots`` slots (so a slot's ring is
    left and taken over while others decode), 30 decoded positions (the
    ring of 8 wraps more than three times): every served position's
    logits against the reference's full forward (masked attention, no
    cache of either kind)."""
    cfg, tree = kexaone_config(c), _tree(c)
    eng = _engine(tree, cfg, max_batch=slots, moe_impl=moe_impl,
                  attn_impl=attn)
    assert eng.adapter.moe_form == form and eng.adapter.attn_impl == attn
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (37, 64, 5)]
    reqs, rows = _serve_capturing(eng, prompts, 30)
    for prompt, req, got in zip(prompts, reqs, rows):
        assert req.state == "finished" and len(req.generated) == 30
        want = _ref_logits(tree, c, prompt + req.generated[:-1])
        assert _gap(got, want[len(prompt) - 1:]) < 2e-5
        assert (want[len(prompt) - 1:].argmax(-1) == req.generated).all()
    count = eng.registry.counter
    computed = sum(-(-len(p) // CHUNK) * CHUNK for p in prompts)
    assert count("serve.prefill_computed_tokens").value == computed
    assert count("serve.prefill_state_writes").value == 3
    routed = count("serve.moe_pairs_routed").value
    assert routed == computed * 4 * 7
    share = count("serve.moe_pairs_held").value / routed
    if moe_impl == "dense":  # weighs every pair and counts none
        assert share == 0
    elif c is TINY:  # every expert is here
        assert share == 1
    else:  # a quarter of them
        assert 0.1 < share < 0.45
    gauges = eng.registry.gauge
    assert gauges("serve.moe_experts_held").value == cfg.held[1]
    assert gauges("serve.moe_experts_published").value == 16
    assert gauges("serve.window_layers").value == 6
    assert gauges("serve.full_layers").value == 2
    assert gauges("serve.window_positions").value == 8
    # 2 kv heads of 16, K and V, float32: 256 B a layer and position
    assert gauges("serve.kv_bytes_per_token").value == 2 * 256
    assert gauges("serve.window_state_bytes_per_stream").value == 6 * 8 * 256
    # pages for the full layers alone, a ring a slot for the others
    assert eng.adapter.cache.pools["k"].shape[0] == 2
    assert eng.adapter._state["k"].shape == (6, slots, 8, 2, 16)


def test_bfloat16_serving_is_within_a_tolerance_that_float8_fails():
    from benchmark.drivers.serve import through_fp8

    def gap(control):
        tree = _tree(SHARE4, dtype=jnp.bfloat16)
        tree32 = jax.tree.map(lambda w: w.astype(jnp.float32), tree)
        if control:
            tree = jax.tree.map(through_fp8, tree)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (40, 23)]
        reqs, rows = _serve_capturing(
            _engine(tree, kexaone_config(SHARE4), "bfloat16"), prompts, 16)
        d, s = [], []
        for prompt, req, got in zip(prompts, reqs, rows):
            want = _ref_logits(tree32, SHARE4, prompt + req.generated[:-1])
            want = want[len(prompt) - 1:]
            d.append(np.abs(got - want).ravel())
            s.append(want.std())
        return float(np.mean(np.concatenate(d)) / np.mean(s))

    sound, control = gap(False), gap(True)
    print("bf16 gap", sound, "float8 control", control)
    # read at this size on the CPU: sound 0.011, through float8 0.103
    assert sound < 0.035 < control


def test_admission_reckons_with_the_full_layers_pool_alone():
    """Four slots and a pool that holds two long streams: the third long
    request waits for pages while a slot stands empty, a request the pool
    could never hold is rejected at the door, greedy tokens are those of
    an engine with room for all; and what a stream holds in window layers
    does not depend on its context."""
    cfg, tree = kexaone_config(SHARE), _tree(SHARE)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (60, 58, 50, 7)]
    roomy = _engine(tree, cfg, max_batch=4)
    want = [roomy.submit(p, 10) for p in prompts]
    roomy.run()
    # 64 + 10 positions a long stream -> 10 pages of 8; 22 hold two
    tight = _engine(tree, cfg, max_batch=4, num_pages=2 + 22)
    reqs = [tight.submit(p, 10) for p in prompts]
    most_live, in_use = 0, set()
    ring_bytes = sum(a.nbytes for a in tight.adapter._state.values())
    while tight.has_work():
        tight.step()
        live = sum(r is not None for r in tight._slots)
        most_live = max(most_live, live)
        in_use.add(tight.adapter.pages_in_use)
        assert tight.adapter.pages_in_use <= 22
        # the rings never grow, whatever the streams' contexts
        assert sum(a.nbytes for a in tight.adapter._state.values()) == ring_bytes
    assert most_live <= 3  # never all four: pages, not slots, held one back
    assert len(in_use) > 3  # the pages did grow
    assert tight.adapter.cache.failed_allocs == 0
    for a, b in zip(want, reqs):
        assert b.state == "finished" and a.generated == b.generated
    # 10 pages hold 80 positions: 60 + 30 could never fit
    small = _engine(tree, cfg, num_pages=2 + 10)
    with pytest.raises(RequestRejected, match="full-attention pages"):
        small.submit(list(range(1, 61)), 30)
    # a stream's window-layer bytes: the same at 128 and at 1024 positions
    long = _engine(tree, cfg, max_seq_len=1024)
    assert (long.adapter.state_bytes_per_stream
            == small.adapter.state_bytes_per_stream == 6 * 8 * 256)
    assert ring_bytes == 4 * 6 * 8 * 256
    assert A.ring_shape(cfg, long.adapter.scfg)[2] == cfg.sliding_window


def test_refusals_name_what_is_not_built():
    cfg, tree = kexaone_config(SHARE), _tree(SHARE)
    for kw, word in (({"kv_quant": "int8"}, "full-width"),
                     ({"serve_layout": "tp=2"}, "exchange"),
                     ({"speculator_path": "/x"}, "llama-only"),
                     ({"moe_impl": "dispatch"}, "moe_impl")):
        with pytest.raises(ValueError, match=word):
            _engine(tree, cfg, **kw)
    assert not _engine(tree, cfg).adapter.supports_handoff
    c = {**SHARE, "num_hidden_layers": 2,
         "layer_types": ["sliding_attention"] * 2,
         "mlp_layer_types": ["sparse"] * 2}
    with pytest.raises(ValueError, match="without one of them"):
        _engine(_tree(c), kexaone_config(c))


# ---------------------------------------------------------------------------
# the code shared with the other families
# ---------------------------------------------------------------------------

JAX_VERSION = "0.9.0"
# sha256 of jit(...).lower(...).as_text() of a small sarvam engine's
# programs, read off the tree before the held experts' layer moved out of
# models/sarvam.py (PR 33's parent) on that jax, under this file's
# highest matmul precision (at the default: bbeeb528..., 8b794284...,
# 8e09e6bc..., 064218f2..., the same on both trees). The two prefill
# programs are PR 44's: they return the row tiles the grouped product
# met beside its trips and the pairs, and the routed one multiplies
# through ops/grouped_matmul.py (b5ab43b9... and c284750a... since PR 35,
# which brought the trips and the slab; 8ae51275... and 4351de2c... until
# then); the decode programs are the text they were
SARVAM_DIGESTS = {
    "decode all_experts":
        "3625f5e13a91ad80336aa90508c9bde832bb9ed1ad2eb868db5598fd0c49b4b8",
    "decode per_pair":
        "6a85865e42e78975aad0d4a44498f40f116872ab0de706ce105dff3abef4144c",
    "prefill routed":
        "d83569b8dca7fe9303dccae056a23677e9bceba231b64ed765d3cf760e468cb4",
    "prefill dense":
        "802fc1b3226e1e6157726503b7e08f90a29857c3cc9a951bb397b9b89455fb06",
}


@pytest.mark.parametrize("program", sorted(SARVAM_DIGESTS))
def test_sarvam_programs_are_the_text_they_were(program):
    """``_router``, ``_shared``, ``_held_mixture``, ``_moe_token``,
    ``_moe_grouped`` and ``_gmm`` moved to models/moe_held.py, where both
    families import them: the sarvam programs lower to the text they had
    (Mixtral's: tests/test_sarvam.py, tests/test_family_adapters.py)."""
    if jax.__version__ != JAX_VERSION:
        pytest.skip(f"digests hold for jax {JAX_VERSION}")
    from fms_fsdp_tpu.models import sarvam as S
    from fms_fsdp_tpu.models.configs import SarvamConfig
    from fms_fsdp_tpu.serve.families import sarvam as SA

    assert S._moe_grouped is H._moe_grouped and S._moe_token is H._moe_token
    cfg = SarvamConfig(
        src_vocab_size=128, emb_dim=64, nheads=4, nlayers=3,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
        kv_lora_rank=32, hidden_dim=96, first_k_dense=1, moe_hidden_dim=32,
        num_experts=8, experts_held=(2, 4), top_k=2, num_shared_experts=1,
        max_expected_seq_len=64)
    params = jax.eval_shape(
        lambda k: S.init_sarvam_params(k, cfg), jax.random.PRNGKey(0))
    kind, how = program.split()
    sd = jax.ShapeDtypeStruct
    if kind == "decode":
        slots = 4 if how == "all_experts" else 1
        scfg = ServeConfig(
            max_batch=slots, max_seq_len=64, page_size=8,
            compute_dtype="float32", moe_impl="routed",
            attn_impl="reference")
        ps, maxp, n = SA.page_geometry(cfg, scfg)
        pool = sd((cfg.nlayers, n, ps, S.pool_width(cfg)), jnp.float32)
        text = SA.decode_program(cfg, scfg, ps, jnp.float32).lower(
            params, {"latent": pool}, sd((slots, maxp), jnp.int32),
            sd((slots,), jnp.int32), sd((slots,), jnp.int32),
            sd((2,), jnp.uint32)).as_text()
    else:
        scfg = ServeConfig(
            max_batch=2, max_seq_len=64, page_size=8,
            compute_dtype="float32", moe_impl=how, attn_impl="reference")
        text = SA.prefill_program(cfg, scfg, 32, 32, jnp.float32).lower(
            params, sd((1, 32), jnp.int32), sd((1,), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == SARVAM_DIGESTS[program]


@pytest.mark.parametrize("attn,rows", [("kernel", 1), ("reference", 0)])
def test_step_record_counts_the_blocks_the_kernel_walks(
    attn, rows, walked_blocks, monkeypatch
):
    """Three streams whose lengths cross a block's edge (16 positions, two
    pages, at this size): ``attn_blocks`` of each step record is ``seq_len
    // block + 1`` over the live streams, ``serve.decode_attn_grid_blocks``
    the grid of every block a slot could hold; both 0 where the pages are
    gathered."""
    from fms_fsdp_tpu.serve import families

    monkeypatch.setattr(families, "DECODE_BLOCK_TOKENS", 16)
    eng = _engine(_tree(TINY), kexaone_config(TINY), max_batch=3,
                  max_prefill_per_step=3, moe_impl="dense", attn_impl=attn)
    assert (eng.adapter.page_size, eng.adapter.block_kv) == (8, 16)
    by_hand = walked_blocks(eng, (5, 14, 28), 6, 3 * (128 // 16), rows)
    if rows:  # 5..9 one block, 14, 15 | 16..18 two, 28..31 | 32 three
        assert by_hand[0] == 1 + 1 + 2 and by_hand[-1] == 1 + 2 + 3
