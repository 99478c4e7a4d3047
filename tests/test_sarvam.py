"""The sarvam family (multi-head latent attention, a sigmoid-routed
mixture of many small experts beside a shared one, a leading dense
layer, the held share of the experts) against the plain float32
reference ``benchmark/reference/sarvam.py``, at a small size that keeps
every ratio: 4 heads, nope/rope/v widths 16/8/24 that all differ, 16
experts top-4 with a bias that changes the choice, seeded weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import sarvam as reference
from fms_fsdp_tpu.models import mixtral as X
from fms_fsdp_tpu.models import moe_held as H
from fms_fsdp_tpu.models import sarvam as M
from fms_fsdp_tpu.models.configs import SarvamConfig, sarvam_config
from fms_fsdp_tpu.ops.rope import yarn_mscale, yarn_rope_table
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import (
    check_params_family,
    family_of,
    load_model_config,
)
from fms_fsdp_tpu.serve.kv_cache import PagedKVCache
from fms_fsdp_tpu.serve.scheduler import RequestRejected

TINY = {
    "model_type": "sarvam_mla",
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 128,
    "moe_intermediate_size": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24,
    "num_experts": 16, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "vocab_size": 256,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "deepseek_yarn"},
}
# one chip's share of four: experts 4-7 of 16
SHARE = {**TINY, "num_experts": 4, "published": {"num_experts": 16},
         "first_expert_held": 4}
CHUNK, BLOCK, BUCKET = 16, 32, 32


@pytest.fixture(autouse=True)
def _small_loops(monkeypatch):
    """Chunks and gather blocks small enough that a test prompt takes
    several trips of each loop."""
    monkeypatch.setattr(M, "PREFILL_CHUNK", CHUNK)
    monkeypatch.setattr(M, "DECODE_BLOCK_TOKENS", BLOCK)


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
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs",
                           "sarvam-105b.1chip.json")) as f:
        file = json.load(f)
    cfg = load_model_config(file)
    assert family_of(cfg) == "sarvam"
    assert (cfg.emb_dim, cfg.nheads, cfg.q_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.latent_dim) == (4096, 64, 192, 128, 512, 576)
    assert (cfg.moe_hidden_dim, cfg.hidden_dim, cfg.top_k, cfg.num_experts,
            cfg.routed_scaling_factor, cfg.rope_factor) == (
        2048, 16384, 8, 128, 2.5, 40)
    assert cfg.held == (0, 32) and cfg.nlayers == 6 and cfg.first_k_dense == 1
    assert cfg.src_vocab_size == 65536
    assert round(cfg.n_params() * 2 / 1e9, 2) == 10.92
    # the published file itself is the whole model: 105B
    whole = {k: v for k, v in file.items()
             if k not in ("published", "first_expert_held", "family")}
    whole.update(file["published"])
    cfg = load_model_config(whole)
    assert cfg.held == (0, 128) and cfg.nlayers == 32
    assert 105e9 < cfg.n_params() < 107e9
    with pytest.raises(ValueError, match="no range"):
        SarvamConfig(experts_held=(120, 16))


def test_tree_is_the_programs_own():
    for c in (TINY, SHARE):
        cfg = sarvam_config(c)
        mine = jax.eval_shape(lambda: _tree(c))
        theirs = jax.eval_shape(
            lambda k: M.init_sarvam_params(k, cfg), jax.random.PRNGKey(0))
        weights.require_same_tree(mine, theirs, "sarvam")
        check_params_family(mine, "sarvam")
    with pytest.raises(ValueError, match="mismatch"):
        check_params_family(mine, "mixtral")


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------


def test_yarn_frequencies_and_the_softmax_scale():
    cfg = sarvam_config(TINY)
    cos, sin = M.rope_tables(cfg, 40)
    inv = np.asarray(reference.yarn_inv_freq(TINY))
    ang = np.arange(40)[:, None] * inv[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=1e-5)
    # the fastest pair keeps theta's frequency, the slowest is cut 40-fold
    plain = 1.0 / 10000 ** (np.arange(4) / 4)
    assert abs(inv[0] - plain[0]) < 1e-6 and abs(inv[-1] - plain[-1] / 40) < 1e-9
    assert not np.allclose(inv, plain) and not np.allclose(inv, plain / 40)
    m = 0.1 * np.log(40) + 1
    assert abs(yarn_mscale(40, 1) - m) < 1e-12
    assert abs(M.softmax_scale(cfg) - m * m / np.sqrt(24)) < 1e-9
    # at the published sizes: 1.3689 squared over sqrt(192)
    assert abs(M.softmax_scale(SarvamConfig()) - 1.3689**2 / 192**0.5) < 1e-5
    # mscale on cos and sin is the ratio of the two, 1 as published
    c2, _ = yarn_rope_table(
        4, 8, 10000.0, factor=40, original_max_position=64, beta_fast=32,
        beta_slow=1, mscale=1.0, mscale_all_dim=0.0)
    assert abs(float(c2[0, 0]) - m) < 1e-6


def test_router_is_sigmoid_biased_for_the_choice_alone_and_sums_to_the_scale():
    cfg = sarvam_config(TINY)
    layer = jax.tree.map(lambda a: a[0], _tree(TINY)["layers"])
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64))
    idx, w = H._router(h, layer, cfg)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-5)
    scores = np.asarray(jax.nn.sigmoid(h @ layer["gate"]))
    picked = np.take_along_axis(scores, np.asarray(idx), -1)
    # the weights are the sigmoids themselves, normalised: no bias in them
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # not a softmax: the sigmoids of one row do not sum to one
    assert np.abs(scores.sum(-1) - 1).min() > 0.5
    # the bias changes who is chosen
    flat = dict(layer, gate_bias=jnp.zeros_like(layer["gate_bias"]))
    idx0, _ = H._router(h, flat, cfg)
    moved = np.mean(np.sort(np.asarray(idx), -1) != np.sort(np.asarray(idx0), -1))
    print("choices the bias moved:", moved)
    assert 0.02 < moved < 0.5
    # and agrees with the reference's router
    ridx, rw = reference.route(h, layer, TINY)
    assert (np.sort(np.asarray(ridx), -1) == np.sort(np.asarray(idx), -1)).all()
    np.testing.assert_allclose(np.sort(rw, -1), np.sort(w, -1), rtol=1e-5)


def test_absorbed_attention_equals_expanded():
    """One query a row over a cache of 40 positions: the decode step's
    absorbed form over latent pages against plain attention over keys
    and values expanded from the same latent."""
    cfg = sarvam_config(TINY)
    layer = jax.tree.map(lambda a: a[0], _tree(TINY)["layers"])
    B, S, ps = 2, 40, 8
    cos, sin = M.rope_tables(cfg, 64)
    h = jax.random.normal(jax.random.PRNGKey(2), (B, S, 64))
    lat = M._mla_latent(h, layer, cfg, cos, sin, None)  # (B, S, 40)
    lens = jnp.array([39, 21], jnp.int32)  # each row's query position
    hq = jnp.take_along_axis(h, lens[:, None, None], 1)
    q_nope, q_rope = M._mla_q(hq, layer, cfg, cos, sin, lens[:, None])
    # expanded
    k, v = M._mla_expand(lat, layer, cfg)
    q = jnp.concatenate([q_nope, q_rope], -1)
    s = jnp.einsum("bqnd,bsnd->bnqs", q, k) * M.softmax_scale(cfg)
    s = jnp.where(jnp.arange(S)[None, None, None] <= lens[:, None, None, None],
                  s, -jnp.inf)
    want = jnp.einsum("bnqs,bsnd->bqnd", jax.nn.softmax(s, -1), v)[:, 0]
    # absorbed, through pages: row b's pages are 2 + 5b .. 2 + 5b + 4
    pool = jnp.zeros((1, 16, ps, cfg.latent_dim))
    table = jnp.asarray([[2 + 5 * b + i for i in range(5)] + [0] * 3
                         for b in range(B)], jnp.int32)
    pool = pool.at[0, table[:, :5]].set(lat.reshape(B, 5, ps, -1))
    wk, wv = M._wkv_b_heads(layer, cfg)
    qq = jnp.concatenate(
        [jnp.einsum("bnd,rnd->bnr", q_nope[:, 0], wk), q_rope[:, 0]], -1)
    assert M.decode_block_pages(8, ps) == 4  # two trips of the gather loop
    for kernel in (False, True):  # gathered blocks; the ragged paged kernel
        u = M._latent_attend(qq, pool, 0, table, lens, cfg, ps, kernel=kernel)
        got = jnp.einsum("bnr,rnd->bnd", u, wv)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, err_msg=str(kernel))


def _moe_layer_by_token_loop(h, layer, cfg):
    """The held experts' part, token by token and choice by choice."""
    idx, w = H._router(h[None], layer, cfg)
    idx, w = np.asarray(idx[0]), np.asarray(w[0])
    first, held = cfg.held
    out = np.zeros(h.shape, np.float64)
    pairs = 0
    for t in range(h.shape[0]):
        for k in range(idx.shape[1]):
            e = idx[t, k] - first
            if 0 <= e < held:
                pairs += 1
                out[t] += w[t, k] * np.asarray(M._swiglu(
                    h[t], layer["w1"][e], layer["w3"][e], layer["w2"][e]))
    return out, pairs


@pytest.mark.parametrize("c", [TINY, SHARE], ids=["whole", "share"])
def test_routed_prefill_equals_the_token_loop_under_skewed_routing(c):
    """A bias that sends nearly every token to expert 5 first: its group
    is many times the mean, others are empty, and no pair is dropped."""
    cfg = sarvam_config(c)
    layer = jax.tree.map(lambda a: a[1], _tree(c)["layers"])
    layer["gate_bias"] = layer["gate_bias"].at[5].set(3.0).at[6].set(-3.0)
    h = jax.random.normal(jax.random.PRNGKey(4), (96, 64))
    y, n, *_ = jax.jit(lambda h, l: M._moe_grouped(h, l, cfg))(h, layer)
    want, pairs = _moe_layer_by_token_loop(h, layer, cfg)
    idx, _ = H._router(h[None], layer, cfg)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=16)
    assert counts[5] == 96 and counts[6] == 0  # skewed indeed
    assert int(n) == pairs
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    # the decode step's two forms give the same mixture
    for rows in (96, 1):  # 96 * 4 pairs >= held: all_experts; 4 < held: per_pair
        form = X.routed_moe_form(rows * 4, cfg.held[1])
        got = M._moe_token(h[:rows, None], layer, cfg, "routed")[:, 0]
        np.testing.assert_allclose(
            np.asarray(got), want[:rows], atol=2e-5, err_msg=form)
    assert X.routed_moe_form(4, 16) == "per_pair"


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of a quarter of the experts each, the shared expert
    counted once, equal the uncut layer: in the program (all three forms
    of the held part) and in the reference."""
    whole = sarvam_config(TINY)
    tree = _tree(TINY)
    layer = jax.tree.map(lambda a: a[0], tree["layers"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64))
    want = M._moe_dense_held(h, layer, whole) + M._shared(h, layer)
    ref_whole = reference.moe(h, layer, TINY)
    np.testing.assert_allclose(np.asarray(want), np.asarray(ref_whole), atol=2e-5)

    def cut(first):
        c = {**SHARE, "first_expert_held": first}
        part = dict(layer, **{
            w: layer[w][first:first + 4] for w in ("w1", "w3", "w2")})
        return c, sarvam_config(c), part

    sums = {"dense": 0, "grouped": 0, "token": 0, "reference": 0}
    n_pairs = 0
    for first in (0, 4, 8, 12):
        c, cfg, part = cut(first)
        assert cfg.held == (first, 4) and cfg.num_experts == 16
        sums["dense"] += M._moe_dense_held(h, part, cfg)
        y, n, *_ = M._moe_grouped(h[0], part, cfg)
        sums["grouped"] += y[None]
        n_pairs += int(n)
        sums["token"] += M._moe_token(
            jnp.moveaxis(h, 1, 0), part, cfg, "routed")[:, 0][None]
        sums["reference"] += reference.held_experts(h, part, c)
    assert n_pairs == 48 * 4  # every pair landed on exactly one share
    shared = M._shared(h, layer)
    for form, y in sums.items():
        np.testing.assert_allclose(
            np.asarray(y + shared), np.asarray(want), atol=3e-5, err_msg=form)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c", [TINY, SHARE], ids=["whole", "share"])
def test_full_forward_agrees_with_the_reference(c):
    tree = _tree(c)
    tokens = np.random.default_rng(0).integers(1, 256, size=48).tolist()
    got = M.sarvam_forward(
        tree, jnp.asarray([tokens]), sarvam_config(c),
        compute_dtype=jnp.float32)[0]
    assert _gap(np.asarray(got), _ref_logits(tree, c, tokens)) < 1e-5


# the flash kernel at one head width (24 and 24) and with values narrower
# than keys (24 and 16: the published model's 192 and 128 in small)
NARROW_V = {**SHARE, "v_head_dim": 16}


@pytest.mark.parametrize("attn_impl,c", [
    ("xla", SHARE), ("pallas", SHARE), ("pallas", NARROW_V),
], ids=["xla", "pallas", "pallas-two-widths"])
def test_prefill_in_chunks_is_the_forward(attn_impl, c, monkeypatch):
    """Two ragged rows through three chunks: the last real position's
    logits, and the latent that the pages take (zero past the length).
    ``pallas``: the flash kernel at the head widths as they are, nothing
    padded to a common width of 256 and no output column sliced away."""
    import re

    cfg, tree = sarvam_config(c), _tree(c)
    chunk = 256 if attn_impl == "pallas" else CHUNK
    monkeypatch.setattr(M, "PREFILL_CHUNK", chunk)
    S = 3 * chunk
    lengths = [S - 5, 2 * chunk + 1]
    rng = np.random.default_rng(1)
    toks = np.zeros((2, S), np.int32)
    for b, n in enumerate(lengths):
        toks[b, :n] = rng.integers(1, 256, size=n)
    prefill = jax.jit(
        lambda p, t, l: M.sarvam_prefill(
            p, t, l, cfg, compute_dtype=jnp.float32, kv_len=S + 16,
            attn_impl=attn_impl))
    args = (tree, jnp.asarray(toks), jnp.asarray(lengths))
    logits, lat, pairs, slabs, tiles = prefill(*args)
    form = M.prefill_attn_form(cfg, attn_impl, S)
    assert form == {"xla": "einsum"}.get(
        attn_impl, "flash" if c is SHARE else "flash_two_width")
    if attn_impl == "pallas":
        # queries, keys, values or an output of 4 heads 256 wide, in the
        # model's layout or the kernel's: the parent's padded operands
        text = prefill.lower(*args).as_text()
        assert not re.search(r"tensor<2x(256x4|4x256)x256xf32>", text)
        dq, dv = cfg.q_head_dim, cfg.v_head_dim
        assert f"tensor<2x4x256x{dq}xf32>" in text
        assert f"tensor<2x4x256x{dv}xf32>" in text
    assert lat.shape == (3, 2, S + 16, M.pool_width(cfg))
    assert float(jnp.abs(lat[..., cfg.latent_dim:]).max()) == 0.0
    for b, n in enumerate(lengths):
        want = _ref_logits(tree, c, toks[b, :n].tolist())[-1]
        assert _gap(np.asarray(logits[b]), want) < 2e-5
        assert float(jnp.abs(lat[:, b, n:]).max()) == 0.0
        assert float(jnp.abs(lat[:, b, :n]).max(axis=-1).min()) > 0
    # every computed position routes top_k pairs a MoE layer; a quarter
    # of the experts is here
    assert 0.1 < int(pairs) / (2 * S * 4 * 2) < 0.45
    # and the landed pairs of a layer and chunk fit one slab: a trip each
    assert int(slabs) == 2 * 3
    # and a trip's product meets a row tile or more, at most the slab's
    # and one more for each further group
    slab = H.grouped_slab(cfg, 2 * chunk * 4)
    most = slab // H.grouped_tile_rows(cfg, 2 * chunk * 4) + cfg.held[1] - 1
    assert int(slabs) <= int(tiles) <= int(slabs) * most


# ---------------------------------------------------------------------------
# prefill then decode through the engine and the paged latent cache
# ---------------------------------------------------------------------------


def _engine(tree, cfg, dtype="float32", **kw):
    scfg = ServeConfig(**{
        "max_batch": 2, "max_seq_len": 128, "compute_dtype": dtype,
        "attn_impl": "reference", "prefill_bucket": BUCKET, "page_size": 8,
        "max_prefill_per_step": 2, **kw})
    return ServingEngine(tree, cfg, scfg)


def _serve_capturing(eng, prompts, max_new):
    """-> per request, the logits row of every served position, read
    where the adapter hands them to the engine (tests/test_jamba.py)."""
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


# (config, slots, moe_impl): the share through both routed decode forms
# (2 slots x 4 pairs >= 4 held: all_experts; the whole model on 1 slot,
# 4 pairs < 16 held: per_pair) and the dense parity mode
# and the decode attention both ways: gathered blocks of pages in plain
# jax, and the ragged paged latent kernel (interpreted here)
ENGINES = [(SHARE, 2, "routed", "all_experts", "reference"),
           (SHARE, 2, "routed", "all_experts", "kernel"),
           (TINY, 1, "routed", "per_pair", "reference"),
           (SHARE, 2, "dense", "dense", "reference")]


@pytest.mark.parametrize(
    "c,slots,moe_impl,form,attn", ENGINES,
    ids=["share-all_experts", "share-kernel", "whole-per_pair", "dense"])
def test_engine_agrees_with_the_reference_on_logits_float32(
        c, slots, moe_impl, form, attn):
    """Prompts on, below and above a bucket edge and longer than a gather
    block, three requests on ``slots`` slots (so slots are left and
    joined while others decode): every served position's logits against
    the reference's full forward (expanded attention, no cache)."""
    cfg, tree = sarvam_config(c), _tree(c)
    eng = _engine(tree, cfg, max_batch=slots, moe_impl=moe_impl,
                  attn_impl=attn)
    assert eng.adapter.moe_form == form and eng.adapter.attn_impl == attn
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (37, 64, 9)]
    reqs, rows = _serve_capturing(eng, prompts, 14)
    for prompt, req, got in zip(prompts, reqs, rows):
        assert req.state == "finished" and len(req.generated) == 14
        want = _ref_logits(tree, c, prompt + req.generated[:-1])
        # float32 on both sides: reduction order, and the absorbed
        # products' association
        assert _gap(got, want[len(prompt) - 1:]) < 1e-5
        assert (want[len(prompt) - 1:].argmax(-1) == req.generated).all()
    count = eng.registry.counter
    computed = sum(-(-len(p) // CHUNK) * CHUNK for p in prompts)
    assert count("serve.prefill_computed_tokens").value == computed
    routed = count("serve.moe_pairs_routed").value
    assert routed == computed * 4 * 2
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
    # 40 values a position and layer, kept in whole rows of 128 lanes
    assert cfg.latent_dim == 40 and M.pool_width(cfg) == 128
    assert gauges("serve.latent_bytes_per_token").value == 3 * 128 * 4
    assert eng.adapter.cache.pools["latent"].shape[2:] == (8, 128)
    assert list(eng.adapter.cache.pools) == ["latent"]


@pytest.mark.parametrize("moe_impl", ["routed", "dense"])
def test_a_prefill_counts_its_grouped_products_trips_beside_its_pairs(
        moe_impl, tmp_path):
    """``serve.moe_slabs`` and ``moe_slabs`` on the ``prefill.done`` span,
    beside ``moe_pairs_held``: the trips the grouped product's loop took,
    one a MoE layer and chunk here (a chunk's landed pairs fit a slab);
    the dense form takes none."""
    import glob

    from jax.profiler import ProfileData

    cfg = sarvam_config(SHARE)
    eng = _engine(_tree(SHARE), cfg, moe_impl=moe_impl)
    prompt = np.random.default_rng(2).integers(1, 256, size=37).tolist()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.submit(prompt, 2)
        eng.run()
    chunks = -(-len(prompt) // CHUNK)
    want = cfg.n_moe_layers * chunks if moe_impl == "routed" else 0
    count = eng.registry.counter
    assert count("serve.moe_slabs").value == want
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    (done,) = [dict(e.stats) for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events
               if e.name == "serve/prefill.done"]
    assert done["moe_slabs"] == want
    assert done["moe_pairs_held"] == count("serve.moe_pairs_held").value
    assert (done["moe_pairs_held"] > 0) == (moe_impl == "routed")
    assert done["moe_pairs_routed"] == chunks * CHUNK * 4 * cfg.n_moe_layers


def test_a_prefill_counts_the_row_tiles_its_grouped_products_met(
        tmp_path, monkeypatch):
    """``serve.moe_row_tiles`` and ``moe_row_tiles`` on the
    ``prefill.done`` span, beside ``moe_pairs_held`` and ``moe_slabs``:
    the (group, row tile) meetings a grouped product of each trip ran,
    equal to the count by hand from the chunk's group sizes. A prefill's
    router is replaced by a fixed choice, the same for every layer, so the hand
    knows the sizes: of a chunk's 256 pairs (64 positions) 164 land on
    the four held experts (ids 4-7), 60, 64, 30 and 10 of them; a slab
    is all 256 rows in two tiles of 128, the sorted groups lie at rows
    0-60, 60-124, 124-154 and 154-164, so they meet 1, 1, 2 and 1
    tiles."""
    import glob

    from jax.profiler import ProfileData

    monkeypatch.setattr(M, "PREFILL_CHUNK", 64)
    cfg = sarvam_config(SHARE)
    t = np.arange(64)
    chosen = np.stack([
        np.where(t < 60, 4, 0), np.full(64, 5), np.where(t < 30, 6, 1),
        np.where(t < 10, 7, 2)], axis=1)
    sizes = [int((chosen == e).sum()) for e in (4, 5, 6, 7)]
    assert sizes == [60, 64, 30, 10]
    assert (H.grouped_slab(cfg, 256), H.grouped_tile_rows(cfg, 256)) == (
        256, 128)
    met = 1 + 1 + 2 + 1
    weights = jnp.full(chosen.shape, 0.25, jnp.float32)
    router = H._router

    def fixed(h, layer, cfg):
        if h.ndim == 2:  # a prefill chunk's rows
            return jnp.asarray(chosen), weights
        return router(h, layer, cfg)

    monkeypatch.setattr(H, "_router", fixed)
    eng = _engine(_tree(SHARE), cfg)
    prompt = np.random.default_rng(2).integers(1, 256, size=37).tolist()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.submit(prompt, 2)
        eng.run()
    want = cfg.n_moe_layers * met  # the prompt's 37 positions: one chunk
    count = eng.registry.counter
    assert count("serve.moe_row_tiles").value == want
    assert count("serve.moe_pairs_held").value == (
        cfg.n_moe_layers * sum(sizes))
    assert count("serve.moe_slabs").value == cfg.n_moe_layers
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    (done,) = [dict(e.stats) for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events
               if e.name == "serve/prefill.done"]
    assert done["moe_row_tiles"] == want
    # the share of the rows a product multiplied that were pairs
    assert done["moe_pairs_held"] / (done["moe_row_tiles"] * 128) == (
        164 / (5 * 128))


def test_bfloat16_serving_is_within_a_tolerance_that_float8_fails():
    from benchmark.drivers.serve import through_fp8

    def gap(control):
        tree = _tree(SHARE, dtype=jnp.bfloat16)
        tree32 = jax.tree.map(lambda w: w.astype(jnp.float32), tree)
        if control:
            tree = jax.tree.map(through_fp8, tree)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (40, 23)]
        reqs, rows = _serve_capturing(
            _engine(tree, sarvam_config(SHARE), "bfloat16"), prompts, 16)
        d, s = [], []
        for prompt, req, got in zip(prompts, reqs, rows):
            want = _ref_logits(tree32, SHARE, prompt + req.generated[:-1])
            want = want[len(prompt) - 1:]
            d.append(np.abs(got - want).ravel())
            s.append(want.std())
        return float(np.mean(np.concatenate(d)) / np.mean(s))

    sound, control = gap(False), gap(True)
    print("bf16 gap", sound, "float8 control", control)
    # read at this size on the CPU: sound 0.02, through float8 0.2
    assert sound < 0.06 < control


def test_the_pool_and_not_the_slots_refuses_an_admission():
    """Four slots and a pool that holds two long streams: the third long
    request waits for pages while a slot stands empty, a request the pool
    could never hold is rejected at the door, and greedy tokens are those
    of an engine with room for all."""
    cfg, tree = sarvam_config(SHARE), _tree(SHARE)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (60, 58, 50, 7)]
    roomy = _engine(tree, cfg, max_batch=4)
    want = [roomy.submit(p, 10) for p in prompts]
    roomy.run()
    # 64 + 10 positions a long stream -> 10 pages of 8; 22 hold two
    tight = _engine(tree, cfg, max_batch=4, num_pages=2 + 22)
    reqs = [tight.submit(p, 10) for p in prompts]
    most_live = 0
    while tight.has_work():
        tight.step()
        live = sum(r is not None for r in tight._slots)
        most_live = max(most_live, live)
        assert tight.adapter.pages_in_use <= 22
    assert most_live <= 3  # never all four: pages, not slots, held one back
    assert tight.adapter.cache.failed_allocs == 0
    for a, b in zip(want, reqs):
        assert b.state == "finished" and a.generated == b.generated
    # 10 pages hold 80 positions: 60 + 30 could never fit
    small = _engine(tree, cfg, num_pages=2 + 10)
    with pytest.raises(RequestRejected, match="latent pages"):
        small.submit(list(range(1, 61)), 30)


def test_refusals_name_what_is_not_built():
    cfg, tree = sarvam_config(SHARE), _tree(SHARE)
    for kw, word in (({"kv_quant": "int8"}, "full-width"),
                     ({"serve_layout": "tp=2"}, "exchange"),
                     ({"speculator_path": "/x"}, "llama-only"),
                     ({"moe_impl": "dispatch"}, "moe_impl")):
        with pytest.raises(ValueError, match=word):
            _engine(tree, cfg, **kw)
    assert not _engine(tree, cfg).adapter.supports_handoff


# ---------------------------------------------------------------------------
# the code shared with the other families
# ---------------------------------------------------------------------------


def test_mixtral_decode_program_is_the_text_it_was():
    """``_expert_mix`` took a ``first`` for a share of the experts; with
    every expert held (Mixtral) the decode program has to lower to the
    text it had before. The parent's definition, verbatim, in its place
    gives the same StableHLO in both routed forms."""
    from fms_fsdp_tpu.models.configs import MixtralConfig
    from fms_fsdp_tpu.serve.families import mixtral as A

    def parents_expert_mix(top_idx, top_w, E: int):
        return jnp.sum(
            jax.nn.one_hot(top_idx, E, dtype=jnp.float32) * top_w[..., None],
            axis=-2,
        )

    cfg = MixtralConfig(
        src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
        hidden_dim=96, num_experts=4, top_k=2, max_expected_seq_len=64)
    params = jax.eval_shape(
        lambda k: X.init_mixtral_params(k, cfg), jax.random.PRNGKey(0))

    def text(slots):
        scfg = ServeConfig(max_batch=slots, max_seq_len=64, page_size=8,
                           compute_dtype="float32", moe_impl="routed")
        ps, _, _, maxp, n = A.page_geometry(cfg, scfg)
        pool = jax.ShapeDtypeStruct((2, n, ps, 2, 16), jnp.float32)
        return A.decode_program(cfg, scfg, ps, jnp.float32).lower(
            params, {"k": pool, "v": pool},
            jax.ShapeDtypeStruct((slots, maxp), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()

    for slots in (4, 1):  # all_experts, per_pair
        mine = text(slots)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(X, "_expert_mix", parents_expert_mix)
            theirs = text(slots)
        assert mine == theirs


@pytest.mark.parametrize("pools", [None, {"latent": (40,)}],
                         ids=["k-and-v", "latent"])
def test_declared_pools_keep_every_page_operation_bit_for_bit(pools):
    """write_prompt, the page table, export, import into another pool and
    defrag over whatever pools were declared; the default is still ``k``
    and ``v`` per kv head."""
    def make():
        return PagedKVCache(2, 12, 4, 2, 8, dtype=jnp.float32, pools=pools)

    a, b = make(), make()
    names = list(a.pools)
    assert names == (["latent"] if pools else ["k", "v"])
    entry = (40,) if pools else (2, 8)
    assert all(p.shape == (2, 12, 4) + entry for p in a.pools.values())
    rng = np.random.default_rng(0)
    vals = [jnp.asarray(rng.standard_normal((2, 8) + entry), jnp.float32)
            for _ in names]
    assert a.ensure(7, 3) and a.ensure(9, 8)  # 9 takes pages behind 7's
    a.write_prompt(9, *vals)
    a.free(7)
    out = a.gather_pages(9)
    for name, v in zip(names, vals):
        np.testing.assert_array_equal(
            out[name].reshape((2, 8) + entry), np.asarray(v))
    assert float(jnp.abs(a.pools[names[0]][:, :2]).max()) == 0  # reserved
    assert b.scatter_pages(3, out, 8) and b.tokens_of(3) == 8
    for name in names:
        np.testing.assert_array_equal(b.gather_pages(3)[name], out[name])
    assert a.defrag() == 2 and a.pages_of(9) == [2, 3]
    for name in names:
        np.testing.assert_array_equal(a.gather_pages(9)[name], out[name])
    if pools:
        with pytest.raises(ValueError, match="full-width"):
            PagedKVCache(2, 12, 4, quant="int8", pools=pools)
