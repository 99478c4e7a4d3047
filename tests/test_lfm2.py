"""The lfm2 family (gated short-convolution layers that keep a window of
two positions a slot beside grouped-query attention layers over pages, a
sigmoid-routed mixture of many small experts with no shared one behind
two leading dense layers, a tied head) against the plain float32
reference ``benchmark/reference/lfm2.py``, at a small size that keeps
every kind of layer: two dense convolution layers, then ``attn conv
attn`` (two attention layers, so that the second reads its own part of
the pool), 4 query heads on 2 kv heads of 16, 16 experts top-4 with a
bias that changes the choice, seeded weights.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers.serve_hybrid import as_program_tree
from benchmark.reference import lfm2 as reference
from fms_fsdp_tpu.models import lfm2 as M
from fms_fsdp_tpu.models import moe_held as H
from fms_fsdp_tpu.models.configs import Lfm2MoeConfig, lfm2_moe_config
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import (
    check_params_family,
    family_of,
    load_model_config,
)
from fms_fsdp_tpu.serve.families import lfm2 as A
from fms_fsdp_tpu.serve.scheduler import RequestRejected

TYPES = ["conv", "conv", "full_attention", "conv", "full_attention"]
TINY = {
    "model_type": "lfm2_moe",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 5, "layer_types": TYPES, "conv_L_cache": 3,
    "conv_bias": False, "num_dense_layers": 2, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 16, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 256,
    "max_position_embeddings": 512, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
}
CHUNK, BUCKET = 16, 16
# float32 against float32 under the highest matmul precision: what is
# left is the order of the sums (a chunked prefill, a paged softmax, a
# grouped product), 1e-6 of the largest logit on this CPU; 2e-5 leaves
# room for another backend's order and is a thousandth of what bfloat16
# anywhere in the path reads (test_bfloat16_where_float32_is_stated...)
TOL = 2e-5


@pytest.fixture(autouse=True)
def _small_loops(monkeypatch):
    """Chunks small enough that a test prompt takes several trips of the
    prefill's loop."""
    monkeypatch.setattr(M, "PREFILL_CHUNK", CHUNK)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tree(c, seed=3, dtype=jnp.float32):
    return as_program_tree(weights.make_tree(
        weights.seed_key(seed), reference.param_spec(c), dtype))


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
                           "lfm2-24b-a2b.1chip.json")) as f:
        file = json.load(f)
    assert file["model_type"] == "lfm2_moe"
    cfg = load_model_config(file)
    assert family_of(cfg) == "lfm2" and isinstance(cfg, Lfm2MoeConfig)
    assert (cfg.emb_dim, cfg.nheads, cfg.kvheads, cfg.head_dim,
            cfg.conv_kernel, cfg.rope_theta, cfg.norm_eps) == (
        2048, 32, 8, 64, 3, 1e6, 1e-5)
    assert (cfg.moe_hidden_dim, cfg.hidden_dim, cfg.top_k, cfg.num_experts,
            cfg.routed_scaling_factor, cfg.router_sum_eps) == (
        1536, 11776, 4, 64, 1.0, 1e-6)
    assert cfg.held == (0, 64) and cfg.src_vocab_size == 65536
    # the two leading dense layers and two whole periods behind them
    assert cfg.nlayers == 10 and cfg.attn_layers == (2, 6)
    assert cfg.conv_layers == (0, 1, 3, 4, 5, 7, 8, 9)
    assert cfg.n_moe_layers == 8 and file["parameters_held"] == cfg.n_params()
    assert 5.26e9 < cfg.n_params() < 5.28e9  # 10.53 GB in bfloat16
    # a stream's cache by kind of operator, at the published widths
    assert A.cache_bytes(cfg, jnp.bfloat16) == {
        "per_token": 4096, "per_stream": 8 * 8192}
    # the published file itself is the whole model: 23.8B
    whole = {k: v for k, v in file.items() if k not in ("published", "family")}
    whole.update(file["published"])
    cfg = load_model_config(whole)
    assert cfg.nlayers == 40 and len(cfg.attn_layers) == 10
    assert 23.8e9 < cfg.n_params() < 23.9e9
    with pytest.raises(ValueError, match="conv_bias"):
        load_model_config({**whole, "conv_bias": True})
    with pytest.raises(ValueError, match="sliding_attention"):
        load_model_config(
            {**TINY, "layer_types": ["sliding_attention"] + TYPES[1:]})
    with pytest.raises(ValueError, match="no range"):
        Lfm2MoeConfig(experts_held=(60, 16), layer_types=("conv",) * 40)
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(nlayers=2, layer_types=("conv",))


def test_tree_is_the_programs_own():
    cfg = lfm2_moe_config(TINY)
    theirs = jax.eval_shape(
        lambda k: M.init_lfm2_params(k, cfg), jax.random.PRNGKey(0))
    weights.require_same_tree(
        jax.eval_shape(lambda: _tree(TINY)), theirs, "lfm2")
    check_params_family(theirs, "lfm2")
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(theirs))
    assert n == cfg.n_params()
    with pytest.raises(ValueError, match="mismatch"):
        check_params_family(theirs, "mamba")


# ---------------------------------------------------------------------------
# the expert layer: every expert here, none shared
# ---------------------------------------------------------------------------

E64 = {**TINY, "num_experts": 64}


def _e64_layer():
    layer = _tree(E64)["layers"][3]
    # a bias large enough to change the choice
    return dict(layer, gate_bias=layer["gate_bias"] * 10.0)


def _held_part(form, h, part, cfg, c):
    if form == "dense":
        return H._moe_token(h, part, cfg, "dense")
    if form == "grouped":
        return H._moe_grouped(h[0], part, cfg)[0][None]
    if form == "token":
        return H._moe_token(
            jnp.moveaxis(h, 1, 0), part, cfg, "routed")[:, 0][None]
    return reference.moe(h, part, c)


@pytest.mark.parametrize("form", ["dense", "grouped", "token", "reference"])
def test_the_four_shares_add_up_to_the_whole_layer(form):
    """Held shares (0,16) .. (48,16) of a router 64 wide, in each form of
    the held part, equal the layer that holds all 64 as the reference
    computes it: there is no shared expert to count once."""
    layer = _e64_layer()
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64))
    want = np.asarray(reference.moe(h, layer, E64))
    total = 0
    for first in range(0, 64, 16):
        c = reference.share(E64, first, 16)
        cfg = lfm2_moe_config(c)
        assert cfg.held == (first, 16) and cfg.num_experts == 64
        part = dict(layer, **{
            w: layer[w][first:first + 16] for w in ("w1", "w3", "w2")})
        total += _held_part(form, h, part, cfg, c)
    np.testing.assert_allclose(np.asarray(total), want, atol=3e-5)


def test_a_layer_with_no_shared_expert():
    """``_shared`` answers zeros where a layer has no shared expert (the
    sarvam and kexaone layers have one), the whole layer is the routed
    mixture alone, and the chosen weights are normalised over ``sum +
    1e-6``: a config field that the other families do not have."""
    cfg, layer = lfm2_moe_config(E64), _e64_layer()
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 64))
    assert not np.asarray(H._shared(h, layer)).any()
    idx, w = H._router(h, layer, cfg)
    scores = np.asarray(jax.nn.sigmoid(h @ layer["gate"]))
    chosen = np.take_along_axis(scores, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # the bias chose: without it another set of experts is taken
    plain, _ = H._router(h, dict(layer, gate_bias=layer["gate_bias"] * 0), cfg)
    assert (np.sort(np.asarray(idx)) != np.sort(np.asarray(plain))).any()

    class NoEps:  # a family without the field lowers to no add
        top_k, routed_scaling_factor = cfg.top_k, 1.0

    _, w0 = H._router(h, layer, NoEps)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 1.0, rtol=1e-6)
    assert "add" not in str(jax.make_jaxpr(
        lambda s: 1.0 * s / jnp.sum(s, -1, keepdims=True))(w0))


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_full_forward_agrees_with_the_reference():
    tree = _tree(TINY)
    tokens = np.random.default_rng(0).integers(1, 256, size=48).tolist()
    cfg = lfm2_moe_config(TINY)
    got = M.lfm2_forward(
        tree, jnp.asarray([tokens]), cfg, compute_dtype=jnp.float32)[0]
    want = _ref_logits(tree, TINY, tokens)
    assert _gap(np.asarray(got), want) < TOL


def test_bfloat16_is_told_from_float32_and_float8_from_bfloat16():
    """The forward in bfloat16 where float32 is stated fails the
    tolerance a thousand times over; bfloat16 weights and arithmetic
    against the float32 reference of the same weights read a sixth of
    what weights through float8 read (mean |logit difference| over the
    logits' spread, on this CPU: 0.029 and 0.18: a sixth)."""
    from benchmark.drivers.serve import through_fp8

    cfg = lfm2_moe_config(TINY)
    tokens = np.random.default_rng(0).integers(1, 256, size=48).tolist()

    def served(tree, dtype=jnp.bfloat16):
        return np.asarray(M.lfm2_forward(
            tree, jnp.asarray([tokens]), cfg, compute_dtype=dtype)[0],
            np.float32)

    tree = _tree(TINY)
    assert _gap(served(tree), _ref_logits(tree, TINY, tokens)) > 100 * TOL
    tree16 = _tree(TINY, dtype=jnp.bfloat16)
    want = _ref_logits(
        jax.tree.map(lambda w: w.astype(jnp.float32), tree16), TINY, tokens)
    sound, control = (
        float(np.mean(np.abs(served(t) - want)) / want.std())
        for t in (tree16, jax.tree.map(through_fp8, tree16)))
    print("bf16 gap", sound, "float8 control", control)
    assert sound < 0.06 < 0.1 < control


def test_a_convolution_layer_forgets_and_an_attention_layer_does_not():
    """One layer of each kind alone: altering positions more than two
    behind position t leaves a convolution layer's output at t as it was,
    to the bit, and moves an attention layer's."""
    def logits(kind, tokens):
        c = {**TINY, "num_hidden_layers": 1, "layer_types": [kind],
             "num_dense_layers": 0}
        return np.asarray(M.lfm2_forward(
            _tree(c), jnp.asarray([tokens]), lfm2_moe_config(c),
            compute_dtype=jnp.float32)[0])

    rng = np.random.default_rng(1)
    a = rng.integers(1, 256, size=40).tolist()
    b = rng.integers(1, 256, size=20).tolist() + a[20:]  # 0-19 altered
    c_a, c_b = (logits("conv", t) for t in (a, b))
    # position 22 sees 20-22 under a kernel of 3: nothing that changed
    assert (c_a[22:] == c_b[22:]).all() and (c_a[21] != c_b[21]).any()
    f_a, f_b = (logits("full_attention", t) for t in (a, b))
    assert np.abs(f_a[22:] - f_b[22:]).max() > 1e-3


def _prefill(tree, cfg, toks, lengths, S, **kw):
    return jax.jit(lambda p, t, l: M.lfm2_prefill(
        p, t, l, cfg, compute_dtype=jnp.float32, kv_len=S,
        **{"attn_impl": "xla", "moe_impl": "routed", **kw}))(
        tree, jnp.asarray(toks), jnp.asarray(lengths, jnp.int32))


LENGTHS = (45, 16, 1, 2)


def _padded_prompts(lengths, S, seed=3):
    rng = np.random.default_rng(seed)
    toks = np.zeros((len(lengths), S), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = rng.integers(1, 256, size=n)
    return toks


def test_prefill_in_chunks_is_the_forward(moe_impl="routed"):
    """A prompt that crosses two chunk boundaries, one that fills its
    chunk, and prompts of one and of two positions (shorter than, and as
    long as, the window): the last position's logits, the attention
    layers' keys and values (zero past the length) and the convolution
    layers' windows are the whole-sequence forward's."""
    cfg, tree = lfm2_moe_config(TINY), _tree(TINY)
    S = 48
    toks = _padded_prompts(LENGTHS, S)
    assert M.prefill_attn_form(cfg, "xla", S) == "einsum"
    logits, kv, win, pairs, slabs, tiles = _prefill(
        tree, cfg, toks, LENGTHS, S, moe_impl=moe_impl)
    # a position's 2 kv heads of 16 side by side, a row a position
    assert kv["k"].shape == (2, len(LENGTHS), S, 32)
    assert win["z"].shape == (3, len(LENGTHS), 2, 64)
    # every pair lands: every expert is here
    assert int(pairs) == S * len(LENGTHS) * 4 * cfg.n_moe_layers
    assert int(slabs) == cfg.n_moe_layers * (S // CHUNK)
    assert int(tiles) >= int(slabs)  # a trip's product meets a row tile
    for i, n in enumerate(LENGTHS):
        want = _ref_logits(tree, TINY, toks[i, :n].tolist())[-1]
        assert _gap(np.asarray(logits[i]), want) < TOL
        assert not np.asarray(kv["k"][:, i, n:]).any()
        assert np.asarray(kv["k"][:, i, :n]).all(axis=-1).all()
    # a window holds the prompt's last two values of z, oldest first: the
    # newest of the prompt cut to its last position but one is the oldest
    # of the whole prompt's, and a prompt of one position has zeros first
    _, _, short, *_ = _prefill(tree, cfg, toks[:1], [LENGTHS[0] - 1], S)
    np.testing.assert_allclose(
        np.asarray(win["z"][:, 0, 0]), np.asarray(short["z"][:, 0, 1]),
        atol=1e-5)
    assert not np.asarray(win["z"][:, 2, 0]).any()
    assert np.asarray(win["z"][:, 2, 1]).any()


def test_a_window_dropped_at_one_chunk_boundary_fails_the_tolerance(
        monkeypatch):
    """A prompt of 20 positions crosses one chunk boundary. A prefill
    that does not carry the windows over it reads 10000 times the
    tolerance at the last position."""
    cfg, tree = lfm2_moe_config(TINY), _tree(TINY)
    toks = _padded_prompts((20,), 32)
    want = _ref_logits(tree, TINY, toks[0, :20].tolist())[-1]
    sound = _prefill(tree, cfg, toks, [20], 32)[0][0]
    assert _gap(np.asarray(sound), want) < TOL
    conv = M._short_conv
    monkeypatch.setattr(
        M, "_short_conv",
        lambda tail, z, w: conv(
            jnp.zeros_like(tail) if z.shape[1] > 1 else tail, z, w))
    dropped = _prefill(tree, cfg, toks, [20], 32)[0][0]
    gap = _gap(np.asarray(dropped), want)
    print("dropped window gap", gap)
    assert gap > 1000 * TOL


def test_flash_prefill_at_heads_of_64(monkeypatch):
    """The causal flash kernel (interpreted here) on heads 64 wide, four
    query heads a kv head as published, over two chunks of 256: the
    chunked prefill is the forward."""
    c = {**TINY, "hidden_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 1, "num_hidden_layers": 2,
         "layer_types": ["conv", "full_attention"], "num_dense_layers": 1}
    cfg, tree = lfm2_moe_config(c), _tree(c)
    assert cfg.head_dim == 64
    monkeypatch.setattr(M, "PREFILL_CHUNK", 256)
    toks = _padded_prompts((300,), 512)
    assert M.prefill_attn_form(cfg, "pallas", 512) == "flash_head64"
    logits = _prefill(tree, cfg, toks, [300], 512, attn_impl="pallas")[0][0]
    want = _ref_logits(tree, c, toks[0, :300].tolist())[-1]
    assert _gap(np.asarray(logits), want) < TOL


# ---------------------------------------------------------------------------
# prefill then decode through the engine: windows and pages
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
    reqs = [eng.submit(p, n) for p, n in zip(prompts, max_new)]
    eng.run()
    return reqs, [np.stack(rows[r.rid][: len(r.generated)]) for r in reqs]


ENGINES = [(4, "routed", "all_experts", "reference"),
           (1, "dense", "dense", "kernel")]


@pytest.mark.parametrize(
    "slots,moe_impl,form,attn", ENGINES, ids=["all_experts", "dense-kernel"])
def test_engine_agrees_with_the_reference_on_logits_float32(
        slots, moe_impl, form, attn):
    """A prompt that crosses a chunk boundary and a program's edge, one on
    a bucket's edge, one of a single position (shorter than the window),
    and more requests than slots, among them one of one position that
    takes over a slot a longer stream leaves (its window is written
    whole at hand-over: nothing of the stream before leaks; on one slot
    every request does), outputs of different lengths: every served
    position's logits against the reference's full forward (no window, no
    pages)."""
    cfg, tree = lfm2_moe_config(TINY), _tree(TINY)
    eng = _engine(tree, cfg, max_batch=slots, moe_impl=moe_impl,
                  attn_impl=attn)
    assert eng.adapter.moe_form == form and eng.adapter.attn_impl == attn
    assert eng.adapter._dispatch_fields == {
        "moe_form": form, "attn_form": attn}
    rng = np.random.default_rng(7)
    lengths, outputs = (37, 32, 1, 1, 5), (6, 12, 12, 12, 12)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in lengths]
    reqs, rows = _serve_capturing(eng, prompts, outputs)
    for prompt, req, got, n in zip(prompts, reqs, rows, outputs):
        assert req.state == "finished" and len(req.generated) == n
        want = _ref_logits(tree, TINY, prompt + req.generated[:-1])
        assert _gap(got, want[len(prompt) - 1:]) < TOL
        assert (want[len(prompt) - 1:].argmax(-1) == req.generated).all()
    count, gauges = eng.registry.counter, eng.registry.gauge
    # programs of 16, 32 and 64 positions, chunks of 16
    assert sorted(eng.adapter._prefill_cache) == [(16, 16), (32, 32), (64, 64)]
    computed = sum(-(-n // CHUNK) * CHUNK for n in lengths)
    assert count("serve.prefill_computed_tokens").value == computed
    assert count("serve.prefill_state_writes").value == 5
    assert count("serve.conv_windows_written").value == 5 * 3
    routed = count("serve.moe_pairs_routed").value
    assert routed == computed * 4 * 3
    assert count("serve.moe_pairs_held").value == (
        0 if moe_impl == "dense" else routed)
    # a decode step's own counts, read with its tokens: the live streams'
    # pairs, and of the 3 x 16 (layer, expert) pairs those they touched
    decoded = sum(outputs) - len(outputs)
    assert count("serve.decode_tokens").value == decoded
    assert count("serve.moe_pairs").value == decoded * 4 * 3
    touched = count("serve.moe_experts_touched").value
    assert decoded * 4 * 3 / slots <= touched <= count("serve.moe_pairs").value
    if slots == 1:  # a stream's four experts a layer are distinct
        assert touched == count("serve.moe_pairs").value
    assert not eng.adapter._step_counts  # every step's counts were read
    assert gauges("serve.moe_experts_held").value == 16
    assert gauges("serve.conv_layers").value == 3
    assert gauges("serve.attn_layers").value == 2
    assert gauges("serve.conv_window_positions").value == 2
    # 2 kv heads of 16, K and V, float32: 256 B a layer and position
    assert gauges("serve.kv_bytes_per_token").value == 2 * 256
    assert gauges("serve.conv_state_bytes_per_stream").value == 3 * 2 * 256
    # pages for the attention layers alone, a window a slot for the others
    assert eng.adapter.cache.pools["k"].shape == (
        2, eng.adapter.cache.num_pages, 8, 32)
    assert eng.adapter._state["z"].shape == (3, slots, 2, 64)


@pytest.mark.parametrize("attn", ["kernel", "reference"])
def test_pages_hold_a_position_as_rows_of_128_lanes(attn):
    """Four kv heads of 64 (the published head width; 8 are published): a
    position is two rows of 128 lanes in a page, two heads a row, written
    by a prefill program longer than the prompt and a row pair at a time
    by the decode step, read by the kernel two heads a tile and by the
    gathered form."""
    c = {**TINY, "hidden_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 4, "num_hidden_layers": 2,
         "layer_types": ["conv", "full_attention"], "num_dense_layers": 1}
    cfg, tree = lfm2_moe_config(c), _tree(c)
    eng = _engine(tree, cfg, max_batch=1, attn_impl=attn, moe_impl="dense")
    assert eng.adapter.cache.pools["k"].shape[2:] == (8 * 2, 128)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (19, 3)]
    reqs, rows = _serve_capturing(eng, prompts, (5, 5))
    for prompt, req, got in zip(prompts, reqs, rows):
        want = _ref_logits(tree, c, prompt + req.generated[:-1])
        assert _gap(got, want[len(prompt) - 1:]) < TOL


def test_a_dead_slots_window_stays_as_it_was():
    """A decode step shifts the windows of the live slots alone."""
    cfg, tree = lfm2_moe_config(TINY), _tree(TINY)
    eng = _engine(tree, cfg, max_batch=2)
    eng.submit(list(range(1, 20)), 6)
    eng.run()
    # slot 1 never held a stream: zeros still; slot 0 holds its last one's
    z = np.asarray(eng.adapter._state["z"])
    assert z[:, 0].any() and not z[:, 1].any()


def test_admission_reckons_with_the_attention_layers_pool_alone():
    """Four slots and a pool that holds two long streams: the third long
    request waits for pages while a slot stands empty, a request the pool
    could never hold is rejected at the door; and what a stream holds in
    convolution layers does not depend on its context."""
    cfg, tree = lfm2_moe_config(TINY), _tree(TINY)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (60, 58, 50, 7)]
    # 64 + 10 positions a long stream -> 10 pages of 8; 22 hold two
    tight = _engine(tree, cfg, max_batch=4, num_pages=2 + 22)
    reqs = [tight.submit(p, 10) for p in prompts]
    most_live = 0
    while tight.has_work():
        tight.step()
        most_live = max(most_live, sum(r is not None for r in tight._slots))
        assert tight.adapter.pages_in_use <= 22
    assert most_live <= 3  # never all four: pages, not slots, held one back
    assert tight.adapter.cache.failed_allocs == 0
    assert all(r.state == "finished" for r in reqs)
    small = _engine(tree, cfg, num_pages=2 + 10)
    with pytest.raises(RequestRejected, match="attention pages"):
        small.submit(list(range(1, 61)), 30)
    long = _engine(tree, cfg, max_seq_len=1024)
    assert (long.adapter.state_bytes_per_stream
            == small.adapter.state_bytes_per_stream == 3 * 2 * 256)


def test_refusals_name_what_is_not_built():
    cfg, tree = lfm2_moe_config(TINY), _tree(TINY)
    for kw, word in (({"kv_quant": "int8"}, "full-width"),
                     ({"serve_layout": "tp=2"}, "exchange"),
                     ({"speculator_path": "/x"}, "llama-only"),
                     ({"prefill_chunk_tokens": 8}, "between decode steps"),
                     ({"moe_impl": "dispatch"}, "moe_impl")):
        with pytest.raises(ValueError, match=word):
            _engine(tree, cfg, **kw)
    assert not _engine(tree, cfg).adapter.supports_handoff
    c = {**TINY, "num_hidden_layers": 2, "layer_types": ["conv"] * 2}
    with pytest.raises(ValueError, match="without one of them"):
        _engine(_tree(c), lfm2_moe_config(c))


@pytest.mark.parametrize("attn,rows", [("kernel", 1), ("reference", 0)])
def test_step_record_counts_the_blocks_the_kernel_walks(
    attn, rows, walked_blocks, monkeypatch
):
    """tests/test_kexaone.py's, over this family's pages of packed rows."""
    from fms_fsdp_tpu.serve import families

    monkeypatch.setattr(families, "DECODE_BLOCK_TOKENS", 16)
    eng = _engine(_tree(TINY), lfm2_moe_config(TINY), max_batch=3,
                  max_prefill_per_step=3, moe_impl="dense", attn_impl=attn)
    assert (eng.adapter.page_size, eng.adapter.block_kv) == (8, 16)
    by_hand = walked_blocks(eng, (5, 14, 28), 6, 3 * (128 // 16), rows)
    if rows:
        assert by_hand[0] == 1 + 1 + 2 and by_hand[-1] == 1 + 2 + 3
