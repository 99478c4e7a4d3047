"""The phi4flash family (a self-decoder of Mamba-1 and window-attention
layers, one full-attention layer whose keys and values the cross layers
behind it read again, gated memory units that reuse a layer's scan
output, differential attention) against the plain float32 reference
``benchmark/reference/phi4flash.py``, at a small size in the published
pattern: 12 layers (the layer rule needs a multiple of 4: Mamba at 0, 2,
4 and 6, which hands out; windows of 8 at 1, 3, 5; the full layer at 7;
gated memory units at 8 and 10; cross layers at 9 and 11), 8 query heads
on 4 kv heads of 8 (two query pairs to a key pair, as published), seeded
weights.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers.serve_hybrid import as_program_tree
from benchmark.reference import phi4flash as reference
from fms_fsdp_tpu.models import phi4flash as M
from fms_fsdp_tpu.models.configs import Phi4FlashConfig, phi4flash_config
from fms_fsdp_tpu.ops import attention as OA
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import (
    check_params_family,
    family_of,
    load_model_config,
)
from fms_fsdp_tpu.serve.families import phi4flash as A
from fms_fsdp_tpu.serve.scheduler import RequestRejected

TINY = {
    "model_type": "phi4flash", "hidden_size": 64, "intermediate_size": 128,
    "layer_norm_eps": 1e-5, "max_position_embeddings": 512,
    "mb_per_layer": 2, "num_attention_heads": 8, "num_hidden_layers": 12,
    "num_key_value_heads": 4, "sliding_window": 8,
    "tie_word_embeddings": True, "vocab_size": 256,
}
KINDS = ["mamba", "window"] * 3 + ["mamba", "full"] + ["gmu", "cross"] * 2
CHUNK, BUCKET, WINDOW = 16, 16, 8
# float32 against float32 under the highest matmul precision: what is
# left is the order of the sums (a chunked scan and prefill, a ring, a
# paged softmax), 6e-6 of the largest logit on this CPU; 2e-5 leaves room
# for another backend's order and is a five-thousandth of what bfloat16
# anywhere in the path reads (test_bfloat16_is_told_from_float32...)
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


def _tree(c=TINY, seed=3, dtype=jnp.float32):
    return as_program_tree(weights.make_tree(
        weights.seed_key(seed), reference.param_spec(c), dtype))


def _ref_logits(tree, tokens, c=TINY):
    return np.asarray(
        reference.forward(tree, jnp.asarray([tokens], jnp.int32), c)[0])


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _published():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs",
                           "phi-4-mini-flash.1chip.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the config and the tree
# ---------------------------------------------------------------------------


def test_load_model_config_on_the_published_keys():
    file = _published()
    assert file["model_type"] == "phi4flash" and file["reduced"] == []
    cfg = load_model_config(file)
    assert family_of(cfg) == "phi4flash" and isinstance(cfg, Phi4FlashConfig)
    assert (cfg.emb_dim, cfg.nheads, cfg.kvheads, cfg.head_dim, cfg.nlayers,
            cfg.hidden_dim, cfg.sliding_window, cfg.src_vocab_size) == (
        2560, 40, 20, 64, 32, 10240, 512, 200064)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank_) == (
        5120, 16, 4, 160)
    assert cfg.hand_out_layer == 16 and cfg.full_layer == 17
    assert cfg.layers_of("mamba") == tuple(range(0, 17, 2))
    assert cfg.layers_of("window") == tuple(range(1, 16, 2))
    assert cfg.layers_of("gmu") == tuple(range(18, 32, 2))
    assert cfg.layers_of("cross") == tuple(range(19, 32, 2))
    # 3852.6M: the config's count, the reference's and the tree's leaves
    theirs = jax.eval_shape(
        lambda k: M.init_phi4flash_params(k, cfg), jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(theirs))
    assert leaves == cfg.n_params() == reference.n_params(file)
    assert leaves == file["n_params"] and round(leaves / 1e5) == 38526
    # a stream's cache at the published widths: 5120 B a position in one
    # layer's pages whatever reads them; 8 rings and 9 slabs a slot
    cost = A.cache_bytes(cfg, jnp.bfloat16)
    assert cost["per_token"] == 20 * 64 * 2 * 2 == 5120
    ring = 8 * 512 * 5120
    slab = 9 * (5120 * 16 * 4 + 5120 * 3 * 2)
    assert cost["per_stream"] == ring + slab and round(
        cost["per_stream"] / 1e5) == 242


@pytest.mark.parametrize("change,word", [
    ({"rope_theta": 1e4}, "rope_theta"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"mb_per_layer": 4}, "mb_per_layer"),
    ({"num_hidden_layers": 10}, "multiple of 4"),
    ({"num_key_value_heads": 1}, "pairs"),
    ({"sliding_window": 0}, "sliding_window"),
])
def test_config_refuses_by_name(change, word):
    with pytest.raises(ValueError, match=word):
        load_model_config({**TINY, **change})


def test_a_missing_key_is_asked_for_by_name():
    with pytest.raises(ValueError, match="sliding_window"):
        load_model_config(
            {k: v for k, v in TINY.items() if k != "sliding_window"})


def test_tree_is_the_programs_own_and_a_cross_layer_has_no_key_weights():
    cfg = phi4flash_config(TINY)
    assert [cfg.kind(i) for i in range(12)] == KINDS
    assert [reference.layer_kind(i, TINY) for i in range(12)] == KINDS
    theirs = jax.eval_shape(
        lambda k: M.init_phi4flash_params(k, cfg), jax.random.PRNGKey(0))
    weights.require_same_tree(
        jax.eval_shape(lambda: _tree()), theirs, "phi4flash")
    check_params_family(theirs, "phi4flash")
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(theirs))
    assert n == cfg.n_params() == reference.n_params(TINY)
    with pytest.raises(ValueError, match="mismatch"):
        check_params_family(theirs, "mamba")
    for i, layer in enumerate(theirs["layers"]):
        mixer = set(layer["mixer"])
        assert ("wk" in mixer) == (KINDS[i] in ("window", "full"))
        if KINDS[i] == "cross":
            assert mixer == {
                "wq", "bq", "wo", "bo", "subln", "lambda_q1", "lambda_k1",
                "lambda_q2", "lambda_k2"}
        if KINDS[i] == "mamba":  # the mixer as published: no norm leaves
            assert not mixer & {"dt_norm", "B_norm", "C_norm"}


@pytest.mark.parametrize("i", [0, 1, 7, 17, 31])
def test_lambda_init_by_layer_index(i):
    want = 0.8 - 0.6 * np.exp(-0.3 * i)
    assert Phi4FlashConfig.lambda_init(i) == pytest.approx(want, abs=1e-12)
    assert reference.lambda_init(i) == pytest.approx(want, abs=1e-12)
    # lambda is its init where the learned vectors are zero, and moves
    # with them
    zero = {f"lambda_{n}": jnp.zeros((8,)) for n in ("q1", "k1", "q2", "k2")}
    assert float(OA.diff_lambda(zero, want)) == pytest.approx(want, abs=1e-6)
    some = {**zero, "lambda_q1": jnp.full((8,), 0.5),
            "lambda_k1": jnp.full((8,), 0.25)}
    assert float(OA.diff_lambda(some, want)) == pytest.approx(
        np.exp(1.0) - 1.0 + want, abs=1e-5)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _forward(tree, tokens, dtype=jnp.float32):
    cfg = phi4flash_config(TINY)
    return np.asarray(M.phi4flash_forward(
        jax.tree.map(lambda a: a.astype(dtype), tree),
        jnp.asarray(tokens, jnp.int32), cfg, compute_dtype=dtype,
    ).astype(jnp.float32))


def test_full_forward_agrees_with_the_reference():
    tree = _tree()
    toks = np.random.default_rng(0).integers(1, 256, size=(2, 40))
    want = np.asarray(reference.forward(tree, jnp.asarray(toks), TINY))
    assert _gap(_forward(tree, toks), want) < TOL


def test_bfloat16_is_told_from_float32():
    """The tolerance is one that a bfloat16 run of a float32 configuration
    fails, by three orders."""
    tree = _tree()
    toks = np.random.default_rng(0).integers(1, 256, size=(2, 40))
    want = np.asarray(reference.forward(tree, jnp.asarray(toks), TINY))
    assert _gap(_forward(tree, toks, jnp.bfloat16), want) > 1000 * TOL


def test_the_memory_is_the_hand_out_layers_and_before_its_gate():
    """A gated memory unit reads layer ``n / 2``'s scan output of its own
    position: another Mamba layer's, or the gated one, is told."""
    tree = _tree()
    toks = np.random.default_rng(1).integers(1, 256, size=(1, 24))
    want = np.asarray(reference.forward(tree, jnp.asarray(toks), TINY))
    cfg = phi4flash_config(TINY)

    class Wrong(Phi4FlashConfig):
        hand_out_layer = 4

    wrong = Wrong(**{f: getattr(cfg, f) for f in cfg.__dataclass_fields__})
    got = np.asarray(M.phi4flash_forward(
        tree, jnp.asarray(toks), wrong, compute_dtype=jnp.float32))
    assert _gap(got, want) > 1000 * TOL


# ---------------------------------------------------------------------------
# differential attention in its three settings
# ---------------------------------------------------------------------------


def _diff_plain(q, k, v, layer, lam_init, window):
    """The reference's differential attention over one sequence."""
    return np.asarray(reference.differential_attention(
        q, k, v, layer, lam_init, window))


def _diff_layer(H, seed=0):
    r = np.random.default_rng(seed)
    return {
        **{f"lambda_{n}": jnp.asarray(r.normal(0, 0.2, H), jnp.float32)
           for n in ("q1", "k1", "q2", "k2")},
        "subln": jnp.asarray(1 + r.normal(0, 0.1, 2 * H), jnp.float32)}


@pytest.mark.parametrize("window", [0, 8])
def test_rows_of_two_heads_are_the_two_softmaxes(window):
    """``diff_rows`` + one grouped-query attention over pairs of heads
    side by side + ``diff_combine`` is the published form: pairs, two
    softmaxes, the difference under lambda, the norm by head."""
    B, S, N, Nkv, H = 2, 24, 8, 4, 8
    r = np.random.default_rng(2)
    q, k, v = (jnp.asarray(r.normal(0, 1, (B, S, n, H)), jnp.float32)
               for n in (N, Nkv, Nkv))
    layer, lam_init = _diff_layer(H), 0.35
    want = _diff_plain(q, k, v, layer, lam_init, window)
    pos = jnp.arange(S)
    rows = (B, S, Nkv // 2, 2 * H)
    o, _ = OA.masked_attention(
        OA.diff_rows(q), k.reshape(rows), v.reshape(rows),
        OA.band_mask(pos, pos, window)[None], scale=H**-0.5)
    got = np.asarray(OA.diff_combine(o, layer, lam_init, 1e-5))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("flash", [False, True], ids=["einsum", "flash"])
def test_a_key_one_place_outside_the_window_changes_nothing(flash):
    """A chunk's window attention behind a carried tail: query ``j`` of
    the chunk sees the tail's entries past ``j`` and no other; moving the
    key at entry ``j`` (one place outside its window) leaves its output
    as it was to the bit, moving the one at ``j + 1`` (the oldest inside)
    does not. Through the einsum and through the windowed flash kernel
    over rows of 128 lanes."""
    B, N, Nkv, H = 1, 8, 4, 64
    c, W = (256, 128) if flash else (16, 8)
    r = np.random.default_rng(3)
    rows = lambda s: (B, s, Nkv // 2, 2 * H)  # noqa: E731
    q = OA.diff_rows(jnp.asarray(r.normal(0, 1, (B, c, N, H)), jnp.float32))
    k, v, tk, tv = (jnp.asarray(r.normal(0, 1, rows(s)), jnp.float32)
                    for s in (c, c, W, W))

    def run(tail_k):
        return np.asarray(OA.window_chunk_attention(
            q, k, v, tail_k, tv, 2 * c, W, flash, scale=H**-0.5))

    base, j = run(tk), 3
    outside = run(tk.at[:, j].add(5.0))
    inside = run(tk.at[:, j + 1].add(5.0))
    assert (outside[:, j] == base[:, j]).all()
    assert np.abs(inside[:, j] - base[:, j]).max() > 1e-3
    assert np.abs(outside[:, j - 1] - base[:, j - 1]).max() > 1e-3
    assert (inside[:, W:] == base[:, W:]).all()  # past the tail's reach


# ---------------------------------------------------------------------------
# the prefill stops half way
# ---------------------------------------------------------------------------


@functools.cache
def _prefill_48():
    """One program for every case: the lengths are its arguments."""
    cfg = phi4flash_config(TINY)
    return jax.jit(lambda tree, t, n: M.phi4flash_prefill(
        tree, t, n, cfg, compute_dtype=jnp.float32, attn_impl="xla"))


@pytest.mark.parametrize("lengths", [(37, 40), (16, 5), (1, 48)])
def test_prefill_is_the_forward_at_the_last_position(lengths):
    """Prompts longer than the window, across chunk boundaries, on a
    chunk's edge, of one position: the first token's logits are the full
    forward's, the state handed over is the forward's (ring in ring
    order, the scan's state at the prompt's end, the full layer's keys
    and values zero past it)."""
    tree, S = _tree(), 48
    toks = np.random.default_rng(4).integers(1, 256, size=(2, S))
    logits, kv, state = _prefill_48()(
        tree, jnp.asarray(toks, jnp.int32), jnp.asarray(lengths, jnp.int32))
    for b, n in enumerate(lengths):
        want = _ref_logits(tree, toks[b, :n].tolist())
        assert _gap(np.asarray(logits[b]), want[-1]) < TOL
        k = np.asarray(kv["k"][0, b]).reshape(S, -1)
        assert np.abs(k[:n]).min(axis=-1).max() > 0 and not k[n:].any()
    assert state["ring_k"].shape == (3, 2, WINDOW * 2, 16)
    assert state["ssd"].shape == (4, 2, 16, 128)
    assert state["conv"].shape == (4, 2, 3, 128)


def test_prefill_computes_the_second_half_for_one_position():
    """The program's own text: the second half's products are one row a
    prompt. A cross layer's ``wq`` (64 x 64) meets a (B, 64) operand and
    never a (B, chunk, 64) one."""
    cfg, tree = phi4flash_config(TINY), _tree()
    text = jax.jit(lambda t, n: M.phi4flash_prefill(
        tree, t, n, cfg, compute_dtype=jnp.float32, attn_impl="xla")
    ).lower(jax.ShapeDtypeStruct((1, 48), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()
    # a gated memory unit's in_proj is the only (64, 128) x (1, 64)
    # product outside the loop, and no (1, 16, 64) x (64, 128) is a gmu's:
    # the loop's are the Mamba in_proj's, four layers of two
    loop = text.count("tensor<1x16x64xf32>, tensor<64x128xf32>")
    tail = text.count("tensor<1x64xf32>, tensor<64x128xf32>")
    assert loop == 4 * 2 + 7 * 2  # in_proj halves; an MLP's w1, w3 a layer
    assert tail == 2 + 5 * 2  # two gmu in_proj; w1, w3 of layers 7 to 11


# ---------------------------------------------------------------------------
# prefill then decode through the engine: ring, slab and pages
# ---------------------------------------------------------------------------


def _engine(tree, cfg, dtype="float32", **kw):
    scfg = ServeConfig(**{
        "max_batch": 2, "max_seq_len": 128, "compute_dtype": dtype,
        "attn_impl": "reference", "prefill_bucket": BUCKET, "page_size": 8,
        "max_prefill_per_step": 2, **kw})
    return ServingEngine(tree, cfg, scfg)


def _serve_capturing(eng, prompts, max_new):
    """-> per request, the logits row of every served position, read
    where the adapter hands them to the engine (tests/test_lfm2.py)."""
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


@pytest.mark.parametrize(
    "slots,attn", [(4, "reference"), (1, "kernel")], ids=["gathered", "kernel"])
def test_engine_agrees_with_the_reference_on_logits_float32(slots, attn):
    """A prompt longer than the window that crosses a chunk boundary and a
    program's edge, one on a bucket's edge, one of a single position, and
    more requests than slots (a slot's ring and slab are written whole at
    hand-over: nothing of the stream before leaks), outputs long enough
    to wrap the ring twice: every served position's logits against the
    reference's full forward (no ring, no slab, no pages)."""
    cfg, tree = phi4flash_config(TINY), _tree()
    eng = _engine(tree, cfg, max_batch=slots, attn_impl=attn)
    assert eng.adapter.attn_impl == attn
    assert eng.adapter._dispatch_fields == {
        "attn_form": attn, "ssm_form": "jnp"}
    rng = np.random.default_rng(7)
    lengths, outputs = (37, 32, 1, 1, 5), (6, 20, 20, 12, 12)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in lengths]
    reqs, rows = _serve_capturing(eng, prompts, outputs)
    for prompt, req, got, n in zip(prompts, reqs, rows, outputs):
        assert req.state == "finished" and len(req.generated) == n
        want = _ref_logits(tree, prompt + req.generated[:-1])
        assert _gap(got, want[len(prompt) - 1:]) < TOL
        assert (want[len(prompt) - 1:].argmax(-1) == req.generated).all()
    count, gauges = eng.registry.counter, eng.registry.gauge
    # programs of 16, 32 and 64 positions, chunks of 16
    assert sorted(eng.adapter._prefill_cache) == [(16, 16), (32, 32), (64, 64)]
    computed = sum(-(-n // CHUNK) * CHUNK for n in lengths)
    assert count("serve.prefill_computed_tokens").value == computed
    assert count("serve.prefill_self_positions").value == computed
    assert count("serve.prefill_cross_positions").value == len(prompts)
    assert count("serve.prefill_state_writes").value == 5
    assert gauges("serve.ssm_layers").value == 4
    assert gauges("serve.window_layers").value == 3
    assert gauges("serve.cross_layers").value == 2
    # 4 kv heads of 8, K and V, float32, one layer's whatever reads them
    assert gauges("serve.kv_bytes_per_position").value == 2 * 4 * 8 * 4
    per_stream = 3 * 2 * 8 * 32 * 4 + 4 * (3 * 128 * 4 + 16 * 128 * 4)
    assert eng.adapter.state_bytes_per_stream == per_stream
    assert gauges("serve.state_bytes_per_stream").value == per_stream
    # pages for one layer: rows of a pair of heads, two a position
    assert eng.adapter.cache.pools["k"].shape == (
        1, eng.adapter.cache.num_pages, 8 * 2, 16)
    assert {k: v.shape for k, v in eng.adapter._state.items()} == {
        "ring_k": (3, slots, WINDOW * 2, 16),
        "ring_v": (3, slots, WINDOW * 2, 16),
        "conv": (4, slots, 3, 128), "ssd": (4, slots, 16, 128)}
    assert eng.adapter._state["ssd"].dtype == jnp.float32


def _through_the_step_kernel(monkeypatch):
    """Make the decode step take the one-position scan kernel, as it does
    on a TPU, in interpret mode. -> the slab shapes it was called with."""
    from fms_fsdp_tpu.ops import selective_scan as ss

    calls, kernel = [], ss.selective_scan_step_kernel

    def interpreted(*args):
        calls.append(args[6].shape)
        return kernel(*args, interpret=True)

    monkeypatch.setattr(ss, "selective_scan_step_kernel", interpreted)
    monkeypatch.setattr(ss, "scan_step_form", lambda *shape: "kernel")
    monkeypatch.setattr(A, "scan_step_form", ss.scan_step_form)
    return calls


def test_engine_through_the_step_kernel_agrees_with_the_reference(monkeypatch):
    """On a TPU a decode step steps the nine scan states through the
    Pallas kernel, in place in the stacked slab; here, in interpret mode,
    every served position's logits are the reference's, with more
    requests than slots so that a slot lies dead for a while, and every
    ``decode.dispatch`` span says ``ssm_form`` ``kernel``."""
    from fms_fsdp_tpu.serve import families

    calls = _through_the_step_kernel(monkeypatch)
    seen, span = [], families.span

    def noted(name, **fields):
        seen.append((name, fields))
        return span(name, **fields)

    monkeypatch.setattr(families, "span", noted)
    cfg, tree = phi4flash_config(TINY), _tree()
    eng = _engine(tree, cfg, max_batch=2)
    assert eng.adapter.ssm_form == "kernel"
    rng = np.random.default_rng(11)
    lengths, outputs = (37, 5, 1), (6, 20, 12)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in lengths]
    reqs, rows = _serve_capturing(eng, prompts, outputs)
    for prompt, req, got, n in zip(prompts, reqs, rows, outputs):
        assert req.state == "finished" and len(req.generated) == n
        want = _ref_logits(tree, prompt + req.generated[:-1])
        assert _gap(got, want[len(prompt) - 1:]) < TOL
    # one trace of the decode program: four Mamba layers, each handed the
    # whole stacked slab
    assert calls == [(4, 2, 16, 128)] * 4
    dispatched = [f for name, f in seen if name == "decode.dispatch"]
    assert dispatched and all(
        f["ssm_form"] == "kernel" and f["attn_form"] == "reference"
        for f in dispatched)


def test_every_cross_layer_reads_the_full_layers_pages(monkeypatch):
    """One page write a step, by the full layer; it and each cross layer
    behind it attend the same pools: altering the pages moves every one
    of those reads."""
    cfg, tree = phi4flash_config(TINY), _tree()
    n, page, P = 19, 8, 8
    toks = np.zeros((1, 32), np.int32)
    toks[0, :n] = np.random.default_rng(6).integers(1, 256, size=n)
    _, kv, state = M.phi4flash_prefill(
        tree, jnp.asarray(toks), jnp.asarray([n], jnp.int32), cfg,
        compute_dtype=jnp.float32, kv_len=32, attn_impl="xla")
    pools = {
        name: jnp.zeros((1, P, page * 2, 16)).at[0, 2:6].set(
            kv[name][0, 0].reshape(4, page * 2, 16))
        for name in ("k", "v")}
    table = jnp.asarray([[2, 3, 4, 5] + [0] * 4], jnp.int32)
    reads = []
    attend = M._pages_attend

    def spy(q, pools, *rest):
        out = attend(q, pools, *rest)
        reads.append((pools["k"], np.asarray(out)))
        return out

    monkeypatch.setattr(M, "_pages_attend", spy)

    def step(pools):
        reads.clear()
        logits, _, _ = M.phi4flash_decode_step(
            tree, state, pools, table, jnp.asarray([n]), jnp.asarray([7]),
            cfg, page_size=page, compute_dtype=jnp.float32)
        return np.asarray(logits), list(reads)

    base, first = step(pools)
    assert len(first) == 1 + len(cfg.layers_of("cross")) == 3
    assert all(k is first[0][0] for k, _ in first)  # one pool, three reads
    again, second = step({"k": pools["k"], "v": pools["v"] + 0.5})
    assert all(
        np.abs(a - b).max() > 1e-3 for (_, a), (_, b) in zip(first, second))
    assert np.abs(again - base).max() > 1e-4


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_a_dead_slots_state_stays_as_it_was(form, monkeypatch):
    """A decode step steps the slabs of the live slots alone, whichever
    form steps the scan's state."""
    if form == "kernel":
        _through_the_step_kernel(monkeypatch)
    cfg, tree = phi4flash_config(TINY), _tree()
    eng = _engine(tree, cfg, max_batch=2)
    assert eng.adapter.ssm_form == form
    eng.submit(list(range(1, 20)), 6)
    eng.run()
    ssd, conv = (np.asarray(eng.adapter._state[n]) for n in ("ssd", "conv"))
    assert ssd[:, 0].any() and not ssd[:, 1].any() and not conv[:, 1].any()


def test_admission_reckons_with_the_one_layers_pool_alone():
    """Four slots and a pool that holds two long streams: the third long
    request waits for pages while a slot stands empty, a request the pool
    could never hold is rejected at the door; ring and slab do not depend
    on a stream's context."""
    cfg, tree = phi4flash_config(TINY), _tree()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (60, 58, 50, 7)]
    tight = _engine(tree, cfg, max_batch=4, num_pages=2 + 22)
    reqs = [tight.submit(p, 10) for p in prompts]
    most_live = 0
    while tight.has_work():
        tight.step()
        most_live = max(most_live, sum(r is not None for r in tight._slots))
        assert tight.adapter.pages_in_use <= 22
    assert most_live <= 3 and tight.adapter.cache.failed_allocs == 0
    assert all(r.state == "finished" for r in reqs)
    small = _engine(tree, cfg, num_pages=2 + 10)
    with pytest.raises(RequestRejected, match="full-attention pages"):
        small.submit(list(range(1, 61)), 30)
    long = _engine(tree, cfg, max_seq_len=1024)
    assert (long.adapter.state_bytes_per_stream
            == small.adapter.state_bytes_per_stream)


@pytest.mark.parametrize("knob,word", [
    ({"kv_quant": "int8"}, "kv_quant"),
    ({"serve_layout": "tp=2"}, "serve_layout"),
    ({"speculator_path": "/x"}, "speculator_path"),
    ({"prefill_chunk_tokens": 8}, "prefill_chunk_tokens"),
    ({"role": "prefill"}, "role"),
])
def test_refusals_name_the_knob(knob, word):
    cfg, tree = phi4flash_config(TINY), _tree()
    with pytest.raises(ValueError, match=word):
        _engine(tree, cfg, **knob)


def test_handoff_is_refused_by_name():
    cfg, tree = phi4flash_config(TINY), _tree()
    adapter = _engine(tree, cfg).adapter
    assert not adapter.supports_handoff and not adapter.supports_layout
    with pytest.raises(AssertionError, match="phi4flash does not support"):
        adapter.export_handoff(0, 0)


@pytest.mark.parametrize("attn,rows", [("kernel", 1), ("reference", 0)])
def test_step_record_counts_the_blocks_the_kernel_walks(
    attn, rows, walked_blocks, monkeypatch
):
    """tests/test_kexaone.py's: the count is of one reading of the full
    layer's pages (the cross layers walk the same blocks again; the rings
    are one block a slot whatever the stream)."""
    from fms_fsdp_tpu.serve import families

    monkeypatch.setattr(families, "DECODE_BLOCK_TOKENS", 16)
    eng = _engine(_tree(), phi4flash_config(TINY), max_batch=3,
                  max_prefill_per_step=3, attn_impl=attn)
    assert (eng.adapter.page_size, eng.adapter.block_kv) == (8, 16)
    by_hand = walked_blocks(eng, (5, 14, 28), 6, 3 * (128 // 16), rows)
    if rows:
        assert by_hand[0] == 1 + 1 + 2 and by_hand[-1] == 1 + 2 + 3
