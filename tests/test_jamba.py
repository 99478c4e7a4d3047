"""The Jamba hybrid (Mamba-1 selective-scan mixers, attention with no
positional embedding and one KV head, tied head) against the plain
float32 reference ``benchmark/reference/jamba.py``, at a small size with
seeded weights: the full forward, the three forms of the scan, and
prefill-then-decode through ``ServingEngine`` **on logits** at every
served position.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.drivers.serve import through_fp8
from benchmark.drivers.serve_hybrid import make_params
from benchmark.families import jamba as family
from benchmark.reference import jamba as reference
from fms_fsdp_tpu.models import mamba as M
from fms_fsdp_tpu.ops import selective_scan as ss
from fms_fsdp_tpu.serve.disagg import unpack_handoff
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import (
    check_params_family,
    family_of,
    load_model_config,
)

# a published Jamba config.json's keys at a small size: 6 layers, layer 3
# attention, 4 query heads on 1 KV head, d_inner 128 = one row of lanes
TINY = {
    "family": "jamba", "model_type": "jamba",
    "attn_layer_offset": 3, "attn_layer_period": 4,
    "hidden_size": 64, "intermediate_size": 128,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8,
    "mamba_expand": 2, "num_attention_heads": 4, "num_experts": 1,
    "num_hidden_layers": 6, "num_key_value_heads": 1,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": True, "vocab_size": 512,
}
CFG = family.model_config(TINY)
SPEC = reference.param_spec(TINY)
BUCKET = 8


def _params(dtype=jnp.float32, seed=7):
    return make_params(weights.seed_key(seed), SPEC, dtype)


@pytest.fixture(scope="module")
def params():
    return _params()


def _ref_logits(tree32, tokens):
    """The reference's full forward in float32: (S, V) for one row."""
    return np.asarray(
        reference.forward(tree32, jnp.asarray([tokens], jnp.int32), TINY)[0])


def _engine(tree, dtype="float32", **kw):
    scfg = ServeConfig(**{
        "max_batch": 2, "max_seq_len": 64, "compute_dtype": dtype,
        "attn_impl": "reference", "prefill_bucket": BUCKET,
        "max_prefill_per_step": 2, **kw})
    return ServingEngine(tree, CFG, scfg)


def _serve_capturing(eng, prompts, max_new):
    """Serve ``prompts`` together; -> per request, the logits row of every
    served position (the prefill's, then each decode step's), read where
    the adapter hands them to the engine."""
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
    # the last decode step's logits pick a token that is never served
    return reqs, [np.stack(rows[r.rid][: len(r.generated)]) for r in reqs]


def _gaps(rows, want):
    """Largest |difference| relative to the reference's largest |logit|."""
    return float(np.max(np.abs(rows - want)) / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_full_forward_agrees_with_the_reference(params):
    toks = np.random.default_rng(0).integers(1, 512, size=(2, 40))
    mine = M.mamba_forward(
        params, jnp.asarray(toks, jnp.int32), CFG,
        compute_dtype=jnp.float32, attn_impl="xla")
    want = reference.forward(params, jnp.asarray(toks, jnp.int32), TINY)
    # float32 on both sides: reduction order only
    assert _gaps(np.asarray(mine), np.asarray(want)) < 1e-4


def test_tree_is_the_programs_own_and_has_no_head_leaf(params):
    theirs = jax.eval_shape(
        lambda k: M.init_mamba_params(k, CFG), jax.random.PRNGKey(0))
    weights.require_same_tree(params, theirs, "jamba")
    assert "lm_head" not in params and CFG.tie_embeddings
    assert sum(x.size for x in jax.tree.leaves(params)) == CFG.n_params()
    check_params_family(params, "mamba")
    # the published widths: 3029M parameters (ISSUE 27's arithmetic)
    import json, os

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "jamba2-3b.1chip.json")) as f:
        full = load_model_config(json.load(f))
    assert family_of(full) == "mamba" and full.mamba1
    assert full.attn_layer_idx == (7, 21) and full.attn_cfg.num_heads_kv == 1
    assert full.n_params() == 3029337472


def test_attention_has_no_rope(params):
    """The reference applies no positional embedding; a rotated q and k
    change the output, so agreeing with it proves there is none."""
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 64))
    mixer = params["layers"][3]["mixer"]
    live = jnp.ones((1, 16), bool)
    buf = jnp.zeros((1, 16, 1, 16))  # one chunk, nothing before it
    out, _, _ = M._attn_prefill(
        h, mixer, CFG.attn_cfg, None, None, live, buf, buf, 0, "xla")
    want = reference.attention_mixer(h, mixer, TINY)
    assert _gaps(np.asarray(out), np.asarray(want)) < 1e-5
    roped = dataclasses.replace(CFG.attn_cfg, rotary_emb_dim=16)
    cos, sin = M.rope_table(16, 16, 10000.0)
    rot, _, _ = M._attn_prefill(
        h, mixer, roped, cos, sin, live, buf, buf, 0, "xla")
    assert _gaps(np.asarray(rot), np.asarray(want)) > 1e-2


# ---------------------------------------------------------------------------
# the four forms of the scan
# ---------------------------------------------------------------------------


def _scan_inputs(rows, S, C, N, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (rows, S, C))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, S, C)) - 1.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (N, C)))
    B = jax.random.normal(ks[3], (rows, S, N))
    Cm = jax.random.normal(ks[4], (rows, S, N))
    h0 = jax.random.normal(ks[5], (rows, N, C))  # a carried state
    return u, dt, A, B, Cm, jnp.ones((C,)), h0


@pytest.mark.parametrize("rows,S,C,N", [(2, 24, 128, 16), (3, 16, 1024, 4)])
def test_scan_forms_agree_with_a_carried_state_and_ragged_rows(rows, S, C, N):
    u, dt, A, B, Cm, D, h0 = _scan_inputs(rows, S, C, N, seed=S)
    lengths = jnp.asarray([S, S - 5, 3][:rows], jnp.int32)
    dt = ss.freeze_past(dt, lengths)
    y_ref, h_ref = ss.selective_scan_reference(u, dt, A, B, Cm, D, h0)
    assert ss.kernel_supports(C)
    y_k, h_k = ss.selective_scan_kernel(u, dt, A, B, Cm, D, h0, interpret=True)
    np.testing.assert_allclose(y_k, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_k, h_ref, rtol=1e-5, atol=1e-5)
    # one position at a time, each row stopping at its own length
    h, ys = h0, []
    for t in range(S):
        y, h = ss.selective_scan_step(
            u[:, t], dt[:, t], A, B[:, t], Cm[:, t], D, h)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, h_ref, rtol=1e-5, atol=1e-5)
    # frozen: a row's final state is its state at its length
    _, h_cut = ss.selective_scan_reference(
        u[1:2, : S - 5], dt[1:2, : S - 5], A, B[1:2, : S - 5],
        Cm[1:2, : S - 5], D, h0[1:2])
    np.testing.assert_array_equal(h_ref[1:2], h_cut)


@pytest.mark.parametrize(
    "layers,slots,C", [(1, 2, 128), (3, 16, 1024), (2, 24, 5120)])
def test_the_one_position_kernel_steps_a_slab_where_it_lies(layers, slots, C):
    """The fourth form against ``selective_scan_step`` on a stacked slab
    with some rows dead: ``y`` and the stepped layer's live rows agree,
    dead rows and every other layer are the bits they were, and the slab
    that comes back is the buffer that went in where the backend
    donates."""
    N = 16
    u, dt, A, B, Cm, D, _ = (
        x[:, 0] if x.ndim == 3 else x
        for x in _scan_inputs(slots, 1, C, N, seed=slots))
    slab = jax.random.normal(jax.random.PRNGKey(C), (layers, slots, N, C))
    live = jnp.arange(slots) % 3 != 1
    layer = layers - 1
    assert ss.step_kernel_supports(slots, N, C)
    y_ref, h_ref = ss.selective_scan_step(u, dt, A, B, Cm, D, slab[layer])
    before = np.asarray(slab)
    step = jax.jit(
        lambda slab, layer: ss.selective_scan_step_kernel(
            u, dt, A, B, Cm, D, slab, layer, live, interpret=True),
        donate_argnums=0)
    held = slab.unsafe_buffer_pointer()
    y, out = step(slab, jnp.int32(layer))
    if slab.is_deleted():  # the backend donates
        assert out.unsafe_buffer_pointer() == held
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    out, alive = np.asarray(out), np.asarray(live)
    np.testing.assert_allclose(
        out[layer][alive], np.asarray(h_ref)[alive], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(out[layer][~alive], before[layer][~alive])
    np.testing.assert_array_equal(out[:layer], before[:layer])
    # one layer's state alone is the same call with one layer, and
    # without ``live`` every row is stepped
    y1, h1 = ss.selective_scan_step_kernel(
        u, dt, A, B, Cm, D, jnp.asarray(before[layer]), interpret=True)
    assert h1.shape == (slots, N, C)
    np.testing.assert_allclose(y1, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h1, h_ref, rtol=1e-5, atol=1e-5)


def test_prefill_through_the_kernel_equals_the_scan_form(params, monkeypatch):
    """On a TPU the sequence prefill runs the Pallas kernel; here, in
    interpret mode, it gives what the ``lax.scan`` form gives."""
    toks = jnp.asarray(
        np.random.default_rng(1).integers(1, 512, size=(2, 16)), jnp.int32)
    lengths = jnp.asarray([16, 11], jnp.int32)

    def run():
        return M.mamba_prefill(
            params, toks, lengths, CFG, compute_dtype=jnp.float32,
            kv_len=16, attn_impl="xla")

    want = run()
    calls = []

    def kernel(*args):
        calls.append(1)
        return ss.selective_scan_kernel(*args, interpret=True)

    monkeypatch.setattr(M, "selective_scan", kernel)
    got = run()
    assert len(calls) == 5  # one per Mamba layer
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the looped prefill: a chunk at a time, stopped at the prompt's length
# ---------------------------------------------------------------------------

C, P_PAD = 4, 16  # four chunks to a bucket


def _prefill_rows(params, rows, chunk, monkeypatch, **kw):
    """``mamba_prefill`` of ``rows`` (token lists) padded to P_PAD, with
    the loop's chunk set to ``chunk``: P_PAD makes it one chunk, which is
    the whole-sequence form."""
    monkeypatch.setattr(M, "PREFILL_CHUNK", chunk)
    assert M.prefill_chunk(P_PAD) == chunk
    toks = np.zeros((len(rows), P_PAD), np.int32)
    for i, r in enumerate(rows):
        toks[i, : len(r)] = r
    kw = {"compute_dtype": jnp.float32, "kv_len": P_PAD, "attn_impl": "xla",
          **kw}
    return M.mamba_prefill(
        params, jnp.asarray(toks),
        jnp.asarray([len(r) for r in rows], jnp.int32), CFG, **kw)


def _decode_token_by_token(params, row):
    """The prompt through the recurrent decode step, one position at a
    time, its K/V in one page of P_PAD slots: -> (last logits, slab,
    {"k", "v"} (n_attn, P_PAD, nkv, hd))."""
    step = jax.jit(lambda st, pools, t, tok: M.mamba_decode_step(
        params, st, pools, jnp.asarray([[1]], jnp.int32), t, tok, CFG,
        page_size=P_PAD, compute_dtype=jnp.float32))
    state = M.init_mamba_decode_state(CFG, 1, jnp.float32)
    a = CFG.attn_cfg
    pools = {k: jnp.zeros((1, 2, P_PAD, a.num_heads_kv, a.head_dim))
             for k in ("k", "v")}
    for t, tok in enumerate(row):
        logits, state, pools = step(
            state, pools, jnp.asarray([t], jnp.int32),
            jnp.asarray([tok], jnp.int32))
    return logits[0], state, {k: v[:, 1] for k, v in pools.items()}


@pytest.mark.parametrize("lengths", [
    (1,), (C - 1,), (C,), (C + 1,), (P_PAD - 1,), (P_PAD,),
    (P_PAD - 1, C + 1),  # two ragged rows in one call
])
def test_looped_prefill_is_the_whole_sequence_and_the_decode_step(
        params, lengths, monkeypatch):
    rng = np.random.default_rng(sum(lengths))
    rows = [rng.integers(1, 512, size=n).tolist() for n in lengths]
    logits, slab, kv = _prefill_rows(params, rows, C, monkeypatch)
    whole = _prefill_rows(params, rows, P_PAD, monkeypatch)
    # against the whole-sequence form: the scan and the conv walk the
    # same positions in the same order; products and the attention's sum
    # differ by their tiling
    for got, want in zip(jax.tree.leaves((logits, slab, kv)),
                         jax.tree.leaves(whole)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for r, (row, n) in enumerate(zip(rows, lengths)):
        # K/V are exactly zero from the length on
        assert not np.asarray(kv["k"][:, r, n:]).any()
        assert not np.asarray(kv["v"][:, r, n:]).any()
        want_logits, want_slab, want_kv = _decode_token_by_token(params, row)
        np.testing.assert_allclose(
            logits[r], want_logits, rtol=1e-4, atol=1e-4)
        for got, want in zip(slab, want_slab):
            for part in got:  # conv, ssd; {} for the attention layer
                np.testing.assert_allclose(
                    got[part][r], want[part][0], rtol=2e-5, atol=2e-5)
        for part in ("k", "v"):
            np.testing.assert_allclose(
                kv[part][:, r], want_kv[part], rtol=2e-5, atol=2e-5)


def test_kernel_carries_its_state_across_a_chunk_edge(params, monkeypatch):
    """The Pallas scan (interpret mode here) started from the state the
    chunk before it left, two chunks of 8 to a bucket of 16, one row
    ending inside the second: what the scan form gives in one chunk."""
    rng = np.random.default_rng(5)
    rows = [rng.integers(1, 512, size=n).tolist() for n in (P_PAD, 11)]
    want = _prefill_rows(params, rows, P_PAD, monkeypatch)
    carried = []

    def kernel(*args):
        carried.append(args[-1])  # h0
        return ss.selective_scan_kernel(*args, interpret=True)

    monkeypatch.setattr(M, "selective_scan", kernel)
    got = _prefill_rows(params, rows, 8, monkeypatch)
    assert len(carried) == 5  # the loop's body is traced once
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_chunk_attention_through_the_flash_kernel_merges_exactly():
    """The form the chip runs: the chunk's own block under the causal
    mask and every earlier block whole, both through the flash kernel
    (interpret mode here), merged through the log-sum-exp; against
    causal attention over the whole sequence."""
    from fms_fsdp_tpu.ops.attention import chunk_attention, xla_attention

    c, L = 256, 768
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, L, 4, 128))
    k = jax.random.normal(ks[1], (1, L, 1, 128))
    v = jax.random.normal(ks[2], (1, L, 1, 128))
    want = xla_attention(q, k, v, causal=True)
    for start in (0, 256, 512):
        # the cache beyond the chunk holds what a later chunk would write:
        # it must not be read
        cache_k = k.at[:, start + c:].set(1e4)
        cache_v = v.at[:, start + c:].set(1e4)
        for impl in ("pallas", "xla"):
            got = jax.jit(chunk_attention, static_argnames="impl")(
                q[:, start: start + c], cache_k, cache_v,
                jnp.int32(start), impl=impl)
            np.testing.assert_allclose(
                got, want[:, start: start + c], rtol=2e-5, atol=2e-5)


def _entry_computation(text):
    """The lines of an optimized HLO module's ENTRY computation."""
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    return lines[start + 1: end]


def test_largest_program_is_one_loop_over_chunks(monkeypatch):
    """The compiled prefill program of the longest bucket: one ``while``
    at its top, over the chunks, with every layer's work inside it, and
    nowhere an array of the whole bucket's positions by a model width."""
    from fms_fsdp_tpu.serve.families.mamba import prefill_program

    monkeypatch.setattr(M, "PREFILL_CHUNK", 8)
    p_pad = 48  # no width of the model
    scfg = ServeConfig(
        max_batch=2, max_seq_len=96, compute_dtype="float32",
        attn_impl="reference", prefill_bucket=p_pad)
    shapes = jax.eval_shape(lambda: _params())
    text = prefill_program(CFG, scfg, p_pad, p_pad, jnp.float32).lower(
        shapes, jax.ShapeDtypeStruct((1, p_pad), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32)).compile().as_text()
    assert sum(" while(" in l for l in _entry_computation(text)) == 1
    # (positions, d_model), (positions, d_inner), (positions, d_intermediate)
    for width in (CFG.d_model, CFG.d_inner, CFG.d_intermediate):
        assert not re.search(rf"\[(\d+,)*{p_pad},{width}\]", text), width
    # the chunk's are there
    assert re.search(rf"\[1,8,{CFG.d_inner}\]", text)


def test_computed_tokens_are_whole_chunks_up_to_the_prompts_end(
        params, monkeypatch, tmp_path):
    """``serve.prefill_computed_tokens`` and the ``prefill.done`` span's
    ``computed_tokens``: ceil(p / C) * C, never above the padded
    tokens."""
    from jax.profiler import ProfileData
    import glob

    monkeypatch.setattr(M, "PREFILL_CHUNK", C)
    eng = _engine(params, prefill_bucket=P_PAD)
    lengths = (1, C, C + 1, P_PAD - 1, P_PAD, P_PAD + 3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for n in lengths:
            eng.submit(list(range(1, n + 1)), 2)
        eng.run()
    want = [-(-n // C) * C for n in lengths]
    reg = eng.registry
    assert reg.counter("serve.prefill_computed_tokens").value == sum(want)
    padded = reg.counter("serve.prefill_padded_tokens").value
    assert padded == sum(-(-n // P_PAD) * P_PAD for n in lengths)
    assert sum(want) < padded
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = {
        name: [dict(e.stats) for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events
               if e.name == "serve/" + name]
        for name in ("prefill", "prefill.done")}
    by_rid = {e["rid"]: e for e in events["prefill"]}
    assert len(events["prefill.done"]) == len(lengths)
    for e in events["prefill.done"]:
        mine = by_rid[e["rid"]]
        p = mine["prompt_tokens"]
        assert e["computed_tokens"] == -(-p // C) * C <= mine["padded_tokens"]


# ---------------------------------------------------------------------------
# prefill then decode through the engine, on logits
# ---------------------------------------------------------------------------

# on, below and above a bucket edge; served two at a time, so the two
# slots hold streams of different lengths
PROMPT_LENGTHS = [(BUCKET, BUCKET - 3), (2 * BUCKET + 1, 5)]
NEW = 24


def _served_against_reference(tree, tree32, dtype, lengths, seed):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 512, size=n).tolist() for n in lengths]
    reqs, rows = _serve_capturing(_engine(tree, dtype), prompts, NEW)
    out = []
    for prompt, req, got in zip(prompts, reqs, rows):
        assert len(req.generated) == NEW and got.shape[0] == NEW
        full = _ref_logits(tree32, prompt + list(req.generated[:-1]))
        out.append((got, full[len(prompt) - 1:]))
    return out


@pytest.mark.parametrize("lengths", PROMPT_LENGTHS)
def test_engine_agrees_with_the_reference_on_logits_float32(params, lengths):
    for got, want in _served_against_reference(
            params, params, "float32", lengths, seed=sum(lengths)):
        # float32 on both sides: reduction order only
        assert _gaps(got, want) < 1e-4


def test_engine_through_the_step_kernel_agrees_and_keeps_an_idle_slot_zero(
        params, monkeypatch):
    """Where the one-position kernel compiles, the hybrid's decode step
    steps each layer's scan state in place and the adapter selects the
    conv windows alone; here, in interpret mode: a stream's logits are
    the reference's and the idle slot beside it stays zero."""
    from fms_fsdp_tpu.serve.families import mamba as A

    kernel = ss.selective_scan_step_kernel
    monkeypatch.setattr(
        ss, "selective_scan_step_kernel",
        lambda *args: kernel(*args, interpret=True))
    monkeypatch.setattr(ss, "scan_step_form", lambda *shape: "kernel")
    monkeypatch.setattr(A, "scan_step_form", ss.scan_step_form)
    eng = _engine(params)
    assert eng.adapter._dispatch_fields == {"ssm_form": "kernel"}
    prompt = np.random.default_rng(3).integers(1, 512, size=11).tolist()
    req = eng.submit(prompt, NEW)
    for _ in range(5):
        eng.step()
    assert 0 < len(req.generated) < NEW
    for x in jax.tree.leaves(eng.adapter.slab_slice(0)):
        assert float(jnp.abs(x).max()) > 0
    for x in jax.tree.leaves(eng.adapter.slab_slice(1)):
        assert float(jnp.abs(x).max()) == 0.0
    eng = _engine(params)
    (req,), (got,) = _serve_capturing(eng, [prompt], NEW)
    want = _ref_logits(params, prompt + list(req.generated[:-1]))
    assert _gaps(got, want[len(prompt) - 1:]) < 1e-4


def _bf16_gap(control):
    """Mean |logit - reference logit| over the served positions, in units
    of the reference logits' spread."""
    tree = _params(jnp.bfloat16)
    tree32 = jax.tree.map(lambda w: w.astype(jnp.float32), tree)
    if control:
        tree = jax.tree.map(through_fp8, tree)
    diffs, spread = [], []
    for lengths in PROMPT_LENGTHS:
        for got, want in _served_against_reference(
                tree, tree32, "bfloat16", lengths, seed=sum(lengths)):
            diffs.append(np.abs(got - want).ravel())
            spread.append(want.std())
    return float(np.mean(np.concatenate(diffs)) / np.mean(spread))


# bfloat16 serving against the float32 reference of the same (bfloat16-
# rounded) weights: rounding of activations only. Read at this size on
# the CPU: sound 0.021, weights through float8 0.24
BF16_GAP_LIMIT = 0.07


def test_engine_in_bfloat16_is_within_a_tolerance_that_float8_fails():
    sound, control = _bf16_gap(control=False), _bf16_gap(control=True)
    print("bf16 gap", sound, "float8 control", control)
    assert sound < BF16_GAP_LIMIT < control


# ---------------------------------------------------------------------------
# the slab
# ---------------------------------------------------------------------------


def test_slab_is_mamba1_shaped_and_constant_in_generated_length(params):
    eng = _engine(params)
    ad = eng.adapter
    per_layer = 3 * 128 * 4 + 16 * 128 * 4  # conv window + float32 state
    assert ad.state_bytes_per_stream == 5 * per_layer
    assert M.mamba_state_bytes_per_stream(CFG, jnp.float32) == 5 * per_layer
    assert ad.ssm_layers == 5
    assert eng.registry.gauge("serve.ssm_layers").value == 5
    assert eng.registry.gauge(
        "serve.ssm_state_bytes_per_stream").value == 5 * per_layer
    req = eng.submit([5, 6, 7, 8, 9], 20)
    sizes = []
    while eng.has_work():
        eng.step()
        sizes.append(sum(x.nbytes for x in jax.tree.leaves(ad.slab)))
    assert len(req.generated) == 20 and len(set(sizes)) == 1
    assert sizes[0] == 2 * 5 * per_layer  # two slots
    assert eng.registry.counter("serve.prefill_state_writes").value == 1
    assert [s["ssd"].shape for s in ad.slab if s] == [(2, 16, 128)] * 5
    assert ad.slab[3] == {}  # the attention layer keeps pages, no slab


def test_release_zeroes_the_slice(params):
    eng = _engine(params)
    eng.submit([5, 6, 7, 8, 9], 4)
    eng.step()
    eng.step()
    assert any(float(jnp.abs(x).max()) > 0
               for x in jax.tree.leaves(eng.adapter.slab_slice(0)))
    eng.run()
    for x in jax.tree.leaves(eng.adapter.slab):
        assert float(jnp.abs(x).max()) == 0.0


def test_handoff_round_trip_of_a_mamba1_stream(params):
    prompt, max_new = [3, 5, 7, 11, 13, 17, 19, 23, 29], 12
    ref = _engine(params)
    want = ref.submit(prompt, max_new)
    ref.run()

    src = _engine(params)
    req = src.submit(prompt, max_new)
    for _ in range(4):
        src.step()
    assert 0 < len(req.generated) < max_new
    data = src.pack_stream(req)
    header, arrays = unpack_handoff(data)
    assert header["codec"] == "mamba_slab" and header["ssm_layer"] == "Mamba1"
    assert header["ssd_shape"] == [16, 128] and header["conv_shape"] == [3, 128]
    assert arrays["slab.0000.ssd"].shape == (16, 128)
    assert arrays["slab.0000.ssd"].dtype == np.float32
    assert "slab.0003.ssd" not in arrays and "kv.k" in arrays
    dst = _engine(params)
    moved = dst.submit_handoff(data)
    dst.run()
    assert list(moved.generated) == list(want.generated)
    # a Mamba-2 replica refuses the frame at the door
    from fms_fsdp_tpu.serve.disagg import HandoffError

    with pytest.raises(HandoffError):
        src.adapter.check_handoff_header({**header, "ssm_layer": "Mamba2"})


# sha256 of the programs' StableHLO at the parent of PR 46 (6140358), where
# the Mamba-1 mixer stood in models/mamba.py and applied its three norms
# unconditionally
MIXER_MOVE_DIGESTS = {
    "decode":
        "a7aa5a2c4cb2921b8a993f866c91b024fdd5ec34bc930cc5fde50d480bd58ba3",
    "prefill 16":
        "ff09ea6bc9c191064ee0df4b6927ec347cd7ccf040100437599b65ed0ef17a72",
    "prefill 48":
        "e2e3b5adb7a5fb0e53f99d2071ce8d6beb307ebf96deb341f23a94eca0c4ce8e",
}


def _lowered_text(program):
    from fms_fsdp_tpu.serve.families import mamba as A

    sd = jax.ShapeDtypeStruct
    scfg = ServeConfig(
        max_batch=2, max_seq_len=64, page_size=8, compute_dtype="float32",
        attn_impl="reference")
    shapes = jax.eval_shape(_params)
    if program == "decode":
        ps, maxp, n = A.page_geometry(CFG, scfg)
        a = CFG.attn_cfg
        pool = sd((len(CFG.attn_layer_idx), n, ps, a.num_heads_kv, a.head_dim),
                  jnp.float32)
        state = jax.eval_shape(
            lambda: M.init_mamba_decode_state(CFG, 2, jnp.float32))
        return A.decode_program(CFG, scfg, ps, jnp.float32).lower(
            shapes, state, {"k": pool, "v": pool}, sd((2, maxp), jnp.int32),
            sd((2,), jnp.int32), sd((2,), jnp.int32), sd((2,), jnp.uint32)
        ).as_text()
    n = int(program.split()[1])
    return A.prefill_program(CFG, scfg, n, n, jnp.float32).lower(
        shapes, sd((1, n), jnp.int32), sd((1,), jnp.int32)).as_text()


@pytest.mark.parametrize("program", sorted(MIXER_MOVE_DIGESTS))
def test_jamba_programs_are_the_text_they_were(program, monkeypatch):
    """The Mamba-1 mixer moved to models/mamba1.py and took a flag for
    Jamba's three norms and one for handing its scan output out (the
    phi4flash family runs it without the first and with the second):
    jamba's decode program and its prefill programs, of one chunk and of
    three, lower to the text they had."""
    import hashlib

    if jax.__version__ != "0.9.0":
        pytest.skip("digests hold for jax 0.9.0")
    monkeypatch.setattr(M, "PREFILL_CHUNK", 16)
    text = _lowered_text(program)
    assert hashlib.sha256(text.encode()).hexdigest() == MIXER_MOVE_DIGESTS[
        program]
