"""Family-adapter serving (fms_fsdp_tpu/serve/families/, docs/serving.md
"Family adapters").

One engine, three families. The anchors, per the PR-17 contract:

- greedy adapter decode bit-identical (float32 + reference impls) to
  the family's jitted dense full-forward argmax walk — mamba against
  ``mamba_forward(mamba_kernel="reference")``, mixtral against
  ``mixtral_forward(moe_impl="dense")``; llama's anchor already lives
  in tests/test_serving.py and is untouched;
- Mamba decode-state bytes constant in generated length (the slab),
  pinned while llama's kv pages grow;
- Mixtral routed decode == dense-mix decode in both of its loop orders
  (routing is a bytes-and-FLOPs knob, not a numerics knob);
- pool pressure: eviction + recompute-on-resume per family, with the
  mamba slab slice zeroed on release;
- checkpoint→family resolution errors are actionable.

Bitwise caveat baked into the tiny configs: XLA CPU matmul rows only
decompose bitwise for small contraction dims (the llama TINY configs
rely on the same property), so d_intermediate/hidden_dim stay small
here. Two comparisons are cross-program and therefore token-level, not
bit-level: hybrid mamba attn decodes via gqa_attend while the dense
walk uses the xla attention impl, and the chunked training forward
(mamba_forward) compiles its transcendentals in a different fusion
context than the prefill/decode scan (~1e-7 logit ulp, measured). The
mamba bit-level oracle is therefore the *full-recurrence rescan walk*:
re-running the jitted prefill scan from scratch over prompt+generated
each step — a state-free O(L) recomputation the O(1)-slab incremental
decode must reproduce exactly, which is precisely the constant-memory
claim.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fms_fsdp_tpu.models.configs import (
    LlamaConfig,
    MambaConfig,
    MixtralConfig,
)
from fms_fsdp_tpu.models.llama import init_llama_params
from fms_fsdp_tpu.models.mamba import (
    init_mamba_params,
    mamba_forward,
    mamba_prefill,
    mamba_state_bytes_per_stream,
)
from fms_fsdp_tpu.models.mixtral import (
    _moe_token,
    init_mixtral_params,
    mixtral_forward,
)
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import (
    FAMILY_CODES,
    check_params_family,
    family_of,
    init_params_for,
    load_model_config,
)

TINY_LLAMA = LlamaConfig(
    src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
    max_expected_seq_len=256,
)
# small dims everywhere: bitwise row-decomposability of the CPU matmuls
# (see module docstring)
TINY_MAMBA = MambaConfig(
    d_model=64, n_layer=2, vocab_size=128, d_state=16, headdim=16,
    chunk_size=8, attn_layer_idx=(), d_intermediate=128,
)
_attn = dataclasses.replace(
    TINY_MAMBA.attn_cfg, head_dim=16, num_heads=4, num_heads_kv=2,
    rotary_emb_dim=8,
)
TINY_HYBRID = dataclasses.replace(
    TINY_MAMBA, n_layer=3, attn_layer_idx=(1,), attn_cfg=_attn,
)
TINY_MIXTRAL = MixtralConfig(
    src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
    hidden_dim=128, num_experts=4, top_k=2, max_expected_seq_len=64,
)


@pytest.fixture(scope="module")
def mamba_params():
    return init_mamba_params(jax.random.PRNGKey(0), TINY_MAMBA)


@pytest.fixture(scope="module")
def hybrid_params():
    return init_mamba_params(jax.random.PRNGKey(1), TINY_HYBRID)


@pytest.fixture(scope="module")
def mixtral_params():
    return init_mixtral_params(jax.random.PRNGKey(2), TINY_MIXTRAL)


def _engine(params, cfg, max_batch=2, max_seq=64, **kw):
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("attn_impl", "reference")
    kw.setdefault("page_size", 16)
    kw.setdefault("max_prefill_per_step", max_batch)
    scfg = ServeConfig(max_batch=max_batch, max_seq_len=max_seq, **kw)
    return ServingEngine(params, cfg, scfg)


def _dense_walk(fwd, prompt, max_new):
    """The family's parity oracle: jitted dense full-forward over the
    growing sequence, greedy argmax of the last position each step.
    Returns (tokens, per-step logits rows)."""
    toks = list(prompt)
    out, logits = [], []
    for _ in range(max_new):
        lg = fwd(jnp.asarray([toks], dtype=jnp.int32))
        row = np.asarray(lg[0, -1])
        logits.append(row)
        nxt = int(row.argmax())
        out.append(nxt)
        toks.append(nxt)
    return out, logits


def _mamba_fwd(params, cfg):
    return jax.jit(functools.partial(
        mamba_forward, params, cfg=cfg, compute_dtype=jnp.float32,
        mamba_kernel="reference", attn_impl="xla",
    ))


def _mixtral_fwd(params, cfg):
    return jax.jit(functools.partial(
        mixtral_forward, params, cfg=cfg, compute_dtype=jnp.float32,
        attn_impl="xla", moe_impl="dense",
    ))


def _run_capturing(eng, reqs):
    """Drive the engine, collecting the (B, V) decode logits of every
    iteration that decoded."""
    step_logits = []
    while eng.has_work():
        eng.step()
        if eng.last_logits is not None:
            step_logits.append(np.asarray(eng.last_logits))
            eng.last_logits = None
    return step_logits


# ---------------------------------------------------------------------------
# greedy parity anchors
# ---------------------------------------------------------------------------


def _mamba_rescan_walk(params, cfg, prompt, max_new):
    """The mamba bit-level oracle: full-recurrence rescan from scratch
    each step (jitted prefill over the growing sequence, no carried
    state), greedy argmax of the last real position."""
    pf = jax.jit(functools.partial(
        mamba_prefill, cfg=cfg, compute_dtype=jnp.float32,
    ))
    toks = list(prompt)
    lgs = []
    for _ in range(max_new):
        lg, _, _ = pf(
            params,
            jnp.asarray([toks], jnp.int32),
            jnp.asarray([len(toks)], jnp.int32),
        )
        row = np.asarray(lg[0])
        lgs.append(row)
        toks.append(int(row.argmax()))
    return toks[len(prompt):], lgs


def test_mamba_greedy_parity_bitwise(mamba_params):
    """Pure-Mamba acceptance anchor: the O(1)-slab decode through the
    engine reproduces the state-free full-recurrence rescan walk
    bit-for-bit per decode step (fp32 + mamba_kernel="reference") — the
    constant-memory path loses nothing vs recomputing from scratch.
    The chunked training forward agrees token-for-token (cross-program
    transcendental ulp keeps its logits off by ~1e-7; see module
    docstring)."""
    prompts = [[5, 9, 2, 7], [11, 3, 8, 1]]
    max_new = 6
    dense = [
        _mamba_rescan_walk(mamba_params, TINY_MAMBA, p, max_new)
        for p in prompts
    ]
    fwd = _mamba_fwd(mamba_params, TINY_MAMBA)
    train = [_dense_walk(fwd, p, max_new) for p in prompts]
    eng = _engine(mamba_params, TINY_MAMBA, max_batch=2)
    assert eng.family == "mamba" and eng.cache is None
    reqs = [eng.submit(p, max_new) for p in prompts]
    step_logits = _run_capturing(eng, reqs)
    for i, (toks, lgs) in enumerate(dense):
        assert reqs[i].generated == toks
        assert reqs[i].generated == train[i][0]  # training-path walk too
        # engine decode step t vs rescan step t+1 (token 1 of both came
        # from prefill logits / the prompt-only rescan)
        for t in range(max_new - 1):
            assert (step_logits[t][i] == lgs[t + 1]).all(), (i, t)
            assert np.allclose(
                step_logits[t][i], train[i][1][t + 1], atol=1e-5
            ), (i, t)


def test_mamba_hybrid_greedy_token_parity(hybrid_params):
    """Hybrid (mamba + attn layers): slab + paged-KV decode matches the
    dense walk token-for-token (cross-impl attention — see module
    docstring — so tokens, not logit bits)."""
    plans = [([5, 9, 2, 7, 6], 6), ([11, 3], 8)]
    fwd = _mamba_fwd(hybrid_params, TINY_HYBRID)
    dense = [_dense_walk(fwd, p, n)[0] for p, n in plans]
    eng = _engine(hybrid_params, TINY_HYBRID, max_batch=2)
    assert eng.cache is not None  # attn layers ride pages
    reqs = [eng.submit(p, n) for p, n in plans]
    eng.run()
    for r, toks in zip(reqs, dense):
        assert r.state == "finished"
        assert r.generated == toks


def test_mixtral_greedy_parity_bitwise(mixtral_params):
    """Mixtral acceptance anchor: paged attention + dense-mix decode
    through the engine == the jitted dense full-forward argmax walk
    (fp32, moe_impl="dense" both sides), logits bit-for-bit per decode
    step. The routed serving default rides the same paged attention and
    is pinned against this engine in
    test_mixtral_routed_engine_matches_dense_engine."""
    prompts = [[5, 9, 2, 7], [11, 3, 8, 1]]
    max_new = 6
    fwd = _mixtral_fwd(mixtral_params, TINY_MIXTRAL)
    dense = [_dense_walk(fwd, p, max_new) for p in prompts]
    eng = _engine(mixtral_params, TINY_MIXTRAL, max_batch=2,
                  moe_impl="dense")
    assert eng.family == "mixtral"
    reqs = [eng.submit(p, max_new) for p in prompts]
    step_logits = _run_capturing(eng, reqs)
    for i, (toks, lgs) in enumerate(dense):
        assert reqs[i].generated == toks
        for t in range(max_new - 1):
            assert (step_logits[t][i] == lgs[t + 1]).all(), (i, t)


def test_mamba_bucketed_prefill_padding_invariant(mamba_params):
    """prefill_bucket > 1 pads the prompt; the masked prefill scan must
    freeze per-row state past the real length, so padded and exact
    prefill serve identical streams."""
    prompt, max_new = [5, 9, 2, 7, 6], 6
    exact = _engine(mamba_params, TINY_MAMBA)
    r1 = exact.submit(prompt, max_new)
    exact.run()
    padded = _engine(mamba_params, TINY_MAMBA, prefill_bucket=8)
    r2 = padded.submit(prompt, max_new)
    padded.run()
    assert r1.generated == r2.generated


# ---------------------------------------------------------------------------
# constant-memory claim
# ---------------------------------------------------------------------------


def test_mamba_state_bytes_flat_while_llama_pages_grow(mamba_params):
    """THE constant-memory pin: a mamba stream's decode-state bytes do
    not change with max_new_tokens, while the llama baseline's peak kv
    pages grow. The tiny-config slab is pinned literally: 2 layers x
    ((d_conv-1)*conv_dim*4B conv + H*P*N*4B fp32 ssd) = 20224."""
    assert mamba_state_bytes_per_stream(TINY_MAMBA, jnp.float32) == 20224

    def peak_mamba(max_new):
        eng = _engine(mamba_params, TINY_MAMBA, max_seq=64)
        eng.submit([5, 9, 2, 7], max_new)
        bytes_seen, shapes = set(), set()
        while eng.has_work():
            eng.step()
            bytes_seen.add(eng.serving_stats()["state_bytes_per_stream"])
            shapes.add(
                tuple(
                    a.shape
                    for layer in eng.adapter._state
                    for a in jax.tree.leaves(layer)
                )
            )
        return bytes_seen, shapes

    b_short, s_short = peak_mamba(4)
    b_long, s_long = peak_mamba(32)
    # flat within a run, identical across run lengths, equal to the pin
    assert b_short == b_long == {20224.0}
    assert s_short == s_long and len(s_short) == 1

    llama_params = init_llama_params(jax.random.PRNGKey(0), TINY_LLAMA)

    def peak_llama(max_new):
        eng = _engine(llama_params, TINY_LLAMA, max_seq=64)
        eng.submit([5, 9, 2, 7], max_new)
        peak = 0
        while eng.has_work():
            eng.step()
            peak = max(peak, eng.cache.pages_in_use)
        return peak

    assert peak_llama(32) > peak_llama(4)  # paged KV grows; the slab didn't


def test_llama_and_mixtral_report_zero_slab(mixtral_params):
    llama_params = init_llama_params(jax.random.PRNGKey(0), TINY_LLAMA)
    for params, cfg, code in (
        (llama_params, TINY_LLAMA, 0),
        (mixtral_params, TINY_MIXTRAL, 2),
    ):
        eng = _engine(params, cfg)
        stats = eng.serving_stats()
        assert stats["family"] == float(code)
        assert stats["state_bytes_per_stream"] == 0.0
    eng = _engine(init_mamba_params(jax.random.PRNGKey(0), TINY_MAMBA),
                  TINY_MAMBA)
    assert eng.serving_stats()["family"] == float(FAMILY_CODES["mamba"])


# ---------------------------------------------------------------------------
# mixtral routed-vs-dense equivalence
# ---------------------------------------------------------------------------


# (experts, rows B, positions m) -> the loop order the routed program
# takes for n = B * m * top_k routed pairs against E experts
ROUTED_SHAPES = [
    (4, 2, 3, "all_experts"),  # n 12 >= 4
    (4, 2, 1, "all_experts"),  # n 4 == E: the edge streams every expert
    (8, 4, 1, "all_experts"),  # n 8 == E
    (4, 1, 1, "per_pair"),  # n 2 < 4: one live stream
    (8, 1, 3, "per_pair"),  # n 6 < 8: a three-position verify chunk
    (8, 3, 1, "per_pair"),  # n 6 < 8
]


@pytest.mark.parametrize("experts,B,m,form", ROUTED_SHAPES)
def test_mixtral_routed_equals_dense_mix(experts, B, m, form):
    """Routed computes the dense mixture in both loop orders: only the
    routed (row, expert) pairs contribute, non-chosen experts carry
    exactly-zero mix weights and fp32 addition of the two chosen terms
    is commutative. ``per_pair`` lowers to other dot-generals than the
    all-experts matmul, so it sits one ulp off dense (measured 2.3e-10)
    rather than bitwise on it — pin that ceiling tightly for both. The
    token-level _moe_token dense path must replay the training FFN
    (_moe_ffn_dense) bit-for-bit: that is the bridge the engine's
    bitwise anchor stands on."""
    from fms_fsdp_tpu.models.mixtral import _moe_ffn_dense, routed_moe_form

    cfg = dataclasses.replace(TINY_MIXTRAL, num_experts=experts)
    assert routed_moe_form(B * m * cfg.top_k, experts) == form
    lp = jax.tree.map(
        lambda a: a[0].astype(jnp.float32),
        init_mixtral_params(jax.random.PRNGKey(2), cfg)["layers"],
    )
    h = jax.random.normal(jax.random.PRNGKey(3), (B, m, 64), jnp.float32)
    dense = np.asarray(_moe_token(h, lp, cfg, "dense"))
    routed = np.asarray(_moe_token(h, lp, cfg, "routed"))
    train = np.asarray(_moe_ffn_dense(h, lp, cfg)[0])
    assert (dense == train).all()
    assert np.abs(routed - dense).max() < 1e-8
    assert np.abs(dense).max() > 1e-3  # the mixture is not all zeros


# max_batch -> (moe_form, expert copies a layer reads) at E = 4, top_k = 2
ROUTED_ENGINES = {1: ("per_pair", 2), 2: ("all_experts", 4),
                  4: ("all_experts", 4)}


@pytest.mark.parametrize("max_batch", sorted(ROUTED_ENGINES))
def test_mixtral_routed_engine_matches_dense_engine(mixtral_params, max_batch):
    """Same streams end-to-end: the routed serving default generates
    exactly the dense-mix engine's tokens in both loop orders, with
    per-step logits inside the single-ulp routing envelope."""
    prompt, max_new = [5, 9, 2, 7], 6
    routed = _engine(mixtral_params, TINY_MIXTRAL, max_batch=max_batch)
    assert routed.adapter.moe_impl == "routed"  # serving default
    assert routed.adapter.moe_form == ROUTED_ENGINES[max_batch][0]
    r1 = routed.submit(prompt, max_new)
    lg_routed = _run_capturing(routed, [r1])

    dense = _engine(
        mixtral_params, TINY_MIXTRAL, max_batch=max_batch, moe_impl="dense"
    )
    r2 = dense.submit(prompt, max_new)
    lg_dense = _run_capturing(dense, [r2])

    assert r1.generated == r2.generated
    assert len(lg_routed) == len(lg_dense) == max_new - 1
    for a, b in zip(lg_routed, lg_dense):
        assert np.abs(a[0] - b[0]).max() < 1e-6
        assert a[0].argmax() == b[0].argmax()


@pytest.mark.parametrize(
    "kw,form,reads",
    [({"max_batch": b}, *ROUTED_ENGINES[b]) for b in sorted(ROUTED_ENGINES)]
    + [({"max_batch": 1, "moe_impl": "dense"}, "dense", 4)],
)
def test_mixtral_engine_says_which_moe_form_it_built(
    mixtral_params, kw, form, reads
):
    """One fact per engine, set where the decode program is built: the
    adapter's ``moe_form`` (also a field of every serve/decode.dispatch
    span, tests/test_serve_spans.py) and the gauge of the expert copies a
    decode step reads in each layer, ``min(n, E)``."""
    eng = _engine(mixtral_params, TINY_MIXTRAL, **kw)
    assert eng.adapter.moe_form == form
    assert eng.adapter.moe_expert_reads_per_layer == reads
    snap = eng.registry.snapshot(clear_windows=False)
    assert snap["serve.moe_expert_reads_per_layer"] == float(reads)


def test_families_without_experts_read_none(mamba_params):
    eng = _engine(mamba_params, TINY_MAMBA)
    assert eng.registry.snapshot()["serve.moe_expert_reads_per_layer"] == 0.0


# ---------------------------------------------------------------------------
# pool pressure: eviction + recompute-on-resume per family
# ---------------------------------------------------------------------------


def _pressure_run(params, cfg, plans, **kw):
    """Tight pool: force at least one eviction, then check every stream
    still finishes with exactly the tokens of an unpressured engine
    (recompute-on-resume re-prefills prompt + generated-so-far)."""
    calm = _engine(params, cfg, max_batch=2, max_seq=64)
    want = []
    for p, n in plans:
        r = calm.submit(p, n)
        calm.run()
        want.append(r.generated)
    eng = _engine(params, cfg, max_batch=2, max_seq=64, **kw)
    reqs = [eng.submit(p, n) for p, n in plans]
    eng.run()
    assert eng.scheduler.evicted >= 1
    for r, toks in zip(reqs, want):
        assert r.state == "finished"
        assert r.generated == toks
    return eng


PRESSURE_PLANS = [([5, 9, 2, 7], 20), ([11, 3, 8, 1], 20)]


def test_pool_pressure_llama():
    params = init_llama_params(jax.random.PRNGKey(0), TINY_LLAMA)
    _pressure_run(params, TINY_LLAMA, PRESSURE_PLANS, num_pages=3 + 2)


def test_pool_pressure_mixtral(mixtral_params):
    _pressure_run(
        mixtral_params, TINY_MIXTRAL, PRESSURE_PLANS, num_pages=3 + 2
    )


def test_pool_pressure_mamba_hybrid_zeroes_slab(hybrid_params):
    """Hybrid mamba under page pressure: the LIFO victim's slab slice
    is zeroed at eviction (release), recompute-on-resume re-prefills
    it, and the stream still matches the calm run."""
    eng = _pressure_run(
        hybrid_params, TINY_HYBRID, PRESSURE_PLANS, num_pages=3 + 2
    )
    # after drain every slot is released — all slab slices exactly zero
    for layer in eng.adapter._state:
        for leaf in jax.tree.leaves(layer):
            assert not np.asarray(leaf).any()


def test_mamba_slab_zeroed_on_completion(mamba_params):
    """Completion lands in release() like eviction does: the finished
    stream's slab slice is exactly zero while a neighbor keeps
    decoding (the live-mask keeps idle slices zero mid-flight)."""
    eng = _engine(mamba_params, TINY_MAMBA, max_batch=2)
    short = eng.submit([5, 9, 2, 7], 2)
    long = eng.submit([11, 3, 8, 1], 12)
    while eng.has_work():
        eng.step()
        if short.state == "finished" and long.state != "finished":
            slab = eng.adapter.slab_slice(0)
            for leaf in jax.tree.leaves(slab):
                assert not np.asarray(leaf).any()
    assert short.state == long.state == "finished"


# ---------------------------------------------------------------------------
# checkpoint -> family resolution
# ---------------------------------------------------------------------------


def test_family_of_and_init_params_for():
    assert family_of(TINY_LLAMA) == "llama"
    assert family_of(TINY_MAMBA) == "mamba"
    assert family_of(TINY_MIXTRAL) == "mixtral"
    with pytest.raises(ValueError, match="unknown model config"):
        family_of(object())
    key = jax.random.PRNGKey(0)
    for cfg, fam in (
        (TINY_LLAMA, "llama"),
        (TINY_MAMBA, "mamba"),
        (TINY_MIXTRAL, "mixtral"),
    ):
        params = init_params_for(cfg)(key)
        check_params_family(params, fam)  # self-consistent


def test_load_model_config_infers_and_respects_family():
    llama = load_model_config({"emb_dim": 64, "nheads": 4, "nlayers": 2})
    assert isinstance(llama, LlamaConfig)
    mamba = load_model_config(
        {"d_model": 64, "n_layer": 2, "attn_layer_idx": [1],
         "attn_cfg": {"head_dim": 16, "num_heads": 4, "num_heads_kv": 2}}
    )
    assert isinstance(mamba, MambaConfig)
    assert mamba.attn_layer_idx == (1,)
    assert mamba.attn_cfg.head_dim == 16
    mixtral = load_model_config({"num_experts": 4, "emb_dim": 64})
    assert isinstance(mixtral, MixtralConfig)
    explicit = load_model_config({"family": "llama", "emb_dim": 64})
    assert isinstance(explicit, LlamaConfig)
    with pytest.raises(ValueError, match="unknown model family"):
        load_model_config({"family": "gpt5", "emb_dim": 64})
    # wrong keys for the inferred family: the error names the fix
    with pytest.raises(ValueError, match="set \"family\" explicitly"):
        load_model_config({"d_model": 64, "num_experts": 4})


def test_mixed_family_checkpoint_errors_are_actionable(
    mamba_params, mixtral_params
):
    """A mixtral checkpoint against a mamba config (and every other
    cross-pairing) must fail at engine build, naming both families and
    the fix — not at the first prefill with a shape error."""
    scfg = ServeConfig(max_batch=2, max_seq_len=64,
                      compute_dtype="float32", attn_impl="reference",
                      page_size=16)
    with pytest.raises(ValueError) as ei:
        ServingEngine(mixtral_params, TINY_MAMBA, scfg)
    msg = str(ei.value)
    assert "mixtral" in msg and "mamba" in msg and "mismatch" in msg
    with pytest.raises(ValueError) as ei:
        ServingEngine(mamba_params, TINY_LLAMA, scfg)
    assert "mamba" in str(ei.value) and "llama" in str(ei.value)
    with pytest.raises(ValueError, match="do not look like"):
        check_params_family({"layers": 7}, "llama")


def test_unsupported_knobs_error_actionably(mamba_params, mixtral_params):
    """v1 limits fail at build with the knob named, not mid-decode."""
    for params, cfg in (
        (mamba_params, TINY_MAMBA),
        (mixtral_params, TINY_MIXTRAL),
    ):
        with pytest.raises(ValueError, match="attn_impl"):
            _engine(params, cfg, attn_impl="kernel")
        with pytest.raises(ValueError, match="kv_quant"):
            _engine(params, cfg, kv_quant="int8")
    with pytest.raises(ValueError, match="moe_impl"):
        _engine(mixtral_params, TINY_MIXTRAL, moe_impl="sparse")
