"""Raw decode speed, PR 19: speculative serving, chunked prefill and
the paged-attention kernel v2 (fms_fsdp_tpu/serve/, ops/paged_attention).

The anchor is unchanged: everything here must preserve greedy
bit-parity. Speculative serving's accept rule emits exactly the tokens
non-speculative greedy would (the verify forward's per-position logits
are bit-identical to sequential decode steps — pinned at function
level below); chunked prefill's logits are bit-identical to
whole-prompt prefill (decode_chunk and prefill run the same attention
op-for-op over the same zeroed cache); kernel v2 stays allclose to the
reference walk over GQA heads, multi-page blocks, ragged tails and
int8/fp8 pages read natively.

CI runs this file as its own step (.github/workflows/pytest.yml
"speculative serving") and deselects it from the main sweep.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fms_fsdp_tpu.models.configs import LlamaConfig
from fms_fsdp_tpu.models.llama import init_llama_params
from fms_fsdp_tpu.models.speculator import (
    SpeculatorConfig,
    init_speculator_params,
    load_speculator,
    save_speculator,
)
from fms_fsdp_tpu.ops.paged_attention import (
    paged_attention_kernel,
    paged_attention_reference,
)
from fms_fsdp_tpu.ops.quant import kv_dequantize, kv_quantize
from fms_fsdp_tpu.serve import PagedKVCache, ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.decode import paged_decode_step, paged_verify_step

TINY = LlamaConfig(
    src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
    max_expected_seq_len=256,
)


@pytest.fixture(scope="module")
def tiny_params():
    return init_llama_params(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    """A random-init speculator checkpoint: acceptance is ~0, which is
    the HARD case for parity (every step exercises the reject/rollback
    path; the bonus token is still committed every verify)."""
    scfg = SpeculatorConfig(
        emb_dim=TINY.emb_dim, inner_dim=32,
        vocab_size=TINY.src_vocab_size, n_predict=3,
    )
    params = init_speculator_params(jax.random.PRNGKey(7), scfg)
    path = str(tmp_path_factory.mktemp("spec") / "speculator.pkl")
    save_speculator(path, params, scfg)
    return path


def _engine(params, max_batch=4, max_seq=128, **kw):
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("attn_impl", "reference")
    kw.setdefault("page_size", 16)
    kw.setdefault("max_prefill_per_step", max_batch)
    scfg = ServeConfig(max_batch=max_batch, max_seq_len=max_seq, **kw)
    return ServingEngine(params, TINY, scfg)


def _serve(params, prompts, max_new=12, **kw):
    eng = _engine(params, **kw)
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    return eng, [r.generated for r in reqs]


def _prompts(sizes=(37, 5, 60, 9, 23), vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, vocab, size=n))) for n in sizes]


# ---------------------------------------------------------------------------
# speculative serving: the parity anchor
# ---------------------------------------------------------------------------


def test_verify_step_bitwise_vs_sequential_decode(tiny_params):
    """The parity core: paged_verify_step's logits at position j equal
    feeding the same tokens one at a time through paged_decode_step —
    bit-for-bit on fp32 reference. Everything the accept rule compares
    is therefore the same numbers plain greedy would compute."""
    prompt = [5, 9, 2, 7, 11, 3]
    cand = jnp.asarray([[4, 8, 15, 16]], jnp.int32)  # m=4
    from fms_fsdp_tpu.models.generation import prefill

    _, _, cache = prefill(
        tiny_params, jnp.asarray([prompt], jnp.int32), TINY,
        max_seq_len=32, compute_dtype=jnp.float32,
    )
    for quant in ("none", "int8"):
        c = PagedKVCache(
            TINY.nlayers, 12, 8, TINY.n_kv_heads, TINY.head_dim,
            dtype=jnp.float32, quant=quant,
        )
        c.ensure(1, len(prompt))
        c.write_prompt(1, cache["k"][:, 0, :8], cache["v"][:, 0, :8])
        table = jnp.asarray(c.page_table([1], max_pages=4))
        lens = jnp.asarray([len(prompt)], jnp.int32)
        ver_lg, _, _ = jax.jit(functools.partial(
            paged_verify_step, cfg=TINY, page_size=8,
            compute_dtype=jnp.float32, quant=quant,
        ))(tiny_params, c.pools, table, lens, cand)
        # sequential: one paged_decode_step per candidate token
        pools = c.pools
        step = jax.jit(functools.partial(
            paged_decode_step, cfg=TINY, page_size=8,
            compute_dtype=jnp.float32, quant=quant,
            attn_impl="reference",
        ))
        for j in range(cand.shape[1]):
            lg, _, pools = step(
                tiny_params, pools, table,
                lens + j, cand[:, j],
            )
            assert (np.asarray(ver_lg[:, j]) == np.asarray(lg)).all(), (
                quant, j,
            )


def test_speculative_greedy_token_identical(tiny_params, spec_path):
    prompts = _prompts()
    _, ref = _serve(tiny_params, prompts)
    eng, spec = _serve(tiny_params, prompts, speculator_path=spec_path)
    assert spec == ref
    st = eng.serving_stats()
    assert st["spec_draft_tokens"] == 3.0
    assert 0.0 <= st["spec_accept_rate"] <= 1.0


def test_speculative_draft_cap_and_eos(tiny_params, spec_path):
    prompts = _prompts(sizes=(12, 30, 7))
    # eos mid-stream: the per-token commit must truncate exactly where
    # the non-speculative engine stops
    _, ref = _serve(tiny_params, prompts, eos_token=3)
    _, spec = _serve(
        tiny_params, prompts, eos_token=3, speculator_path=spec_path,
    )
    assert spec == ref
    _, capped = _serve(
        tiny_params, prompts, eos_token=3, speculator_path=spec_path,
        spec_draft_tokens=1,
    )
    assert capped == ref


def test_speculative_survives_eviction_recompute(tiny_params, spec_path):
    """A pool too small for all streams forces LIFO eviction; the
    evicted stream resumes by re-prefilling prompt+generated, which
    re-seeds the draft state — greedy streams must still match."""
    prompts = _prompts(sizes=(40, 44, 48))
    kw = dict(max_batch=3, max_seq=128, num_pages=14)
    _, ref = _serve(tiny_params, prompts, **kw)
    eng, spec = _serve(
        tiny_params, prompts, speculator_path=spec_path, **kw
    )
    assert spec == ref


def test_speculative_quantized_pages_parity(tiny_params, spec_path):
    """int8 pages: speculative vs plain on the SAME quantized engine
    config — the verify forward reads/writes quantized pools exactly
    like sequential decode (the only cross-position dataflow is through
    the pools), so greedy parity survives quantization."""
    prompts = _prompts(sizes=(20, 9, 33))
    _, ref = _serve(tiny_params, prompts, kv_quant="int8")
    _, spec = _serve(
        tiny_params, prompts, kv_quant="int8", speculator_path=spec_path,
    )
    assert spec == ref


def test_speculator_checkpoint_roundtrip(tmp_path):
    scfg = SpeculatorConfig(
        emb_dim=16, inner_dim=8, vocab_size=32, n_predict=2,
    )
    params = init_speculator_params(jax.random.PRNGKey(1), scfg)
    path = str(tmp_path / "s.pkl")
    save_speculator(path, params, scfg)
    params2, scfg2 = load_speculator(path)
    assert scfg2 == scfg
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        assert (np.asarray(a) == np.asarray(b)).all()
    # a bare params pickle is NOT a serving speculator checkpoint:
    # n_predict is not recoverable from tied weights
    import pickle

    bare = str(tmp_path / "bare.pkl")
    with open(bare, "wb") as f:
        pickle.dump({"model_state": {}}, f)
    with pytest.raises(ValueError, match="speculator_config"):
        load_speculator(bare)


def test_unsupported_spec_knobs_error_actionably(tiny_params, spec_path):
    with pytest.raises(ValueError, match="greedy-only"):
        _engine(tiny_params, speculator_path=spec_path, do_sample=True)
    with pytest.raises(ValueError, match="spec_draft_tokens"):
        _engine(tiny_params, speculator_path=spec_path, spec_draft_tokens=9)
    with pytest.raises(ValueError, match="unified-only"):
        _engine(tiny_params, speculator_path=spec_path, role="prefill")
    from fms_fsdp_tpu.models.configs import MambaConfig, MixtralConfig
    from fms_fsdp_tpu.serve.families import init_params_for

    mam = MambaConfig(
        d_model=64, n_layer=2, vocab_size=128, d_state=16, headdim=16,
        chunk_size=8, attn_layer_idx=(), d_intermediate=128,
    )
    mam_params = init_params_for(mam)(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="speculator_path"):
        ServingEngine(
            mam_params, mam,
            ServeConfig(compute_dtype="float32", speculator_path=spec_path),
        )
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ServingEngine(
            mam_params, mam,
            ServeConfig(compute_dtype="float32", prefill_chunk_tokens=8),
        )
    mix = MixtralConfig(
        src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
        hidden_dim=128, num_experts=4, top_k=2, max_expected_seq_len=64,
    )
    mix_params = init_params_for(mix)(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="speculator_path"):
        ServingEngine(
            mix_params, mix,
            ServeConfig(
                compute_dtype="float32", max_seq_len=64,
                speculator_path=spec_path,
            ),
        )


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_row_bitwise(tiny_params):
    """Adapter level: the first-token logits row a chunked prefill
    produces is bit-identical to whole-prompt prefill — including a
    chunk size that does not divide the prompt length."""
    from fms_fsdp_tpu.serve.families.llama import LlamaAdapter

    prompt = _prompts(sizes=(45,))[0]
    whole = LlamaAdapter(
        tiny_params, TINY,
        ServeConfig(
            max_batch=2, max_seq_len=128, compute_dtype="float32",
            attn_impl="reference", page_size=16,
        ),
    )
    row_whole = np.asarray(whole.prefill(1, 0, prompt))
    for chunk in (8, 7):
        ad = LlamaAdapter(
            tiny_params, TINY,
            ServeConfig(
                max_batch=2, max_seq_len=128, compute_dtype="float32",
                attn_impl="reference", page_size=16,
                prefill_chunk_tokens=chunk,
            ),
        )
        ad.prefill_start(1, 0, prompt)
        row = None
        while row is None:
            row = ad.prefill_chunk(1)
        assert (np.asarray(row) == row_whole).all(), chunk


def test_chunked_prefill_token_parity_and_interleave(tiny_params):
    prompts = _prompts(sizes=(60, 5, 37, 9))
    _, ref = _serve(tiny_params, prompts)
    eng, ch = _serve(tiny_params, prompts, prefill_chunk_tokens=8)
    assert ch == ref
    assert eng.serving_stats()["prefill_chunks"] > 0


def test_chunked_prefill_unblocks_short_requests(tiny_params):
    """The TTFT win in miniature: while a long prompt streams in by
    chunks, a short request admitted behind it must get its first token
    BEFORE the long one finishes prefilling — whole-prompt prefill
    would serialize them."""
    eng = _engine(tiny_params, max_batch=2, prefill_chunk_tokens=8,
                  max_prefill_per_step=1)
    long_req = eng.submit(_prompts(sizes=(90,))[0], 4)
    short_req = eng.submit([7, 11, 13], 4)
    for _ in range(4):  # long prompt needs ~12 chunks; short admits now
        eng.step()
    assert short_req.first_token_time is not None
    assert long_req.first_token_time is None
    eng.run()
    assert long_req.state == "finished"
    assert short_req.state == "finished"


def test_chunked_prefill_expiry_mid_chunk_releases_pages(tiny_params):
    import itertools

    clk = itertools.count().__next__
    scfg = ServeConfig(
        max_batch=2, max_seq_len=128, compute_dtype="float32",
        attn_impl="reference", page_size=16, prefill_chunk_tokens=8,
    )
    eng = ServingEngine(
        tiny_params, TINY, scfg, clock=lambda: float(clk()),
    )
    req = eng.submit(_prompts(sizes=(80,))[0], 4, deadline_s=3.0)
    eng.step()  # admits + first chunk; the fake clock then blows past
    eng.step()  # the deadline -> in-flight expiry mid-chunk
    for _ in range(20):
        eng.step()
    assert req.state == "expired"
    assert eng.adapter.pages_in_use == 0


# ---------------------------------------------------------------------------
# paged-attention kernel v2
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nq,nkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("block_kv", [16, 32])
def test_kernel_v2_multipage_matches_reference(nq, nkv, block_kv):
    """Multi-page DMA cells (block_kv > page_size), ragged lens, GQA,
    and a page count the block width does not divide."""
    P, ps, hd, B = 12, 8, 128, 3
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, nkv, hd), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, ps, nkv, hd), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(4), (B, nq, hd), jnp.float32)
    # 5 pages/row: nblocks = ceil(5 / (block_kv//ps)) leaves a ragged
    # tail block whose dead slots must clamp, not read junk
    table = jnp.asarray(
        [[2, 3, 4, 5, 6], [7, 8, 9, 0, 0], [10, 11, 2, 3, 4]], jnp.int32
    )
    lens = jnp.asarray([33, 17, 39], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, table, lens)
    ker = paged_attention_kernel(
        q, kp, vp, table, lens, block_kv=block_kv, interpret=True,
    )
    assert jnp.allclose(ref, ker, atol=1e-5), float(jnp.abs(ref - ker).max())


@pytest.mark.parametrize("wire", ["int8", "fp8"])
@pytest.mark.parametrize("block_kv", [8, 16])
def test_kernel_v2_quantized_native_matches_dequantized(wire, block_kv):
    """Native quantized page reads: the kernel's in-VMEM dequantize must
    match the reference walk over host-dequantized pools."""
    P, ps, nkv, hd, B, nq = 10, 8, 2, 128, 3, 8
    k = jax.random.normal(jax.random.PRNGKey(5), (P, ps, nkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(6), (P, ps, nkv, hd), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(8), (B, nq, hd), jnp.float32)
    kq, ks = kv_quantize(k, wire)
    vq, vs = kv_quantize(v, wire)
    table = jnp.asarray([[2, 3, 4, 0], [5, 6, 0, 0], [7, 8, 9, 2]], jnp.int32)
    lens = jnp.asarray([17, 9, 30], jnp.int32)
    ref = paged_attention_reference(
        q, kv_dequantize(kq, ks, jnp.float32),
        kv_dequantize(vq, vs, jnp.float32), table, lens,
    )
    ker = paged_attention_kernel(
        q, kq, vq, table, lens, k_scales=ks, v_scales=vs,
        block_kv=block_kv, interpret=True,
    )
    assert jnp.allclose(ref, ker, atol=1e-5), float(jnp.abs(ref - ker).max())


def test_kernel_v2_zero_length_rows_finite():
    P, ps, nkv, hd = 6, 8, 2, 128
    kp = jax.random.normal(jax.random.PRNGKey(5), (P, ps, nkv, hd), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(6), (P, ps, nkv, hd), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 4, hd), jnp.float32)
    table = jnp.asarray([[2, 3], [4, 5]], jnp.int32)
    lens = jnp.zeros((2,), jnp.int32)
    ref = paged_attention_reference(q, kp, vp, table, lens)
    ker = paged_attention_kernel(
        q, kp, vp, table, lens, block_kv=16, interpret=True,
    )
    assert np.isfinite(np.asarray(ker)).all()
    assert jnp.allclose(ref, ker, atol=1e-5)


def test_kernel_v2_rejects_bad_block_kv():
    P, ps, nkv, hd = 4, 8, 2, 128
    kp = jnp.zeros((P, ps, nkv, hd), jnp.float32)
    q = jnp.zeros((1, 4, hd), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    lens = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="block_kv"):
        paged_attention_kernel(
            q, kp, kp, table, lens, block_kv=12, interpret=True,
        )


def test_speculative_kernel_impl_token_parity(tiny_params, spec_path):
    """Speculative engine on the kernel impl (interpret on CPU): the
    verify forward gathers (the decode kernel is m=1), but the stream
    must still match the reference engine token-for-token."""
    prompts = _prompts(sizes=(20, 9))
    _, ref = _serve(tiny_params, prompts, max_batch=2)
    _, spec = _serve(
        tiny_params, prompts, max_batch=2, attn_impl="kernel",
        speculator_path=spec_path,
    )
    assert spec == ref


def test_v14_stats_fields(tiny_params, spec_path):
    eng, _ = _serve(
        tiny_params, _prompts(sizes=(20, 40)),
        speculator_path=spec_path, prefill_chunk_tokens=8,
    )
    st = eng.serving_stats()
    for k in (
        "spec_accept_rate", "spec_draft_tokens", "prefill_chunks",
        "paged_kernel_impl",
    ):
        assert k in st, k
    assert st["spec_draft_tokens"] == 3.0
    assert st["prefill_chunks"] > 0
    assert st["paged_kernel_impl"] == 0.0  # reference impl engaged
