"""Serving engine v1: paged KV cache, ragged paged-attention decode,
continuous batching (fms_fsdp_tpu/serve/, docs/serving.md).

The anchor is bit-parity: greedy paged decode on the reference attention
impl must match the dense decode path (models/generation.py) — logits
bit-for-bit on the same-shape batch, token-for-token on ragged batches,
through eviction/recompute, and from a restored checkpoint. Around it:
allocator contract (all-or-nothing, zero/scratch page discipline,
defrag), the Pallas kernel vs the reference, quantized page storage,
scheduler policy (FIFO + interleave cap, deadlines, LIFO eviction),
tuner resolution of the page size, schema-v9 serving records.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fms_fsdp_tpu.models.configs import LlamaConfig
from fms_fsdp_tpu.models.generation import decode_step, prefill
from fms_fsdp_tpu.models.llama import init_llama_params
from fms_fsdp_tpu.ops.paged_attention import (
    gather_pages,
    paged_attention_kernel,
    paged_attention_reference,
)
from fms_fsdp_tpu.ops.quant import kv_dequantize, kv_quantize
from fms_fsdp_tpu.serve import (
    ContinuousBatchingScheduler,
    PagedKVCache,
    Request,
    ServeConfig,
    ServingEngine,
)
from fms_fsdp_tpu.serve.decode import paged_decode_step
from fms_fsdp_tpu.serve.kv_cache import SCRATCH_PAGE, ZERO_PAGE


TINY = LlamaConfig(
    src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
    max_expected_seq_len=256,
)


@pytest.fixture(scope="module")
def tiny_params():
    return init_llama_params(jax.random.PRNGKey(0), TINY)


def _dense_greedy(params, cfg, prompt, max_new, max_seq, collect_logits=False):
    """Per-sequence greedy reference: jitted prefill + jitted decode_step
    (fp32) — the dense path the paged engine must reproduce."""
    import functools

    pre = jax.jit(functools.partial(
        prefill, cfg=cfg, max_seq_len=max_seq, compute_dtype=jnp.float32
    ))
    step = jax.jit(functools.partial(
        decode_step, cfg=cfg, compute_dtype=jnp.float32
    ))
    inp = jnp.asarray([prompt], jnp.int32)
    logits, _, cache = pre(params, inp)
    tok = jnp.argmax(logits[:, -1], -1)
    toks, lg_list = [int(tok[0])], []
    pos = len(prompt)
    for _ in range(max_new - 1):
        lg, _, cache = step(params, cache, tok[:, None], jnp.int32(pos))
        if collect_logits:
            lg_list.append(lg)
        tok = jnp.argmax(lg, -1)
        toks.append(int(tok[0]))
        pos += 1
    return toks, lg_list


def _engine(params, max_batch=2, max_seq=64, **kw):
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("attn_impl", "reference")
    kw.setdefault("page_size", 16)
    kw.setdefault("max_prefill_per_step", max_batch)
    scfg = ServeConfig(max_batch=max_batch, max_seq_len=max_seq, **kw)
    return ServingEngine(params, TINY, scfg)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_reuse():
    c = PagedKVCache(1, 10, 4, 2, 8)
    assert c.pages_free == 8  # pages 0/1 reserved
    assert c.ensure(7, 9)  # 3 pages
    assert c.pages_of(7) == [2, 3, 4]
    assert c.pages_in_use == 3
    assert c.ensure(8, 4)
    assert c.pages_of(8) == [5]
    assert c.free(7) == 3
    # freed pages recycle lowest-first (deterministic)
    assert c.ensure(9, 2)
    assert c.pages_of(9) == [2]
    assert c.free_count == 3 and c.alloc_count == 5


def test_allocator_all_or_nothing_oom():
    c = PagedKVCache(1, 4, 4, 2, 8)  # 2 allocatable pages
    assert c.ensure(1, 8)  # both
    before = c.pages_of(1)
    assert not c.ensure(2, 5)  # needs 2, has 0 -> nothing changes
    assert c.pages_of(2) == [] and c.pages_of(1) == before
    assert c.failed_allocs == 1
    assert not c.can_ensure(2, 5) and c.can_ensure(1, 8)


def test_page_table_zero_and_scratch_fill():
    c = PagedKVCache(1, 10, 4, 2, 8)
    c.ensure(1, 6)
    t = c.page_table([1, None], max_pages=4)
    assert t.dtype == np.int32
    assert t[0].tolist() == [2, 3, ZERO_PAGE, ZERO_PAGE]
    assert t[1].tolist() == [SCRATCH_PAGE] * 4


def test_fragmentation_tail_waste():
    c = PagedKVCache(1, 10, 4, 2, 8)
    c.ensure(1, 5)  # 2 pages for 5 tokens -> 3 wasted slots of 8
    assert c.fragmentation() == pytest.approx(3 / 8)
    c.free(1)
    assert c.fragmentation() == 0.0


def test_defrag_compacts_and_preserves_content():
    c = PagedKVCache(2, 12, 4, 2, 8, dtype=jnp.float32)
    c.ensure(1, 8)
    c.ensure(2, 8)
    c.ensure(3, 4)
    # distinct page contents so moves are detectable
    c.pools = {
        k: jnp.arange(np.prod(p.shape), dtype=jnp.float32).reshape(p.shape)
        for k, p in c.pools.items()
    }
    t_before = {
        s: gather_pages(c.pools["k"][0], jnp.asarray([c.page_table_row(s, 3)]))
        for s in (2, 3)
    }
    c.free(1)  # holes at the pool head
    moves = c.defrag()
    assert moves > 0 and c.defrag_moves == moves
    assert c.pages_of(2) == [2, 3] and c.pages_of(3) == [4]
    for s in (2, 3):
        after = gather_pages(
            c.pools["k"][0], jnp.asarray([c.page_table_row(s, 3)])
        )
        assert (np.asarray(after) == np.asarray(t_before[s])).all()
    # freed tail is reallocatable
    assert c.pages_free == 7
    assert c.ensure(4, 4) and c.pages_of(4) == [5]


@pytest.mark.parametrize("wire", ["int8", "fp8"])
def test_kv_page_quant_roundtrip(wire):
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 2, 16), jnp.float32)
    q, s = kv_quantize(x, wire)
    back = kv_dequantize(q, s, jnp.float32)
    err = float(jnp.max(jnp.abs(back - x)))
    amax = float(jnp.max(jnp.abs(x)))
    assert err <= amax * (0.01 if wire == "int8" else 0.08)


# ---------------------------------------------------------------------------
# paged attention: gather discipline + kernel
# ---------------------------------------------------------------------------


def test_gather_matches_dense_cache_bitwise(tiny_params):
    """The zero-page discipline: a prefilled sequence's gathered pages
    equal the dense prefill cache bit-for-bit — the root fact under the
    whole parity story."""
    prompt = [5, 9, 2, 7, 11, 3]
    inp = jnp.asarray([prompt], jnp.int32)
    _, _, cache = prefill(
        tiny_params, inp, TINY, max_seq_len=32, compute_dtype=jnp.float32
    )
    c = PagedKVCache(
        TINY.nlayers, 10, 8, TINY.n_kv_heads, TINY.head_dim,
        dtype=jnp.float32,
    )
    c.ensure(1, len(prompt))
    c.write_prompt(1, cache["k"][:, 0, :8], cache["v"][:, 0, :8])
    table = jnp.asarray(c.page_table([1], max_pages=4))
    for name in ("k", "v"):
        for layer in range(TINY.nlayers):
            g = gather_pages(c.pools[name][layer], table)  # (1, 32, ...)
            assert (np.asarray(g) == np.asarray(cache[name][layer])).all()


@pytest.mark.parametrize(
    "nq,nkv,hd", [(4, 4, 128), (8, 2, 128), (32, 8, 64), (8, 2, 64), (4, 1, 64)])
def test_paged_kernel_matches_reference(nq, nkv, hd):
    """Heads of 128, and pages whose rows are 8 kv heads of 64 under 32
    query heads (the lfm2 geometry as published; no head padded): as
    blocks of 64 lanes, and as the chip reads them, two heads a row of
    128 lanes (``packed_pages_attention_kernel``)."""
    P, ps, B = 10, 8, 3
    kp = jax.random.normal(jax.random.PRNGKey(2), (P, ps, nkv, hd), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(3), (P, ps, nkv, hd), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(4), (B, nq, hd), jnp.float32)
    table = jnp.asarray([[2, 3, 4, 0], [5, 6, 0, 0], [7, 8, 9, 2]], jnp.int32)
    lens = jnp.asarray([17, 9, 30], jnp.int32)  # ragged, mid-page
    ref = paged_attention_reference(q, kp, vp, table, lens)
    ker = paged_attention_kernel(q, kp, vp, table, lens, interpret=True)
    assert jnp.allclose(ref, ker, atol=1e-5), float(jnp.abs(ref - ker).max())
    if hd == 64 and nkv % 2 == 0:
        from fms_fsdp_tpu.ops.paged_attention import (
            packed_pages_attention_kernel,
            packed_row_width,
            tile_rows,
        )

        assert packed_row_width(nkv, hd) == 128
        rows = (P, ps * tile_rows(nkv, hd), 128)
        ker = packed_pages_attention_kernel(
            q, kp.reshape(rows), vp.reshape(rows), table, lens, nkv=nkv,
            block_kv=2 * ps, interpret=True)
        assert jnp.allclose(ref, ker, atol=1e-5), float(jnp.abs(ref - ker).max())


def test_paged_kernel_position_zero_rows():
    """A row at position 0 attends one token; the kernel's masked walk
    must neither NaN nor leak later pages."""
    P, ps, nkv, hd = 6, 8, 2, 128
    kp = jax.random.normal(jax.random.PRNGKey(5), (P, ps, nkv, hd), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(6), (P, ps, nkv, hd), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 4, hd), jnp.float32)
    table = jnp.asarray([[2, 3], [4, 5]], jnp.int32)
    lens = jnp.zeros((2,), jnp.int32)
    ref = paged_attention_reference(q, kp, vp, table, lens)
    ker = paged_attention_kernel(q, kp, vp, table, lens, interpret=True)
    assert np.isfinite(np.asarray(ker)).all()
    assert jnp.allclose(ref, ker, atol=1e-5)


# ---------------------------------------------------------------------------
# parity: the correctness anchor
# ---------------------------------------------------------------------------


def test_paged_decode_step_bitwise_vs_dense(tiny_params):
    """One decode step, function level: same prefilled state, dense
    decode_step vs paged_decode_step — logits must be bit-identical."""
    import functools

    prompts = [[5, 9, 2, 7], [11, 3, 8, 1]]
    inp = jnp.asarray(prompts, jnp.int32)
    max_seq = 32
    pre = jax.jit(functools.partial(
        prefill, cfg=TINY, max_seq_len=max_seq, compute_dtype=jnp.float32
    ))
    logits, _, cache = pre(tiny_params, inp)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

    dense_lg, _, _ = jax.jit(functools.partial(
        decode_step, cfg=TINY, compute_dtype=jnp.float32
    ))(tiny_params, cache, tok[:, None], jnp.int32(4))

    c = PagedKVCache(
        TINY.nlayers, 12, 8, TINY.n_kv_heads, TINY.head_dim,
        dtype=jnp.float32,
    )
    for i in (0, 1):
        c.ensure(i, 4)
        c.write_prompt(i, cache["k"][:, i, :8], cache["v"][:, i, :8])
    table = jnp.asarray(c.page_table([0, 1], max_pages=4))
    paged_lg, _, _ = jax.jit(functools.partial(
        paged_decode_step, cfg=TINY, page_size=8,
        compute_dtype=jnp.float32, attn_impl="reference",
    ))(tiny_params, c.pools, table, jnp.asarray([4, 4], jnp.int32), tok)
    assert (np.asarray(dense_lg) == np.asarray(paged_lg)).all()


def test_greedy_parity_same_length_batch_bitwise(tiny_params):
    """The acceptance anchor: engine greedy decode vs the dense path,
    same-shape batch — per-step logits bit-identical, tokens equal."""
    prompts = [[5, 9, 2, 7], [11, 3, 8, 1]]
    max_new = 6
    dense = [
        _dense_greedy(tiny_params, TINY, p, max_new, 64, collect_logits=True)
        for p in prompts
    ]
    eng = _engine(tiny_params, max_batch=2, max_seq=64)
    reqs = [eng.submit(p, max_new) for p in prompts]
    step_logits = []
    while eng.has_work():
        eng.step()
        if eng.last_logits is not None:
            step_logits.append(np.asarray(eng.last_logits))
    for i, (toks, lgs) in enumerate(dense):
        assert reqs[i].generated == toks
        # engine decode step t == dense per-seq decode step t (token 1
        # of both came from prefill logits); the batched engine rows
        # must match the B=1 dense runs bit-for-bit
        for t, lg in enumerate(lgs):
            assert (step_logits[t][i] == np.asarray(lg)[0]).all(), (i, t)


def test_greedy_parity_ragged_token_for_token(tiny_params):
    """Mixed-length prompts and mixed max_new decoded in ONE continuous
    batch — each stream token-for-token equal to its own dense run."""
    plans = [([5, 9, 2, 7, 6, 1, 12], 5), ([11, 3], 8), ([4] * 11, 6)]
    dense = [
        _dense_greedy(tiny_params, TINY, p, n, 64)[0] for p, n in plans
    ]
    eng = _engine(tiny_params, max_batch=3, max_seq=64)
    reqs = [eng.submit(p, n) for p, n in plans]
    eng.run()
    for r, toks in zip(reqs, dense):
        assert r.state == "finished"
        assert r.generated == toks
    # zero page stayed pristine through the whole run
    assert not np.asarray(eng.cache.pools["k"][:, ZERO_PAGE]).any()


def test_eviction_requeues_and_still_matches_dense(tiny_params):
    """Pool pressure: the LIFO victim is evicted mid-stream, requeued,
    re-prefilled (prompt + generated so far) — and its final stream
    still matches the dense reference token-for-token."""
    plans = [([5, 9, 2, 7], 20), ([11, 3, 8, 1], 20)]
    dense = [_dense_greedy(tiny_params, TINY, p, n, 64)[0] for p, n in plans]
    # 3 allocatable pages of 16: both prompts fit (1 page each), but the
    # two streams cannot BOTH grow a second page
    eng = _engine(
        tiny_params, max_batch=2, max_seq=64,
        num_pages=3 + 2,
    )
    reqs = [eng.submit(p, n) for p, n in plans]
    eng.run()
    assert eng.scheduler.evicted >= 1
    assert reqs[1].evictions >= 1
    for r, toks in zip(reqs, dense):
        assert r.state == "finished"
        assert r.generated == toks


def test_same_step_admissions_respect_live_pool(tiny_params):
    """Two requests that each fit alone but not together must not be
    over-admitted in one iteration: capacity is re-checked after each
    prefill's allocation, the loser waits (and completes later)."""
    plans = [([5] * 33, 4), ([9] * 33, 4)]  # 3 pages of 16 each
    dense = [_dense_greedy(tiny_params, TINY, p, n, 64)[0] for p, n in plans]
    eng = _engine(
        tiny_params, max_batch=4, max_seq=64,
        num_pages=5 + 2,  # 5 allocatable: 3 + 3 do not fit together
        max_prefill_per_step=2,
    )
    reqs = [eng.submit(p, n) for p, n in plans]
    finished = eng.step()
    # only the first admitted this round; no assert-crash, no over-admit
    assert reqs[1].state == "queued" and not finished
    eng.run()
    for r, toks in zip(reqs, dense):
        assert r.state == "finished" and r.generated == toks


def test_quantized_pages_close_and_completes(tiny_params):
    """int8/fp8 page storage: not bit-parity (by design) but the decode
    logits stay close and the engine serves to completion."""
    import functools

    prompts = [[5, 9, 2, 7], [11, 3, 8, 1]]
    inp = jnp.asarray(prompts, jnp.int32)
    _, _, cache = prefill(
        tiny_params, inp, TINY, max_seq_len=32, compute_dtype=jnp.float32
    )
    tok = jnp.asarray([7, 9], jnp.int32)
    dense_lg, _, _ = decode_step(
        tiny_params, cache, tok[:, None], 4, TINY, compute_dtype=jnp.float32
    )
    for wire in ("int8", "fp8"):
        c = PagedKVCache(
            TINY.nlayers, 12, 8, TINY.n_kv_heads, TINY.head_dim,
            dtype=jnp.float32, quant=wire,
        )
        for i in (0, 1):
            c.ensure(i, 4)
            c.write_prompt(i, cache["k"][:, i, :8], cache["v"][:, i, :8])
        table = jnp.asarray(c.page_table([0, 1], max_pages=4))
        lg, _, _ = jax.jit(functools.partial(
            paged_decode_step, cfg=TINY, page_size=8,
            compute_dtype=jnp.float32, quant=wire, attn_impl="reference",
        ))(tiny_params, c.pools, table, jnp.asarray([4, 4], jnp.int32), tok)
        assert jnp.allclose(lg, dense_lg, atol=0.15), wire
    eng = _engine(tiny_params, max_batch=2, max_seq=64, kv_quant="int8")
    reqs = [eng.submit(p, 4) for p in prompts]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    assert all(len(r.generated) == 4 for r in reqs)


def test_kernel_impl_token_parity(tiny_params):
    """The Pallas kernel path (interpret on CPU) is not bitwise but must
    agree token-for-token with the reference impl on greedy decode."""
    plans = [([5, 9, 2, 7], 5), ([11, 3, 8, 1], 5)]
    ref_eng = _engine(tiny_params, max_batch=2, max_seq=64)
    ref = [ref_eng.submit(p, n) for p, n in plans]
    ref_eng.run()
    ker_eng = _engine(
        tiny_params, max_batch=2, max_seq=64, attn_impl="kernel"
    )
    ker = [ker_eng.submit(p, n) for p, n in plans]
    ker_eng.run()
    for a, b in zip(ref, ker):
        assert a.generated == b.generated


# ---------------------------------------------------------------------------
# scheduler policy
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_scheduler_fifo_and_interleave_cap():
    clk = FakeClock()
    s = ContinuousBatchingScheduler(4, max_prefill_per_step=2, clock=clk)
    reqs = [s.submit(Request([1], 4)) for _ in range(4)]
    got = s.admit(free_slots=4, can_fit=lambda r: True)
    assert got == reqs[:2]  # interleave cap before slot count
    got = s.admit(free_slots=1, can_fit=lambda r: True)
    assert got == reqs[2:3]  # slot count before cap
    assert s.queue_depth() == 1


def test_scheduler_head_of_line_blocks():
    """A too-big head request must not be bypassed by smaller ones."""
    s = ContinuousBatchingScheduler(4, max_prefill_per_step=4)
    big = s.submit(Request([1] * 100, 4))
    s.submit(Request([1], 4))
    got = s.admit(free_slots=4, can_fit=lambda r: len(r.prompt) < 10)
    assert got == [] and s.queue_depth() == 2 and s.queue[0] is big


def test_expiry_spares_evicted_partially_served_requests():
    """Only UNSERVED requests expire: an evicted mid-stream request
    waiting for re-admission (first token delivered) has the most sunk
    work — load shedding drops the cheap end, never it."""
    clk = FakeClock()
    s = ContinuousBatchingScheduler(2, clock=clk)
    fresh = s.submit(Request([1], 4, deadline=1.0))
    served = s.submit(Request([2], 4, deadline=1.0))
    served.first_token_time = 0.5  # evicted after delivering output
    clk.t = 5.0
    dead = s.expire_queued()
    assert dead == [fresh]
    assert served.state == "queued" and s.queue[0] is served


def test_scheduler_deadline_expiry_and_lifo_eviction():
    clk = FakeClock()
    s = ContinuousBatchingScheduler(2, clock=clk)
    r1 = s.submit(Request([1], 4, deadline=1.0))
    r2 = s.submit(Request([2], 4, deadline=10.0))
    clk.t = 5.0
    dead = s.expire_queued()
    assert dead == [r1] and r1.state == "expired" and s.expired == 1
    assert s.queue_depth() == 1
    # LIFO eviction: latest admission is the victim, requeued at front
    a = s.admit(2, lambda r: True)
    assert a == [r2]
    v = s.evict_victim([r2])
    s.mark_evicted(v)
    assert s.queue[0] is r2 and r2.evictions == 1 and s.evicted == 1


def test_engine_deadline_expires_queued_request(tiny_params):
    clk = FakeClock()
    scfg = ServeConfig(
        max_batch=1, max_seq_len=64, page_size=16,
        compute_dtype="float32", attn_impl="reference",
    )
    eng = ServingEngine(tiny_params, TINY, scfg, clock=clk)
    r1 = eng.submit([5, 9, 2, 7], 8)
    r2 = eng.submit([11, 3, 8, 1], 4, deadline_s=0.5)  # will rot queued
    clk.t = 2.0  # past r2's deadline before any admission of it
    eng.run()
    assert r1.state == "finished" and len(r1.generated) == 8
    assert r2.state == "expired" and r2.generated == []
    assert eng.scheduler.expired == 1
    assert eng.registry.counter("serve.requests_expired").value == 1


# ---------------------------------------------------------------------------
# checkpoint restore, tuner resolution, obs, bench
# ---------------------------------------------------------------------------


def test_engine_from_checkpoint_matches_direct(tiny_params, tmp_path):
    path = tmp_path / "params.pkl"
    with open(path, "wb") as f:
        pickle.dump({"model_state": jax.tree.map(np.asarray, tiny_params)}, f)
    scfg = ServeConfig(
        max_batch=1, max_seq_len=64, page_size=16,
        compute_dtype="float32", attn_impl="reference",
    )
    eng = ServingEngine.from_checkpoint(str(path), TINY, scfg)
    r = eng.submit([5, 9, 2, 7], 5)
    eng.run()
    dense, _ = _dense_greedy(tiny_params, TINY, [5, 9, 2, 7], 5, 64)
    assert r.generated == dense


def test_tuner_resolves_page_size(tiny_params):
    from fms_fsdp_tpu.tune.lookup import choices, configure_kernel_tuning

    try:
        # v5e chip: the committed cost-model entry answers (nearest
        # signature), page size from the table
        configure_kernel_tuning("auto", chip="v5e")
        # the table is keyed by dtype: serve in the table's bfloat16
        eng = _engine(tiny_params, page_size=0, compute_dtype="bfloat16")
        assert eng.page_size == 64  # the committed table's pick
        assert choices()["paged"]["how"] in ("exact", "nearest")
        assert eng.serve_cfg.max_seq_len % eng.page_size == 0
        # off: static default (halved until it divides max_seq_len)
        configure_kernel_tuning("off")
        eng = _engine(tiny_params, page_size=0, max_seq=64)
        assert eng.page_size == 64 and choices()["paged"]["how"] == "off"
        # pinned beats the table
        configure_kernel_tuning("auto", chip="v5e")
        eng = _engine(tiny_params, page_size=16)
        assert eng.page_size == 16 and choices()["paged"]["how"] == "pinned"
        # a pinned page size that does not divide max_seq_len fails
        # loud instead of silently building a different allocator
        with pytest.raises(ValueError, match="does not divide"):
            _engine(tiny_params, page_size=48, max_seq=64)
    finally:
        configure_kernel_tuning(None)


def test_paged_candidates_cost_model():
    from fms_fsdp_tpu.tune import candidates as cand

    sig = cand.paged_decode_sig(8, 32, 8, 128, 4096)
    cands = cand.paged_decode_candidates(sig, "bfloat16", "v5e")
    assert cands, "no legal paged candidates for the 7B serving shape"
    for c in cands:
        assert sig["max_seq"] % c["page_size"] == 0
        assert c["block_kv"] % c["page_size"] == 0
        assert c["vmem_bytes"] <= cand.vmem_budget("v5e")
        assert cand.paged_decode_config_legal(c, sig, "bfloat16", "v5e")
    # a non-dividing page size is illegal
    assert not cand.paged_decode_config_legal(
        {"page_size": 48, "block_kv": 48}, sig, "bfloat16", "v5e"
    )
    # bigger block_kv must cost more VMEM (the multi-page pricing)
    small = cand.paged_decode_vmem_bytes(sig, "bfloat16", 64, 64)
    big = cand.paged_decode_vmem_bytes(sig, "bfloat16", 64, 256)
    assert big > small


def test_serving_stats_land_in_schema_v9_record(tiny_params):
    from fms_fsdp_tpu.obs.observer import Observer
    from fms_fsdp_tpu.obs.schema import validate_record

    obs = Observer()
    eng = ServingEngine(
        tiny_params,
        TINY,
        ServeConfig(
            max_batch=2, max_seq_len=64, page_size=16,
            compute_dtype="float32", attn_impl="reference",
        ),
        registry=obs.registry,
    )
    reqs = [eng.submit([5, 9, 2, 7], 4), eng.submit([11, 3], 3)]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    stats = eng.serving_stats()
    for k in (
        "tokens_per_s", "ttft_s", "queue_depth", "kv_pages_in_use",
        "requests_completed", "p99_latency_s",
    ):
        assert k in stats, k
    assert stats["requests_completed"] == 2.0
    assert stats["tokens_per_s"] > 0
    rec = obs.report(
        step=1,
        steps_in_window=1,
        loss=0.0,
        tokens_per_sec_per_chip=stats["tokens_per_s"],
        serving=stats,
    )
    assert validate_record(rec) == []
    assert rec["serving"]["requests_completed"] == 2.0
    # the serve.* registry metrics ride extra as usual
    assert rec["extra"]["serve.requests_completed"] == 2.0
    assert "serve.ttft_s_mean" in rec["extra"]
