"""An admission leaves the host nothing to wait for (serve/engine.py, PR 42).

``ServingEngine.step()`` dispatches an admission's prefill program and
its sampler and reads nothing: the first token stays on the device, goes
into the next decode step's token row there, and is read behind that
step's dispatch, at the end of the same ``step()`` (``_land``). What is
pinned here, at test size on the CPU, for llama, mixtral, a pure Mamba-2
stack and the lfm2 family (held experts, whose prefill program counts):

(1) in a step that admits, the decode step is dispatched with the first
    token still unread, and the host reads nothing of the device between
    the prefill's dispatch and the decode step's; ``prefill.sample`` ends
    behind ``decode.dispatch``; ``serve.admissions_overlapped`` counts it;
(2) greedy and sampled tokens are those of an engine that lands every
    admission where it is sampled (the order before this change);
(3) a pool so small that ``_grow`` evicts the stream just admitted: its
    resume prompt holds its first token;
(4) a stream that ends at its first token (``eos_token``,
    ``max_new_tokens == 1``) has ridden one step, whose token is dropped
    and counted;
(5) a prefill replica and a speculative engine have the token before
    they use it;
(6) ``serve.ttft_s`` once a request, and the admission's counts and host
    time in the record of the step that admitted it.
"""

import contextlib
import glob
import os

import jax
import numpy as np
import pytest

from fms_fsdp_tpu.models import lfm2 as L
from fms_fsdp_tpu.models.configs import (
    LlamaConfig,
    MambaConfig,
    MixtralConfig,
    lfm2_moe_config,
)
from fms_fsdp_tpu.obs.spans import PREFIX
from fms_fsdp_tpu.serve.disagg import unpack_handoff
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import init_params_for

CONFIGS = {
    "llama": LlamaConfig(
        src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
        max_expected_seq_len=64,
    ),
    "mixtral": MixtralConfig(
        src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
        hidden_dim=128, num_experts=4, top_k=2, max_expected_seq_len=64,
    ),
    "mamba": MambaConfig(
        d_model=64, n_layer=2, vocab_size=128, d_state=16, headdim=16,
        chunk_size=8, attn_layer_idx=(), d_intermediate=128,
    ),
    # tests/test_lfm2.py's: two dense convolution layers, then attention,
    # convolution, attention; 16 experts, 4 a token
    "lfm2": lfm2_moe_config({
        "model_type": "lfm2_moe",
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 5,
        "layer_types": ["conv", "conv", "full_attention", "conv",
                        "full_attention"],
        "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 128, "max_position_embeddings": 512, "norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    }),
}
FAMILIES = tuple(CONFIGS)
ENGINE = dict(
    max_batch=3, max_seq_len=64, page_size=8, prefill_bucket=8,
    attn_impl="reference", compute_dtype="float32",
)
# (prompt length, max_new_tokens): a mixed queue over three slots; none
# ends at its first token
REQUESTS = ((5, 6), (9, 3), (16, 9), (3, 2), (12, 7), (7, 12), (10, 4))
SEED = 11


@pytest.fixture(scope="module")
def params():
    return {
        f: init_params_for(cfg)(jax.random.PRNGKey(i))
        for i, (f, cfg) in enumerate(CONFIGS.items())}


@pytest.fixture(scope="module", autouse=True)
def small_chunk():
    # lfm2's looped prefill walks chunks of 512: 8 at test size
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(L, "PREFILL_CHUNK", 8)
        yield


def engine(params, family, **kw):
    return ServingEngine(
        params[family], CONFIGS[family], ServeConfig(**{**ENGINE, **kw}),
        seed=SEED)


def prompts(requests=REQUESTS):
    rng = np.random.default_rng(5)
    return [(rng.integers(1, 128, size=p).tolist(), new)
            for p, new in requests]


def serve(eng, plans):
    reqs = [eng.submit(p, new) for p, new in plans]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    assert not eng.has_work() and not eng._pending
    return reqs


def lands_where_it_samples(eng):
    """``eng`` with every admission landed where its token is sampled,
    through the engine's own door: the order of the loop before the
    first token stayed on the device."""
    sample = eng._sample_first

    def sample_and_land(*args):
        sample(*args)
        eng._land()

    eng._sample_first = sample_and_land
    return eng


def count(eng, name):
    return eng.registry.counter(name).value


# -- (1) nothing is read between the two dispatches ---------------------------


@contextlib.contextmanager
def host_reads(log):
    """Every ``int()``, ``float()``, ``bool()``, ``.item()`` and
    ``.tolist()`` of a device array is noted in ``log`` while this is
    open (they all go through ``ArrayImpl._value``; a ``np.asarray``
    does not, and the engine's are under the two spans checked below)."""
    from jax._src.array import ArrayImpl

    value = ArrayImpl.__dict__.get("_value")
    if not isinstance(value, property):
        pytest.skip("this jax reads an array another way")

    def noted(self):
        log.append("read")
        return value.fget(self)

    ArrayImpl._value = property(noted)
    try:
        yield
    finally:
        ArrayImpl._value = value


@pytest.mark.parametrize("family", FAMILIES)
def test_an_admission_reads_nothing_before_the_decode_dispatch(
        params, family):
    eng = engine(params, family)
    log, seen = [], []
    prefill, dispatch = eng.adapter.prefill, eng.adapter.decode_dispatch

    def noted_prefill(*args):
        log.append("prefill")
        return prefill(*args)

    def noted_dispatch(*args, first=(), **kw):
        log.append("dispatch")
        for adm in eng._pending:
            # the host has not got the token: nothing of it is used
            seen.append((adm.req.rid, list(adm.req.generated),
                         adm.req.first_token_time, adm.rode))
        assert [slot for slot, _ in first] == [a.slot for a in eng._pending]
        assert all(isinstance(tok, jax.Array) for _, tok in first)
        return dispatch(*args, first=first, **kw)

    eng.adapter.prefill = noted_prefill
    eng.adapter.decode_dispatch = noted_dispatch
    with host_reads(log):
        reqs = serve(eng, prompts())
    # every admission rode the decode step dispatched in its step()
    assert seen == [(r.rid, [], None, True) for r in sorted(
        reqs, key=lambda r: r.admit_time)]
    assert count(eng, "serve.admissions_overlapped") == len(reqs)
    # between a prefill's dispatch and the next decode step's, no read
    at = [i for i, what in enumerate(log) if what == "prefill"]
    assert len(at) == len(reqs)
    for i in at:
        assert log[i + 1] == "dispatch", log[i: i + 4]
    assert "read" in log  # the landing's and the commit's, behind them


def spans_of(run, trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        run()
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name[len(PREFIX):],
         dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX))


def test_the_first_token_is_read_behind_the_decode_dispatch(params, tmp_path):
    eng = engine(params, "lfm2")
    plans = prompts()[:4]
    spans = spans_of(lambda: serve(eng, plans), tmp_path)
    steps = [s for s in spans if s[2] == "step"]
    admitting = 0
    for lo, hi, _, _ in steps:
        inside = [s for s in spans if lo <= s[0] and s[1] <= hi]
        names = [s[2] for s in inside]
        if "prefill" not in names:
            assert "prefill.land" not in names
            continue
        admitting += 1
        (pd,) = [s for s in inside if s[2] == "prefill.dispatch"]
        (dd,) = [s for s in inside if s[2] == "decode.dispatch"]
        (sample,) = [s for s in inside if s[2] == "prefill.sample"]
        (done,) = [s for s in inside if s[2] == "prefill.done"]
        (land,) = [s for s in inside if s[2] == "prefill.land"]
        assert pd[1] <= dd[0] and dd[1] <= sample[0] < sample[1] <= done[0]
        assert land[0] <= sample[0] and done[1] <= land[1]
        assert sample[3]["overlapped"] == 1
        assert sample[3]["rid"] == done[3]["rid"] == land[3]["rid"]
        # the spans that hold the engine's reads: none between the two
        # dispatches
        between = [s[2] for s in inside if pd[1] <= s[0] and s[1] <= dd[0]]
        assert not {"prefill.sample", "prefill.done", "decode.wait",
                    "decode.commit"} & set(between), between
        # the held-experts family's counts came with the marker
        assert {"moe_pairs_routed", "moe_pairs_held", "moe_slabs"} <= set(
            done[3])
    assert admitting == len(plans)


# -- (2) the tokens are those of a loop that lands at once --------------------


SAMPLED = dict(do_sample=True, temperature=1.3, top_k=20)


@pytest.mark.parametrize("per_step", (1, 2))
@pytest.mark.parametrize("family", FAMILIES)
def test_tokens_equal_those_of_admissions_landed_at_once(
        params, family, per_step):
    plans = prompts()
    for kw in ({}, SAMPLED):
        kw = dict(kw, max_prefill_per_step=per_step)
        eng = engine(params, family, **kw)
        got = [r.generated for r in serve(eng, plans)]
        at_once = lands_where_it_samples(engine(params, family, **kw))
        want = [r.generated for r in serve(at_once, plans)]
        assert got == want
        assert [len(t) for t in got] == [new for _, new in REQUESTS]
        assert count(at_once, "serve.admissions_overlapped") == 0
        # a step's last admission rides its decode step; those before it
        # land first, as the slots and the pages of the next need
        assert 0 < count(eng, "serve.admissions_overlapped") <= len(plans)
        if per_step == 1:
            assert count(eng, "serve.admissions_overlapped") == len(plans)
        for e in (eng, at_once):
            assert count(e, "serve.decode_tokens_discarded") == 0
            assert e.adapter.pages_in_use == 0
    sampled, greedy = got, [
        r.generated for r in serve(engine(params, family), plans)]
    assert sampled != greedy  # the key decided something


# -- (3) the stream just admitted is the victim -------------------------------


def test_an_evicted_admission_resumes_from_its_first_token(params):
    """4 allocatable pages of 8. The first stream holds two when the
    second (a prompt of a whole page) is admitted with two free; in that
    step's ``_grow`` the first takes its third, the second's next
    position has none, and the LIFO victim is the second itself, its
    first token still on the device."""
    (pa, _), (pb, _) = prompts(((5, 20), (8, 6)))
    roomy = engine(params, "llama")
    want_a, want_b = (r.generated for r in serve(
        roomy, [(pa, 20), (pb, 6)]))
    eng = engine(params, "llama", num_pages=4 + 2)
    evict, seen = eng._evict, []

    def checked(victim):
        # through the door first: nothing in flight, nothing pending
        seen.append((victim, eng._inflight, list(eng._pending),
                     list(victim.generated)))
        evict(victim)

    eng._evict = checked
    a = eng.submit(pa, 20)
    for _ in range(11):
        eng.step()
    assert len(a.generated) == 11 and eng.adapter.pages_in_use == 2
    b = eng.submit(pb, 6)
    eng.step()
    assert seen == [(b, None, [], want_b[:1])]
    assert b.state == "queued" and b.evictions == 1
    assert b.resume_prompt() == pb + want_b[:1]
    assert b.first_token_time is not None
    # landed before the decode step was dispatched: not behind one
    assert count(eng, "serve.admissions_overlapped") == 1  # a's
    eng.run()
    assert a.generated == want_a and b.generated == want_b
    assert eng.adapter.pages_in_use == 0
    assert count(eng, "serve.requests_evicted") == 1
    # one time to the first token a request, the resumed one's too
    assert len(eng.registry.hist("serve.ttft_s").samples) == 2
    assert count(eng, "serve.decode_tokens_discarded") == 0


# -- (4) a stream that ends at its first token --------------------------------


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("how", ("max_new_tokens", "eos_token"))
def test_a_stream_that_ends_at_its_first_token_rode_one_step(
        params, family, how):
    (prompt, _), (other, _) = prompts(((9, 5), (6, 8)))
    first, rest = (r.generated for r in serve(
        engine(params, family), [(prompt, 5), (other, 8)]))
    if how == "eos_token" and first[0] in rest:
        pytest.skip("the other stream makes this token too")
    kw = {"eos_token": first[0]} if how == "eos_token" else {}
    eng = engine(params, family, **kw)
    long = eng.submit(other, 8)
    eng.step()
    short = eng.submit(prompt, 1 if how == "max_new_tokens" else 5)
    out = eng.step()
    # one token, seen in the step that admitted it; the slot is free
    assert out == [short] and short.state == "finished"
    assert short.generated == first[:1] and short not in eng._slots
    assert count(eng, "serve.admissions_overlapped") == 2
    assert count(eng, "serve.decode_tokens_discarded") == 0
    assert eng._inflight is not None
    assert any(r is short for _, r in eng._inflight.streams)
    eng.run()
    assert long.generated == rest and short.generated == first[:1]
    assert count(eng, "serve.decode_tokens_discarded") == 1
    assert count(eng, "serve.decode_tokens") == len(rest) - 1
    assert count(eng, "serve.requests_completed") == 2
    assert eng.adapter.pages_in_use == 0 and not any(eng._slots)
    assert len(eng.registry.hist("serve.ttft_s").samples) == 2


# -- (5) whoever needs the token at once has it -------------------------------


def test_a_prefill_replica_packs_the_first_token(params):
    plans = prompts()[:4]
    want = [r.generated for r in serve(engine(params, "llama"), plans)]
    pe = engine(params, "llama", role="prefill")
    packed = serve(pe, plans)
    assert count(pe, "serve.admissions_overlapped") == 0
    assert count(pe, "serve.handoffs_exported") == len(plans)
    de = engine(params, "llama", role="decode")
    resumed = []
    for req, toks in zip(packed, want):
        header, _ = unpack_handoff(req.handoff_out)
        assert header["generated"] == req.generated == toks[:1]
        assert header["seq_len"] == len(req.prompt)
        resumed.append(de.submit_handoff(req.handoff_out))
    de.run()
    assert [r.generated for r in resumed] == want
    # a decode replica prefills only what it evicted: nothing here
    assert count(de, "serve.admissions_overlapped") == 0


def test_a_speculative_engine_has_the_token_before_it_drafts(
        params, tmp_path):
    from fms_fsdp_tpu.models.speculator import (
        SpeculatorConfig,
        init_speculator_params,
        save_speculator,
    )

    cfg = CONFIGS["llama"]
    scfg = SpeculatorConfig(
        emb_dim=cfg.emb_dim, inner_dim=32, vocab_size=cfg.src_vocab_size,
        n_predict=3)
    path = str(tmp_path / "speculator.pkl")
    save_speculator(
        path, init_speculator_params(jax.random.PRNGKey(7), scfg), scfg)
    plans = [(p, new) for p, new in prompts() if len(p) + new < 50]
    want = [r.generated for r in serve(engine(params, "llama"), plans)]
    eng = engine(params, "llama", speculator_path=path)
    spec, seen = eng.adapter.decode_spec, []

    def checked(slot_rids, lens, tokens):
        for slot, req in enumerate(eng._slots):
            if req is not None:
                seen.append(int(tokens[slot]) == req.generated[-1])
        assert not eng._pending
        return spec(slot_rids, lens, tokens)

    eng.adapter.decode_spec = checked
    assert [r.generated for r in serve(eng, plans)] == want
    assert seen and all(seen)
    assert count(eng, "serve.admissions_overlapped") == 0


# -- (6) the record of the step that admitted ---------------------------------


@pytest.mark.parametrize("family", FAMILIES)
def test_the_admissions_counts_fall_in_the_admitting_step(params, family):
    eng = engine(params, family)
    plans = prompts()
    reqs = [eng.submit(p, new) for p, new in plans]
    seen = {}
    while eng.has_work():
        before = {r.rid for r in reqs if r.generated}
        eng.step()
        for r in reqs:
            if r.generated and r.rid not in before:
                # the first token shows at the return of the step that
                # admitted the request (benchmark/drivers/serve.py)
                seen[r.rid] = eng.iterations
                assert len(r.generated) == 1
    log = {r["step"]: r for r in eng.step_log}
    assert sorted(seen.values()) == sorted(
        s for s, r in log.items() if r["admitted"])
    bucket = ENGINE["prefill_bucket"]
    for r in reqs:
        rec = log[seen[r.rid]]
        assert rec["admitted"] == 1
        assert rec["padded_tokens"] == -(-len(r.prompt) // bucket) * bucket
        assert 0 < rec["computed_tokens"] <= rec["padded_tokens"]
        assert 0 < rec["prefill_sample_us"] < rec["prefill_us"]
        assert rec["prefill_dispatch_us"] < rec["prefill_us"] <= rec["wall_us"]
    for rec in log.values():
        if not rec["admitted"]:
            assert rec["computed_tokens"] == rec["prefill_us"] == 0
    assert len(eng.registry.hist("serve.ttft_s").samples) == len(reqs)
    assert count(eng, "serve.prefill_tokens") == sum(len(p) for p, _ in plans)
    assert count(eng, "serve.prefill_computed_tokens") == sum(
        r["computed_tokens"] for r in log.values())
