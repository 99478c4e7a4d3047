"""The decode loop keeps one step in flight (serve/engine.py, PR 30).

``ServingEngine.step()`` dispatches decode step n+1 from step n's token
output, which stays on the device, before it reads step n's tokens. What
is pinned here, at test size on the CPU, for llama, mixtral, a pure
Mamba-2 stack and the Mamba-1 hybrid:

(a) the tokens are those of a loop that reads every step's tokens
    before it dispatches the next (``SyncLoop``: the loop the engine ran
    before, in miniature, over the same adapter programs), greedy one
    stream at a time and sampled over the whole set with a fixed key;
(b) a stream that ends by ``eos_token`` is seen one step late, and the
    token of the step it rode too many is dropped and counted;
(c) whatever takes a stream away or reads its tokens outside the commit
    (expiry, eviction, ``drain``, ``pack_stream``) first collects the
    step in flight, and a stream that ends at such a collect still leaves
    by ``step()`` (the replica loop's ``drain`` message sends its ``done``);
(d) ``serve.decode_steps_overlapped`` counts every decode step but the
    first after an idle engine;
(e) the ``decode.dispatch`` span says whether a step was in flight, and a
    speculative engine never has one.
"""

import glob
import os

import jax
import numpy as np
import pytest

from fms_fsdp_tpu.models import mamba as M
from fms_fsdp_tpu.models.configs import LlamaConfig, MambaConfig, MixtralConfig
from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.obs.spans import PREFIX
from fms_fsdp_tpu.serve.disagg import unpack_handoff
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import init_params_for, load_model_config

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
    "hybrid": load_model_config({
        "family": "jamba", "model_type": "jamba",
        "attn_layer_offset": 3, "attn_layer_period": 4,
        "hidden_size": 64, "intermediate_size": 128,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8,
        "mamba_expand": 2, "num_attention_heads": 4, "num_experts": 1,
        "num_hidden_layers": 6, "num_key_value_heads": 1,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "vocab_size": 512,
    }),
}
FAMILIES = tuple(CONFIGS)
ENGINE = dict(
    max_batch=3, max_seq_len=64, page_size=8, prefill_bucket=8,
    attn_impl="reference", compute_dtype="float32",
    max_prefill_per_step=2,
)
# (prompt length, max_new_tokens): seven requests over three slots, none
# finishes inside its own prefill
REQUESTS = ((5, 6), (9, 3), (16, 9), (3, 2), (12, 7), (7, 12), (10, 4))
SEED = 11


@pytest.fixture(scope="module")
def params():
    return {
        f: init_params_for(cfg)(jax.random.PRNGKey(i))
        for i, (f, cfg) in enumerate(CONFIGS.items())}


@pytest.fixture(scope="module", autouse=True)
def small_chunk():
    # the hybrid's looped prefill walks chunks of 512: 4 at test size
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(M, "PREFILL_CHUNK", 4)
        yield


def engine(params, family, clock=None, **kw):
    extra = {} if clock is None else {"clock": clock}
    return ServingEngine(
        params[family], CONFIGS[family], ServeConfig(**{**ENGINE, **kw}),
        seed=SEED, **extra)


def prompts(family):
    vocab = getattr(CONFIGS[family], "src_vocab_size", None) or CONFIGS[
        family].vocab_size
    rng = np.random.default_rng(5)
    return [
        (rng.integers(1, vocab, size=p).tolist(), new) for p, new in REQUESTS]


def serve(eng, plans):
    reqs = [eng.submit(p, new) for p, new in plans]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    assert not eng.has_work() and eng._inflight is None
    return [list(r.generated) for r in reqs]


class SyncLoop:
    """The synchronous loop: admit (prefill, split the key, sample), then
    split the key, one decode step over the live slots, read its tokens,
    commit them, and only then go on. Over an engine's adapter, whose
    ``step()`` is never called."""

    def __init__(self, eng):
        self.ad, self.scfg = eng.adapter, eng.serve_cfg
        b = self.scfg.max_batch
        self.slots = [None] * b
        self.lens = np.zeros((b,), np.int32)
        self.toks = np.zeros((b,), np.int32)
        self.key = jax.random.PRNGKey(SEED)

    def _split(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def run(self, plans):
        c = self.scfg
        queue = [(rid, p, new, []) for rid, (p, new) in enumerate(plans)]
        out = {rid: gen for rid, _, _, gen in queue}
        while queue or any(s is not None for s in self.slots):
            for _ in range(c.max_prefill_per_step):
                if not queue or None not in self.slots:
                    break
                rid, p, new, gen = queue.pop(0)
                slot = self.slots.index(None)
                row = self.ad.prefill(rid, slot, p)
                gen.append(int(sample_token(
                    row[None], self._split(), c.temperature, c.top_k,
                    c.do_sample)[0]))
                self.slots[slot] = (rid, new, gen)
                self.toks[slot], self.lens[slot] = gen[-1], len(p)
            live = [i for i, s in enumerate(self.slots) if s is not None]
            for i in live:
                assert self.ad.grow(self.slots[i][0], int(self.lens[i]) + 1)
            rids = [s[0] if s is not None else None for s in self.slots]
            # every token from the host: nothing stays on the device
            dev, _ = self.ad.decode_dispatch(
                rids, self.lens.copy(), self.toks.copy(), self._split(),
                np.ones_like(self.toks, bool))
            toks = self.ad.decode_collect(dev)
            for i in live:
                rid, new, gen = self.slots[i]
                self.lens[i] += 1
                gen.append(int(toks[i]))
                self.toks[i] = toks[i]
                if len(gen) >= new:
                    self.ad.release(rid, i)
                    self.slots[i] = None
                    self.lens[i] = self.toks[i] = 0
        return [out[rid] for rid in range(len(plans))]


# -- (a) the tokens of the synchronous loop -----------------------------------


@pytest.mark.parametrize("family", FAMILIES)
def test_greedy_tokens_equal_a_one_stream_synchronous_loop(params, family):
    plans = prompts(family)
    served = serve(engine(params, family), plans)
    loop = SyncLoop(engine(params, family))
    alone = [loop.run([plan])[0] for plan in plans]
    assert served == alone
    assert [len(t) for t in served] == [new for _, new in REQUESTS]


@pytest.mark.parametrize("family", FAMILIES)
def test_sampled_tokens_equal_the_synchronous_loop(params, family):
    """Same key, same order of splits (each admission's, then the
    step's), admissions at the same iterations: the sampled tokens are
    the synchronous loop's to the token."""
    plans = prompts(family)
    kw = dict(do_sample=True, temperature=1.3, top_k=20)
    served = serve(engine(params, family, **kw), plans)
    assert served == SyncLoop(engine(params, family, **kw)).run(plans)
    greedy = serve(engine(params, family), plans)
    assert served != greedy  # the key decided something


# -- (b) an end-of-sequence token is seen one step late -----------------------


def pick_eos(tokens):
    """A token that ends some streams early and not all: -> (eos, per
    stream the index of its first occurrence or None)."""
    best = None
    for eos in sorted({t for toks in tokens for t in toks[1:-1]}):
        at = [toks.index(eos) if eos in toks else None for toks in tokens]
        early = sum(
            g is not None and 0 < g < len(toks) - 1
            for g, toks in zip(at, tokens))
        if early and any(g is None for g in at):
            if best is None or early > best[0]:
                best = (early, eos, at)
    assert best is not None, tokens
    return best[1], best[2]


@pytest.mark.parametrize("family", FAMILIES)
def test_eos_ends_a_stream_one_step_late_and_drops_that_token(params, family):
    plans = prompts(family)
    free = serve(engine(params, family), plans)
    eos, at = pick_eos(free)
    eng = engine(params, family, eos_token=eos)
    served = serve(eng, plans)
    early = 0
    for toks, want, g in zip(served, free, at):
        if g is None:
            assert toks == want
            continue
        assert toks == want[: g + 1] and toks[-1] == eos
        assert eos not in toks[:-1]
        # found at a commit, with a step already dispatched over it
        early += 0 < g < len(want) - 1
    assert early >= 1
    reg = eng.registry
    assert reg.counter("serve.decode_tokens_discarded").value == early
    assert reg.counter("serve.decode_tokens").value == sum(
        len(t) - 1 for t in served)
    assert reg.counter("serve.decode_live_slots").value == early + sum(
        len(t) - 1 for t in served)
    assert eng.adapter.pages_in_use == 0


# -- (c) the doors collect the step in flight first ---------------------------


class FakeClock:
    t = 0.0

    def __call__(self):
        return self.t


def running(eng, req, steps=3):
    """``req`` mid-stream with a decode step in flight over it."""
    for _ in range(steps):
        eng.step()
    assert req.state == "running" and eng._inflight is not None
    assert any(r is req for _, r in eng._inflight.streams)
    return len(req.generated)


def consistent(eng, req):
    """The host's length of the stream's slot counts every token but the
    last: nothing dispatched is uncommitted."""
    slot = eng._slots.index(req)
    return int(eng._lens[slot]) == len(req.prompt) + len(req.generated) - 1


def door_expire(params, want):
    clk = FakeClock()
    eng = engine(params, "llama", clock=clk)
    req = eng.submit(want["prompt"], 20, deadline_s=5.0)
    other = eng.submit([9, 8, 7], 4)
    seen = running(eng, req)
    release, at_release = eng._release_slot, []

    def checked(r, slot):
        at_release.append((r, eng._inflight, consistent(eng, r)))
        release(r, slot)

    eng._release_slot = checked
    clk.t = 10.0
    eng.step()
    assert req.state == "expired"
    assert at_release[0] == (req, None, True)
    assert len(req.generated) == seen + 1  # the step in flight was kept
    assert req.generated == want["tokens"][: seen + 1]
    assert eng.scheduler.expired_inflight == 1
    return eng, [other]


def door_evict(params, want):
    # 5 allocatable pages of 8: two streams of 3 pages each cannot both
    # reach 17 positions, the later one is evicted and resumes
    eng = engine(params, "llama", num_pages=5 + 2)
    seen = []
    evict = eng._evict

    def checked(victim):
        seen.append((eng._inflight, consistent(eng, victim)))
        evict(victim)

    eng._evict = checked
    reqs = [eng.submit(want["prompt"], 20), eng.submit(want["prompt"], 20)]
    eng.run()
    assert eng.scheduler.evicted >= 1
    assert seen and all(fl is None and ok for fl, ok in seen)
    assert all(r.generated == want["tokens"] for r in reqs)
    return eng, reqs


def door_drain(params, want):
    eng = engine(params, "llama")
    req = eng.submit(want["prompt"], 20)
    short = eng.submit([4, 5, 6], 3)
    for _ in range(2):
        eng.step()
    # short's last token is in flight: it gave its slot back at dispatch
    assert short.state == "running" and short not in eng._slots
    seen = len(req.generated)
    eng.drain()
    assert eng._inflight is None and len(req.generated) == seen + 1
    assert consistent(eng, req) and not eng.drained
    # short ended at the collect: the next step() returns it
    assert len(short.generated) == 3 and eng.live_requests() == [req]
    assert short in eng.step()
    eng.run()
    assert req.generated == want["tokens"] and eng.drained
    return eng, [req]


def door_pack_stream(params, want):
    eng = engine(params, "llama")
    req = eng.submit(want["prompt"], 20)
    seen = running(eng, req)
    header, _ = unpack_handoff(eng.pack_stream(req))
    assert eng._inflight is None
    assert header["generated"] == want["tokens"][: seen + 1]
    assert header["seq_len"] == len(req.prompt) + seen
    dst = engine(params, "llama")
    moved = dst.submit_handoff(eng.pack_stream(req))
    dst.run()
    assert moved.generated == want["tokens"]
    eng.run()
    return eng, [req]


def door_replica_drain(params, want):
    """The router's ``drain`` message while the only stream's last token
    is in flight: ``engine.drain()`` collects it with every slot free and
    the queue empty, and the replica loop still has to step once more to
    send ``done`` before it returns."""
    from fms_fsdp_tpu.serve import replica

    eng = engine(params, "llama")
    sent, inbox = [], []
    step = eng.step

    def reader(q):
        inbox.append(q)
        q.put({"type": "submit", "rid": "r0", "prompt": want["prompt"],
               "max_new_tokens": 4})

    def step_then_drain():
        out = step()
        # past the loop's warm-up request, the last token dispatched and
        # its slot given back
        if (eng.scheduler.completed == 1 and eng._inflight is not None
                and not any(eng._slots) and len(inbox) == 1):
            inbox.append(None)
            inbox[0].put({"type": "drain"})
        return out

    eng.step = step_then_drain
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(replica, "_stdin_reader", reader)
        mp.setattr(replica, "_emit", sent.append)
        replica.serve_loop(eng, 0, idle_sleep_s=0.001)
    assert len(inbox) == 2 and eng.drained
    (done,) = [m for m in sent if m["type"] == "done"]
    assert done["rid"] == "r0" and done["tokens"] == want["tokens"][:4]
    assert not [m for m in sent if m["type"] == "returned"]
    return eng, []


DOORS = {
    "expire": door_expire, "evict": door_evict, "drain": door_drain,
    "pack_stream": door_pack_stream, "replica_drain": door_replica_drain,
}


@pytest.fixture(scope="module")
def long_stream(params):
    prompt = prompts("llama")[0][0]
    (tokens,) = serve(engine(params, "llama"), [(prompt, 20)])
    return {"prompt": prompt, "tokens": tokens}


@pytest.mark.parametrize("door", sorted(DOORS))
def test_door_sees_the_streams_last_token(params, long_stream, door):
    eng, reqs = DOORS[door](params, long_stream)
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    assert not eng.has_work() and eng._inflight is None
    assert eng.adapter.pages_in_use == 0
    assert eng.registry.counter("serve.decode_tokens_discarded").value == 0


def test_a_step_in_flight_is_work(params):
    """The last token of the last stream: every slot is free, the queue
    empty, and the loop has to step once more to see it."""
    eng = engine(params, "mamba")
    req = eng.submit([3, 4, 5], 2)
    assert eng.step() == []
    assert req not in eng._slots and req.state == "running"
    assert eng.has_work() and len(req.generated) == 1
    assert eng.step() == [req]
    assert not eng.has_work() and len(req.generated) == 2
    assert eng.last_logits is not None


# -- (d) the counter -----------------------------------------------------------


@pytest.mark.parametrize("family", FAMILIES)
def test_every_step_but_the_first_after_idle_overlaps(params, family):
    eng = engine(params, family)
    plans = prompts(family)
    count = eng.registry.counter
    for wave in (1, 2):
        serve(eng, plans[:4] if wave == 1 else plans[4:])
        # each wave: its first decode step follows an idle engine, its
        # last iteration dispatches nothing and commits the step in flight
        dispatched = count("serve.steps").value - wave
        assert count("serve.decode_steps_overlapped").value == (
            dispatched - wave)
    assert count("serve.decode_tokens_discarded").value == 0


# -- (e) the span's field, and the speculative engine -------------------------


def dispatch_fields(run, trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        run()
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    events = sorted(
        (e.start_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name == PREFIX + "decode.dispatch")
    return [stats["in_flight"] for _, stats in events]


def test_dispatch_span_says_whether_a_step_was_in_flight(params, tmp_path):
    eng = engine(params, "mixtral")
    plans = prompts("mixtral")[:3]
    flags = dispatch_fields(lambda: serve(eng, plans), tmp_path)
    assert flags[0] == 0 and set(flags[1:]) == {1}
    assert sum(flags) == eng.registry.counter(
        "serve.decode_steps_overlapped").value


def test_speculative_engine_never_has_a_step_in_flight(params, tmp_path):
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
    plans = [(p, new) for p, new in prompts("llama") if len(p) + new < 50]
    want = serve(engine(params, "llama"), plans)
    eng = engine(params, "llama", speculator_path=path)
    assert eng.adapter.speculative
    reqs = [eng.submit(p, new) for p, new in plans]

    def run():
        while eng.has_work():
            before = [len(r.generated) for r in reqs]
            eng.step()
            assert eng._inflight is None
            # a step's tokens are visible when it returns
            assert [len(r.generated) for r in reqs] != before

    flags = dispatch_fields(run, tmp_path / "trace")
    assert flags and set(flags) == {0}
    assert [r.generated for r in reqs] == want
    assert eng.registry.counter("serve.decode_steps_overlapped").value == 0
