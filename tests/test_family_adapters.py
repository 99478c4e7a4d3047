"""The serving adapters' skeleton (serve/families/__init__.py::
FamilyAdapter) under each family that fills it in: llama, mixtral, a
pure Mamba-2 stack and the Mamba-1 hybrid, at test size on the CPU.

What is pinned is "no behaviour changed" against the tree before the
skeleton (commit c0d9850): (a) the page-capacity rule's answers and its
rejection text, (b) what three requests of fixed lengths count into the
registry, (c) the lowered text of each family's decode program and of
one prefill program, by digest. The numbers and digests were read off
that tree with this file's own ``__main__`` (``PYTHONPATH=<checkout>
python tests/test_family_adapters.py`` prints both tables from the tree
it is given; the digests hold for one jax version and are skipped on
another).
"""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from fms_fsdp_tpu.models import mamba as M
from fms_fsdp_tpu.models.configs import LlamaConfig, MambaConfig, MixtralConfig
from fms_fsdp_tpu.obs.registry import MetricRegistry
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families import (
    init_params_for,
    load_model_config,
    resolve_adapter,
)

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
    # a published Jamba config.json's keys at a small size: Mamba-1
    # mixers, layer 3 of 6 attention on one KV head, tied head
    "hybrid": load_model_config({
        "family": "jamba", "model_type": "jamba",
        "attn_layer_offset": 3, "attn_layer_period": 4,
        "hidden_size": 64, "intermediate_size": 128,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8,
        "mamba_expand": 2, "num_attention_heads": 4, "num_experts": 1,
        "num_hidden_layers": 6, "num_key_value_heads": 1,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
        "vocab_size": 512,
    }),
}
FAMILIES = tuple(CONFIGS)
PAGED = ("llama", "mixtral", "hybrid")  # the families that keep K/V pages
ENGINE = dict(
    max_batch=2, max_seq_len=64, page_size=8, prefill_bucket=8,
    attn_impl="reference", compute_dtype="float32",
)
# the hybrid's looped prefill stops at the prompt's last chunk: a chunk
# of 4 (models/mamba.py::PREFILL_CHUNK is 512) shows that at test size
CHUNK = 4
# (prompt length, max_new_tokens): three requests over two slots; the
# third fills its bucket of 16
REQUESTS = ((5, 4), (9, 3), (16, 5))


def make_params():
    return {
        f: init_params_for(cfg)(jax.random.PRNGKey(i))
        for i, (f, cfg) in enumerate(CONFIGS.items())}


@pytest.fixture(scope="module")
def params():
    return make_params()


@pytest.fixture(scope="module", autouse=True)
def small_chunk():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(M, "PREFILL_CHUNK", CHUNK)
        yield


def engine(params, family, **kw):
    return ServingEngine(
        params[family], CONFIGS[family], ServeConfig(**{**ENGINE, **kw}),
        seed=3)


def serve_three(eng):
    reqs = [
        eng.submit([1 + (i + j) % 100 for j in range(p)], new)
        for i, (p, new) in enumerate(REQUESTS)]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    return eng


# -- (a) the capacity rule ----------------------------------------------------

# (prompt, max_new, bucket, num_pages, draft) -> pages the request needs
# at worst when the pool of num_pages - 2 cannot hold them, else None;
# pages of 8 tokens. Worst case: the bucket's multiple over prompt +
# max_new - 1, one more position, and a speculative engine's drafts.
CAPACITY = (
    ((5, 4, 8, 4, 0), None),  # 8 + 1 positions: 2 pages of 2
    ((12, 6, 8, 4, 0), 4),  # 24 + 1 positions: 4 pages
    ((10, 6, 1, 4, 0), None),  # exact lengths, 15 + 1: 2 pages
    ((10, 7, 1, 4, 0), 3),  # one token more: one page too large
    ((5, 4, 8, 4, 8), 3),  # 8 + 1 + 8 drafts: a page more than without
    ((5, 4, 8, 5, 8), None),
)


@pytest.mark.parametrize("family", FAMILIES)
def test_capacity_rule(params, family):
    noun = "attn pages" if family == "hybrid" else "pages"
    for (p, new, bucket, num_pages, draft), need in CAPACITY:
        if draft and family != "llama":
            continue  # only llama serves speculatively
        a = engine(
            params, family, prefill_bucket=bucket, num_pages=num_pages
        ).adapter
        a.spec_draft_tokens = draft  # what a loaded speculator sets
        err = a.admission_error(p, new)
        if family not in PAGED:
            # a constant slab: fits iff a slot exists
            assert a.cache is None and err is None
            assert a.can_admit(0, p) and a.grow(0, p + new)
            a.release(0, 0)
            continue
        if need is None:
            assert err is None, (p, new, bucket, err)
        else:
            assert err == (
                f"request needs up to {need} {noun} but the pool holds "
                f"{num_pages - 2}; raise num_pages or shrink "
                f"prompt/max_new_tokens")
        # admission looks one position past the padded prompt
        fits = -(-(-(-p // bucket) * bucket + 1) // 8) <= num_pages - 2
        assert a.can_admit(0, p) is fits
        # growth is by the token: the pool's pages, then no more
        held = (num_pages - 2) * 8
        assert a.grow(0, held) and not a.grow(0, held + 1)
        assert a.pages_in_use == num_pages - 2 and not a.can_admit(1, 1)
        a.release(0, 0)
        assert a.pages_in_use == 0 and a.can_admit(1, 1)


def test_engine_rejects_with_the_rules_text(params):
    from fms_fsdp_tpu.serve.scheduler import RequestRejected

    eng = engine(params, "mixtral", num_pages=4)
    with pytest.raises(RequestRejected, match="needs up to 4 pages but the "
                       "pool holds 2; raise num_pages") as e:
        eng.submit([1] * 12, 6)
    assert e.value.reason == "too_large"


# -- (b) what three requests count --------------------------------------------

# read off the tree before the skeleton for the same three requests
COUNTS = {
    #          programs built, table uploads, state writes, computed, padded
    "llama": (3, 3, 0, 40, 40),
    "mixtral": (3, 3, 0, 40, 40),
    "mamba": (2, 0, 3, 40, 40),
    "hybrid": (2, 3, 3, 36, 40),
}
COUNTERS = (
    "serve.prefill_programs_built", "serve.page_table_uploads",
    "serve.prefill_state_writes", "serve.prefill_computed_tokens",
    "serve.prefill_padded_tokens")


def counts(registry):
    return tuple(int(registry.counter(c).value) for c in COUNTERS)


@pytest.fixture(scope="module")
def served(params, small_chunk):
    return {f: serve_three(engine(params, f)) for f in FAMILIES}


@pytest.mark.parametrize("family", FAMILIES)
def test_three_requests_count_what_they_did(served, family):
    eng = served[family]
    assert eng.adapter.registry is eng.registry
    assert counts(eng.registry) == COUNTS[family]


@pytest.mark.parametrize("family", FAMILIES)
def test_an_adapter_given_no_registry_counts_into_its_own(params, family):
    cfg, scfg = CONFIGS[family], ServeConfig(**ENGINE)
    a = resolve_adapter(params[family], cfg, scfg)
    other = MetricRegistry()
    b = resolve_adapter(params[family], cfg, scfg, None, other)
    assert isinstance(a.registry, MetricRegistry) and b.registry is other
    assert a.registry is not other
    a.prefill(0, 0, list(range(1, 10)))
    # dispatched, not yet counted: the engine counts when it has read
    # the first token (``count_prefill``, once)
    assert counts(a.registry)[3] == 0
    a.count_prefill(0)
    a.count_prefill(0)
    computed = 12 if family == "hybrid" else 16
    slab = int(family not in ("llama", "mixtral"))
    assert counts(a.registry) == (1, 0, slab, computed, 0)
    assert counts(other) == (0, 0, 0, 0, 0)


# -- (c) the same programs ----------------------------------------------------

JAX_VERSION = "0.9.0"
# sha256 of jit(...).lower(...).as_text(), read off the tree before the
# skeleton on that jax
DIGESTS = {
    ("llama", "decode"):
        "2b9117facee05cbf8b1f2afef990fcda97e51332bb457d73f9177bb27f777dee",
    ("llama", "prefill"):
        "db125b04f052c52b02f394f997449bad30b3a6e8a4ad76d77d499779b79dbfce",
    ("mixtral", "decode"):
        "f07b949d5d0f0150d60a07e3c3ce78bcc2f503ade503da31b45a1231dab232cb",
    ("mixtral", "prefill"):
        "0461c65b98688fbbb5f1353d4c66cd14167449da35ff7f504a981e6f8e049631",
    ("mamba", "decode"):
        "80b94e511603c2eab717a3b82b6bbbf4e31ed235d9cc6a14bc9e3d67ec9db448",
    ("mamba", "prefill"):
        "bce486cff43020996b01215627910f3873687217a03febd05e33d0368d27ff5e",
    ("hybrid", "decode"):
        "a7aa5a2c4cb2921b8a993f866c91b024fdd5ec34bc930cc5fde50d480bd58ba3",
    ("hybrid", "prefill"):
        "34c1520d01a75238650db1de8f13db894d2d5132cfcc788864a606bfd61fc661",
}
# the prefill program of a 9-token prompt in its bucket of 16
PREFILL_KEY = {
    "llama": (16, 16, True), "mixtral": (16, 16, True),
    "mamba": (16, 0), "hybrid": (16, 16)}


def lowered_text(eng, family, program):
    a = eng.adapter
    B = ENGINE["max_batch"]
    ints = jnp.zeros((B,), jnp.int32)
    if program == "prefill":
        args = [a.params, jnp.zeros((1, 16), jnp.int32)]
        if family not in ("llama", "mixtral"):
            args.append(jnp.asarray([9], jnp.int32))
        return a._prefill_cache[PREFILL_KEY[family]].lower(*args).as_text()
    state = [] if family in ("llama", "mixtral") else [a._state]
    if family in PAGED:
        state += [a.cache.pools, jnp.zeros((B, a.max_pages), jnp.int32)]
    return a._decode_fn.lower(
        a.params, *state, ints, ints, jax.random.PRNGKey(0)).as_text()


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("family,program", sorted(DIGESTS))
def test_lowered_program_is_the_one_before_the_skeleton(
        served, family, program):
    if jax.__version__ != JAX_VERSION:
        pytest.skip(f"digests hold for jax {JAX_VERSION}")
    text = lowered_text(served[family], family, program)
    name = "jit__step" if program == "decode" else {
        "llama": "jit__unknown", "mixtral": "jit__unknown",
    }.get(family, "jit__prefill_16")
    assert f"module @{name} " in text
    assert digest(text) == DIGESTS[family, program]


if __name__ == "__main__":
    M.PREFILL_CHUNK = CHUNK
    made = make_params()
    print("jax", jax.__version__)
    for f in FAMILIES:
        eng = serve_three(engine(made, f))
        print(f, counts(eng.registry))
        for program in ("decode", "prefill"):
            print(f, program, digest(lowered_text(eng, f, program)))
