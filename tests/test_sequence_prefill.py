"""The sequence prefill's one loop (``models/sequence_prefill.py``): the
template alone on a toy body, and the five families' prefill programs
held to the text and the scopes they had before the loop was theirs in
common (PR 45): lowering and toy sizes only, nothing of a family runs.
"""

import ast
import collections
import functools
import hashlib
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fms_fsdp_tpu.models import sequence_prefill as T
from fms_fsdp_tpu.models.configs import SarvamConfig
from fms_fsdp_tpu.obs import scopes
from fms_fsdp_tpu.serve.engine import ServeConfig
from fms_fsdp_tpu.serve.families import load_model_config

JAX_VERSION = "0.9.0"
CHUNK = 16
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

JAMBA = {
    "model_type": "jamba", "attn_layer_offset": 1, "attn_layer_period": 2,
    "hidden_size": 64, "intermediate_size": 128, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
    "num_attention_heads": 4, "num_experts": 1, "num_hidden_layers": 4,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "vocab_size": 128,
}
SARVAM = {
    "src_vocab_size": 128, "emb_dim": 64, "nheads": 4,
    "nlayers": 3, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "kv_lora_rank": 32, "hidden_dim": 96,
    "first_k_dense": 1, "moe_hidden_dim": 32, "num_experts": 8,
    "experts_held": (2, 4), "top_k": 2, "num_shared_experts": 1,
    "max_expected_seq_len": 64,
}
KEXAONE = {
    "model_type": "exaone_moe", "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 3,
    "layer_types": ["sliding_attention"] * 2 + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 2,
    "first_k_dense_replace": 1, "sliding_window": 8,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 4, "published": {"num_experts": 16},
    "first_expert_held": 4, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "vocab_size": 128,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "num_nextn_predict_layers": 0,
}
SALA = {
    "model_type": "minicpm_sala", "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 3,
    "mixer_types": ["minicpm4", "lightning-attn", "minicpm4"],
    "intermediate_size": 128, "lightning_nh": 4, "lightning_nkv": 4,
    "lightning_head_dim": 16, "lightning_use_rope": True,
    "lightning_scale": "1/sqrt(d)", "attn_use_rope": False, "qk_norm": True,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 16, "vocab_size": 128, "hidden_act": "silu",
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "published": {"num_hidden_layers": 32},
    "sparse_config": {
        "kernel_size": 8, "kernel_stride": 4, "block_size": 16, "topk": 6,
        "init_blocks": 1, "window_size": 32, "dense_len": 64,
    },
}
LFM2 = {
    "model_type": "lfm2_moe", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 3,
    "layer_types": ["conv", "full_attention", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 128,
    "max_position_embeddings": 512, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
}


# family -> (its module's name under models/ and serve/families/, its
# small config, its init, the scopes its per-layer metrics sum over)
FAMILIES = {
    "jamba": ("mamba", JAMBA, "init_mamba_params", scopes.HYBRID_SCOPES),
    "sarvam": ("sarvam", SARVAM, "init_sarvam_params", scopes.SARVAM_SCOPES),
    "kexaone": (
        "kexaone", KEXAONE, "init_kexaone_params", scopes.KEXAONE_SCOPES),
    "sala": ("minicpm_sala", SALA, "init_sala_params", scopes.SALA_SCOPES),
    "lfm2": ("lfm2", LFM2, "init_lfm2_params", scopes.LFM2_SCOPES),
}


def _family(name):
    """-> (model module, adapter module, model config, init, scopes)."""
    module, d, init, names = FAMILIES[name]
    M = importlib.import_module(f"fms_fsdp_tpu.models.{module}")
    A = importlib.import_module(f"fms_fsdp_tpu.serve.families.{module}")
    # sarvam's small size is written in the dataclass's own fields
    cfg = SarvamConfig(**d) if name == "sarvam" else load_model_config(d)
    return M, A, cfg, getattr(M, init), names


@functools.cache
def lowered(case):
    """The prefill program of ``"<family> <form> <padded length>"`` as the
    adapter builds it, lowered on abstract parameters: nothing executes.
    Chunks of ``CHUNK`` (sala: 48, whole blocks of 16), so the longer
    length of a family takes several. Kept for the case's other test."""
    name, form, n = case.split()
    n = int(n)
    M, A, cfg, init, names = _family(name)
    scfg = ServeConfig(
        max_batch=2, max_seq_len=256, page_size=16 if name == "sala" else 8,
        compute_dtype="float32", attn_impl="reference",
        **({"moe_impl": form} if form in ("routed", "dense") else {}),
    )
    params = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    program = (
        A.prefill_program(cfg, scfg, n, jnp.float32) if name == "sala"
        else A.prefill_program(cfg, scfg, n, n, jnp.float32)
    )
    sd = jax.ShapeDtypeStruct
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(M, "PREFILL_CHUNK", 48 if name == "sala" else CHUNK)
        return program.lower(
            params, sd((1, n), jnp.int32), sd((1,), jnp.int32)
        ), names


def scope_counts(low, names):
    """Sorted ``(scope, instructions)`` of the compiled program."""
    table = scopes.scope_table(low.compile().as_text(), names)
    return sorted(collections.Counter(table.values()).items())


# sha256 of each program's StableHLO at the parent of PR 45 (a7289d1),
# where every family had the loop in its own file; sala's forms are its
# chunks' (``chunk_forms``): 64 positions are two dense chunks, 192 a
# dense, a masked and two chosen ones
DIGESTS = {
    "jamba one 16":
        "ef01c9145bc0f0f69fa0e8dd5cedcc80aefc8a0a7b2df1e8ce77800aec5937c7",
    "jamba one 48":
        "19d8c4c01735c66809a0ade035678559cd166b9a47185ba1e6091a03caa494b7",
    "sarvam routed 16":
        "c971c00585804b50d7ddfc417aeb9998741044f9d5fbbb8332f99d8e47dd53ca",
    "sarvam routed 48":
        "f8f363d94c5d606cfe42d48ad8c81a8cd8c034208f485bedb193b3fdfe377d23",
    "sarvam dense 16":
        "32c09536e17b7771124ee36b29bf1ba48aa45d1ba88274cbb2a110cae07e2b31",
    "sarvam dense 48":
        "64e1d1d66fc2cdb761a1bac93f667c7df3d0c949c110f5405ac80d1cd9c20deb",
    "kexaone routed 16":
        "845a220be0a65397421de41696c0ff6c2ae3eed5d18ee784b237b59cb3a2ca46",
    "kexaone routed 48":
        "7ef4dd8bab1026b83626793422a6cb8e13d8417aa235a321e0f882643d387381",
    "kexaone dense 16":
        "ab6d4779f727c6dd678c5efa93245fc2e6f009d700329560f838f8ad9b27b7d3",
    "kexaone dense 48":
        "cfb3947010615a661daba3d8762fcce64fa10dad4771718e83b0d4f591f2b039",
    "sala dense 64":
        "c8fa15a7aa424e7d607ce3526d04c5a543bb6f52911d3122e18cf539934269fa",
    "sala chosen 192":
        "ec7b2a84c1e935bc6c50aaab7a7681fb4073e84982868dbc1cd3bfdf16a6cbd9",
    "lfm2 routed 16":
        "547114e61e5643eb2101faa52246b357a906b7597607d318a90aa6fbe909bec3",
    "lfm2 routed 48":
        "b5bd802827286e62e55cdca9074989949f132baf3731263509d435fd4503d53d",
    "lfm2 dense 16":
        "2b78d2d74f605a6173014835c93948fe476bdf17040d40dce68445da5724f135",
    "lfm2 dense 48":
        "cd16484775fc42a701e61a7b5802418888ae190295b94f23e79af7a69069d475",
}

# the scopes ``obs/scopes.py::scope_table`` reads from each family's
# compiled (CPU) program at the parent of PR 45, instructions a scope:
# what benchmark/layer_metrics sums over
SCOPES = {
    "jamba one 48": [
        ("", 260), ("attn", 517), ("attn_out", 4), ("embed", 27),
        ("kv_write", 84), ("lm_head", 3), ("mlp", 64), ("norm", 333),
        ("qkv", 36), ("ssm_conv", 251), ("ssm_gate_out", 24),
        ("ssm_in_proj", 31), ("ssm_params", 259), ("ssm_scan", 382),
    ],
    "sarvam routed 48": [
        ("", 273), ("attn", 316), ("attn_out", 12), ("embed", 27),
        ("latent_write", 54), ("layers", 322), ("lm_head", 3),
        ("mla_expand", 138), ("mla_kv_down", 180), ("mla_q", 38), ("mlp", 18),
        ("moe_combine", 37), ("moe_experts", 1104), ("moe_group", 199),
        ("moe_router", 77), ("moe_shared", 17), ("norm", 168),
    ],
    "kexaone routed 48": [
        ("", 279), ("attn_full", 291), ("attn_out", 18), ("attn_window", 303),
        ("embed", 27), ("kv_write", 45), ("layers", 123), ("lm_head", 3),
        ("mlp", 24), ("moe_combine", 96), ("moe_experts", 2204),
        ("moe_group", 457), ("moe_router", 158), ("moe_shared", 34),
        ("norm", 256), ("qk_norm", 137), ("qkv", 41), ("rope", 88),
        ("win_write", 101),
    ],
    "sala chosen 192": [
        ("", 309), ("attn", 648), ("attn_out", 55), ("embed", 19),
        ("kv_write", 99), ("layers", 485), ("lin_gate", 53),
        ("lin_scan", 240), ("lm_head", 8), ("mlp", 70), ("norm", 243),
        ("qk_norm", 140), ("qkv", 45), ("rope", 47), ("sparse_attn", 3148),
        ("sparse_compress", 151), ("sparse_select", 1374),
    ],
    "lfm2 routed 48": [
        ("", 236), ("attn_full", 297), ("attn_out", 6), ("conv_in", 24),
        ("conv_out", 16), ("dense_mlp", 22), ("embed", 27), ("head", 35),
        ("kv_write", 49), ("layers", 114), ("moe_combine", 93),
        ("moe_experts", 2204), ("moe_group", 442), ("moe_router", 157),
        ("norm", 216), ("qk_norm", 40), ("qkv", 13), ("rope", 60),
        ("short_conv", 74),
    ],
}


@pytest.mark.parametrize("case", sorted(DIGESTS))
def test_prefill_programs_are_the_text_they_were(case):
    """The five prefills are calls into one loop: each program lowers to
    the text it had when the loop was written in its own file. A digest
    that moves is a changed program (an operand's place, a cast, an
    operation made and never used): put it back, do not pin again."""
    if jax.__version__ != JAX_VERSION:
        pytest.skip(f"digests hold for jax {JAX_VERSION}")
    text = lowered(case)[0].as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == DIGESTS[case]


@pytest.mark.parametrize("case", sorted(SCOPES))
def test_prefill_programs_keep_their_scopes(case):
    """The text above carries no names: the scopes do, and
    benchmark/layer_metrics sums device time over them. None may vanish
    or change what it holds."""
    if jax.__version__ != JAX_VERSION:
        pytest.skip(f"counts hold for jax {JAX_VERSION}")
    assert scope_counts(*lowered(case)) == SCOPES[case]


# ---------------------------------------------------------------------------
# the template alone
# ---------------------------------------------------------------------------

C, S, D = 4, 16, 3


def _running_sums(vals, lengths):
    """``chunk_loop`` over a toy body: the running sum of each row's
    values. -> (each row's sum at its last real position, the trips)."""
    B = vals.shape[0]

    def body(chunk, carry):
        total, last, trips = carry
        v = jax.lax.dynamic_slice_in_dim(vals, chunk.start, C, axis=1)
        x = total[:, None] + jnp.cumsum(
            jnp.where(chunk.live[:, :, None], v, 0.0), axis=1
        )
        assert chunk.positions.shape == (B, C)
        return x, (x[:, -1], last, trips + 1)

    _, last, trips = jax.jit(lambda: T.chunk_loop(
        lengths, C, body,
        lambda: (
            jnp.zeros((B, D)), jnp.zeros((B, D)), jnp.zeros((), jnp.int32)),
        last=1,
    ))()
    return np.asarray(last), int(trips)


@pytest.mark.parametrize("lengths", [(0, 1, C, C + 1, S), (0, 1, C, C + 1, 2)])
def test_chunk_loop_stops_at_the_longest_and_picks_each_rows_end(lengths):
    vals = jax.random.normal(jax.random.PRNGKey(0), (len(lengths), S, D))
    last, trips = _running_sums(vals, jnp.asarray(lengths, jnp.int32))
    assert trips == -(-max(lengths) // C)
    for b, n in enumerate(lengths):
        want = np.asarray(vals[b, :n]).sum(axis=0)  # zeros for no prompt
        np.testing.assert_allclose(last[b], want, rtol=1e-5, atol=1e-6)


def test_chunk_facts():
    chunk = T.Chunk(2, C, jnp.asarray([0, 9, 11, 30], jnp.int32))
    assert int(chunk.start) == 8 and chunk.c == C
    assert np.asarray(chunk.ahead).tolist() == [-8, 1, 3, 22]
    assert np.asarray(chunk.live).tolist() == [
        [False] * 4, [True] + [False] * 3, [True] * 3 + [False], [True] * 4]
    assert np.asarray(chunk.positions).tolist() == [[8, 9, 10, 11]] * 4


def test_chunk_of_and_positions_computed():
    assert T.chunk_of(48, 16) == 16 and T.chunk_of(40, 16) == 10
    assert T.chunk_of(7, 16) == 7 and T.chunk_of(13, 4) == 1
    # in units of a block: whole blocks, one at the least
    assert T.chunk_of(192, 48, unit=16) == 48
    assert T.chunk_of(64, 48, unit=16) == 32
    assert T.chunk_of(64, 8, unit=16) == 16
    with pytest.raises(AssertionError):
        T.chunk_of(40, 48, unit=16)
    assert [T.positions_computed(p, 16) for p in (1, 16, 17)] == [16, 16, 32]
    assert T.largest_divisor(12, 5) == 4


def test_kernel_wanted_by_name_or_left_to_a_tpu():
    assert T.kernel_wanted("pallas") and not T.kernel_wanted("xla")
    assert T.kernel_wanted("auto") == (jax.default_backend() == "tpu")


def test_write_live_is_zero_past_a_rows_length():
    B, kv_len = 3, 12
    ahead = jnp.asarray([0, 2, 9], jnp.int32)  # from position 4 on
    live = jnp.arange(C)[None, :] < ahead[:, None]
    buf = jnp.full((B, kv_len, 2, D), 7.0)
    new = jnp.ones((B, C, 2, D))
    k, v = T.write_live((buf, buf), (new, 2 * new), live, 4)
    want = np.full((B, kv_len, 2, D), 7.0)
    want[:, 4:8] = 0.0
    want[1, 4:6] = want[2, 4:8] = 1.0
    np.testing.assert_array_equal(np.asarray(k), want)
    np.testing.assert_array_equal(np.asarray(v)[:, 4:8], 2 * want[:, 4:8])
    # one array of every layer's, rows narrower than the buffer's
    lat = T.write_live(
        jnp.full((2, B, kv_len, 5), 7.0), jnp.ones((B, C, D)), live, 4,
        layer=1,
    )
    lat = np.asarray(lat)
    assert (lat[0] == 7.0).all() and (lat[1, :, :4] == 7.0).all()
    assert (lat[1, :, 4:8, D:] == 0.0).all()
    np.testing.assert_array_equal(lat[1, :, 4:8, :D], want[:, 4:8, 0])


def test_next_tail_is_the_end_of_each_rows_prompt():
    W, lengths = 3, (0, 1, C, C + 1, S)
    seq = jax.random.normal(jax.random.PRNGKey(1), (len(lengths), S, D))
    tail = jnp.zeros((len(lengths), W, D))
    for start in range(0, S, C):
        tail = T.next_tail(
            tail, seq[:, start:start + C],
            jnp.asarray(lengths, jnp.int32) - start, W,
        )
    padded = np.concatenate([np.zeros((len(lengths), W, D)), seq], axis=1)
    for b, n in enumerate(lengths):  # zeros before a prompt's start
        np.testing.assert_array_equal(np.asarray(tail[b]), padded[b, n:n + W])


def test_stack_or_empty():
    parts = [jnp.ones((2, 3)), jnp.zeros((2, 3))]
    assert T.stack_or_empty(parts, (2, 3), jnp.float32).shape == (2, 2, 3)
    empty = T.stack_or_empty([], (2, 3), jnp.bfloat16)
    assert empty.shape == (0, 2, 3) and empty.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# who imports whom
# ---------------------------------------------------------------------------

FAMILY_MODELS = ("mamba", "sarvam", "kexaone", "minicpm_sala", "lfm2")


def _imports(path):
    """The modules a source file imports, anywhere in it."""
    with open(path) as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module)
            out.update(f"{node.module}.{a.name}" for a in node.names)
    return out


@pytest.mark.parametrize("name", FAMILY_MODELS)
def test_no_family_model_imports_a_sibling(name):
    """What two families share has a home below both
    (models/sequence_prefill.py, models/moe_held.py, ops/): an edit for
    one family moves no other unseen."""
    got = _imports(
        os.path.join(ROOT, "fms_fsdp_tpu", "models", f"{name}.py"))
    siblings = {
        f"fms_fsdp_tpu.models.{m}" for m in FAMILY_MODELS if m != name}
    assert not got & siblings


def test_the_shared_loop_imports_no_family_and_no_config():
    got = _imports(
        os.path.join(ROOT, "fms_fsdp_tpu", "models", "sequence_prefill.py"))
    assert not any(g.startswith("fms_fsdp_tpu.models") for g in got), got


def test_no_adapter_imports_a_sibling_adapter():
    """An adapter takes what it shares from the package
    (serve/families/__init__.py), which alone resolves adapters by
    name."""
    here = os.path.join(ROOT, "fms_fsdp_tpu", "serve", "families")
    adapters = sorted(
        f[:-3] for f in os.listdir(here)
        if f.endswith(".py") and f != "__init__.py")
    assert {"kexaone", "lfm2", "mamba", "minicpm_sala", "sarvam"} <= set(
        adapters)
    for name in adapters:
        got = _imports(os.path.join(here, f"{name}.py"))
        siblings = {
            f"fms_fsdp_tpu.serve.families.{a}" for a in adapters if a != name}
        assert not got & siblings, (name, got & siblings)
