"""Autoregressive generation with a kv-cache for the Llama family.

Replaces the reference's embeds-returning ``generate`` copy
(ref:speculator/train_speculator_utils.py:28-118): prefill + a
``lax.scan`` decode loop entirely under jit — no Python in the token loop
(SURVEY.md §7 hard part 4). Supports temperature / top-k sampling or
greedy decode, and optionally returns the final hidden state (embedding)
of every generated position for speculator stage-2 training.

The kv cache is a pytree {"k", "v"} of (L, B, S_max, Nkv, H) arrays
carried through the scan; each decode step runs the layer stack as an
inner ``lax.scan`` whose xs are the stacked layer params + cache slices.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.models.configs import LlamaConfig
from fms_fsdp_tpu.models.llama import llama_forward
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.paged_attention import gqa_attend
from fms_fsdp_tpu.ops.rope import apply_rotary, rope_table


def prefill(
    params,
    tokens,
    cfg: LlamaConfig,
    max_seq_len: int,
    compute_dtype=jnp.bfloat16,
    full_logits: bool = False,
):
    """Run the prompt through the model, building the kv cache.

    Returns (logits, embeds (B, S, D), cache). ``logits`` covers only the
    final position (B, 1, V) unless ``full_logits`` — generation discards
    the rest, and at 128k vocab the full (B, S, V) matmul is pure waste.
    The cache holds max_seq_len positions; positions >= len(prompt) are
    zeros until decode writes them.
    """
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    b, s = tokens.shape
    hd, nkv = cfg.head_dim, cfg.n_kv_heads
    nlayers = params["layers"]["wq"].shape[0]

    with jax.named_scope("rope"):
        cos, sin = rope_table(max_seq_len, hd, cfg.rope_theta)
    with jax.named_scope("embed"):
        x = params["embedding"][tokens]

    def body(x, layer):
        with jax.named_scope("qkv"):
            h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q = (h @ layer["wq"]).reshape(b, s, cfg.nheads, hd)
            k = (h @ layer["wk"]).reshape(b, s, nkv, hd)
            v = (h @ layer["wv"]).reshape(b, s, nkv, hd)
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        from fms_fsdp_tpu.ops.attention import attention

        with jax.named_scope("attn"):
            o = attention(q, k, v, causal=True, impl="xla")
        with jax.named_scope("attn_out"):
            x = x + o.reshape(b, s, cfg.nheads * hd) @ layer["wo"]
        with jax.named_scope("ffn"):
            h2 = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
            ffn = (
                jax.nn.silu(h2 @ layer["w1"]) * (h2 @ layer["w3"])
            ) @ layer["w2"]
            x = x + ffn
        # cache entries padded out to max_seq_len
        pad = [(0, 0), (0, max_seq_len - s), (0, 0), (0, 0)]
        return x, (jnp.pad(k, pad), jnp.pad(v, pad))

    with jax.named_scope("layers"):
        x, (k_cache, v_cache) = lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        embeds = rms_norm(x, params["norm"], cfg.norm_eps)
        src = embeds if full_logits else embeds[:, -1:]
        logits = src @ params["lm_head"]
    return logits, embeds, {"k": k_cache, "v": v_cache}


@scoped("qkv")
def decode_layer_qkv(x, layer, cfg: LlamaConfig, cos, sin, positions):
    """Pre-attention half of one decode layer: norm -> q/k/v projections
    -> rotary at ``positions``. Shared by the dense decode path below and
    the paged decode path (fms_fsdp_tpu/serve/decode.py) so both run the
    exact same ops — the bit-parity contract between them."""
    b, m = x.shape[:2]
    hd, nkv = cfg.head_dim, cfg.n_kv_heads
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = (h @ layer["wq"]).reshape(b, m, cfg.nheads, hd)
    k = (h @ layer["wk"]).reshape(b, m, nkv, hd)
    v = (h @ layer["wv"]).reshape(b, m, nkv, hd)
    q = apply_rotary(q, cos, sin, positions)
    k = apply_rotary(k, cos, sin, positions)
    return q, k, v


def decode_layer_out(x, layer, cfg: LlamaConfig, o):
    """Post-attention half of one decode layer: residual + SwiGLU FFN.
    Shared with the paged decode path (see decode_layer_qkv)."""
    with jax.named_scope("attn_out"):
        x = x + o @ layer["wo"]
    with jax.named_scope("ffn"):
        h2 = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        ffn = (
            jax.nn.silu(h2 @ layer["w1"]) * (h2 @ layer["w3"])
        ) @ layer["w2"]
        return x + ffn


def decode_chunk(params, cache, tokens, pos, cfg: LlamaConfig, compute_dtype=jnp.bfloat16):
    """Cached decode of m tokens at positions pos..pos+m-1 in one forward
    (the verification step of speculative decoding; decode_step is the
    m=1 case). Returns (logits (B, m, V), embeds (B, m, D), cache)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    b, m = tokens.shape
    hd = cfg.head_dim
    max_seq = cache["k"].shape[2]

    with jax.named_scope("rope"):
        cos, sin = rope_table(max_seq, hd, cfg.rope_theta)
    positions = pos + jnp.arange(m, dtype=jnp.int32)[None, :]  # (1, m)
    positions = jnp.broadcast_to(positions, (b, m))
    with jax.named_scope("embed"):
        x = params["embedding"][tokens]

    def body(x, inp):
        layer, k_cache, v_cache = inp
        q, k, v = decode_layer_qkv(x, layer, cfg, cos, sin, positions)
        with jax.named_scope("kv_write"):
            k_cache = lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
            v_cache = lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
        # q position pos+i sees cache entries <= pos+i
        o = gqa_attend(q, k_cache, v_cache, positions)
        return decode_layer_out(x, layer, cfg, o), (k_cache, v_cache)

    with jax.named_scope("layers"):
        x, (k_cache, v_cache) = lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"])
        )
    with jax.named_scope("lm_head"):
        embeds = rms_norm(x, params["norm"], cfg.norm_eps)
        logits = embeds @ params["lm_head"]
    return logits, embeds, {"k": k_cache, "v": v_cache}


def decode_step(params, cache, token, pos, cfg: LlamaConfig, compute_dtype=jnp.bfloat16):
    """One cached decode step. token (B, 1) int32 at position ``pos``.
    Returns (logits (B, V), embeds (B, D), updated cache) — the m=1 case
    of decode_chunk."""
    logits, embeds, cache = decode_chunk(
        params, cache, token, pos, cfg, compute_dtype
    )
    return logits[:, 0], embeds[:, 0], cache


@scoped("sample")
def sample_token(logits, key, temperature, top_k, do_sample):
    """Greedy argmax or temperature / top-k sampling of one token per
    row. Public: the serving engine (fms_fsdp_tpu/serve/engine.py) uses
    the same sampler as ``generate`` so greedy serving is token-for-token
    the dense path."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits.astype(jnp.float32) / temperature
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


_sample = sample_token


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "max_seq_len",
        "max_new_tokens",
        "temperature",
        "top_k",
        "do_sample",
        "include_embeds",
    ),
)
def generate(
    params,
    input_ids,
    cfg: LlamaConfig,
    *,
    key,
    max_seq_len: int = 2048,
    max_new_tokens: int = 256,
    temperature: float = 1.0,
    top_k: int = 10,
    do_sample: bool = True,
    include_embeds: bool = True,
):
    """Autoregressive generation (ref:train_speculator_utils.py:28-118).

    input_ids (B, P) -> result (B, P + max_new_tokens); with
    ``include_embeds`` also returns embeds (B, max_new_tokens, D): the
    final hidden state at each *generated* position (the state that
    predicted the NEXT token), matching the reference's embeds capture.
    """
    b, prompt_len = input_ids.shape
    assert prompt_len + max_new_tokens <= max_seq_len, (
        f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) exceeds "
        f"max_seq_len ({max_seq_len}): the kv cache would overflow (dynamic "
        "slice writes clamp silently)"
    )
    logits, prefill_embeds, cache = prefill(params, input_ids, cfg, max_seq_len)
    last_logits = logits[:, -1]
    last_embed = prefill_embeds[:, -1]

    def step(carry, key_t):
        cache, last_logits, last_embed, pos = carry
        tok = _sample(last_logits, key_t, temperature, top_k, do_sample)
        logits, embeds, cache = decode_step(
            params, cache, tok[:, None], pos, cfg
        )
        return (cache, logits, embeds, pos + 1), (tok, last_embed)

    keys = jax.random.split(key, max_new_tokens)
    (_, _, _, _), (tokens, embeds) = lax.scan(
        step, (cache, last_logits, last_embed, prompt_len), keys
    )
    tokens = jnp.moveaxis(tokens, 0, 1)  # (B, T)
    result = jnp.concatenate([input_ids, tokens], axis=1)
    if include_embeds:
        return result, jnp.moveaxis(embeds, 0, 1)  # (B, T, D)
    return result
