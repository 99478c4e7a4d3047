"""The paged decode step: one ragged token step over the page pool.

Mirrors models/generation.py::decode_step op-for-op — it runs the same
``decode_layer_qkv`` / ``gqa_attend`` / ``decode_layer_out`` functions —
with exactly two differences: k/v land in the paged pool (a batched
scatter at each row's (page, slot) write target) instead of a dense
per-sequence cache, and each batch row carries its own position
(``seq_lens``) instead of one shared scalar. Under the reference
attention impl the gathered pages equal the dense cache bit-for-bit
(zero-page discipline, serve/kv_cache.py), so greedy paged decode is
bit-identical to the dense path — the tier-1 parity anchor.
"""

import jax
import jax.numpy as jnp
from jax import lax

from fms_fsdp_tpu.models.configs import LlamaConfig
from fms_fsdp_tpu.models.generation import (
    decode_layer_out,
    decode_layer_qkv,
)
from fms_fsdp_tpu.ops.paged_attention import (
    gather_pages,
    gqa_attend,
    paged_attention_kernel,
)
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.quant import kv_dequantize, kv_quantize
from fms_fsdp_tpu.ops.rope import rope_table


def paged_decode_step(
    params,
    pools,
    page_table,
    seq_lens,
    tokens,
    cfg: LlamaConfig,
    *,
    page_size: int,
    compute_dtype=jnp.bfloat16,
    quant: str = "none",
    attn_impl: str = "reference",
    block_kv=None,
    interpret=None,
):
    """One decode step for a ragged batch.

    tokens (B,) int32 — the next token of each row, written at cache
    position ``seq_lens[b]`` (the row then attends to positions
    <= seq_lens[b]); page_table (B, max_pages) int32; pools is the
    PagedKVCache.pools dict (leading L dim per leaf). Returns
    (logits (B, V), embeds (B, D), pools) — the paged analog of
    ``decode_step``'s (logits, embeds, cache). Under the kernel impl,
    quantized pools are read natively (the kernel applies the scale
    pools in VMEM) and ``block_kv`` sets the pages-per-cell fetch
    width.
    """
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    b = tokens.shape[0]
    hd = cfg.head_dim
    max_seq = page_table.shape[1] * page_size
    with jax.named_scope("rope"):
        cos, sin = rope_table(max_seq, hd, cfg.rope_theta)
    positions = seq_lens[:, None].astype(jnp.int32)  # (B, 1)
    with jax.named_scope("embed"):
        x = params["embedding"][tokens[:, None]]  # (B, 1, D)

    with jax.named_scope("kv_write"):  # each row's write target
        rows = jnp.arange(b)
        page_ids = page_table[rows, seq_lens // page_size]  # (B,)
        slots = seq_lens % page_size

    quantized = quant != "none"

    def attend(q, layer_pools):
        if attn_impl == "kernel":
            with jax.named_scope("attn"):
                return paged_attention_kernel(
                    q[:, 0],
                    layer_pools["k"],
                    layer_pools["v"],
                    page_table,
                    seq_lens,
                    k_scales=layer_pools.get("k_scale"),
                    v_scales=layer_pools.get("v_scale"),
                    block_kv=block_kv,
                    interpret=interpret,
                )[:, None]
        if quantized:
            with jax.named_scope("kv_gather"):
                k = kv_dequantize(
                    gather_pages(layer_pools["k"], page_table),
                    gather_pages(layer_pools["k_scale"], page_table),
                    compute_dtype,
                )
                v = kv_dequantize(
                    gather_pages(layer_pools["v"], page_table),
                    gather_pages(layer_pools["v_scale"], page_table),
                    compute_dtype,
                )
        else:
            k = gather_pages(layer_pools["k"], page_table)
            v = gather_pages(layer_pools["v"], page_table)
        return gqa_attend(q, k, v, positions)

    def body(x, inp):
        layer, layer_pools = inp
        q, k, v = decode_layer_qkv(x, layer, cfg, cos, sin, positions)
        # scatter this step's k/v to each row's (page, slot) target —
        # idle rows' tables point every slot at the scratch page, so
        # their write lands where no live sequence reads
        with jax.named_scope("kv_write"):
            if quantized:
                qk, sk = kv_quantize(k[:, 0], quant)
                qv, sv = kv_quantize(v[:, 0], quant)
                layer_pools = {
                    "k": layer_pools["k"].at[page_ids, slots].set(qk),
                    "v": layer_pools["v"].at[page_ids, slots].set(qv),
                    "k_scale": layer_pools["k_scale"]
                    .at[page_ids, slots]
                    .set(sk),
                    "v_scale": layer_pools["v_scale"]
                    .at[page_ids, slots]
                    .set(sv),
                }
            else:
                layer_pools = {
                    "k": layer_pools["k"].at[page_ids, slots].set(k[:, 0]),
                    "v": layer_pools["v"].at[page_ids, slots].set(v[:, 0]),
                }
        o = attend(q, layer_pools)
        return decode_layer_out(x, layer, cfg, o), layer_pools

    with jax.named_scope("layers"):
        x, pools = lax.scan(body, x, (params["layers"], pools))
    with jax.named_scope("lm_head"):
        embeds = rms_norm(x, params["norm"], cfg.norm_eps)
        logits = embeds @ params["lm_head"]
    return logits[:, 0], embeds[:, 0], pools


def paged_verify_step(
    params,
    pools,
    page_table,
    seq_lens,
    tokens,
    cfg: LlamaConfig,
    *,
    page_size: int,
    compute_dtype=jnp.bfloat16,
    quant: str = "none",
    attn_impl: str = "reference",
    interpret=None,
):
    """Score m candidate tokens per row in one ragged forward — the
    speculative-decoding verify step (models/generation.py::decode_chunk
    over pages, per-row positions instead of one scalar).

    tokens (B, m) int32: token j of row b is written at cache position
    ``seq_lens[b] + j`` and attends to positions <= it, exactly the
    decode_chunk rule, so under the reference impl the per-position
    logits are bit-identical to feeding the same tokens one at a time
    through ``paged_decode_step`` — which is what lets the greedy accept
    rule keep speculative serving token-identical to plain greedy.
    Returns (logits (B, m, V), embeds (B, m, D), pools). The engine owns
    rollback: positions past a row's accepted prefix hold stale k/v that
    the <=pos mask hides until a later write replaces them, so rejecting
    a draft costs no pool traffic at all.

    Verification attends through the gather path under every impl (the
    decode kernel is specialized to m=1 queries); the quantized round
    trip matches paged_decode_step's reference branch.
    """
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    b, m = tokens.shape
    hd = cfg.head_dim
    max_seq = page_table.shape[1] * page_size
    with jax.named_scope("rope"):
        cos, sin = rope_table(max_seq, hd, cfg.rope_theta)
    positions = (
        seq_lens[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
    ).astype(jnp.int32)  # (B, m)
    with jax.named_scope("embed"):
        x = params["embedding"][tokens]  # (B, m, D)

    with jax.named_scope("kv_write"):  # each token's write target
        page_ids = page_table[
            jnp.arange(b)[:, None], positions // page_size
        ]  # (B, m)
        slots = positions % page_size

    quantized = quant != "none"

    def attend(q, layer_pools):
        if quantized:
            with jax.named_scope("kv_gather"):
                k = kv_dequantize(
                    gather_pages(layer_pools["k"], page_table),
                    gather_pages(layer_pools["k_scale"], page_table),
                    compute_dtype,
                )
                v = kv_dequantize(
                    gather_pages(layer_pools["v"], page_table),
                    gather_pages(layer_pools["v_scale"], page_table),
                    compute_dtype,
                )
        else:
            k = gather_pages(layer_pools["k"], page_table)
            v = gather_pages(layer_pools["v"], page_table)
        return gqa_attend(q, k, v, positions)

    def body(x, inp):
        layer, layer_pools = inp
        q, k, v = decode_layer_qkv(x, layer, cfg, cos, sin, positions)
        with jax.named_scope("kv_write"):
            if quantized:
                qk, sk = kv_quantize(k, quant)
                qv, sv = kv_quantize(v, quant)
                layer_pools = {
                    "k": layer_pools["k"].at[page_ids, slots].set(qk),
                    "v": layer_pools["v"].at[page_ids, slots].set(qv),
                    "k_scale": layer_pools["k_scale"]
                    .at[page_ids, slots]
                    .set(sk),
                    "v_scale": layer_pools["v_scale"]
                    .at[page_ids, slots]
                    .set(sv),
                }
            else:
                layer_pools = {
                    "k": layer_pools["k"].at[page_ids, slots].set(k),
                    "v": layer_pools["v"].at[page_ids, slots].set(v),
                }
        o = attend(q, layer_pools)
        return decode_layer_out(x, layer, cfg, o), layer_pools

    with jax.named_scope("layers"):
        x, pools = lax.scan(body, x, (params["layers"], pools))
    with jax.named_scope("lm_head"):
        embeds = rms_norm(x, params["norm"], cfg.norm_eps)
        logits = embeds @ params["lm_head"]
    return logits, embeds, pools
