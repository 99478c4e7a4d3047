"""Speculative decoding: the inference-side consumer of the trained
MLPSpeculator.

The reference trains speculators for fms-extras' speculative_generate;
this module closes the loop natively (beyond fms-fsdp itself, which ships
only the training half): the speculator proposes ``n_predict`` tokens per
step, the frozen base verifies the whole candidate chain in ONE cached
forward over n_predict+1 positions, and the longest matching prefix is
accepted — greedy speculative decoding reproduces plain greedy decoding
token-for-token while running the base ~(accepted+1) tokens per forward.

Single-candidate chain (no tree), greedy acceptance, batch size 1 (the
accepted length is data-dependent per row; a batched variant needs
per-row bookkeeping).
"""

from typing import Dict

import jax
import jax.numpy as jnp

from fms_fsdp_tpu.models.configs import LlamaConfig
from fms_fsdp_tpu.models.generation import decode_chunk, prefill
from fms_fsdp_tpu.models.speculator import (
    SpeculatorConfig,
    head_step,
    scale_input,
)


def speculator_propose(spec_params, embed, last_tok, scfg: SpeculatorConfig):
    """Greedy n_predict-token proposal chain. embed (B, D): the base
    hidden state that predicted ``last_tok`` (B,). Returns (B, n_predict)
    int32 — each head's argmax feeds the next head's token input
    (at inference the teacher-forced inds of speculator_forward are the
    chain of the speculator's own picks)."""
    state = scale_input(embed[:, None, :], scfg)  # (B, 1, D)

    tok = last_tok[:, None]  # (B, 1)
    outs = []
    for i in range(scfg.n_predict):
        state, logits = head_step(spec_params, scfg, state, tok, i)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, 1)
        outs.append(tok)
    return jnp.concatenate(outs, axis=1)  # (B, n_predict)


def speculative_decode(
    base_params,
    spec_params,
    input_ids,
    cfg: LlamaConfig,
    scfg: SpeculatorConfig,
    *,
    max_seq_len: int = 2048,
    max_new_tokens: int = 64,
) -> Dict[str, jnp.ndarray]:
    """Greedy speculative decoding. Returns {"tokens": (1, P+T),
    "accept_rate": mean accepted proposals per verification}.

    Output is token-identical to plain greedy decoding: a proposal is
    accepted only when it equals the base's own greedy pick, and the
    first mismatch position emits the base's pick instead.
    """
    assert input_ids.shape[0] == 1, "speculative_decode is B=1 (see module doc)"
    n = scfg.n_predict
    b, plen = input_ids.shape
    assert plen + max_new_tokens + n + 1 <= max_seq_len

    logits, embeds, cache = prefill(base_params, input_ids, cfg, max_seq_len)
    last_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)  # (B,)
    state_embed = embeds[:, -1]
    pos = plen

    chunk = jax.jit(decode_chunk, static_argnames=("cfg",))
    propose = jax.jit(speculator_propose, static_argnames=("scfg",))

    out = [int(last_tok[0])]
    accepted_counts = []
    while len(out) < max_new_tokens:
        props = propose(spec_params, state_embed, last_tok, scfg)  # (1, n)
        cand = jnp.concatenate([last_tok[:, None], props], axis=1)  # (1, n+1)
        logits, embeds, cache = chunk(base_params, cache, cand, pos, cfg)
        base_next = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (1, n+1)
        match = jnp.cumprod(
            (props == base_next[:, :-1]).astype(jnp.int32), axis=1
        )
        # ONE host sync per verification step — per-element int() pulls
        # would each pay a full device round trip
        props_h, next_h, match_h = jax.device_get((props, base_next, match))
        k = int(match_h[0].sum())  # accepted proposals (0..n)
        accepted_counts.append(k)
        out.extend([int(t) for t in props_h[0, :k]] + [int(next_h[0, k])])
        last_tok = base_next[:, k]
        state_embed = embeds[:, k]
        pos = pos + k + 1

    tokens = jnp.concatenate(
        [input_ids, jnp.asarray(out[:max_new_tokens], jnp.int32)[None, :]],
        axis=1,
    )
    rate = float(sum(accepted_counts)) / max(1, len(accepted_counts))
    return {"tokens": tokens, "accept_rate": rate}
