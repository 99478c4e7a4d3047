"""Mixtral (sparse MoE Llama-family) — trainable model + speculator base.

The reference touches Mixtral only as a frozen speculator base
(``EmbedMixtral``, ref:speculator/train_speculator_utils.py:500-569,
with the model math imported from fms). Here it is both that frozen base
and a first-class trainable family: Llama-style attention (GQA + RoPE +
RMSNorm) with the FFN replaced by a top-k-of-E SwiGLU mixture, trained
with expert parallelism over the mesh's "expert" axis.

Two MoE implementations, selected by ``moe_impl``:

- ``"dense"`` (default; the frozen-base path): every expert computes every
  token, mixed by the renormalized top-k softmax weights. Exact and
  jit-trivial; costs E/top_k extra FFN FLOPs — fine for a frozen teacher.
- ``"dispatch"`` (the training path): capacity-based routing moved by one
  scatter-add and one gather. Each expert processes at most
  ``capacity = capacity_factor * top_k * S / E`` tokens per batch row;
  first choices fill buffers before second choices; overflow tokens drop
  that expert's contribution (their residual stream passes through).
  When the mesh has an expert axis > 1, the batch->expert reshard is an
  explicit ``lax.all_to_all`` in a shard_map manual over only that axis
  (``_moe_ffn_dispatch_a2a``); single-axis meshes use the plain GSPMD
  formulation.
- ``"dispatch_einsum"``: the same routing semantics expressed as
  GShard-style (B, S, E, C) one-hot einsums. Kept as the oracle the
  scatter path is tested against — the dispatch+combine einsum pair costs
  ``2 * B*S*E*C*D`` MACs with ``E*C = capacity_factor*top_k*S``
  (quadratic in S; ~25-50% of the expert FFN FLOPs at Mixtral shapes),
  where the scatter path is O(B*S*top_k*D) data movement.

The training path also returns the load-balancing auxiliary loss
(Switch-style f.p product, pre-scaled by cfg.aux_loss_weight).
"""

import functools
import itertools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from fms_fsdp_tpu.models.configs import MixtralConfig
from fms_fsdp_tpu.models.llama import attention_block
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.quant import expert_matmul
from fms_fsdp_tpu.ops.rope import rope_table
from fms_fsdp_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    AXIS_DCN,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_REPLICA,
    AXIS_TENSOR,
    DATA_AXES,
)
from fms_fsdp_tpu.parallel.sharding import constrain as _constrain

__all__ = [
    "MixtralConfig",
    "init_mixtral_params",
    "mixtral_forward",
    "mixtral_param_specs",
]

Params = Dict[str, Any]


def init_mixtral_params(key, cfg: MixtralConfig, dtype=jnp.float32) -> Params:
    d, hd, h, E = cfg.emb_dim, cfg.head_dim, cfg.hidden_dim, cfg.num_experts
    std = 0.02
    out_std = std / (2 * cfg.nlayers) ** 0.5
    keys = jax.random.split(key, 10)

    def tn(k, shape, s=std):
        return (
            jax.random.truncated_normal(k, -3, 3, shape, jnp.float32) * s
        ).astype(dtype)

    L = cfg.nlayers
    layers = {
        "attn_norm": jnp.ones((L, d), dtype),
        "wq": tn(keys[0], (L, d, cfg.nheads * hd)),
        "wk": tn(keys[1], (L, d, cfg.kvheads * hd)),
        "wv": tn(keys[2], (L, d, cfg.kvheads * hd)),
        "wo": tn(keys[3], (L, cfg.nheads * hd, d), out_std),
        "ffn_norm": jnp.ones((L, d), dtype),
        "gate": tn(keys[4], (L, d, E)),
        "w1": tn(keys[5], (L, E, d, h)),
        "w3": tn(keys[6], (L, E, d, h)),
        "w2": tn(keys[7], (L, E, h, d), out_std),
    }
    return {
        "embedding": tn(keys[8], (cfg.src_vocab_size, d)),
        "layers": layers,
        "norm": jnp.ones((d,), dtype),
        "lm_head": tn(keys[9], (d, cfg.src_vocab_size)),
    }


def mixtral_param_specs(scan: bool = True) -> Dict[str, Any]:
    """PartitionSpec tree: attention follows the Llama megatron layout;
    expert weights shard E over "expert" AND each expert's matrices over
    fsdp/tensor — EP composes with ZeRO-3 and TP instead of replacing
    them."""
    l = (None,) if scan else ()
    layers = {
        "attn_norm": P(*l, None),
        "wq": P(*l, AXIS_FSDP, AXIS_TENSOR),
        "wk": P(*l, AXIS_FSDP, AXIS_TENSOR),
        "wv": P(*l, AXIS_FSDP, AXIS_TENSOR),
        "wo": P(*l, AXIS_TENSOR, AXIS_FSDP),
        "ffn_norm": P(*l, None),
        # router weight replicated like the norms: it is trivially small
        # (D x E), and a D-over-fsdp-sharded router makes SPMD prefer the
        # (B, S, D) activation D-sharded too — the reshard back to batch
        # sharding is an involuntary-full-remat in the remat'd backward
        "gate": P(*l, None, None),
        "w1": P(*l, AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR),
        "w3": P(*l, AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR),
        "w2": P(*l, AXIS_EXPERT, AXIS_TENSOR, AXIS_FSDP),
    }
    return {
        "embedding": P(AXIS_TENSOR, AXIS_FSDP),
        "layers": layers,
        "norm": P(None),
        "lm_head": P(AXIS_FSDP, AXIS_TENSOR),
    }


def moe_capacity(cfg: MixtralConfig, seq_len: int) -> int:
    """Static per-expert buffer size per batch row."""
    return max(
        1,
        int(
            math.ceil(
                cfg.capacity_factor * cfg.top_k * seq_len / cfg.num_experts
            )
        ),
    )


@scoped("moe_router")
def _router(h, gate_w, cfg: MixtralConfig):
    """Shared routing math: renormalized top-k weights + aux loss.

    Returns (top_idx (B,S,K) int, top_w (B,S,K) fp32, aux scalar fp32).
    Router math is fp32 (softmax over logits from a bf16 matmul is
    routing-decision-critical; the matmul itself is tiny: D x E).
    """
    logits = (h @ gate_w).astype(jnp.float32)  # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch eq. 4 generalized to top-k):
    # E * sum_e (fraction of choices routed to e) * (mean router prob of e);
    # minimized at 1.0 by a uniform router.
    E = cfg.num_experts
    choice = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # (B, S, K, E)
    f = jnp.mean(jnp.sum(choice, axis=2), axis=(0, 1)) / cfg.top_k
    p = jnp.mean(probs, axis=(0, 1))
    aux = cfg.aux_loss_weight * E * jnp.sum(f * p)
    return top_idx, top_w, aux


def _moe_stats(aux, keep=None):
    """Per-layer MoE stats: the load-balancing loss term plus the
    fraction of routing choices dropped by capacity overflow (0 for the
    dense path, which never drops)."""
    drop = (
        1.0 - jnp.mean(keep.astype(jnp.float32))
        if keep is not None
        else jnp.zeros((), jnp.float32)
    )
    return {"balance": aux, "drop_frac": drop}


def _expert_mix(top_idx, top_w, E: int, first: int = 0):
    """The top-k weights as a (B, S, E) fp32 mixture over the ``E``
    experts held, ids ``first`` to ``first + E``: exactly zero for an
    expert the row did not choose, and a choice of an expert that is not
    held adds to no column (the one-hot of an id out of range is all
    zero). ``first = 0`` with every expert held is the whole layer's."""
    if first:
        top_idx = top_idx - first
    return jnp.sum(
        jax.nn.one_hot(top_idx, E, dtype=jnp.float32) * top_w[..., None],
        axis=-2,
    )


def _all_experts_swiglu(h, lp):
    """The SwiGLU of every row in every expert held, over the layer's
    stacked weights where they lie. h (B, S, D); w1/w3 (E, D, H); w2
    (E, H, D), E the experts held. Returns (B, S, E, D)."""
    return jnp.einsum(
        "bseh,ehd->bsed",
        jax.nn.silu(jnp.einsum("bsd,edh->bseh", h, lp["w1"]))
        * jnp.einsum("bsd,edh->bseh", h, lp["w3"]),
        lp["w2"],
    )


@scoped("moe_dense")
def _moe_ffn_dense(h, lp, cfg: MixtralConfig):
    """Dense-mix top-k MoE SwiGLU (every expert computes every token).
    h (B, S, D); w1/w3 (E, D, H); w2 (E, H, D)."""
    top_idx, top_w, aux = _router(h, lp["gate"], cfg)
    mix = _expert_mix(top_idx, top_w, cfg.num_experts)  # (B, S, E)
    expert_out = _all_experts_swiglu(h, lp)  # (B, S, E, D)
    y = jnp.einsum("bse,bsed->bsd", mix.astype(h.dtype), expert_out)
    return y, _moe_stats(aux)


def _priority_slots(top_idx, E: int, C: int):
    """Per-choice expert-buffer slots under priority routing.

    Choice round k claims an expert's slots only after rounds < k have
    claimed theirs; within a round, tokens claim in sequence order.
    Returns ``(slot, keep)``, both (B, S, K): the buffer position within
    the chosen expert and whether it fit under capacity C.
    """
    counts = jnp.zeros((top_idx.shape[0], 1, E), jnp.int32)
    slots = []
    for k in range(top_idx.shape[-1]):
        mask_k = jax.nn.one_hot(top_idx[:, :, k], E, dtype=jnp.int32)
        pos_k = jnp.cumsum(mask_k, axis=1) - mask_k + counts  # (B, S, E)
        slots.append(
            jnp.take_along_axis(pos_k, top_idx[:, :, k, None], axis=-1)[..., 0]
        )
        counts = counts + jnp.sum(mask_k, axis=1, keepdims=True)
    slot = jnp.stack(slots, axis=-1)
    return slot, slot < C


def _expert_swiglu(xd, w1, w3, w2, quant, constrain_hidden=lambda t: t):
    """Per-expert SwiGLU chain over an E-major (E, B, C, D) tensor; the
    (E, B, C, H) hidden passes through ``constrain_hidden`` so each
    caller can apply its own layout (the manual-region caller must not
    mention the expert axis). Shared so the matmul/quant chain cannot
    drift between the GSPMD and all-to-all paths.

    E-major because E is the batch dim of the per-expert dot_generals and
    dot_general batch dims lead the output — B-major activations would
    pay a full relayout of every (E, B, C, H) product (int32-wide on the
    int8 path), measured as a net slowdown at Mixtral bench shapes."""
    hidden = jax.nn.silu(expert_matmul(xd, w1, quant=quant)) * expert_matmul(
        xd, w3, quant=quant
    )
    return expert_matmul(constrain_hidden(hidden), w2, quant=quant)


@scoped("expert_ffn")
def _expert_ffn(xd, lp, mesh, quant: str = "none"):
    """Expert SwiGLU with full GSPMD sharding: E over "expert", batch
    over dcn/replica/fsdp (tokens never leave their slice — the a2a pair
    stays on ICI), hidden width over "tensor"."""
    ep_spec = P(AXIS_EXPERT, (AXIS_DCN, AXIS_REPLICA, AXIS_FSDP), None, None)
    xd = _constrain(xd, ep_spec, mesh)
    out_e = _expert_swiglu(
        xd,
        lp["w1"],
        lp["w3"],
        lp["w2"],
        quant,
        lambda t: _constrain(
            t,
            P(
                AXIS_EXPERT,
                (AXIS_DCN, AXIS_REPLICA, AXIS_FSDP),
                None,
                AXIS_TENSOR,
            ),
            mesh,
        ),
    )
    return _constrain(out_e, ep_spec, mesh)


def _fill_expert_buffer(h, top_idx, slot, keep, C: int, E: int):
    """Scatter local batch rows into the flat E-major expert buffer.

    Returns (dest (B*S*K,) flat row indices — dropped choices point at
    the dump row — and the (E, B, C, D) buffer with the dump row sliced
    off). Shared by the single-program and all-to-all dispatch paths so
    the index arithmetic cannot drift between them.
    """
    B, S, D = h.shape
    K = top_idx.shape[-1]
    b_ix = jnp.arange(B, dtype=top_idx.dtype)[:, None, None]
    dest = jnp.where(keep, (top_idx * B + b_ix) * C + slot, E * B * C)
    dest = dest.reshape(B * S * K)
    src = jnp.broadcast_to(h[:, :, None, :], (B, S, K, D)).reshape(B * S * K, D)
    buf = jnp.zeros((E * B * C + 1, D), h.dtype).at[dest].add(src)
    return dest, buf[: E * B * C].reshape(E, B, C, D)


def _combine_from_buffer(out_e, dest, top_w, S: int):
    """Gather each choice's expert output back (dump row reads as the
    appended zero row) and mix with the renormalized router weights."""
    E, B, C, D = out_e.shape
    K = top_w.shape[-1]
    out_flat = jnp.concatenate(
        [out_e.reshape(E * B * C, D), jnp.zeros((1, D), out_e.dtype)], axis=0
    )
    gathered = jnp.take(out_flat, dest, axis=0).reshape(B, S, K, D)
    return jnp.einsum("bskd,bsk->bsd", gathered, top_w.astype(out_e.dtype))


@scoped("moe_dispatch")
def _moe_ffn_dispatch(
    h, lp, cfg: MixtralConfig, mesh: Optional[Mesh], quant: str = "none"
):
    """Capacity-based dispatch via scatter/gather — the training default.

    Routing semantics are identical to ``_moe_ffn_dispatch_einsum``
    (priority slot claiming, overflow drop), but token movement is one
    scatter-add into the flat (E*B*C)-row expert buffer and one gather
    back — O(B*S*K*D) HBM traffic, the same op class as an embedding
    update — instead of one-hot einsums whose MAC count is quadratic in
    S. Dropped choices target a trailing dump row that is sliced off
    before expert compute and gathered back as zeros. The buffer is laid
    out E-major (see ``_expert_ffn``).
    """
    B, S, D = h.shape
    E = cfg.num_experts
    C = moe_capacity(cfg, S)
    top_idx, top_w, aux = _router(h, lp["gate"], cfg)
    slot, keep = _priority_slots(top_idx, E, C)

    dest, xd = _fill_expert_buffer(h, top_idx, slot, keep, C, E)
    out_e = _expert_ffn(xd, lp, mesh, quant)
    y = _combine_from_buffer(out_e, dest, top_w, S)
    y = _constrain(y, P(DATA_AXES, AXIS_CONTEXT, None), mesh)
    return y, _moe_stats(aux, keep)


def _use_expert_a2a(
    cfg: MixtralConfig, mesh: Optional[Mesh], batch_size: int
) -> bool:
    """The explicit all-to-all path applies when the mesh actually has an
    expert axis to exchange over, it divides the expert count, and it
    divides the global batch (every shard_map input is batch-sharded on
    the expert axis, so a non-divisible batch fails at trace time)."""
    if mesh is None or AXIS_EXPERT not in mesh.shape:
        return False
    ep = int(mesh.shape[AXIS_EXPERT])
    if ep <= 1:
        return False
    if cfg.num_experts % ep != 0:
        import warnings

        warnings.warn(
            f"num_experts={cfg.num_experts} is not divisible by the expert"
            f" axis extent {ep}: falling back to the GSPMD dispatch, whose"
            " expert reshard replicates the token buffer across the expert"
            " axis (~E/top_k x the minimal all-to-all traffic). Pick"
            " expert_parallel_size dividing num_experts.",
            stacklevel=3,
        )
        return False
    if batch_size % ep != 0:
        import warnings

        warnings.warn(
            f"global batch {batch_size} is not divisible by the expert axis"
            f" extent {ep}: falling back to the GSPMD dispatch. Pick a batch"
            " size divisible by expert_parallel_size to enable the explicit"
            " all-to-all EP exchange.",
            stacklevel=3,
        )
        return False
    return True


@scoped("moe_dispatch_a2a")
def _moe_ffn_dispatch_a2a(
    h, lp, cfg: MixtralConfig, mesh: Mesh, quant: str = "none"
):
    """Scatter dispatch with an explicit expert-axis all-to-all (EP).

    Identical routing semantics to ``_moe_ffn_dispatch``, but the
    batch->expert reshard is written as ``lax.all_to_all`` inside a
    shard_map that is manual over ONLY the "expert" mesh axis — the
    fsdp/tensor sharding of the expert weights and the replica/fsdp
    sharding of the local batch stay with GSPMD. Left to GSPMD, the flat
    scatter/gather's expert reshard lowers to replicating the token
    buffer across the expert axis ("involuntary full rematerialization"
    SPMD warnings; ~E/top_k x the minimal traffic). The explicit a2a
    pair moves each token's top_k rows exactly once — the classic
    GShard/Switch EP exchange.

    Each shard scatters its local batch rows into a full (E, B_loc, C, D)
    buffer, the a2a splits the E dim across expert shards while
    concatenating the sender batches, experts compute on (E/ep,
    B_loc*ep, C, D), and the inverse a2a brings each token's rows home
    for the weighted combine.

    The router (and all stats) run OUTSIDE the manual region and the
    routing tensors enter the body batch-sharded: the body must have no
    expert-replicated differentiable inputs, because the shard_map
    transpose would psum their cotangents over the expert axis inside
    the manual region, and a bf16 all-reduce there crashes XLA:CPU's
    AllReducePromotion pass ("Invalid binary instruction opcode copy").
    """
    E = cfg.num_experts
    top_idx, top_w, aux = _router(h, lp["gate"], cfg)
    C = moe_capacity(cfg, h.shape[1])
    slot, keep = _priority_slots(top_idx, E, C)

    def body(h, top_idx, slot, keep, top_w, w1, w3, w2):
        S = h.shape[1]  # h here is this expert shard's batch rows
        dest, buf = _fill_expert_buffer(h, top_idx, slot, keep, C, E)
        xd = lax.all_to_all(
            buf, AXIS_EXPERT, split_axis=0, concat_axis=1, tiled=True
        )  # (E/ep, B*ep, C, D)
        # pin the token dim to the data axes and D to replicated: without
        # this, w1's (fsdp, tensor) sharding back-propagates a D-over-fsdp
        # preference through the buffer scatter into the residual stream,
        # which GSPMD can only satisfy by involuntary full remat. The
        # expert dim is manual here, so only auto axes may appear.
        token_spec = P(None, (AXIS_DCN, AXIS_REPLICA, AXIS_FSDP), None, None)
        xd = _constrain(xd, token_spec, mesh)
        out = _expert_swiglu(
            xd,
            w1,
            w3,
            w2,
            quant,
            # expert dim is manual here; only auto axes may appear
            lambda t: _constrain(t, P(None, None, None, AXIS_TENSOR), mesh),
        )
        out = _constrain(out, token_spec, mesh)
        out = lax.all_to_all(
            out, AXIS_EXPERT, split_axis=1, concat_axis=0, tiled=True
        )  # (E, B, C, D)
        return _combine_from_buffer(out, dest, top_w, S)

    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
            P(AXIS_EXPERT),
        ),
        out_specs=P(AXIS_EXPERT),
        axis_names=frozenset({AXIS_EXPERT}),
        check_vma=False,
    )(h, top_idx, slot, keep, top_w, lp["w1"], lp["w3"], lp["w2"])
    y = _constrain(y, P(DATA_AXES, AXIS_CONTEXT, None), mesh)
    return y, _moe_stats(aux, keep)


def _moe_ffn_dispatch_einsum(
    h, lp, cfg: MixtralConfig, mesh: Optional[Mesh], quant: str = "none"
):
    """Capacity-based einsum dispatch (GShard style) — oracle path.

    Builds (B, S, E, C) one-hot dispatch/combine tensors with first
    choices filling expert buffers before second choices, gathers tokens
    into an E-major (E, B, C, D) dispatched tensor, runs every expert's
    SwiGLU as batched matmuls, and scatters back weighted by the
    renormalized router weights.
    """
    B, S, D = h.shape
    E, K = cfg.num_experts, cfg.top_k
    C = moe_capacity(cfg, S)
    top_idx, top_w, aux = _router(h, lp["gate"], cfg)
    slot, keep = _priority_slots(top_idx, E, C)

    dispatch = jnp.zeros((B, S, E, C), h.dtype)
    combine = jnp.zeros((B, S, E, C), h.dtype)
    for k in range(K):
        d_k = (
            jax.nn.one_hot(top_idx[:, :, k], E, dtype=jnp.float32)[..., None]
            * jax.nn.one_hot(slot[:, :, k], C, dtype=jnp.float32)[:, :, None, :]
            * keep[:, :, k, None, None]
        ).astype(h.dtype)
        dispatch = dispatch + d_k
        combine = combine + d_k * top_w[:, :, k, None, None].astype(h.dtype)

    xd = jnp.einsum("bsec,bsd->ebcd", dispatch, h)
    out_e = _expert_ffn(xd, lp, mesh, quant)
    y = jnp.einsum("bsec,ebcd->bsd", combine, out_e)
    y = _constrain(y, P(DATA_AXES, AXIS_CONTEXT, None), mesh)
    return y, _moe_stats(aux, keep)


# ---------------------------------------------------------------------------
# cached decode (serving path — serve/families/mixtral.py)
# ---------------------------------------------------------------------------
#
# The attention half reuses the llama decode split (decode_layer_qkv /
# gqa_attend — the mixtral layer dict carries the same attn key names on
# purpose), so paged-vs-dense bit-parity rests on the exact zero-page
# argument serve/decode.py documents. The FFN half routes ONE token:
# ``moe_impl="dense"`` replays `_moe_ffn_dense` (every expert computes,
# mixed by the renormalized top-k weights — the parity mode, exact vs the
# dense forward); ``"routed"``, the serving default, lets only the routed
# (row, expert) pairs contribute and reads each expert's weights once,
# where they lie in the layer's stacked w1/w3/w2: every operand of its
# products is the stack itself or one `dynamic_index_in_dim` of it, which
# XLA fuses into the dot, never a gathered (B, m, K, D, H) copy. A decode
# step is bound by the weight bytes it reads, ``min(n, E)`` expert copies
# a layer for ``n = B * m * top_k`` routed pairs, so the loop order
# follows that static shape (`routed_moe_form`):
#
# - ``"all_experts"`` (``n >= E``: nearly every expert is hit) streams
#   every expert once over all rows, `_moe_ffn_dense`'s arithmetic;
#   an expert a row did not choose carries an exactly-zero mix weight;
# - ``"per_pair"`` (``n < E``: one or two live streams) runs one product
#   per routed pair over the expert the pair names, at most E - 1 of them.
#
# Both produce the dense mixture, which tests/test_serving_families.py
# pins.


def routed_moe_form(n_pairs: int, num_experts: int) -> str:
    """The loop order of ``moe_impl="routed"`` for ``n_pairs`` routed
    (row, choice) pairs a step on the ``num_experts`` experts held: a
    static fact of the program's shape. (A program that holds a share of
    the experts still loops over every routed pair in the second form:
    which of them land on its share is known on the device alone.)"""
    return "all_experts" if n_pairs >= num_experts else "per_pair"


def _moe_token(h, lp, cfg: MixtralConfig, moe_impl: str = "dense"):
    """Single-position MoE FFN. h (B, m, D) post-ffn_norm."""
    if moe_impl == "dense":
        return _moe_ffn_dense(h, lp, cfg)[0]
    assert moe_impl == "routed", f"unknown decode moe_impl {moe_impl!r}"
    top_idx, top_w, _ = _router(h, lp["gate"], cfg)  # (B, m, K)
    B, m, K = top_idx.shape
    E = cfg.num_experts
    # ``moe_gather`` is around what selects the experts' weights (the
    # mix, or the index into the stack); streaming them is ``moe_experts``
    if routed_moe_form(B * m * K, E) == "all_experts":
        with jax.named_scope("moe_gather"):
            mix = _expert_mix(top_idx, top_w, E).astype(h.dtype)
        with jax.named_scope("moe_experts"):
            out = _all_experts_swiglu(h, lp)  # (B, m, E, D)
        with jax.named_scope("moe_combine"):
            return jnp.einsum("bme,bmed->bmd", mix, out)
    rows = h.reshape(B * m, -1)
    ids = top_idx.reshape(B * m, K)
    out = []
    for r, k in itertools.product(range(B * m), range(K)):
        with jax.named_scope("moe_gather"):
            w1, w3, w2 = (
                lax.dynamic_index_in_dim(lp[w], ids[r, k], 0, keepdims=False)
                for w in ("w1", "w3", "w2")
            )
        with jax.named_scope("moe_experts"):
            out.append((jax.nn.silu(rows[r] @ w1) * (rows[r] @ w3)) @ w2)
    with jax.named_scope("moe_combine"):
        out = jnp.stack(out).reshape(B, m, K, -1)
        return jnp.einsum("bmkd,bmk->bmd", out, top_w.astype(h.dtype))


def _mixtral_decode_layer_out(x, layer, cfg: MixtralConfig, o, moe_impl: str):
    """Post-attention half of one decode layer: residual + routed MoE.
    Shared by the dense-cache reference walk and the paged decode step so
    the two cannot drift (the llama decode_layer_out analog)."""
    with jax.named_scope("attn_out"):
        x = x + o @ layer["wo"]
    # the norm that feeds the router and the experts counts with the
    # router, the residual add with the combine
    with jax.named_scope("moe_router"):
        h2 = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
    moe = _moe_token(h2, layer, cfg, moe_impl)
    with jax.named_scope("moe_combine"):
        return x + moe


def mixtral_prefill(
    params: Params,
    tokens,
    cfg: MixtralConfig,
    max_seq_len: int,
    compute_dtype=jnp.bfloat16,
    full_logits: bool = False,
):
    """Prompt prefill building the dense kv cache — the mixtral analog of
    models/generation.py::prefill (same cache layout (L, B, S_max, Nkv,
    H), zeros beyond the written prefix), with the FFN as the dense-mix
    MoE. Returns (logits, embeds, {"k", "v"} cache)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    b, s = tokens.shape
    hd, nkv = cfg.head_dim, cfg.n_kv_heads

    with jax.named_scope("rope"):
        cos, sin = rope_table(max_seq_len, hd, cfg.rope_theta)
    with jax.named_scope("embed"):
        x = params["embedding"][tokens]

    def body(x, layer):
        from fms_fsdp_tpu.ops.attention import attention
        from fms_fsdp_tpu.ops.rope import apply_rotary

        with jax.named_scope("qkv"):
            h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q = (h @ layer["wq"]).reshape(b, s, cfg.nheads, hd)
            k = (h @ layer["wk"]).reshape(b, s, nkv, hd)
            v = (h @ layer["wv"]).reshape(b, s, nkv, hd)
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        with jax.named_scope("attn"):
            o = attention(q, k, v, causal=True, impl="xla")
        with jax.named_scope("attn_out"):
            x = x + o.reshape(b, s, cfg.nheads * hd) @ layer["wo"]
        with jax.named_scope("moe_dense"):
            h2 = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
            x = x + _moe_ffn_dense(h2, layer, cfg)[0]
        pad = [(0, 0), (0, max_seq_len - s), (0, 0), (0, 0)]
        return x, (jnp.pad(k, pad), jnp.pad(v, pad))

    with jax.named_scope("layers"):
        x, (k_cache, v_cache) = lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        embeds = rms_norm(x, params["norm"], cfg.norm_eps)
        src = embeds if full_logits else embeds[:, -1:]
        logits = src @ params["lm_head"]
    return logits, embeds, {"k": k_cache, "v": v_cache}


def mixtral_decode_step(
    params: Params,
    cache,
    token,
    pos,
    cfg: MixtralConfig,
    compute_dtype=jnp.bfloat16,
    moe_impl: str = "dense",
):
    """One dense-cache decode step — the family's parity reference walk.
    token (B, 1) int32 at position ``pos``. Returns (logits (B, V),
    updated cache)."""
    from fms_fsdp_tpu.models.generation import decode_layer_qkv
    from fms_fsdp_tpu.ops.paged_attention import gqa_attend

    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    b, m = token.shape
    max_seq = cache["k"].shape[2]
    with jax.named_scope("rope"):
        cos, sin = rope_table(max_seq, cfg.head_dim, cfg.rope_theta)
    positions = jnp.broadcast_to(
        pos + jnp.arange(m, dtype=jnp.int32)[None, :], (b, m)
    )
    with jax.named_scope("embed"):
        x = params["embedding"][token]

    def body(x, inp):
        layer, k_cache, v_cache = inp
        q, k, v = decode_layer_qkv(x, layer, cfg, cos, sin, positions)
        with jax.named_scope("kv_write"):
            k_cache = lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
            v_cache = lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
        o = gqa_attend(q, k_cache, v_cache, positions)
        return (
            _mixtral_decode_layer_out(x, layer, cfg, o, moe_impl),
            (k_cache, v_cache),
        )

    with jax.named_scope("layers"):
        x, (k_cache, v_cache) = lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"])
        )
    with jax.named_scope("lm_head"):
        embeds = rms_norm(x, params["norm"], cfg.norm_eps)
        logits = embeds @ params["lm_head"]
    return logits[:, 0], {"k": k_cache, "v": v_cache}


def mixtral_paged_decode_step(
    params: Params,
    pools,
    page_table,
    seq_lens,
    tokens,
    cfg: MixtralConfig,
    *,
    page_size: int,
    compute_dtype=jnp.bfloat16,
    moe_impl: str = "dense",
):
    """One ragged paged decode step — serve/decode.py::paged_decode_step
    with the FFN swapped for the routed MoE. tokens (B,) int32 at
    positions ``seq_lens``; pools is the adapter's PagedKVCache.pools.
    Returns (logits (B, V), pools)."""
    from fms_fsdp_tpu.models.generation import decode_layer_qkv
    from fms_fsdp_tpu.ops.paged_attention import gather_pages, gqa_attend

    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    b = tokens.shape[0]
    max_seq = page_table.shape[1] * page_size
    with jax.named_scope("rope"):
        cos, sin = rope_table(max_seq, cfg.head_dim, cfg.rope_theta)
    positions = seq_lens[:, None].astype(jnp.int32)
    with jax.named_scope("embed"):
        x = params["embedding"][tokens[:, None]]

    with jax.named_scope("kv_write"):  # each row's write target
        rows = jnp.arange(b)
        page_ids = page_table[rows, seq_lens // page_size]
        slots = seq_lens % page_size

    def body(x, inp):
        layer, layer_pools = inp
        q, k, v = decode_layer_qkv(x, layer, cfg, cos, sin, positions)
        with jax.named_scope("kv_write"):
            layer_pools = {
                "k": layer_pools["k"].at[page_ids, slots].set(k[:, 0]),
                "v": layer_pools["v"].at[page_ids, slots].set(v[:, 0]),
            }
        o = gqa_attend(
            q,
            gather_pages(layer_pools["k"], page_table),
            gather_pages(layer_pools["v"], page_table),
            positions,
        )
        return _mixtral_decode_layer_out(x, layer, cfg, o, moe_impl), layer_pools

    with jax.named_scope("layers"):
        x, pools = lax.scan(body, x, (params["layers"], pools))
    with jax.named_scope("lm_head"):
        embeds = rms_norm(x, params["norm"], cfg.norm_eps)
        logits = embeds @ params["lm_head"]
    return logits[:, 0], pools


def _mixtral_block(
    x,
    layer: Params,
    cfg: MixtralConfig,
    cos,
    sin,
    *,
    attn_impl: str,
    mesh: Optional[Mesh],
    quant: str,
    moe_impl: str,
):
    x = attention_block(
        x, layer, cfg, cos, sin, attn_impl=attn_impl, mesh=mesh, quant=quant
    )

    h = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
    if moe_impl == "dispatch":
        if _use_expert_a2a(cfg, mesh, h.shape[0]):
            y, aux = _moe_ffn_dispatch_a2a(h, layer, cfg, mesh, quant)
        else:
            y, aux = _moe_ffn_dispatch(h, layer, cfg, mesh, quant)
    elif moe_impl == "dispatch_einsum":
        y, aux = _moe_ffn_dispatch_einsum(h, layer, cfg, mesh, quant)
    else:
        y, aux = _moe_ffn_dense(h, layer, cfg)
    return x + y, aux


def mixtral_forward(
    params: Params,
    tokens,
    cfg: MixtralConfig,
    *,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "xla",
    ac_mask: Optional[List[bool]] = None,
    scan_layers: bool = True,
    mesh: Optional[Mesh] = None,
    moe_impl: str = "dense",
    return_embeds: bool = False,
    return_hidden: bool = False,
    return_aux: bool = False,
    quant: str = "none",
    **_unused,
):
    """tokens (B, S) -> logits (B, S, V) in the compute dtype.

    ``return_aux`` additionally returns a stats dict — ``"balance"``,
    the summed (pre-weighted) load-balancing loss the train step adds to
    the objective, and ``"drop_frac"``, the layer-mean fraction of
    routing choices dropped by capacity overflow (reported as a metric).
    ``return_embeds`` returns final hidden states (the frozen-base
    Embed* contract); ``return_hidden`` returns only them (fused-loss
    path).
    """
    params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    b, s = tokens.shape
    nlayers = params["layers"]["wq"].shape[0]
    from fms_fsdp_tpu.parallel.sharding import embed_lookup

    x = embed_lookup(params["embedding"], tokens, mesh)
    cos, sin = rope_table(s, cfg.head_dim, cfg.rope_theta)

    block = functools.partial(
        _mixtral_block,
        cfg=cfg,
        cos=cos,
        sin=sin,
        attn_impl=attn_impl,
        mesh=mesh,
        quant=quant,
        moe_impl=moe_impl,
    )
    ac_mask = ac_mask if ac_mask is not None else [False] * nlayers
    uniform = all(ac_mask) or not any(ac_mask)

    if scan_layers and uniform:
        body = block
        if all(ac_mask):
            body = jax.checkpoint(block, prevent_cse=False)

        def scan_fn(carry, layer):
            y, stats = body(carry, layer)
            return y, stats

        x, stats_stack = lax.scan(scan_fn, x, params["layers"])
        aux_total = {
            "balance": jnp.sum(stats_stack["balance"]),
            "drop_frac": jnp.mean(stats_stack["drop_frac"]),
        }
    else:
        remat_block = jax.checkpoint(block, prevent_cse=False)
        per_layer = []
        for i in range(nlayers):
            layer = jax.tree.map(lambda a: a[i], params["layers"])
            x, stats = (remat_block if ac_mask[i] else block)(x, layer)
            per_layer.append(stats)
        aux_total = {
            "balance": sum(s["balance"] for s in per_layer),
            "drop_frac": sum(s["drop_frac"] for s in per_layer) / nlayers,
        }

    embeds = rms_norm(x, params["norm"], cfg.norm_eps)
    if return_hidden:
        return (embeds, aux_total) if return_aux else embeds
    logits = embeds @ params["lm_head"]
    logits = _constrain(logits, P(DATA_AXES, AXIS_CONTEXT, AXIS_TENSOR), mesh)
    if return_embeds:
        return logits, embeds
    if return_aux:
        return logits, aux_total
    return logits
