"""Hybrid state-space LM, TPU-native: Mamba-2 or Mamba-1 mixers with
interleaved attention layers.

Replaces the reference's external `mamba_ssm` dependency
(ref:main_training_mamba.py:8-13, MambaConfig dict at
ref:config_utils.py:162-185): a stack of pre-norm blocks where each block
is  residual + mixer(norm(residual)), then residual + mlp(norm2(residual))
(when d_intermediate > 0). One stack, the mixer chosen per layer:

- ``ssm_layer="Mamba2"`` (Bamba, mamba_9.8b): fused in_proj ->
  (z | xBC | dt), depthwise causal conv1d with silu over xBC, softplus dt
  with learned bias, negative-exponential A per head, chunked SSD scan
  (ops/ssd.py), gated RMSNorm (norm(y * silu(z))), out_proj;
- ``ssm_layer="Mamba1"`` (the Jamba hybrids): in_proj (u's matrix and
  the gate's, stacked) -> u, z; conv1d
  with silu over u alone, x_proj -> (dt | B | C) of widths (dt_rank,
  d_state, d_state) with an RMSNorm on each, dt_proj with bias and
  softplus, A of shape (d_inner, d_state), the selective scan of
  ops/selective_scan.py (a decay per channel and state), a plain gate
  (y * silu(z), no norm), out_proj;
- causal attention on `attn_layer_idx` layers, GQA down to one KV head,
  rotary over the first ``rotary_emb_dim`` dims of each head, over the
  whole head, or none at all (``rotary_emb_dim=0``: Jamba's attention has
  no positional embedding; the state-space layers carry the order);
- swiglu MLP (d_intermediate) after every mixer;
- fp32 residual stream (`residual_in_fp32`), RMSNorm everywhere, vocab
  padded to pad_vocab_size_multiple; the head is a matrix of its own or,
  with ``tie_embeddings``, the embedding (the tree then has no
  ``lm_head`` leaf).

Layers are heterogeneous, so the stack runs as an unrolled loop (not
lax.scan); params live in a per-layer list pytree.

Serving: recurrent decode from a constant-size slab for both mixers. A
Mamba-1 prompt is prefilled as a sequence, a chunk of ``PREFILL_CHUNK``
positions at a time through the whole stack, in a loop inside the one
program that stops at the prompt's length (``_prefill_sequence``: the
scan's state, the conv's last inputs and the K/V written so far go from
chunk to chunk; the last chunk's state is handed to the slab); a Mamba-2
prompt still scans the decode step over positions (ROADMAP A6).
"""

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from fms_fsdp_tpu.models import sequence_prefill as seq
from fms_fsdp_tpu.models.configs import MambaConfig
from fms_fsdp_tpu.models.mamba1 import (
    conv_step as _conv_step,
    mamba1_mixer as _mamba1_mixer,
    mamba1_mixer_step as _mamba1_mixer_step,
)
from fms_fsdp_tpu.obs.scopes import scoped
from fms_fsdp_tpu.ops.attention import attention, chunk_attention
from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.quant import matmul as qmatmul
from fms_fsdp_tpu.ops.rope import apply_rotary, rope_table
from fms_fsdp_tpu.ops.selective_scan import selective_scan
from fms_fsdp_tpu.ops.ssd import causal_conv1d, ssd_scan
from fms_fsdp_tpu.parallel.mesh import AXIS_CONTEXT, AXIS_FSDP, AXIS_TENSOR, DATA_AXES

Params = Dict[str, Any]


def _conv_dim(cfg: MambaConfig) -> int:
    return cfg.d_inner + 2 * cfg.ngroups * cfg.d_state


def _in_proj_dim(cfg: MambaConfig) -> int:
    return 2 * cfg.d_inner + 2 * cfg.ngroups * cfg.d_state + cfg.nheads


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_mamba_params(key, cfg: MambaConfig, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    v = cfg.padded_vocab_size
    H = cfg.nheads
    std = 0.02
    out_std = std / (2 * cfg.n_layer) ** 0.5

    def tn(k, shape, s):
        return (jax.random.truncated_normal(k, -3, 3, shape, jnp.float32) * s).astype(
            dtype
        )

    keys = iter(
        jax.random.split(key, (10 if cfg.mamba1 else 8) * cfg.n_layer + 4)
    )

    def mamba_mixer():
        # dt bias: softplus^-1 of dt ~ LogUniform[1e-3, 1e-1] (mamba2 init)
        u = jax.random.uniform(next(keys), (H,), jnp.float32)
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        dt = jnp.clip(dt, 1e-4)
        dt_bias = dt + jnp.log(-jnp.expm1(-dt))
        # A ~ Uniform[1, 16]
        A = jax.random.uniform(next(keys), (H,), jnp.float32, 1.0, 16.0)
        return {
            "in_proj": tn(next(keys), (d, _in_proj_dim(cfg)), std),
            "conv_w": tn(next(keys), (_conv_dim(cfg), cfg.d_conv), std * 10),
            "conv_b": jnp.zeros((_conv_dim(cfg),), dtype),
            "dt_bias": dt_bias.astype(dtype),
            "A_log": jnp.log(A).astype(dtype),
            "D": jnp.ones((H,), dtype),
            "norm": jnp.ones((cfg.d_inner,), dtype),
            "out_proj": tn(next(keys), (cfg.d_inner, d), out_std),
        }

    def mamba1_mixer():
        di, N, R = cfg.d_inner, cfg.d_state, cfg.dt_rank_
        # dt bias as above, per channel; A[c, n] = n + 1 (the S4D-real
        # init of mamba_ssm's Mamba-1)
        u = jax.random.uniform(next(keys), (di,), jnp.float32)
        dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        dt = jnp.clip(dt, 1e-4)
        A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (di, N))
        return {
            # u's matrix, then the gate's, stacked: each product reads
            # its own matrix and no fused product's output is split
            "in_proj": tn(next(keys), (2, d, di), std),
            "conv_w": tn(next(keys), (di, cfg.d_conv), std * 10),
            "conv_b": jnp.zeros((di,), dtype),
            "x_proj": tn(next(keys), (di, R + 2 * N), std),
            "dt_norm": jnp.ones((R,), dtype),
            "B_norm": jnp.ones((N,), dtype),
            "C_norm": jnp.ones((N,), dtype),
            "dt_proj": tn(next(keys), (R, di), R**-0.5),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(A).astype(dtype),
            "D": jnp.ones((di,), dtype),
            "out_proj": tn(next(keys), (di, d), out_std),
        }

    ssm_mixer = mamba1_mixer if cfg.mamba1 else mamba_mixer

    def attn_mixer():
        a = cfg.attn_cfg
        hd = a.head_dim
        return {
            "wq": tn(next(keys), (d, a.num_heads * hd), std),
            "wk": tn(next(keys), (d, a.num_heads_kv * hd), std),
            "wv": tn(next(keys), (d, a.num_heads_kv * hd), std),
            "wo": tn(next(keys), (a.num_heads * hd, d), out_std),
        }

    layers: List[Params] = []
    for i in range(cfg.n_layer):
        layer = {
            "norm": jnp.ones((d,), dtype),
            "mixer": attn_mixer() if i in cfg.attn_layer_idx else ssm_mixer(),
        }
        if cfg.d_intermediate > 0:
            layer["norm2"] = jnp.ones((d,), dtype)
            layer["mlp"] = {
                "w1": tn(next(keys), (d, cfg.d_intermediate), std),
                "w3": tn(next(keys), (d, cfg.d_intermediate), std),
                "w2": tn(next(keys), (cfg.d_intermediate, d), out_std),
            }
        layers.append(layer)

    params = {
        "embedding": tn(next(keys), (v, d), std),
        "layers": layers,
        "norm_f": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = tn(next(keys), (d, v), std)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


from fms_fsdp_tpu.parallel.sharding import constrain as _constrain  # noqa: E402


@scoped("mamba_mixer")
def _mamba_mixer(x, p: Params, cfg: MambaConfig, mesh, kernel="auto", quant="none"):
    """x (B, S, D) compute dtype -> (B, S, D)."""
    B, S, d = x.shape
    H, Pd, G, N = cfg.nheads, cfg.headdim, cfg.ngroups, cfg.d_state
    d_inner = cfg.d_inner

    zxbcdt = qmatmul(x, p["in_proj"], quant=quant)
    zxbcdt = _constrain(zxbcdt, P(DATA_AXES, AXIS_CONTEXT, AXIS_TENSOR), mesh)
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner : d_inner + _conv_dim(cfg)]
    dt_raw = zxbcdt[..., d_inner + _conv_dim(cfg) :]  # (B, S, H)

    xBC = causal_conv1d(xBC, p["conv_w"], p["conv_b"], activation="silu")
    xs = xBC[..., :d_inner].reshape(B, S, H, Pd)
    Bm = xBC[..., d_inner : d_inner + G * N].reshape(B, S, G, N)
    Cm = xBC[..., d_inner + G * N :].reshape(B, S, G, N)

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if mesh is not None and mesh.shape[AXIS_CONTEXT] > 1:
        # sequence sharded over the context axis: pass the inter-chunk
        # state across devices explicitly (ops/ssd.py::ssd_scan_cp) —
        # long context for the Mamba family, O(S/cp) per device, instead
        # of letting GSPMD gather the sequence around the chunk scan
        from fms_fsdp_tpu.ops.ssd import ssd_scan_cp

        y = ssd_scan_cp(
            xs, dt, A, Bm, Cm, p["D"], mesh=mesh, chunk_size=cfg.chunk_size,
            kernel=kernel,  # accepted for parity; the cp core is XLA
        )
    else:
        y = ssd_scan(
            xs, dt, A, Bm, Cm, p["D"], chunk_size=cfg.chunk_size,
            kernel=kernel, mesh=mesh,
        )
    y = y.reshape(B, S, d_inner)

    # gated RMSNorm: norm(y * silu(z)) (mamba2 norm_before_gate=False)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = qmatmul(y, p["out_proj"], quant=quant)
    return _constrain(out, P(DATA_AXES, AXIS_CONTEXT, None), mesh)


@scoped("attn_mixer")
def _attn_mixer(x, p: Params, cfg: MambaConfig, cos, sin, attn_impl, mesh, quant="none"):
    B, S, d = x.shape
    a = cfg.attn_cfg
    hd = a.head_dim
    q = qmatmul(x, p["wq"], quant=quant).reshape(B, S, a.num_heads, hd)
    k = qmatmul(x, p["wk"], quant=quant).reshape(B, S, a.num_heads_kv, hd)
    v = qmatmul(x, p["wv"], quant=quant).reshape(B, S, a.num_heads_kv, hd)

    # partial rotary: first rotary_emb_dim dims of each head
    r = a.rotary_emb_dim
    if r and r < hd:
        q = jnp.concatenate(
            [apply_rotary(q[..., :r], cos, sin), q[..., r:]], axis=-1
        )
        k = jnp.concatenate(
            [apply_rotary(k[..., :r], cos, sin), k[..., r:]], axis=-1
        )
    elif r:
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)

    if mesh is not None and mesh.shape[AXIS_CONTEXT] > 1:
        from fms_fsdp_tpu.ops.ring_attention import ring_attention

        o = ring_attention(q, k, v, mesh, causal=a.causal)
    else:
        o = attention(q, k, v, causal=a.causal, impl=attn_impl, mesh=mesh)
    o = qmatmul(o.reshape(B, S, a.num_heads * hd), p["wo"], quant=quant)
    return _constrain(o, P(DATA_AXES, AXIS_CONTEXT, None), mesh)


@scoped("mlp")
def _mlp(x, p: Params, mesh, quant="none"):
    gate = jax.nn.silu(qmatmul(x, p["w1"], quant=quant))
    up = qmatmul(x, p["w3"], quant=quant)
    h = _constrain(gate * up, P(DATA_AXES, AXIS_CONTEXT, AXIS_TENSOR), mesh)
    return _constrain(
        qmatmul(h, p["w2"], quant=quant), P(DATA_AXES, AXIS_CONTEXT, None), mesh
    )


@scoped("lm_head")
def _head(x, params: Params):
    """Logits of final-norm hidden states: the head's own matrix, or the
    embedding where the tree has no ``lm_head`` (``tie_embeddings``)."""
    if "lm_head" in params:
        return x @ params["lm_head"]
    return jnp.einsum("...d,vd->...v", x, params["embedding"])


def mamba_forward(
    params: Params,
    tokens,
    cfg: MambaConfig,
    *,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
    ac_mask: Optional[List[bool]] = None,
    scan_layers: bool = False,  # heterogeneous layers: always unrolled
    mesh: Optional[Mesh] = None,
    return_hidden: bool = False,
    quant: str = "none",
    mamba_kernel: str = "auto",
):
    """tokens (B, S) int32 -> logits (B, S, padded_vocab) in compute dtype."""
    del scan_layers
    params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    n_layer = len(params["layers"])
    ac_mask = ac_mask if ac_mask is not None else [False] * n_layer

    from fms_fsdp_tpu.parallel.sharding import embed_lookup

    x = embed_lookup(params["embedding"], tokens, mesh)
    residual = x.astype(jnp.float32)  # residual_in_fp32

    seq_len = tokens.shape[1]
    a = cfg.attn_cfg
    cos, sin = rope_table(seq_len, a.rotary_emb_dim or a.head_dim, 10000.0)

    def block(residual, layer, is_attn):
        h = rms_norm(residual.astype(compute_dtype), layer["norm"], cfg.norm_eps)
        if is_attn:
            out = _attn_mixer(
                h, layer["mixer"], cfg, cos, sin, attn_impl, mesh, quant=quant
            )
        elif cfg.mamba1:
            out, _ = _mamba1_mixer(h, layer["mixer"], cfg, mesh, quant=quant)
        else:
            out = _mamba_mixer(
                h, layer["mixer"], cfg, mesh, kernel=mamba_kernel, quant=quant
            )
        residual = residual + out.astype(jnp.float32)
        if "mlp" in layer:
            h = rms_norm(
                residual.astype(compute_dtype), layer["norm2"], cfg.norm_eps
            )
            residual = residual + _mlp(
                h, layer["mlp"], mesh, quant=quant
            ).astype(jnp.float32)
        return residual

    for i, layer in enumerate(params["layers"]):
        fn = functools.partial(block, is_attn=i in cfg.attn_layer_idx)
        if ac_mask[i]:
            fn = jax.checkpoint(fn, prevent_cse=False)
        residual = fn(residual, layer)

    x = rms_norm(residual.astype(compute_dtype), params["norm_f"], cfg.norm_eps)
    if return_hidden:
        return x
    logits = _head(x, params)
    return _constrain(logits, P(DATA_AXES, AXIS_CONTEXT, AXIS_TENSOR), mesh)


# ---------------------------------------------------------------------------
# recurrent decode (serving path — serve/families/mamba.py)
# ---------------------------------------------------------------------------
#
# Serving decodes one token per step from O(1) recurrent state instead of a
# growing kv cache: per mamba layer a conv window (the last d_conv-1 xBC
# inputs) plus the fp32 SSD state h (H, headdim, d_state) — together a
# fixed-size slab whose bytes never grow with generated length. Every op
# below replays the exact per-token math of the sequence path
# (`causal_conv1d`'s shifted-FMA sum, `ssd_scan_reference`'s recurrence,
# the gated RMSNorm), which is what makes greedy recurrent decode bitwise
# equal to a dense full-forward walk under fp32 + mamba_kernel="reference"
# — the family's parity anchor (tests/test_serving_families.py). Hybrid
# configs' attn-mixer layers ride a kv cache supplied by the caller
# through ``attn_cb`` (dense buffers in prefill, the paged pools in
# serve-side decode).


def slab_shapes(cfg: MambaConfig):
    """One stream's slab in one mamba layer: (conv window shape, state
    shape), by the mixer kind."""
    if cfg.mamba1:
        return (cfg.d_conv - 1, cfg.d_inner), (cfg.d_state, cfg.d_inner)
    return (
        (cfg.d_conv - 1, _conv_dim(cfg)),
        (cfg.nheads, cfg.headdim, cfg.d_state),
    )


def init_mamba_decode_state(
    cfg: MambaConfig, batch: int, compute_dtype=jnp.float32
) -> List[Params]:
    """Per-layer recurrent decode state for ``batch`` slots.

    Mamba layers: {"conv": (B, d_conv-1, conv width) compute dtype — the
    sliding window of pre-conv inputs; "ssd": the carried fp32 state}.
    The shapes follow the mixer (``slab_shapes``): Mamba-2 convolves xBC
    and carries (H, headdim, d_state); Mamba-1 convolves u alone and
    carries (d_state, d_inner), channels minor (ops/selective_scan.py
    says why). Attention layers of hybrid configs hold no slab here
    ({}): their kv lives in the caller's paged pool."""
    conv, ssd = slab_shapes(cfg)
    return [
        {} if i in cfg.attn_layer_idx else {
            "conv": jnp.zeros((batch,) + conv, compute_dtype),
            "ssd": jnp.zeros((batch,) + ssd, jnp.float32),
        }
        for i in range(cfg.n_layer)
    ]


def mamba_state_bytes_per_stream(cfg: MambaConfig, compute_dtype=jnp.float32) -> int:
    """Slab bytes one decode stream holds — constant in generated length
    (the constant-memory claim a tier-1 test pins)."""
    conv, ssd = slab_shapes(cfg)
    n_mamba = cfg.n_layer - len(cfg.attn_layer_idx)
    return n_mamba * (
        math.prod(conv) * jnp.dtype(compute_dtype).itemsize
        + math.prod(ssd) * 4  # fp32
    )


def _mamba_mixer_step(x, st: Params, p: Params, cfg: MambaConfig):
    """One token through a Mamba2 mixer. x (B, D) post-norm hidden in the
    compute dtype; st the layer's {"conv", "ssd"} slab. Returns
    (out (B, D), new st). Op-for-op the single-position case of
    ``_mamba_mixer``: same split points, the conv as the same ascending-w
    fp32 FMA sum ``causal_conv1d`` unrolls, the state update as the same
    einsums ``ssd_scan_reference`` scans — the bit-parity contract."""
    B, d = x.shape
    H, Pd, G, N = cfg.nheads, cfg.headdim, cfg.ngroups, cfg.d_state
    d_inner = cfg.d_inner

    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :d_inner]
    xBC_in = zxbcdt[..., d_inner : d_inner + _conv_dim(cfg)]
    dt_raw = zxbcdt[..., d_inner + _conv_dim(cfg) :]  # (B, H)

    # causal conv over the window of the last d_conv inputs (current
    # token included) — the position-t row of causal_conv1d's output
    window = jnp.concatenate([st["conv"], xBC_in[:, None, :]], axis=1)
    xBC = _conv_step(window, p["conv_w"], p["conv_b"]).astype(x.dtype)

    xs = xBC[..., :d_inner].reshape(B, H, Pd)
    Bm = xBC[..., d_inner : d_inner + G * N].reshape(B, G, N)
    Cm = xBC[..., d_inner + G * N :].reshape(B, G, N)

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )  # (B, H) fp32
    Af = -jnp.exp(p["A_log"].astype(jnp.float32))
    rep = H // G
    xf = xs.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=1)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=1)

    h_ssd = st["ssd"] * jnp.exp(dt * Af)[:, :, None, None] + jnp.einsum(
        "bh,bhn,bhp->bhpn", dt, Bf, xf
    )
    y = jnp.einsum("bhn,bhpn->bhp", Cf, h_ssd)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xf
    y = y.astype(x.dtype).reshape(B, d_inner)

    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["out_proj"]
    return out, {"conv": window[:, 1:], "ssd": h_ssd}


def _attn_qkv_step(h, p: Params, a, cos, sin, positions):
    """Projections + partial rotary for one decode position of a hybrid
    attn mixer. h (B, 1, D) post-norm; positions (B, 1) int32. Returns
    q (B, 1, nq, hd), k/v (B, 1, nkv, hd)."""
    B, m, _ = h.shape
    hd = a.head_dim
    q = (h @ p["wq"]).reshape(B, m, a.num_heads, hd)
    k = (h @ p["wk"]).reshape(B, m, a.num_heads_kv, hd)
    v = (h @ p["wv"]).reshape(B, m, a.num_heads_kv, hd)
    r = a.rotary_emb_dim
    if r and r < hd:
        q = jnp.concatenate(
            [apply_rotary(q[..., :r], cos, sin, positions), q[..., r:]], axis=-1
        )
        k = jnp.concatenate(
            [apply_rotary(k[..., :r], cos, sin, positions), k[..., r:]], axis=-1
        )
    elif r:
        q = apply_rotary(q, cos, sin, positions)
        k = apply_rotary(k, cos, sin, positions)
    return q, k, v


@scoped("norm")
def _block_norm(residual, w, cfg: MambaConfig, compute_dtype):
    return rms_norm(residual.astype(compute_dtype), w, cfg.norm_eps)


@scoped("norm")
def _add(residual, out):
    return residual + out.astype(jnp.float32)


def _stack_step(params: Params, x_t, cfg: MambaConfig, states, attn_cb,
                live=None):
    """One token through the whole (heterogeneous) layer stack.

    x_t (B, D) embedding row in the compute dtype; ``attn_cb(j, h, mixer)
    -> (B, D)`` runs hybrid attn layer j (qkv + cache interaction + wo)
    against whatever cache the caller owns; ``live``: ``mamba_decode_step``.
    Returns (residual (B, D) fp32, new per-layer states)."""
    compute_dtype = x_t.dtype
    residual = x_t.astype(jnp.float32)
    mixer_step = _mamba_mixer_step
    if cfg.mamba1:
        mixer_step = functools.partial(_mamba1_mixer_step, live=live)
    new_states = []
    attn_j = 0
    for i, layer in enumerate(params["layers"]):
        h = _block_norm(residual, layer["norm"], cfg, compute_dtype)
        if i in cfg.attn_layer_idx:
            out = attn_cb(attn_j, h[:, None], layer["mixer"])
            attn_j += 1
            new_states.append(states[i])
        else:
            out, st = mixer_step(h, states[i], layer["mixer"], cfg)
            new_states.append(st)
        residual = _add(residual, out)
        if "mlp" in layer:
            h2 = _block_norm(residual, layer["norm2"], cfg, compute_dtype)
            residual = _add(residual, _mlp(h2, layer["mlp"], None))
    return residual, new_states


def mamba_prefill(
    params: Params,
    tokens,
    lengths,
    cfg: MambaConfig,
    *,
    compute_dtype=jnp.float32,
    kv_len: int = 0,
    attn_impl: str = "auto",
):
    """Prompt prefill: a Mamba-1 stack takes the prompt as a sequence
    (``_prefill_sequence``; ``attn_impl`` is its attention's), a Mamba-2
    stack scans the recurrent step over positions, as follows.

    tokens (B, S_pad) int32, lengths (B,) int32 actual prompt lengths
    (<= S_pad; state freezes per-row past its length, so bucketed
    padding never corrupts the slab). Returns (logits (B, V) of each
    row's last real position, per-layer state, kv) where kv is a dense
    {"k", "v"} cache (n_attn, B, kv_len, nkv, hd) for hybrid attn layers
    (None when the config has none) — page-multiple ``kv_len`` feeds
    PagedKVCache.write_prompt directly. Because every position runs the
    exact ops of the recurrent decode step, prefill state equals the
    state a token-by-token decode of the prompt would carry, bit for
    bit."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    if cfg.mamba1:
        return _prefill_sequence(
            params, tokens, lengths, cfg, compute_dtype, kv_len, attn_impl
        )
    B, S_pad = tokens.shape
    a = cfg.attn_cfg
    n_attn = len(cfg.attn_layer_idx)
    states = init_mamba_decode_state(cfg, B, compute_dtype)

    if n_attn:
        kv_len = kv_len or S_pad
        assert kv_len >= S_pad, (kv_len, S_pad)
        kv = {
            "k": jnp.zeros(
                (n_attn, B, kv_len, a.num_heads_kv, a.head_dim), compute_dtype
            ),
            "v": jnp.zeros(
                (n_attn, B, kv_len, a.num_heads_kv, a.head_dim), compute_dtype
            ),
        }
        cos, sin = rope_table(kv_len, a.rotary_emb_dim or a.head_dim, 10000.0)
    else:
        kv = {}
        cos = sin = None

    last_res = jnp.zeros((B, cfg.d_model), jnp.float32)

    def body(carry, inp):
        states, kv, last_res = carry
        i, tok = inp
        live = i < lengths  # (B,) rows still inside their prompt
        x_t = params["embedding"][tok]

        def attn_cb(j, h, mixer):
            positions = jnp.full((B, 1), i, jnp.int32)
            q, k, v = _attn_qkv_step(h, mixer, a, cos, sin, positions)
            # zero padded rows' writes: the pages this buffer lands in
            # must match the zero-beyond-prompt discipline the llama
            # prefill keeps (kv_cache.py zero-page contract)
            k = jnp.where(live[:, None, None, None], k, 0)
            v = jnp.where(live[:, None, None, None], v, 0)
            kv["k"] = lax.dynamic_update_slice(
                kv["k"], k[None], (j, 0, i, 0, 0)
            )
            kv["v"] = lax.dynamic_update_slice(
                kv["v"], v[None], (j, 0, i, 0, 0)
            )
            from fms_fsdp_tpu.ops.paged_attention import gqa_attend

            o = gqa_attend(q, kv["k"][j], kv["v"][j], positions)
            return o[:, 0] @ mixer["wo"]

        residual, new_states = _stack_step(params, x_t, cfg, states, attn_cb)
        states = jax.tree.map(
            lambda n, o: jnp.where(
                live.reshape((B,) + (1,) * (n.ndim - 1)), n, o
            ),
            new_states,
            states,
        )
        last_res = jnp.where((i == lengths - 1)[:, None], residual, last_res)
        return (states, kv, last_res), None

    (states, kv, last_res), _ = lax.scan(
        body,
        (states, kv, last_res),
        (jnp.arange(S_pad, dtype=jnp.int32), jnp.moveaxis(tokens, 0, 1)),
    )
    x = rms_norm(last_res.astype(compute_dtype), params["norm_f"], cfg.norm_eps)
    logits = _head(x, params)
    return logits, states, (kv if n_attn else None)


# positions one trip of the sequence prefill's loop takes through the
# stack. A chunk's products do C operations a weight byte (the v5e's
# ridge is 240) and every chunk reads every weight once; timed on the
# chip at 256, 512 and 1024 (PERF.md, PR 28). A constant of the program:
# no option selects it.
PREFILL_CHUNK = 512


def prefill_chunk(p_pad: int) -> int:
    """The chunk of a prompt padded to ``p_pad``: the largest divisor of
    ``p_pad`` up to ``PREFILL_CHUNK``, so that chunks tile the bucket."""
    return seq.chunk_of(p_pad, PREFILL_CHUNK)


def prefill_positions(cfg: MambaConfig, p: int, p_pad: int) -> int:
    """Positions that ``mamba_prefill`` computes for a prompt of ``p``
    tokens padded to ``p_pad``: whole chunks up to the prompt's end for
    a Mamba-1 stack, the whole bucket for a Mamba-2 one."""
    if not cfg.mamba1:
        return p_pad
    return seq.positions_computed(p, prefill_chunk(p_pad))


def _prefill_sequence(
    params: Params, tokens, lengths, cfg: MambaConfig, compute_dtype,
    kv_len: int, attn_impl: str,
):
    """``mamba_prefill`` for a Mamba-1 stack: the prompt as a sequence,
    ``prefill_chunk`` positions at a time through every layer, in one
    loop whose trip count is read from ``lengths`` on the device, so the
    chunks past the longest row's end are never computed. From chunk to
    chunk go, per Mamba layer, the slab (the scan's float32 state and
    the conv's last inputs, each row's frozen at its length), the K/V
    written so far, and each row's residual at its last real position.
    Same results as the per-position form: that position's logits, the
    slab, and K/V (zero past each row's length) for the pages."""
    B, S = tokens.shape
    a = cfg.attn_cfg
    c = prefill_chunk(S)
    kv_len = kv_len or S
    assert kv_len >= S, (kv_len, S)
    assert a.causal, "a chunk cannot attend to the chunks after it"
    cos = sin = None
    if a.rotary_emb_dim:
        cos, sin = rope_table(S, a.rotary_emb_dim, 10000.0)
    n_attn = len(cfg.attn_layer_idx)
    kv_shape = (B, kv_len, a.num_heads_kv, a.head_dim)

    def body(chunk, carry):
        states, ks, vs, last = carry
        start, ahead = chunk.start, chunk.ahead
        with jax.named_scope("embed"):
            toks = lax.dynamic_slice_in_dim(tokens, start, c, axis=1)
            residual = params["embedding"][toks].astype(jnp.float32)
        states, ks, vs, attn_j = list(states), list(ks), list(vs), 0
        for i, layer in enumerate(params["layers"]):
            h = _block_norm(residual, layer["norm"], cfg, compute_dtype)
            if i in cfg.attn_layer_idx:
                out, ks[attn_j], vs[attn_j] = _attn_prefill(
                    h, layer["mixer"], a, cos, sin, chunk.live,
                    ks[attn_j], vs[attn_j], start, attn_impl,
                )
                attn_j += 1
            else:
                out, states[i] = _mamba1_mixer(
                    h, layer["mixer"], cfg, lengths=jnp.clip(ahead, 0, c),
                    scan=selective_scan, carry=states[i],
                )
            residual = _add(residual, out)
            if "mlp" in layer:
                h2 = _block_norm(residual, layer["norm2"], cfg, compute_dtype)
                residual = _add(residual, _mlp(h2, layer["mlp"], None))
        return residual, (states, ks, vs, last)

    states, ks, vs, last = seq.chunk_loop(
        lengths, c, body,
        lambda: (
            init_mamba_decode_state(cfg, B, compute_dtype),
            [jnp.zeros(kv_shape, compute_dtype)] * n_attn,
            [jnp.zeros(kv_shape, compute_dtype)] * n_attn,
            jnp.zeros((B, cfg.d_model), jnp.float32),
        ),
        last=3,
    )
    x = _block_norm(last, params["norm_f"], cfg, compute_dtype)
    kv = {"k": jnp.stack(ks), "v": jnp.stack(vs)} if n_attn else None
    return _head(x, params), states, kv


def _attn_prefill(h, p: Params, a, cos, sin, live, k_buf, v_buf, start,
                  attn_impl):
    """A hybrid attention layer over one chunk of a padded prompt. h
    (B, c, D) at positions ``start`` to ``start + c``; ``live`` (B, c)
    marks real positions; k_buf, v_buf (B, kv_len, nkv, hd) hold the
    chunks before this one. Returns (out (B, c, D), k_buf, v_buf with
    this chunk written: zeros at positions that are not real, the
    zero-beyond-prompt discipline of the pages)."""
    B, c, _ = h.shape
    with jax.named_scope("qkv"):
        positions = jnp.broadcast_to(
            start + jnp.arange(c, dtype=jnp.int32), (B, c)
        )
        q, k, v = _attn_qkv_step(h, p, a, cos, sin, positions)
    with jax.named_scope("kv_write"):
        keep = live[:, :, None, None]
        k_buf = lax.dynamic_update_slice_in_dim(
            k_buf, jnp.where(keep, k, 0), start, axis=1
        )
        v_buf = lax.dynamic_update_slice_in_dim(
            v_buf, jnp.where(keep, v, 0), start, axis=1
        )
    with jax.named_scope("attn"):
        o = chunk_attention(q, k_buf, v_buf, start, impl=attn_impl)
    with jax.named_scope("attn_out"):
        out = o.reshape(B, c, a.num_heads * a.head_dim) @ p["wo"]
    return out, k_buf, v_buf


def mamba_decode_step(
    params: Params,
    state,
    kv_pools,
    page_table,
    seq_lens,
    tokens,
    cfg: MambaConfig,
    *,
    page_size: int = 0,
    compute_dtype=jnp.float32,
    live=None,
):
    """One recurrent decode step for a ragged batch.

    tokens (B,) int32 — each row's current token at position
    ``seq_lens[b]``; ``state`` the per-layer slab (all B slots step
    together; an idle slot's slices update with garbage it alone reads —
    its next prefill overwrites them — unless the caller masks them: the
    serving adapter selects the old rows behind the step, or, where the
    Mamba-1 scan steps its state in place, gives ``live`` (B,) bool and
    that state's dead rows stay as they were). Hybrid attn layers scatter k/v
    into ``kv_pools`` (n_attn-layer paged pools) exactly like
    serve/decode.py does for llama; pure-Mamba configs pass ``{}`` /
    ``None`` and touch no cache at all. Returns (logits (B, V), state,
    kv_pools)."""
    with jax.named_scope("params_cast"):
        params = jax.tree.map(lambda a: a.astype(compute_dtype), params)
    B = tokens.shape[0]
    a = cfg.attn_cfg
    with jax.named_scope("embed"):
        x_t = params["embedding"][tokens]

    if cfg.attn_layer_idx:
        from fms_fsdp_tpu.ops.paged_attention import gather_pages, gqa_attend

        max_seq = page_table.shape[1] * page_size
        cos, sin = rope_table(max_seq, a.rotary_emb_dim or a.head_dim, 10000.0)
        positions = seq_lens[:, None].astype(jnp.int32)
        rows = jnp.arange(B)
        page_ids = page_table[rows, seq_lens // page_size]
        slots = seq_lens % page_size
        new_pools = {"k": [], "v": []}

        def attn_cb(j, h, mixer):
            with jax.named_scope("qkv"):
                q, k, v = _attn_qkv_step(h, mixer, a, cos, sin, positions)
            with jax.named_scope("kv_write"):
                k_pool = kv_pools["k"][j].at[page_ids, slots].set(k[:, 0])
                v_pool = kv_pools["v"][j].at[page_ids, slots].set(v[:, 0])
            new_pools["k"].append(k_pool)
            new_pools["v"].append(v_pool)
            k_seq = gather_pages(k_pool, page_table)
            v_seq = gather_pages(v_pool, page_table)
            with jax.named_scope("attn"):
                o = gqa_attend(q, k_seq, v_seq, positions)
            with jax.named_scope("attn_out"):
                return o[:, 0] @ mixer["wo"]

    else:
        new_pools = None

        def attn_cb(j, h, mixer):  # pragma: no cover - unreachable
            raise AssertionError("attn layer in a config without attn_layer_idx")

    residual, state = _stack_step(params, x_t, cfg, state, attn_cb, live)
    x = _block_norm(residual, params["norm_f"], cfg, compute_dtype)
    logits = _head(x, params)
    if cfg.attn_layer_idx:
        with jax.named_scope("kv_write"):
            kv_pools = {
                "k": jnp.stack(new_pools["k"]),
                "v": jnp.stack(new_pools["v"]),
            }
    return logits, state, kv_pools


# ---------------------------------------------------------------------------
# sharding rulebook
# ---------------------------------------------------------------------------


def mamba_param_specs(cfg: MambaConfig) -> Params:
    """PartitionSpec tree matching init_mamba_params' structure."""

    def mamba_mixer():
        return {
            "in_proj": P(AXIS_FSDP, AXIS_TENSOR),
            "conv_w": P(AXIS_FSDP, None),
            "conv_b": P(AXIS_FSDP),
            "dt_bias": P(None),
            "A_log": P(None),
            "D": P(None),
            "norm": P(None),
            "out_proj": P(AXIS_TENSOR, AXIS_FSDP),
        }

    def mamba1_mixer():
        return {
            "in_proj": P(None, AXIS_FSDP, AXIS_TENSOR),
            "conv_w": P(AXIS_FSDP, None),
            "conv_b": P(AXIS_FSDP),
            "x_proj": P(AXIS_TENSOR, None),
            "dt_norm": P(None),
            "B_norm": P(None),
            "C_norm": P(None),
            "dt_proj": P(None, AXIS_TENSOR),
            "dt_bias": P(None),
            "A_log": P(AXIS_FSDP, None),
            "D": P(None),
            "out_proj": P(AXIS_TENSOR, AXIS_FSDP),
        }

    ssm_mixer = mamba1_mixer if cfg.mamba1 else mamba_mixer

    def attn_mixer():
        return {
            "wq": P(AXIS_FSDP, AXIS_TENSOR),
            "wk": P(AXIS_FSDP, AXIS_TENSOR),
            "wv": P(AXIS_FSDP, AXIS_TENSOR),
            "wo": P(AXIS_TENSOR, AXIS_FSDP),
        }

    layers = []
    for i in range(cfg.n_layer):
        layer = {
            "norm": P(None),
            "mixer": attn_mixer() if i in cfg.attn_layer_idx else ssm_mixer(),
        }
        if cfg.d_intermediate > 0:
            layer["norm2"] = P(None)
            layer["mlp"] = {
                "w1": P(AXIS_FSDP, AXIS_TENSOR),
                "w3": P(AXIS_FSDP, AXIS_TENSOR),
                "w2": P(AXIS_TENSOR, AXIS_FSDP),
            }
        layers.append(layer)

    specs = {
        "embedding": P(AXIS_TENSOR, AXIS_FSDP),
        "layers": layers,
        "norm_f": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(AXIS_FSDP, AXIS_TENSOR)
    return specs


