"""The Mamba-1 mixer (Gu & Dao 2023): one copy, which the families whose
stacks hold such a mixer reach (models/mamba.py's Jamba hybrids,
models/phi4flash.py), as models/moe_held.py is the expert layer of three.
No family and no config class is imported here: ``cfg`` is any config
that gives ``d_inner``, ``d_state``, ``d_conv``, ``dt_rank_`` and, where
the norms are asked for, ``norm_eps``.

``in_proj`` (u's matrix and the gate's, stacked) -> u, z; a depthwise
causal conv with bias and silu over u alone; ``x_proj`` -> (dt | B | C) of
widths (dt_rank, d_state, d_state); ``dt_proj`` with bias and softplus; A
of shape (d_inner, d_state); the selective scan of ops/selective_scan.py
with its ``D`` skip; a plain gate (``y * silu(z)``, no norm); ``out_proj``.

Two things differ by family and are arguments, not options of a run:

- ``norms``: an RMSNorm on each of dt, B and C behind ``x_proj``
  (``dt_norm``, ``B_norm``, ``C_norm``: Jamba's own; the mixer as
  published has none);
- ``hand_out``: the scan's output ``y`` (with its ``D`` skip, before the
  gate, in the mixer's compute dtype) is returned beside the mixer's
  output: the memory another layer gates (phi4flash's gated memory units).

The sequence form (``mamba1_mixer``) and the one-position form
(``mamba1_mixer_step``) run the same arithmetic under the same five
scopes (``ssm_in_proj``, ``ssm_conv``, ``ssm_params``, ``ssm_scan``,
``ssm_gate_out``). Each asks ops/selective_scan.py for the scan that fits
the platform and the shapes: on a TPU a prefill runs the sequence kernel
and a decode step the one-position kernel, which makes one pass over the
slab in place (a stacked slab and the layer's index, or one layer's
state; a dead slot's rows written back as read); elsewhere both run the
plain forms, and a decode program's text is what it was.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from fms_fsdp_tpu.ops.norms import rms_norm
from fms_fsdp_tpu.ops.quant import matmul as qmatmul
from fms_fsdp_tpu.ops.selective_scan import (
    freeze_past,
    selective_scan_reference,
    selective_scan_slab_step,
)
from fms_fsdp_tpu.ops.ssd import causal_conv1d
from fms_fsdp_tpu.parallel.mesh import AXIS_CONTEXT, AXIS_TENSOR, DATA_AXES
from fms_fsdp_tpu.parallel.sharding import constrain as _constrain

Params = Dict[str, Any]


def mamba1_scan_inputs(u, p: Params, cfg, norms: bool = True):
    """x_proj, the three norms where ``norms``, dt_proj with bias and
    softplus, A: what the scan reads besides ``u``. u (..., d_inner)
    post-conv. Returns (dt (..., d_inner) fp32, A (N, d_inner) fp32, B, C
    (..., N))."""
    N, R = cfg.d_state, cfg.dt_rank_
    dbc = u @ p["x_proj"]

    def part(lo, hi, norm):
        x = dbc[..., lo:hi]
        return rms_norm(x, p[norm], cfg.norm_eps) if norms else x

    dt_r = part(0, R, "dt_norm")
    Bm = part(R, R + N, "B_norm")
    Cm = part(R + N, R + 2 * N, "C_norm")
    dt = jax.nn.softplus(
        jnp.dot(dt_r, p["dt_proj"], preferred_element_type=jnp.float32)
        + p["dt_bias"].astype(jnp.float32)
    )
    A = -jnp.exp(p["A_log"].astype(jnp.float32)).T
    return dt, A, Bm, Cm


def mamba1_mixer(
    x, p: Params, cfg, mesh=None, quant="none", *,
    lengths=None, scan=selective_scan_reference, carry=None,
    norms: bool = True, hand_out: bool = False,
):
    """x (B, S, D) compute dtype -> (out (B, S, D), slab) through a
    Mamba-1 mixer. With ``lengths`` (B,) a row's state freezes at its
    length, and ``slab`` is what the recurrent decode step goes on from:
    {"conv": the last d_conv-1 pre-conv inputs before that position,
    "ssd": the state there}. ``carry`` is such a slab to go on from (the
    sequence is then the continuation of the one that left it); without
    it the scan starts from a zero state and the conv from zero inputs.
    ``scan`` is the sequence form of ops/selective_scan.py to run: the
    differentiable ``lax.scan`` one unless the caller (prefill) asks for
    the one that fits the platform. ``norms``, ``hand_out``: the module's
    docstring; with ``hand_out`` the scan's output (B, S, d_inner) is a
    third result."""
    B, S, _ = x.shape
    di, N, K = cfg.d_inner, cfg.d_state, cfg.d_conv
    before = None if carry is None else carry["conv"]
    with jax.named_scope("ssm_in_proj"):
        u_pre, z = (
            _constrain(
                qmatmul(x, p["in_proj"][i], quant=quant),
                P(DATA_AXES, AXIS_CONTEXT, AXIS_TENSOR), mesh,
            )
            for i in range(2)
        )
    with jax.named_scope("ssm_conv"):
        u = causal_conv1d(
            u_pre, p["conv_w"], p["conv_b"], activation="silu", init=before
        )
    with jax.named_scope("ssm_params"):
        dt, A, Bm, Cm = mamba1_scan_inputs(u, p, cfg, norms)
        if lengths is not None:
            dt = freeze_past(dt, lengths)
    with jax.named_scope("ssm_scan"):
        y, h = scan(
            u.astype(jnp.float32), dt, A, Bm.astype(jnp.float32),
            Cm.astype(jnp.float32), p["D"].astype(jnp.float32),
            jnp.zeros((B, N, di), jnp.float32) if carry is None
            else carry["ssd"],
        )
    with jax.named_scope("ssm_gate_out"):
        y = y.astype(x.dtype)
        out = qmatmul(y * jax.nn.silu(z), p["out_proj"], quant=quant)
        out = _constrain(out, P(DATA_AXES, AXIS_CONTEXT, None), mesh)
    memory = (y,) if hand_out else ()
    if lengths is None:
        return (out, None, *memory)
    with jax.named_scope("ssm_conv"):
        if before is None:
            before = jnp.zeros((B, K - 1, di), u_pre.dtype)
        padded = jnp.concatenate([before.astype(u_pre.dtype), u_pre], 1)
        tail = jax.vmap(
            lambda row, n: lax.dynamic_slice_in_dim(row, n, K - 1, axis=0)
        )(padded, lengths)
    return (out, {"conv": tail, "ssd": h}, *memory)


def conv_step(window, w, b):
    """Position t of ``causal_conv1d`` from the window of the last d_conv
    inputs (B, d_conv, C), the current one last: the same ascending-w
    fp32 FMA sum, bias and silu. Returns fp32."""
    wf = w.astype(jnp.float32)
    out = sum(
        window[:, k].astype(jnp.float32) * wf[None, :, k]
        for k in range(w.shape[-1])
    )
    return jax.nn.silu(out + b.astype(jnp.float32)[None, :])


def mamba1_mixer_step(
    x, st: Params, p: Params, cfg, *, norms: bool = True,
    hand_out: bool = False, layer=None, live=None,
):
    """One token through a Mamba-1 mixer. x (B, D) post-norm hidden; st
    the layer's {"conv", "ssd"} slab. Returns (out (B, D), new st) and,
    with ``hand_out``, the scan's output (B, d_inner): the
    single-position case of ``mamba1_mixer``.

    The scan's state is stepped where it lies
    (``selective_scan_slab_step``: the in-place kernel where it compiles,
    else ``jnp``): with ``layer``, ``st["ssd"]`` is the stacked state of
    every Mamba layer, (layers, B, N, d_inner), of which this layer's is
    stepped, and the new ``st["ssd"]`` is the whole stack again; with
    ``live`` (B,) bool a row that is not live keeps its scan state (the
    conv window is the caller's to keep)."""
    with jax.named_scope("ssm_in_proj"):
        u_pre, z = x @ p["in_proj"][0], x @ p["in_proj"][1]
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([st["conv"], u_pre[:, None, :]], axis=1)
        u = conv_step(window, p["conv_w"], p["conv_b"]).astype(x.dtype)
    with jax.named_scope("ssm_params"):
        dt, A, Bm, Cm = mamba1_scan_inputs(u, p, cfg, norms)
    with jax.named_scope("ssm_scan"):
        y, h = selective_scan_slab_step(
            u.astype(jnp.float32), dt, A, Bm.astype(jnp.float32),
            Cm.astype(jnp.float32), p["D"].astype(jnp.float32), st["ssd"],
            layer, live,
        )
    with jax.named_scope("ssm_gate_out"):
        y = y.astype(x.dtype)
        out = (y * jax.nn.silu(z)) @ p["out_proj"]
    memory = (y,) if hand_out else ()
    return (out, {"conv": window[:, 1:], "ssd": h}, *memory)
