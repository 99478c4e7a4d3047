"""Mamba-path kernel microbench at mamba_9.8b shapes (ref:config_utils.py:162-185).

Times the chunked SSD scan (both the group-factored XLA formulation and
the Pallas intra-chunk kernel) and the depthwise causal conv1d on the
real chip, fwd and fwd+bwd. Writes BENCH_SSD.json at the repo root.

What it is for (see ops/ssd.py docstrings): the race between the XLA
einsum formulation and the fused Pallas kernel that decides
``kernel="auto"``, and the depthwise conv1d's share of a Mamba layer.
The round-2 record was deleted in PR 21 (not measured since).

Timing comes from scripts/bench_kernels.py::time_fn: best of 3 reps x N
amortized iters, each window ended by block_until_ready. Fails without
a TPU.
"""

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from bench_kernels import require_tpu, time_fn
from fms_fsdp_tpu.ops.ssd import causal_conv1d, ssd_scan

# mamba_9.8b Mamba2 layer shapes: d_inner 8192, headdim 64 -> 128 heads,
# d_state 128, ngroups 1, conv width 4 over d_inner + 2*G*N channels
B, S, H, P, G, N = 2, 4096, 128, 64, 1, 128
CONV_C, CONV_W = H * P + 2 * G * N, 4


def main():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32))
    Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.bfloat16)
    Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.bfloat16)
    D = jnp.ones((H,), jnp.float32)

    cx = jax.random.normal(ks[5], (B, S, CONV_C), jnp.bfloat16)
    cw = jax.random.normal(ks[0], (CONV_C, CONV_W), jnp.float32) * 0.1
    cb = jnp.zeros((CONV_C,), jnp.float32)

    rows = []

    def add(name, fwd_fn, grad_fn, args):
        print(f"# benching {name}", file=sys.stderr)
        t_f = time_fn(jax.jit(fwd_fn), *args, iters=30)
        t_g = time_fn(jax.jit(grad_fn), *args, iters=15)
        rows.append(
            {
                "kernel": name,
                "fwd_ms": round(t_f * 1e3, 3),
                "fwd_bwd_ms": round(t_g * 1e3, 3),
            }
        )

    # chunk sweep for both formulations: the fused kernel's VMEM residency
    # ((L, L) decay product + per-group state) and the XLA path's
    # materialized (B, L, L, G, R) weight tensor trade off differently
    # with L, so the shipped "auto" choice is the measured best pair
    for mode, chunk in (
        ("xla", 128),
        ("xla", 256),
        ("xla", 512),
        ("pallas", 128),
        ("pallas", 256),
        ("pallas", 512),
    ):
        fwd = functools.partial(ssd_scan, kernel=mode, chunk_size=chunk)

        def loss(x, dt, A, Bm, Cm, D, fwd=fwd):
            return jnp.sum(fwd(x, dt, A, Bm, Cm, D).astype(jnp.float32))

        add(
            f"ssd_scan[{mode},L={chunk}]",
            fwd,
            jax.grad(loss, argnums=(0, 1, 3, 4)),
            (x, dt, A, Bm, Cm, D),
        )

    def closs(cx, cw, cb):
        return jnp.sum(causal_conv1d(cx, cw, cb).astype(jnp.float32))

    add(
        "causal_conv1d",
        causal_conv1d,
        jax.grad(closs, argnums=(0, 1, 2)),
        (cx, cw, cb),
    )

    out = {
        "shapes": (
            f"SSD: B={B} S={S} H={H} P={P} G={G} N={N} chunk swept bf16; "
            f"conv1d: C={CONV_C} W={CONV_W}"
        ),
        "device": require_tpu(),
        "rows": rows,
    }
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_SSD.json",
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
