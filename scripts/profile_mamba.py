"""Component-attribution profile for the Mamba family's MFU (mamba
bf16 measured 0.52 MFU in round 2 with no evidence of where it goes).

Times each component of the mamba_9.8b Mamba2 layer at the bench-row
shapes (B=2, S=4096, d_model 4096, d_inner 8192, 128 heads of 64,
d_state 128, MLP 14336) individually — fwd and fwd+bwd — alongside the
full train-step time from the same protocol bench.py uses, then prints
each component's share of the step and its achieved TF/s vs the chip
peak. The gap rows (share large + TF/s low) are where the MFU goes.

Writes PROFILE_MAMBA.json at the repo root. Needs a TPU; it has never
run on one.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from bench_kernels import time_fn
from fms_fsdp_tpu.ops.ssd import causal_conv1d, ssd_scan

# mamba_9.8b shapes (ref:config_utils.py:162-185): d_model 4096,
# d_inner 8192 -> 128 heads x 64, d_state 128, ngroups 1, conv width 4,
# MLP 14336, vocab cut to 32k exactly as the bench row does
B, S, D = 2, 4096, 4096
H, P, G, N = 128, 64, 1, 128
D_INNER = H * P
CONV_C, CONV_W = D_INNER + 2 * G * N, 4
IN_PROJ = 2 * D_INNER + 2 * G * N + H
MLP_HID = 14336
VOCAB = 32000


def _gemm_flops(*dims):
    out = 2
    for d in dims:
        out *= d
    return out


def main():
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (B, S, D), jnp.bfloat16)
    w_in = jax.random.normal(ks[1], (D, IN_PROJ), jnp.bfloat16) * 0.02
    w_out = jax.random.normal(ks[2], (D_INNER, D), jnp.bfloat16) * 0.02
    w1 = jax.random.normal(ks[3], (D, MLP_HID), jnp.bfloat16) * 0.02
    w2 = jax.random.normal(ks[4], (MLP_HID, D), jnp.bfloat16) * 0.02
    w_head = jax.random.normal(ks[5], (D, VOCAB), jnp.bfloat16) * 0.02

    xs = jax.random.normal(ks[6], (B, S, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[7], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[0], (H,), jnp.float32))
    Bm = jax.random.normal(ks[1], (B, S, G, N), jnp.bfloat16)
    Cm = jax.random.normal(ks[2], (B, S, G, N), jnp.bfloat16)
    Dm = jnp.ones((H,), jnp.float32)
    cx = jax.random.normal(ks[3], (B, S, CONV_C), jnp.bfloat16)
    cw = jax.random.normal(ks[4], (CONV_C, CONV_W), jnp.float32) * 0.1
    cb = jnp.zeros((CONV_C,), jnp.float32)

    tok = B * S
    components = []

    def add(name, fn, args, flops_fwd):
        print(f"# profiling {name}", file=sys.stderr)

        def loss(*a):
            return jnp.sum(fn(*a).astype(jnp.float32))

        t_f = time_fn(jax.jit(fn), *args, iters=20)
        # differentiate w.r.t. EVERY operand (activations AND weights):
        # a training step computes both dx and dw, so the timed backward
        # must too or the 3x amortization overstates the rate (ADVICE r4)
        t_g = time_fn(
            jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))),
            *args,
            iters=10,
        )
        components.append(
            {
                "component": name,
                "fwd_ms": round(t_f * 1e3, 3),
                "fwd_bwd_ms": round(t_g * 1e3, 3),
                "fwd_tflops_per_s": round(flops_fwd / t_f / 1e12, 2),
                # bwd of a GEMM chain (dx + dw) is ~2x fwd FLOPs;
                # grad-of-loss runs fwd+bwd so the amortized rate uses 3x
                "fwd_bwd_tflops_per_s": round(3 * flops_fwd / t_g / 1e12, 2),
            }
        )

    add(
        "in_proj GEMM",
        lambda x, w: x @ w,
        (x, w_in),
        _gemm_flops(tok, D, IN_PROJ),
    )
    add(
        "conv1d (shifted-FMA)",
        lambda c, w, b: causal_conv1d(c, w, b),
        (cx, cw, cb),
        2 * tok * CONV_C * CONV_W,
    )
    add(
        "ssd_scan (auto kernel)",
        lambda xs, dt, A, Bm, Cm, Dm: ssd_scan(xs, dt, A, Bm, Cm, Dm),
        (xs, dt, A, Bm, Cm, Dm),
        # dominant SSD terms: intra-chunk (S*chunk per head) + state IO;
        # count the matmul terms only (B*S*chunk*(N+P) per head family)
        2 * tok * H * (N * P * 2 + N * 256),
    )
    add(
        "out_proj GEMM",
        lambda h, w: h.reshape(B, S, D_INNER) @ w,
        (xs, w_out),
        _gemm_flops(tok, D_INNER, D),
    )
    add(
        "MLP (SwiGLU 2-GEMM core)",
        lambda x, w1, w2: jax.nn.silu(x @ w1) @ w2,
        (x, w1, w2),
        _gemm_flops(tok, D, MLP_HID) * 2,
    )
    add(
        "lm_head GEMM",
        lambda x, w: x @ w,
        (x, w_head),
        _gemm_flops(tok, D, VOCAB),
    )

    # full train step at the bench-row config, same protocol as bench.py
    print("# profiling full step (bench row protocol)", file=sys.stderr)
    step_row = None
    try:
        from bench import run_config

        step_row = run_config(
            "mamba_9.8b",
            batch_size=B,
            sel_ac=0.5,
            model_overrides={
                "n_layer": 2,
                "attn_layer_idx": (),
                "vocab_size": VOCAB,
            },
        )
    except Exception as e:  # noqa: BLE001
        step_row = {"error": f"{type(e).__name__}: {e}"[:200]}

    out = {
        "shapes": {"B": B, "S": S, "d_model": D, "d_inner": D_INNER,
                   "heads": H, "d_state": N, "mlp": MLP_HID, "vocab": VOCAB},
        "components": components,
        "full_step_L2": step_row,
    }
    if step_row and "step_time_s" in (step_row or {}):
        step_ms = step_row["step_time_s"] * 1e3
        for c in out["components"]:
            # 2 layers in the step; per-layer components count twice
            mult = 1 if c["component"] == "lm_head GEMM" else 2
            c["share_of_step_pct"] = round(
                100 * mult * c["fwd_bwd_ms"] / step_ms, 1
            )
    with open("PROFILE_MAMBA.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
