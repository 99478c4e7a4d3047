"""One rank of a 2-process x 1-device world running the context-axis ops
with the context axis ON the process boundary: ring attention's ppermute
and ssd_scan_cp's all_gather + cross-device state recurrence execute
over gloo for real (the entry-level cp modes can't produce this
topology: the mesh places context innermost, so contiguous multi-device
processes keep context pairs intra-process, and a 1-device-per-process
entry run is refused by the data-extent check).

Each rank builds the SAME global inputs from a fixed seed, shards them
over the context axis via make_array_from_process_local_data, runs the
op under jit, and checks the addressable output shard against the
locally-computed single-device reference. Prints RING_OPS_OK on success.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from fms_fsdp_tpu.utils.train_utils import setup

setup()  # env-triple jax.distributed init (gloo)

import jax
import jax.numpy as jnp

from fms_fsdp_tpu.ops.attention import xla_attention
from fms_fsdp_tpu.ops.ring_attention import ring_attention
from fms_fsdp_tpu.ops.ssd import ssd_scan, ssd_scan_cp
from fms_fsdp_tpu.parallel.mesh import AXIS_CONTEXT, MeshConfig, build_mesh


def _shard_seq(mesh, arr, seq_axis=1):
    """Global array with ``seq_axis`` sharded over the context axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * arr.ndim
    spec[seq_axis] = AXIS_CONTEXT
    sharding = NamedSharding(mesh, P(*spec))
    cp = mesh.shape[AXIS_CONTEXT]
    idx = jax.process_index()
    s = arr.shape[seq_axis] // cp
    local = np.take(
        arr, range(idx * s, (idx + 1) * s), axis=seq_axis
    )
    return jax.make_array_from_process_local_data(sharding, local)


def main():
    assert jax.process_count() == 2 and jax.local_device_count() == 1
    mesh = build_mesh(
        MeshConfig(sharding_strategy="fsdp", context_parallel_size=2)
    )
    idx = jax.process_index()
    cp = 2

    # ---- ring attention: q/k/v seq-sharded across the two processes.
    # H=64 exercises the einsum partials; H=128 (flash-eligible at
    # s_local=256) the Pallas flash partials in interpret mode — the
    # kernel+cross-process-collective composition a real pod runs.
    rng = np.random.default_rng(0)
    from fms_fsdp_tpu.ops.ring_attention import _flash_eligible

    for H, expect_flash in ((64, False), (128, True)):
        B, S, NQ, NKV = 1, 512, 4, 2
        q = rng.standard_normal((B, S, NQ, H)).astype(np.float32)
        k = rng.standard_normal((B, S, NKV, H)).astype(np.float32)
        v = rng.standard_normal((B, S, NKV, H)).astype(np.float32)
        assert _flash_eligible(q.shape, k.shape, cp) == expect_flash
        ref = np.asarray(
            xla_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        )

        qg, kg, vg = (_shard_seq(mesh, a) for a in (q, k, v))
        out = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, causal=True)
        )(qg, kg, vg)
        shard = out.addressable_shards[0]  # this process's seq shard
        np.testing.assert_allclose(
            np.asarray(shard.data), ref[shard.index], atol=2e-5
        )

        # backward over the boundary too: the ring bwd's ppermute
        # transpose (traveling dk/dv accumulators) crosses gloo here
        ref_gq = np.asarray(
            jax.grad(
                lambda q: jnp.sum(
                    xla_attention(q, jnp.asarray(k), jnp.asarray(v)) ** 2
                )
            )(jnp.asarray(q))
        )
        gq = jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(
                    ring_attention(q, k, v, mesh, causal=True) ** 2
                )
            )
        )(qg, kg, vg)
        gshard = gq.addressable_shards[0]
        np.testing.assert_allclose(
            np.asarray(gshard.data), ref_gq[gshard.index], atol=5e-4
        )

    # ---- context-parallel SSD: state passed across the process boundary
    b, s, h, p, g, n = 1, 128, 4, 8, 2, 8
    x = rng.standard_normal((b, s, h, p), dtype=np.float32)
    dt = np.logaddexp(0, rng.standard_normal((b, s, h))).astype(np.float32)
    A = -np.exp(rng.standard_normal(h)).astype(np.float32)
    Bm = rng.standard_normal((b, s, g, n)).astype(np.float32)
    Cm = rng.standard_normal((b, s, g, n)).astype(np.float32)
    ref_y = np.asarray(
        ssd_scan(
            jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
            jnp.asarray(Bm), jnp.asarray(Cm), chunk_size=32,
        )
    )
    xg = _shard_seq(mesh, x)
    dtg = _shard_seq(mesh, dt)
    bg = _shard_seq(mesh, Bm)
    cg = _shard_seq(mesh, Cm)
    yg = jax.jit(
        lambda x, dt, Bm, Cm: ssd_scan_cp(
            x, dt, jnp.asarray(A), Bm, Cm, mesh=mesh, chunk_size=32
        )
    )(xg, dtg, bg, cg)
    yshard = yg.addressable_shards[0]
    np.testing.assert_allclose(
        np.asarray(yshard.data), ref_y[yshard.index], atol=2e-5
    )

    # and the cp-SSD backward: the all_gather transpose (psum_scatter)
    # over the state pairs crosses the process boundary
    ref_gx = np.asarray(
        jax.grad(
            lambda x: jnp.sum(
                ssd_scan(
                    x, jnp.asarray(dt), jnp.asarray(A),
                    jnp.asarray(Bm), jnp.asarray(Cm), chunk_size=32,
                )
                ** 2
            )
        )(jnp.asarray(x))
    )
    gx = jax.jit(
        jax.grad(
            lambda x, dt, Bm, Cm: jnp.sum(
                ssd_scan_cp(
                    x, dt, jnp.asarray(A), Bm, Cm, mesh=mesh, chunk_size=32
                )
                ** 2
            )
        )
    )(xg, dtg, bg, cg)
    gxshard = gx.addressable_shards[0]
    np.testing.assert_allclose(
        np.asarray(gxshard.data), ref_gx[gxshard.index], atol=5e-4
    )

    # ---- MoE expert-parallel all-to-all with the expert axis ON the
    # process boundary (same innermost-adjacency reason as the context
    # axis: the entry-level ep mode keeps expert pairs intra-process)
    from fms_fsdp_tpu.models.configs import MixtralConfig
    from fms_fsdp_tpu.models.mixtral import init_mixtral_params, mixtral_forward

    cfg = MixtralConfig(
        src_vocab_size=128,
        emb_dim=64,
        nheads=4,
        kvheads=2,
        nlayers=1,
        hidden_dim=64,
        num_experts=2,
        top_k=2,
        capacity_factor=8.0,  # ample: dispatch must equal dense-mix
        max_expected_seq_len=64,
    )
    emesh = build_mesh(
        MeshConfig(sharding_strategy="fsdp", expert_parallel_size=2)
    )
    params = init_mixtral_params(
        jax.random.PRNGKey(0), cfg, dtype=jnp.float32
    )  # identical on both ranks (replicated jit operand)
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128), np.int32
    )
    ref_moe = np.asarray(
        mixtral_forward(
            params, jnp.asarray(toks), cfg,
            compute_dtype=jnp.float32, moe_impl="dense",
        )
    )
    out_moe = jax.jit(
        lambda p, t: mixtral_forward(
            p, t, cfg, compute_dtype=jnp.float32, moe_impl="dispatch",
            mesh=emesh,
        )
    )(params, jnp.asarray(toks))
    shard = out_moe.addressable_shards[0]
    np.testing.assert_allclose(
        np.asarray(shard.data), ref_moe[shard.index], atol=3e-5
    )
    # the explicit a2a path (not the GSPMD fallback) took this config
    from fms_fsdp_tpu.models.mixtral import _use_expert_a2a

    assert _use_expert_a2a(cfg, emesh, toks.shape[0])

    print("RING_OPS_OK", flush=True)


if __name__ == "__main__":
    main()
