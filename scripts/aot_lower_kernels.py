"""Deviceless Mosaic lowering of every shipped Pallas kernel variant and
jitted train step against a TPU v5e topology — no chip required.

``jax.experimental.topologies.get_topology_desc`` builds a v5e
TopologyDescription on a chipless host, and ``jit(...).lower(...).
compile()`` against it runs the FULL XLA:TPU + Mosaic pipeline (verified:
an invalid kernel fails here exactly as it would on device). This
catches the "kernel never lowered on real TPU" failure class (this
repo's round-2 SSD kernel) without a chip, and answers compile-side
questions like the int8 E-major Mixtral hang attribution. The kernels
of the main path are also compiled by tests/test_aot_compile.py on
every test run; this sweep adds the whole-step and multi-device targets.

What it cannot do: execute. Numerics, runtime hangs, and performance
still need silicon (chip_smoke.py is the quickest such run).

Robustness contract: the parent never imports jax; every target runs as ``--target N`` in its own subprocess under a
watchdog, so one Mosaic crash or hang yields a JSON error/timeout entry
instead of killing the sweep. Results land in AOT_LOWER.json (written
where it is run; not committed).

Run: python scripts/aot_lower_kernels.py            # full sweep
     python scripts/aot_lower_kernels.py --target 0 # one target (child)
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TOPOLOGY = os.environ.get("AOT_TOPOLOGY", "v5e:2x2")
TARGET_TIMEOUT_S = int(os.environ.get("AOT_TARGET_TIMEOUT_S", "1500"))


# -- child-side builders ----------------------------------------------------


def _env_setup():
    # trace REAL Mosaic kernels on this chipless host (pallas_mode.py),
    # and keep jax itself on the CPU client — the TPU side exists only
    # as the AOT compile target
    os.environ["FMS_FORCE_COMPILED_PALLAS"] = "1"
    import jax

    jax.config.update("jax_platforms", "cpu")


_USED_TOPOLOGY = None  # recorded per target into AOT_LOWER.json


def _topology_mesh(shape=(1, 1, 1, 1, 1), topology=None):
    """Full-axis Mesh over the deviceless v5e topology's devices
    (legacy 5-axis shapes get a leading dcn=1 prepended — AOT targets
    are single-slice programs; the dcn axis only matters on multislice
    hardware the deviceless topologies cannot describe). The
    default is a SINGLE-device mesh: an un-shard_mapped Mosaic kernel
    cannot be partitioned by GSPMD, so standalone-kernel targets compile
    single-chip (the bench-row configuration) while multi-device shapes
    are for shard_map'd compositions and full train steps. When the
    requested mesh outgrows the configured topology, it scales up to the
    2-host v5e:2x4, so 8-device programs compile with a REAL host
    boundary in the device assignment; the topology actually used is
    recorded in each result entry."""
    global _USED_TOPOLOGY
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from fms_fsdp_tpu.parallel.mesh import MESH_AXES

    if len(shape) == len(MESH_AXES) - 1:
        shape = (1,) + tuple(shape)
    n = int(np.prod(shape))
    name = topology or TOPOLOGY
    td = topologies.get_topology_desc(platform="tpu", topology_name=name)
    if n > len(td.devices):
        name = "v5e:2x4"
        td = topologies.get_topology_desc(platform="tpu", topology_name=name)
    assert n <= len(td.devices), (shape, len(td.devices))
    _USED_TOPOLOGY = name
    return Mesh(np.asarray(td.devices[:n]).reshape(shape), MESH_AXES), td


def _sds(shape, dtype, sharding=None):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _repl(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def _compile_flash(variant, b, s, nq, nkv, h):
    import jax
    import jax.numpy as jnp

    from fms_fsdp_tpu.ops import flash_attention as fa

    fa.set_kernel_variant(variant)
    mesh, _ = _topology_mesh()
    r = _repl(mesh)

    def loss(q, k, v):
        return jnp.sum(
            fa.flash_attention(q, k, v, causal=True).astype(jnp.float32)
        )

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    q = _sds((b, s, nq, h), jnp.bfloat16, r)
    kv = _sds((b, s, nkv, h), jnp.bfloat16, r)
    f.lower(q, kv, kv).compile()


def _compile_ssd_fused():
    import jax
    import jax.numpy as jnp

    from fms_fsdp_tpu.ops.ssd import ssd_scan

    mesh, _ = _topology_mesh()
    r = _repl(mesh)
    # mamba_9.8b head geometry: 128 heads x P=64, d_state 128, 1 group
    b, s, hh, p, g, n = 1, 4096, 128, 64, 1, 128

    def loss(x, dt, A, Bm, Cm, D):
        return jnp.sum(
            ssd_scan(x, dt, A, Bm, Cm, D, kernel="pallas").astype(jnp.float32)
        )

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5)))
    f.lower(
        _sds((b, s, hh, p), jnp.bfloat16, r),
        _sds((b, s, hh), jnp.float32, r),
        _sds((hh,), jnp.float32, r),
        _sds((b, s, g, n), jnp.bfloat16, r),
        _sds((b, s, g, n), jnp.bfloat16, r),
        _sds((hh,), jnp.float32, r),
    ).compile()


def _compile_scan_step(layers, slots, states, channels):
    """The one-position selective scan over a donated stacked slab, every
    layer through the one lowering (the layer's index an operand): Mosaic
    takes the strided reads of a (slots * states, 128) block, and the
    slab comes back as the buffer it went in as, whole: no temporary of a
    layer's size beside the kernel's calls."""
    import jax
    import jax.numpy as jnp

    from fms_fsdp_tpu.ops.selective_scan import (
        selective_scan_slab_step,
        scan_step_form,
    )

    mesh, _ = _topology_mesh()
    r = _repl(mesh)
    f32 = jnp.float32
    assert scan_step_form(slots, states, channels) == "kernel"

    def steps(u, dt, A, B, C, D, slab, live):
        y = 0.0
        for layer in range(layers):
            out, slab = selective_scan_slab_step(
                u, dt, A, B, C, D, slab, layer, live
            )
            y = y + out
        return y, slab

    row = _sds((slots, channels), f32, r)
    col = _sds((slots, states), f32, r)
    shape = (layers, slots, states, channels)
    compiled = jax.jit(steps, donate_argnums=(6,)).lower(
        row, row, _sds((states, channels), f32, r), col, col,
        _sds((channels,), f32, r), _sds(shape, f32, r),
        _sds((slots,), jnp.bool_, r),
    ).compile()
    m = compiled.memory_analysis()
    slab_bytes = 4 * layers * slots * states * channels
    assert m.alias_size_in_bytes >= slab_bytes, m.alias_size_in_bytes
    assert m.temp_size_in_bytes < slab_bytes // layers, m.temp_size_in_bytes
    assert compiled.as_text().count("tpu_custom_call") == layers


def _compile_ring(cp):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fms_fsdp_tpu.ops.ring_attention import ring_attention
    from fms_fsdp_tpu.parallel.mesh import AXIS_CONTEXT

    mesh, _ = _topology_mesh((1, 1, 1, cp, 1))
    shard = NamedSharding(mesh, P(None, AXIS_CONTEXT, None, None))

    def loss(q, k, v):
        return jnp.sum(
            ring_attention(q, k, v, mesh, causal=True).astype(jnp.float32)
        )

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    q = _sds((1, 4096 * cp, 8, 128), jnp.bfloat16, shard)
    kv = _sds((1, 4096 * cp, 8, 128), jnp.bfloat16, shard)
    f.lower(q, kv, kv).compile()


def _compile_cp_ssd(cp):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fms_fsdp_tpu.ops.ssd import ssd_scan_cp
    from fms_fsdp_tpu.parallel.mesh import AXIS_CONTEXT

    mesh, _ = _topology_mesh((1, 1, 1, cp, 1))
    seq_shard = NamedSharding(mesh, P(None, AXIS_CONTEXT, None, None))
    seq_shard3 = NamedSharding(mesh, P(None, AXIS_CONTEXT, None))
    r = _repl(mesh)
    b, s, hh, p, g, n = 1, 1024 * cp, 128, 64, 1, 128

    def loss(x, dt, A, Bm, Cm, D):
        return jnp.sum(
            ssd_scan_cp(x, dt, A, Bm, Cm, D, mesh=mesh).astype(jnp.float32)
        )

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5)))
    f.lower(
        _sds((b, s, hh, p), jnp.bfloat16, seq_shard),
        _sds((b, s, hh), jnp.float32, seq_shard3),
        _sds((hh,), jnp.float32, r),
        _sds((b, s, g, n), jnp.bfloat16, seq_shard),
        _sds((b, s, g, n), jnp.bfloat16, seq_shard),
        _sds((hh,), jnp.float32, r),
    ).compile()


def _compile_train_step(
    variant, model_overrides, mesh_shape=(1, 4, 1, 1, 1), **cfg_overrides
):
    """AOT-compile the FULL donated jitted train step over a mesh of
    topology devices (default: 4-way fsdp; the _2host targets pass
    hsdp/cp/ep/tp shapes): Pallas kernels + GSPMD partitioning + int8
    GEMMs, compiled exactly as a v5e pod slice would compile them."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from fms_fsdp_tpu.config import TrainConfig
    from fms_fsdp_tpu.parallel.mixed_precision import get_dtype_policy
    from fms_fsdp_tpu.parallel.sharding import (
        batch_pspec,
        infer_state_specs,
        resolve_spec,
        tree_shardings,
    )
    from fms_fsdp_tpu.models import get_model_api
    from fms_fsdp_tpu.train.step import make_optimizer, make_train_step
    from fms_fsdp_tpu.utils.config_utils import get_model_config
    from jax.sharding import NamedSharding

    cfg_kw = dict(
        model_variant=variant,
        sharding_strategy="fsdp",
        batch_size=2,
        seq_length=4096,
        attention_kernel="pallas",
    )
    cfg_kw.update(cfg_overrides)
    cfg = TrainConfig(**cfg_kw)
    model_cfg = get_model_config(variant)
    if model_overrides:
        model_cfg = dataclasses.replace(model_cfg, **model_overrides)

    mesh, _ = _topology_mesh(mesh_shape)
    opt = make_optimizer(cfg)
    policy = get_dtype_policy(cfg)
    init_params, _, specs_fn, _ = get_model_api(model_cfg)

    def init_fn(rng):
        params = init_params(rng, model_cfg, dtype=policy.param_dtype)
        return {
            "params": params,
            "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    specs = infer_state_specs(shapes, specs_fn())
    shardings = tree_shardings(
        mesh, specs, jax.tree.map(lambda s: s.shape, shapes)
    )
    state = jax.tree.map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), shapes, shardings
    )

    from fms_fsdp_tpu.parallel.mesh import data_parallel_extent

    step_fn = make_train_step(model_cfg, cfg, mesh, opt)
    gb = cfg.batch_size * data_parallel_extent(mesh)
    bshape = (gb, cfg.seq_length)
    bsh = NamedSharding(mesh, resolve_spec(batch_pspec(), bshape, mesh))
    batch = (_sds(bshape, jnp.int32, bsh), _sds(bshape, jnp.int32, bsh))
    step_fn.lower(state, batch).compile()


# (name, thunk) — every shipped Pallas kernel variant + the flagship
# jitted train steps at their bench-row configs
TARGETS = [
    # resident (base-2) flash family, fwd+bwd, MHA and GQA
    ("flash_resident_mha_4k", lambda: _compile_flash("resident", 1, 4096, 32, 32, 128)),
    ("flash_resident_gqa_4k", lambda: _compile_flash("resident", 1, 4096, 8, 2, 128)),
    # kv-streamed family at the long-context bench rows
    ("flash_kvgrid_16k", lambda: _compile_flash("kvgrid", 1, 16384, 8, 2, 128)),
    ("flash_kvgrid_32k", lambda: _compile_flash("kvgrid", 1, 32768, 8, 2, 128)),
    # fused whole-sequence SSD kernel (the win-or-delete candidate)
    ("ssd_fused_fwd_bwd", _compile_ssd_fused),
    # the one-position Mamba-1 scan over the slab in place: the phi4flash
    # cell's stacked slab (9 layers, 128 slots) and one Jamba layer's
    # state (16 slots)
    ("scan_step_phi4flash_slab", lambda: _compile_scan_step(9, 128, 16, 5120)),
    ("scan_step_jamba_layer", lambda: _compile_scan_step(1, 16, 16, 5120)),
    # kernel + collective compositions a pod actually runs
    ("ring_attention_cp4", lambda: _compile_ring(4)),
    ("cp_ssd_cp4", lambda: _compile_cp_ssd(4)),
    # full train steps: Pallas + GSPMD + int8, bench-row shapes
    (
        "train_llama7b_int8_pallas",
        lambda: _compile_train_step(
            "llama2_7b",
            {"nlayers": 3},
            quantized_matmuls="int8_dgrad",
            fsdp_activation_checkpointing=True,
            selective_checkpointing=0.25,
        ),
    ),
    (
        "train_mamba9.8b_pallas_int8",
        lambda: _compile_train_step(
            "mamba_9.8b",
            {"n_layer": 2, "attn_layer_idx": (), "vocab_size": 32000},
            quantized_matmuls="int8_dgrad",
            fsdp_activation_checkpointing=True,
            selective_checkpointing=0.5,
            mamba_kernel="pallas",
        ),
    ),
    # the open E-major question: does the int8 Mixtral row COMPILE for
    # v5e? (XLA:CPU already exonerated — NOTES.md r3)
    (
        "train_mixtral_int8_emajor",
        lambda: _compile_train_step(
            "mixtral_8x7b",
            {"nlayers": 1, "num_experts": 4, "capacity_factor": 1.25},
            quantized_matmuls="int8_dgrad",
            fsdp_activation_checkpointing=True,
            selective_checkpointing=1,
        ),
    ),
    # multi-axis mesh plans on an 8-device 2-HOST v5e:2x4 topology: the
    # dryrun_multichip compositions, compiled by the real TPU compiler
    # with a host boundary in the device assignment (the CPU dryrun can
    # only prove these shard; it cannot prove Mosaic+GSPMD compile them)
    (
        "train_llama_hsdp_tp_pallas_int8_2host",
        lambda: _compile_train_step(
            "llama2_7b",
            {"nlayers": 2},
            mesh_shape=(2, 2, 1, 1, 2),
            sharding_strategy="hsdp",
            sharding_group_size=2,
            quantized_matmuls="int8_dgrad",
            fsdp_activation_checkpointing=True,
            selective_checkpointing=0.25,
        ),
    ),
    (
        "train_mamba_hybrid_cp_ring_2host",
        lambda: _compile_train_step(
            "mamba_9.8b",
            {"n_layer": 2, "attn_layer_idx": (1,), "vocab_size": 32000},
            mesh_shape=(1, 4, 1, 2, 1),
            fsdp_activation_checkpointing=True,
            selective_checkpointing=0.5,
        ),
    ),
    (
        "train_mixtral_ep_tp_int8_2host",
        lambda: _compile_train_step(
            "mixtral_8x7b",
            {"nlayers": 1, "num_experts": 4, "capacity_factor": 1.25},
            mesh_shape=(1, 2, 2, 1, 2),
            quantized_matmuls="int8_dgrad",
            fsdp_activation_checkpointing=True,
            selective_checkpointing=1,
        ),
    ),
    # the 32k single-chip long-context step: kv-streamed flash + full AC
    # + chunked fused CE
    (
        "train_llama194m_32k_kvgrid_fusedce",
        lambda: _compile_train_step(
            "llama3_194m_4k",
            {},
            mesh_shape=(1, 1, 1, 1, 1),
            batch_size=1,
            seq_length=32768,
            fused_loss=True,
            flash_kernel_variant="kvgrid",
            fsdp_activation_checkpointing=True,
            selective_checkpointing=1,
        ),
    ),
]


def _child(idx):
    _env_setup()
    name, thunk = TARGETS[idx]
    t0 = time.time()
    try:
        thunk()
        r = {"target": name, "status": "compiled", "seconds": round(time.time() - t0, 1)}
    except Exception as e:  # noqa: BLE001
        r = {
            "target": name,
            "status": "error",
            "seconds": round(time.time() - t0, 1),
            "error": f"{type(e).__name__}: {e}"[:400],
        }
    if _USED_TOPOLOGY:
        r["topology"] = _USED_TOPOLOGY
    print("AOT_TARGET_JSON:" + json.dumps(r))


def main():
    results = []
    for idx, (name, _t) in enumerate(TARGETS):
        t0 = time.time()
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--target", str(idx)],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                timeout=TARGET_TIMEOUT_S,
                text=True,
            )
            r = None
            for line in (proc.stdout or "").splitlines():
                if line.startswith("AOT_TARGET_JSON:"):
                    r = json.loads(line[len("AOT_TARGET_JSON:") :])
            if r is None:
                tail = (proc.stdout or "").strip().splitlines()[-3:]
                r = {
                    "target": name,
                    "status": "error",
                    "error": f"child rc={proc.returncode}: {' | '.join(tail)}"[:400],
                }
        except subprocess.TimeoutExpired:
            r = {
                "target": name,
                "status": "timeout",
                "seconds": round(time.time() - t0, 1),
                "error": f"no result within {TARGET_TIMEOUT_S}s",
            }
        print(f"[aot] {r['target']}: {r['status']} ({r.get('seconds', '?')}s)", flush=True)
        results.append(r)

    out = {
        "topology": (
            f"default {TOPOLOGY}; multi-device targets may scale up — "
            "see each entry's topology field"
        ),
        "note": (
            "AOT lowering+compilation through the full XLA:TPU/Mosaic "
            "pipeline against a deviceless v5e TopologyDescription; "
            "validates kernels COMPILE for the chip (the r2 'never "
            "lowered' failure class), not that they are fast or "
            "numerically correct there"
        ),
        "targets": results,
        "compiled": sum(1 for r in results if r["status"] == "compiled"),
        "total": len(results),
    }
    with open("AOT_LOWER.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"compiled": out["compiled"], "total": out["total"]}))


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--target":
        _child(int(sys.argv[2]))
    else:
        main()
