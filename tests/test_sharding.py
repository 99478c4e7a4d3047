"""Mesh + sharding + end-to-end train-step tests on the 8-device CPU mesh.

Verifies the jax.sharding replacement for the reference's FSDP/HSDP/DDP
trichotomy (ref:train_utils.py:227-234): mesh shapes, param placement, and
that the full jitted train step runs and learns under each strategy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fms_fsdp_tpu.config import TrainConfig
from fms_fsdp_tpu.models.configs import LlamaConfig
from fms_fsdp_tpu.parallel.mesh import MeshConfig, build_mesh
from fms_fsdp_tpu.parallel.sharding import (
    infer_state_specs,
    llama_param_specs,
    resolve_spec,
)
from fms_fsdp_tpu.train.step import (
    init_train_state,
    make_optimizer,
    make_train_step,
)

TINY = LlamaConfig(
    src_vocab_size=256,
    emb_dim=64,
    nheads=4,
    kvheads=2,
    nlayers=2,
    multiple_of=16,
    max_expected_seq_len=64,
)


def _cfg(**kw):
    base = dict(
        model_variant="tiny",
        seq_length=16,
        batch_size=2,
        num_steps=100,
        learning_rate=1e-2,
        report_interval=10,
        vocab_size=256,
        attention_kernel="xla",
    )
    base.update(kw)
    return TrainConfig(**base)


def _shape(**kw):
    base = {
        "dcn": 1,
        "replica": 1,
        "fsdp": 1,
        "expert": 1,
        "context": 1,
        "tensor": 1,
    }
    base.update(kw)
    return base


def test_mesh_shapes():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    m = build_mesh(MeshConfig(sharding_strategy="fsdp"))
    assert dict(m.shape) == _shape(fsdp=8)
    m = build_mesh(MeshConfig(sharding_strategy="ddp"))
    assert dict(m.shape) == _shape(replica=8)
    m = build_mesh(MeshConfig(sharding_strategy="hsdp", sharding_group_size=4))
    assert dict(m.shape) == _shape(replica=2, fsdp=4)
    m = build_mesh(MeshConfig(sharding_strategy="fsdp", tensor_parallel_size=2))
    assert dict(m.shape) == _shape(fsdp=4, tensor=2)
    m = build_mesh(
        MeshConfig(sharding_strategy="fsdp", context_parallel_size=2)
    )
    assert dict(m.shape) == _shape(fsdp=4, context=2)
    m = build_mesh(
        MeshConfig(sharding_strategy="fsdp", expert_parallel_size=4)
    )
    assert dict(m.shape) == _shape(fsdp=2, expert=4)
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(sharding_strategy="hsdp", sharding_group_size=3))


# ---- multi-slice (dcn axis) -------------------------------------------------


def test_multislice_mesh_shapes():
    """The dcn axis takes the cross-slice factor; strategies split the
    PER-SLICE data-parallel extent."""
    m = build_mesh(MeshConfig(sharding_strategy="fsdp", num_slices=2))
    assert dict(m.shape) == _shape(dcn=2, fsdp=4)
    m = build_mesh(
        MeshConfig(sharding_strategy="hsdp", num_slices=2, sharding_group_size=2)
    )
    assert dict(m.shape) == _shape(dcn=2, replica=2, fsdp=2)
    m = build_mesh(
        MeshConfig(
            sharding_strategy="fsdp", num_slices=2, tensor_parallel_size=2
        )
    )
    assert dict(m.shape) == _shape(dcn=2, fsdp=2, tensor=2)
    # each dcn index holds one slice's devices (contiguous blocks on the
    # simulated partition)
    m2 = build_mesh(MeshConfig(sharding_strategy="fsdp", num_slices=2))
    ids = np.vectorize(lambda d: d.id)(m2.devices)
    assert sorted(ids[0].flatten().tolist()) == [0, 1, 2, 3]
    assert sorted(ids[1].flatten().tolist()) == [4, 5, 6, 7]
    with pytest.raises(ValueError, match="slice"):
        build_mesh(MeshConfig(sharding_strategy="fsdp", num_slices=3))


def test_single_slice_mesh_is_legacy_5axis_placement():
    """dcn=1 meshes are the historical 5-axis construction with a
    leading size-1 axis reshaped on: device placement is bit-identical
    for every strategy (elastic fingerprints, checkpoint shardings, and
    collective replica groups all hang off this)."""
    from jax.experimental import mesh_utils

    for cfg, shape5 in [
        (MeshConfig(sharding_strategy="fsdp"), (1, 8, 1, 1, 1)),
        (MeshConfig(sharding_strategy="ddp"), (8, 1, 1, 1, 1)),
        (
            MeshConfig(sharding_strategy="hsdp", sharding_group_size=4),
            (2, 4, 1, 1, 1),
        ),
        (
            MeshConfig(sharding_strategy="fsdp", tensor_parallel_size=2),
            (1, 4, 1, 1, 2),
        ),
    ]:
        m = build_mesh(cfg)
        legacy = mesh_utils.create_device_mesh(shape5, devices=jax.devices())
        got = np.vectorize(lambda d: d.id)(m.devices)
        want = np.vectorize(lambda d: d.id)(legacy)[None]
        assert (got == want).all(), (cfg, got, want)


def test_default_group_size_from_passed_devices():
    """Satellite fix: HSDP group inference derives devices-per-host from
    the PASSED devices (and their slice membership), never from this
    process's jax.local_device_count() — a simulated/partial world must
    get groups for ITS shape."""
    from fms_fsdp_tpu.parallel.mesh import _default_group_size

    class FakeDev:
        def __init__(self, process_index):
            self.process_index = process_index

    # 2 hosts x 4 devices: shard within the 4-device host. The old code
    # consulted jax.local_device_count() (8 on this test backend) and
    # would have returned 8 — one group spanning both hosts.
    two_hosts = [FakeDev(p) for p in (0, 0, 0, 0, 1, 1, 1, 1)]
    assert _default_group_size(8, two_hosts) == 4
    # single host: no multi-host split -> the full extent
    assert _default_group_size(4, [FakeDev(0)] * 4) == 4
    # non-dividing host size degrades to the full extent
    assert _default_group_size(6, [FakeDev(0)] * 4 + [FakeDev(1)] * 2) == 6


def test_slice_assignments_and_context():
    from fms_fsdp_tpu.parallel.mesh import (
        process_slice_context,
        slice_assignments,
    )

    ids, n = slice_assignments(jax.devices())
    assert n == 1 and set(ids) == {0}
    ids, n = slice_assignments(jax.devices(), 2)
    assert n == 2 and ids == [0, 0, 0, 0, 1, 1, 1, 1]
    with pytest.raises(ValueError, match="slice"):
        slice_assignments(jax.devices(), 3)
    # single-process world: this process is always slice 0
    assert process_slice_context() == (1, 0)

    class Cfg:
        num_slices = 2

    assert process_slice_context(Cfg()) == (2, 0)


def test_hierarchical_reduce_info():
    from fms_fsdp_tpu.parallel.sharding import hierarchical_reduce_info

    m1 = build_mesh(MeshConfig(sharding_strategy="fsdp"))
    info = hierarchical_reduce_info(m1)
    assert info == {"ici_axes": ("fsdp",), "dcn_axes": ()}
    m2 = build_mesh(
        MeshConfig(sharding_strategy="hsdp", num_slices=2, sharding_group_size=2)
    )
    info = hierarchical_reduce_info(m2)
    assert info == {"ici_axes": ("replica", "fsdp"), "dcn_axes": ("dcn",)}


def test_resolve_spec_drops_axes_missing_from_mesh():
    """A 5-axis legacy mesh consumes the shared dcn-bearing specs: axes
    the mesh does not carry resolve away instead of KeyError-ing."""
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    from fms_fsdp_tpu.parallel.sharding import batch_pspec

    legacy = Mesh(
        mesh_utils.create_device_mesh((1, 8, 1, 1, 1), devices=jax.devices()),
        ("replica", "fsdp", "expert", "context", "tensor"),
    )
    spec = resolve_spec(batch_pspec(), (8, 64), legacy)
    assert spec == P(("replica", "fsdp", "expert"), "context")


def _compiled_step_text(cfg, mesh):
    import jax.numpy as jnp

    opt = make_optimizer(cfg)
    state, _ = init_train_state(jax.random.PRNGKey(0), TINY, cfg, mesh, opt)
    step_fn = make_train_step(TINY, cfg, mesh, opt)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(8, 17))
    batch = (
        jnp.asarray(tokens[:, :-1], jnp.int32),
        jnp.asarray(tokens[:, 1:], jnp.int32),
    )
    return (
        jax.jit(lambda s, b: step_fn(s, b)).lower(state, batch).compile()
        .as_text(),
        batch,
    )


def test_dcn1_step_adds_no_collectives():
    """The bit-identity pin (same technique class as the quant suite's
    no-narrow-types scan): the compiled train step on a dcn=1 mesh
    carries exactly the collectives of the legacy 5-axis program — no
    cross-slice op, and no extra within-slice op either."""
    import re

    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    from fms_fsdp_tpu.parallel.mesh import hlo_collective_split

    cfg = _cfg(sharding_strategy="fsdp")
    m6 = build_mesh(MeshConfig.from_train_config(cfg))
    legacy = Mesh(
        mesh_utils.create_device_mesh((1, 8, 1, 1, 1), devices=jax.devices()),
        ("replica", "fsdp", "expert", "context", "tensor"),
    )
    txt6, _ = _compiled_step_text(cfg, m6)
    txt5, _ = _compiled_step_text(cfg, legacy)

    def collective_lines(t):
        # a stack_frame_id numbers the frames this process has interned so
        # far, which follows what the worker ran before: not the program's
        return sorted(
            re.sub(r" stack_frame_id=\d+", "", line)
            for line in re.findall(
                r"\b(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
                r"collective-permute)(?:-start)?[.\d]*\([^\n]*",
                t,
            )
        )

    assert collective_lines(txt6) == collective_lines(txt5)
    split = hlo_collective_split(txt6, m6)
    assert split["dcn"] == 0 and split["unattributed"] == 0, split


def test_two_slice_step_has_dcn_collectives_and_agrees():
    """Positive control for the dcn=1 pin: a 2-slice mesh's compiled
    step really does carry cross-slice collectives — and the math is
    the same (first-steps loss matches single-slice fsdp)."""
    import jax.numpy as jnp

    from fms_fsdp_tpu.parallel.mesh import hlo_collective_split

    cfg2 = _cfg(sharding_strategy="fsdp", num_slices=2)
    m2 = build_mesh(MeshConfig.from_train_config(cfg2))
    txt2, batch = _compiled_step_text(cfg2, m2)
    split = hlo_collective_split(txt2, m2)
    assert split["dcn"] > 0, split

    results = {}
    for name, cfg in (("slice2", cfg2), ("fsdp", _cfg(sharding_strategy="fsdp"))):
        mesh = build_mesh(MeshConfig.from_train_config(cfg))
        opt = make_optimizer(cfg)
        state, _ = init_train_state(
            jax.random.PRNGKey(0), TINY, cfg, mesh, opt
        )
        step_fn = make_train_step(TINY, cfg, mesh, opt)
        for _ in range(3):
            state, metrics = step_fn(state, batch)
        results[name] = float(metrics["loss"])
    assert results["slice2"] == pytest.approx(results["fsdp"], rel=2e-2)


def test_resolve_spec_divisibility():
    mesh = build_mesh(MeshConfig(sharding_strategy="fsdp"))
    # 64 divisible by 8 -> kept; 30 not -> dropped
    assert resolve_spec(P("fsdp", None), (64, 3), mesh) == P("fsdp", None)
    assert resolve_spec(P("fsdp", None), (30, 3), mesh) == P(None, None)


def test_llama70b_shardings_resolve():
    """The 70B target config (GQA 64/8 heads, emb 8192) produces valid
    NamedShardings for the full train state on an 8-device FSDP mesh —
    shape-level only (eval_shape; nothing materialized)."""
    from fms_fsdp_tpu.parallel.sharding import tree_shardings
    from fms_fsdp_tpu.train.step import make_optimizer
    from fms_fsdp_tpu.utils.config_utils import get_model_config

    cfg = TrainConfig(sharding_strategy="fsdp", seq_length=4096)
    model_cfg = get_model_config("llama2_70b")
    assert model_cfg.nheads == 64 and model_cfg.n_kv_heads == 8
    mesh = build_mesh(MeshConfig.from_train_config(cfg))
    opt = make_optimizer(cfg)

    from fms_fsdp_tpu.models import get_model_api

    init_params, _, specs_fn, _ = get_model_api(model_cfg)

    def init_fn(rng):
        params = init_params(rng, model_cfg, dtype=jnp.float32)
        return {
            "params": params,
            "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    n_params = sum(
        np.prod(s.shape) for s in jax.tree.leaves(shapes["params"])
    )
    assert n_params > 65e9  # truly 70B-scale
    specs = infer_state_specs(shapes, specs_fn())
    shardings = tree_shardings(
        mesh, specs, jax.tree.map(lambda s: s.shape, shapes)
    )
    # every leaf resolves; the big 2D weights actually shard over fsdp
    for leaf in jax.tree.leaves(shardings):
        assert leaf is not None
    assert "fsdp" in str(shardings["params"]["layers"]["wq"].spec)


def test_state_spec_inference():
    cfg = _cfg(sharding_strategy="fsdp")
    mesh = build_mesh(MeshConfig.from_train_config(cfg))
    opt = make_optimizer(cfg)
    state, shardings = init_train_state(
        jax.random.PRNGKey(0), TINY, cfg, mesh, opt
    )
    # params sharded over fsdp on the model dim
    wq_spec = state["params"]["layers"]["wq"].sharding.spec
    assert wq_spec[1] == "fsdp"
    # adam mu mirrors the param sharding
    mu = state["opt_state"].inner_state[0].mu["layers"]["wq"]
    assert mu.sharding.spec == state["params"]["layers"]["wq"].sharding.spec
    # scalar step replicated
    assert state["step"].sharding.spec == P()


@pytest.mark.parametrize(
    "strategy,extra",
    [
        ("ddp", {}),
        ("fsdp", {}),
        ("hsdp", {"sharding_group_size": 4}),
        ("fsdp", {"tensor_parallel_size": 2}),
    ],
)
def test_train_step_learns(strategy, extra):
    cfg = _cfg(sharding_strategy=strategy, **{k: v for k, v in extra.items()})
    mesh = build_mesh(MeshConfig.from_train_config(cfg))
    opt = make_optimizer(cfg)
    state, _ = init_train_state(jax.random.PRNGKey(0), TINY, cfg, mesh, opt)
    step_fn = make_train_step(TINY, cfg, mesh, opt)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(8, 17))
    inputs = jnp.asarray(tokens[:, :-1], jnp.int32)
    labels = jnp.asarray(tokens[:, 1:], jnp.int32)
    labels = labels.at[:, 0].set(-100)  # causal_lm prompt masking analog

    losses = []
    for _ in range(20):
        state, metrics = step_fn(state, (inputs, labels))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    # memorizing one batch must drive loss down hard
    assert losses[-1] < losses[0] * 0.5, losses
    assert float(metrics["gnorm"]) > 0
    assert int(state["step"]) == 20


def test_strategies_agree():
    """ddp and fsdp are the same math — first-step loss must match."""
    results = {}
    for strategy in ["ddp", "fsdp"]:
        cfg = _cfg(sharding_strategy=strategy)
        mesh = build_mesh(MeshConfig.from_train_config(cfg))
        opt = make_optimizer(cfg)
        state, _ = init_train_state(jax.random.PRNGKey(0), TINY, cfg, mesh, opt)
        step_fn = make_train_step(TINY, cfg, mesh, opt)
        rng = np.random.default_rng(1)
        tokens = rng.integers(0, 256, size=(8, 17))
        inputs = jnp.asarray(tokens[:, :-1], jnp.int32)
        labels = jnp.asarray(tokens[:, 1:], jnp.int32)
        for _ in range(3):
            state, metrics = step_fn(state, (inputs, labels))
        results[strategy] = float(metrics["loss"])
    assert results["ddp"] == pytest.approx(results["fsdp"], rel=2e-2)


def test_base_api_specs_shard_every_arch():
    """Every speculator base arch must ship a spec rulebook so a large
    frozen base is never silently replicated
    (ref:speculator/train_speculator.py:133-160 shards all bases). Big
    weight matrices land sharded, and the sharded forward matches the
    host-side forward."""
    from fms_fsdp_tpu.models import get_base_api
    from fms_fsdp_tpu.models.configs import MixtralConfig
    from fms_fsdp_tpu.models.gpt_bigcode import GPTBigCodeConfig
    from fms_fsdp_tpu.parallel.sharding import shard_params

    cfg = _cfg(sharding_strategy="fsdp")
    mesh = build_mesh(MeshConfig.from_train_config(cfg))

    arch_cfgs = {
        "llama": TINY,
        "gpt_bigcode": GPTBigCodeConfig(
            src_vocab_size=256,
            emb_dim=64,
            nheads=4,
            nlayers=2,
            max_expected_seq_len=64,
        ),
        "mixtral": MixtralConfig(
            src_vocab_size=256,
            emb_dim=64,
            nheads=4,
            kvheads=2,
            nlayers=2,
            hidden_dim=96,
            num_experts=4,
            top_k=2,
            max_expected_seq_len=64,
        ),
    }
    # per arch: one big matrix leaf that MUST be sharded on an fsdp mesh
    must_shard = {
        "llama": lambda p: p["layers"]["w1"],
        "gpt_bigcode": lambda p: p["layers"]["c_fc"],
        "mixtral": lambda p: p["layers"]["w1"],
    }
    for arch, mc in arch_cfgs.items():
        api = get_base_api(arch)
        assert api.param_specs is not None, arch
        params = api.init(jax.random.PRNGKey(0), mc, dtype=jnp.float32)
        logits_host, _ = api.forward_embeds(
            params,
            jnp.zeros((1, 8), jnp.int32),
            mc,
            compute_dtype=jnp.float32,
        )
        sharded = shard_params(params, api.param_specs(), mesh)
        leaf = must_shard[arch](sharded)
        assert not leaf.sharding.is_fully_replicated, (
            arch,
            leaf.shape,
            leaf.sharding,
        )
        logits_dev, _ = api.forward_embeds(
            sharded,
            jnp.zeros((1, 8), jnp.int32),
            mc,
            compute_dtype=jnp.float32,
        )
        np.testing.assert_allclose(
            np.asarray(logits_dev), np.asarray(logits_host), atol=2e-4
        )
