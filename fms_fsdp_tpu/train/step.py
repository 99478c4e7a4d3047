"""The jitted train step and its pieces.

The reference's hot loop — zero_grad / forward / CE loss / backward /
clip_grad_norm / AdamW step / scheduler step
(ref:fms_fsdp/utils/train_utils.py:87-98) — becomes ONE jitted, donated
function over sharded global arrays. XLA overlaps the per-layer param
all-gathers with compute (what FSDP prefetch does by hand) and fuses the
optimizer update into the backward epilogue.

Optimizer parity: AdamW lr=cfg.learning_rate betas=(0.9, 0.95) wd=0.1
(ref:main_training_llama.py:113-115), global-norm clipping at
cfg.grad_clip_thresh (ref:train_utils.py:96), warmup+cosine schedule with
0.1 floor or linear annealing (ref:main_training_llama.py:137-148).
"""

import functools

import jax
import jax.numpy as jnp
import optax

from fms_fsdp_tpu.models import get_model_api
from fms_fsdp_tpu.parallel.ac import selective_ac_mask
from fms_fsdp_tpu.parallel.mixed_precision import get_dtype_policy
from fms_fsdp_tpu.parallel.sharding import (
    batch_pspec,
    infer_state_specs,
    init_amax_state,
    quantized_grad_reduce,
    resolve_spec,
    tree_shardings,
)

# torch CrossEntropyLoss default (ref:train_utils.py:90-91); one definition
# shared with the fused loss path
from fms_fsdp_tpu.ops.fused_ce import IGNORE_INDEX


def cross_entropy_loss(logits, labels):
    """Token-mean CE over labels != -100, matching
    ``CrossEntropyLoss()(output.view(-1, V), label.view(-1))``.

    Stable for bf16 logits: the max subtraction happens in the input dtype
    (exact — it only drops the shared exponent) and the exp/sum accumulate
    in fp32; no fp32 logits tensor is ever materialized.
    """
    mask = labels != IGNORE_INDEX
    safe_labels = jnp.where(mask, labels, 0)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - m).astype(jnp.float32)
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0].astype(
        jnp.float32
    )
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[
        ..., 0
    ].astype(jnp.float32)
    token_loss = (logz - gold) * mask
    return token_loss.sum() / jnp.maximum(mask.sum(), 1)


def get_lr_schedule(cfg, start_step: int = 0):
    """Return optax schedule fn: count -> lr.

    initial stage: lr * min(1 - (1 - x/w)^2,  0.1 + 0.45*(1 + cos(pi x/T)))
    with w = min(2000, T/20) (quadratic warmup into cosine with 0.1 floor);
    annealing stage: lr * (1 - x/T). (ref:main_training_llama.py:137-148)
    """
    T = cfg.num_steps
    lr = cfg.learning_rate

    if cfg.training_stage == "annealing":

        def schedule(count):
            x = count + start_step
            return lr * (1 - x / T)

    else:
        warmup = max(1, min(2000, T // 20))

        def schedule(count):
            x = count + start_step
            wx = jnp.minimum(x, warmup)
            warm = 1 - (1 - wx / warmup) ** 2
            cos = 0.1 + 0.5 * (1 - 0.1) * (
                1 + jnp.cos(jnp.minimum(x, T) / T * jnp.pi)
            )
            return lr * jnp.minimum(warm, cos)

    return schedule


def make_optimizer(cfg, start_step: int = 0):
    """AdamW(0.9, 0.95, wd=0.1). Global-norm clipping happens in the train
    step (fp32 norm, like torch clip_grad_norm_).

    The learning rate is *injected* each step from the schedule evaluated at
    the train state's own step counter, not from optax's internal count —
    so a non-resume load (continued pretraining / annealing over a restored
    optimizer) restarts the schedule simply by resetting state["step"],
    exactly like the reference's fresh LambdaLR over a loaded optimizer
    (ref:main_training_llama.py:130-148).
    """
    del start_step
    return optax.inject_hyperparams(_adamw_fp32_grads)(
        learning_rate=cfg.learning_rate,
        b1=0.9,
        b2=0.95,
        weight_decay=0.1,
    )


def _adamw_fp32_grads(learning_rate, b1, b2, weight_decay):
    """adamw that upcasts incoming (bf16) grads to the param (storage)
    dtype per-leaf inside ``update``. Doing the cast here rather than as a
    whole-tree map before the optimizer keeps each upcast buffer
    leaf-local — the all-live gradient set stays in the reduce dtype,
    which is what lets 7B-shaped layers train on a 16GB chip. Casting to
    the *param* dtype (not unconditionally fp32) keeps moment dtypes
    stable under the pure_bf16 policy, and reusing adamw's own ``init``
    keeps the opt_state pytree identical to plain adamw.
    """
    inner = optax.adamw(
        learning_rate=learning_rate, b1=b1, b2=b2, weight_decay=weight_decay
    )

    def update(grads, state, params):
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        return inner.update(grads, state, params)

    return optax.GradientTransformation(inner.init, update)


def init_train_state(
    rng,
    model_cfg,
    cfg,
    mesh,
    optimizer,
):
    """Create the fully sharded train state {params, opt_state, step} for
    any supported model family (Llama, Mamba hybrid, Mixtral MoE).

    Init runs *inside jit with sharded outputs*: each device materializes
    only its own param/opt shards — the TPU analog of the reference's
    meta-device + per-shard ``reset_parameters`` path used for 70B
    (``low_cpu_fsdp``, ref:main_training_llama.py:60-62,
    ref:policies/param_init.py:9-18) — and it is cheap enough that we always
    do it.
    """
    policy = get_dtype_policy(cfg)
    init_params, _, specs_fn, _ = get_model_api(model_cfg)

    def init_fn(rng):
        params = init_params(rng, model_cfg, dtype=policy.param_dtype)
        state = {
            "params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }
        if policy.reduce_quant == "fp8_delayed":
            # delayed-scaling amax history rides in the train state so
            # it checkpoints / donates / elastic-reshards (replicated)
            # like optimizer state
            state["quant"] = init_amax_state(
                params, int(getattr(cfg, "fp8_amax_history_len", 16))
            )
        return state

    shapes = jax.eval_shape(init_fn, rng)
    specs = infer_state_specs(shapes, specs_fn())
    shardings = tree_shardings(
        mesh, specs, jax.tree.map(lambda s: s.shape, shapes)
    )
    return jax.jit(init_fn, out_shardings=shardings)(rng), shardings


def wrap_step_fn(step_fn, timer):
    """Host-side observability wrapper over the jitted step: attribute
    dispatch wall time to the ``compute`` phase (obs/timing.py). Dispatch
    is asynchronous — per-step host time here is microseconds once XLA's
    queue is ahead — but it is the hook where a *blocked* dispatch
    (device queue full, i.e. genuinely compute-bound) becomes visible,
    and the once-per-report ``device_get`` (also attributed to compute
    by the loop) accounts the rest of the window's device time."""

    def stepped(state, batch):
        with timer.phase("compute"):
            return step_fn(state, batch)

    return stepped


def make_train_step(
    model_cfg,
    cfg,
    mesh,
    optimizer,
    start_step: int = 0,
):
    """Build the jitted train step: (state, (input, label)) -> (state, metrics).

    metrics = {loss, gnorm (pre-clip global grad norm, the value the
    reference logs, ref:train_utils.py:96,109), lr, nonfinite (1.0 when
    the batch produced a non-finite loss or grad norm — the anomaly
    guard's on-device flag, fetched with the rest of the window so the
    host never syncs for it)}.

    Anomaly guard (cfg.anomaly_skip_updates, default on): when the flag
    is set the update is skipped on device — the clip scale collapses to
    0 (zeroing the grads via the jnp.where select below) and params /
    optimizer state carry the previous step's values forward, so one
    poisoned batch can never write NaN into the moments. Host-side
    policy over the flags (report skipped_batches, abort after K
    consecutive) lives in resilience/guards.py.

    The LR is evaluated at ``state["step"] + start_step`` and injected into
    the optimizer each step; ``start_step`` is nonzero only when training
    should behave as if already N steps in while state["step"] starts at 0
    (the annealing-over-loaded-model flow, ref:main_training_llama.py:
    137-148). Resumed checkpoints restore state["step"] itself, so they
    pass 0.
    """
    policy = get_dtype_policy(cfg)
    from fms_fsdp_tpu.ops.attention import configure_flash_variant

    configure_flash_variant(getattr(cfg, "flash_kernel_variant", None))
    # kernel tuning mode/table resolved once per step build, same
    # discipline as the flash variant: cached jits can never disagree
    # with the config that built them
    from fms_fsdp_tpu.tune.lookup import (
        configure_kernel_tuning,
        resolve_ce_chunk,
        resolve_dcn_bucket,
    )

    configure_kernel_tuning(
        getattr(cfg, "kernel_tuning", None),
        getattr(cfg, "kernel_tuning_table", "") or None,
    )
    init_params, forward_fn, specs_fn, n_layers = get_model_api(model_cfg)
    ac_mask = None
    if cfg.fsdp_activation_checkpointing:
        ac_mask = selective_ac_mask(n_layers, cfg.selective_checkpointing)
    schedule = get_lr_schedule(cfg, start_step)

    fused = cfg.fused_loss
    chunk = cfg.loss_chunk_size
    if fused:
        # the logits-chunk knob is tunable: table override under
        # kernel_tuning="auto", exactly cfg.loss_chunk_size when "off"
        d_model = getattr(model_cfg, "emb_dim", None) or getattr(
            model_cfg, "d_model", 0
        )
        vocab = getattr(model_cfg, "src_vocab_size", None) or getattr(
            model_cfg, "vocab_size", 0
        )
        chunk = resolve_ce_chunk(
            d_model,
            vocab,
            jnp.dtype(policy.compute_dtype).name,
            requested=chunk,
        )

    # resilience: skip-on-nonfinite guard + the nan_loss injection site
    # (both resolved at trace time — no per-step host involvement)
    from fms_fsdp_tpu.resilience.faults import fault_params

    guard_updates = bool(getattr(cfg, "anomaly_skip_updates", True))
    nan_fault = fault_params("nan_loss")
    # NOTE: the sdc_grad_flip fault site deliberately does NOT inject
    # here. Any trace-level difference — even an exact multiply-by-1.0
    # gated to one process, or the same op armed identically everywhere
    # — changes XLA's fusion/precision decisions and shifts the
    # compiled program's rounding at bf16 level, silently diverging
    # replicas (or the armed run from the clean run) on every step, not
    # just the injected one. The injection lives host-side at the train
    # loop's step boundary (resilience/divergence.py::inject_sdc),
    # where it perturbs one process's addressable shards with ZERO
    # program changes.

    from fms_fsdp_tpu.models import MambaConfig, MixtralConfig

    extra_kwargs = {}
    moe = isinstance(model_cfg, MixtralConfig)
    if isinstance(model_cfg, MambaConfig):
        extra_kwargs = {"mamba_kernel": cfg.mamba_kernel}
    elif moe:
        # train with capacity-based routing + EP; the dense-mix path is the
        # frozen-base/eval formulation. The forward returns a stats dict
        # {balance, drop_frac} alongside the output: balance (the
        # already-weighted load-balancing loss) joins the objective,
        # drop_frac is reported as a metric.
        extra_kwargs = {"moe_impl": "dispatch", "return_aux": True}

    # DCN overlap (parallel/overlap.py): resolve the bucket schedule once
    # per step build — same discipline as the flash variant and the tuning
    # table above. When disabled ("off", or "auto" on a single-slice
    # mesh), bucket_plan stays None and every branch below is the
    # pre-overlap code path, so the traced program is bit-identical to
    # the unbucketed step (pinned by tests/test_overlap.py).
    from fms_fsdp_tpu.parallel import overlap as dcn_overlap
    from fms_fsdp_tpu.parallel.mesh import num_mesh_slices

    bucket_plan = None
    param_specs = None
    dcn_overlap.set_plan_summary(None)
    if dcn_overlap.overlap_enabled(getattr(cfg, "dcn_overlap", "auto"), mesh):
        param_shapes = jax.eval_shape(
            lambda k: init_params(k, model_cfg, dtype=policy.param_dtype),
            jax.random.PRNGKey(0),
        )
        wire = dcn_overlap.wire_bytes_per_element(policy.reduce_quant)
        shape_leaves = jax.tree.leaves(param_shapes)
        total_wire = sum(int(s.size) for s in shape_leaves) * wire
        bucket_mb = resolve_dcn_bucket(
            grad_mb=-(-total_wire // dcn_overlap.MB),
            leaves=len(shape_leaves),
            slices=num_mesh_slices(mesh),
            wire_bytes=wire,
            requested=int(getattr(cfg, "dcn_bucket_mb", 0)),
        )
        bucket_plan = dcn_overlap.assign_buckets(param_shapes, bucket_mb, wire)
        param_specs = specs_fn()
        dcn_overlap.set_plan_summary(bucket_plan.summary())

    def loss_fn(params, inputs, labels):
        if bucket_plan is not None:
            # bucket anchors go around the params *entering* the forward,
            # so each bucket's cotangents join the backward exactly where
            # that bucket's layers finish differentiating
            params = dcn_overlap.apply_bucket_anchors(
                params, bucket_plan, param_specs, mesh
            )
        out = forward_fn(
            params,
            inputs,
            model_cfg,
            compute_dtype=policy.compute_dtype,
            attn_impl=cfg.attention_kernel,
            ac_mask=ac_mask,
            scan_layers=cfg.scan_layers,
            mesh=mesh,
            return_hidden=fused,
            quant=cfg.quantized_matmuls,
            **extra_kwargs,
        )
        aux = 0.0
        stats = {}
        if moe:
            out, moe_stats = out
            aux = moe_stats["balance"]
            stats["moe_drop_frac"] = moe_stats["drop_frac"]
        if fused:
            from fms_fsdp_tpu.ops.fused_ce import fused_linear_cross_entropy

            # a tied head (MambaConfig.tie_embeddings) is the embedding
            w = params["lm_head"] if "lm_head" in params else params["embedding"].T
            w = w.astype(policy.compute_dtype)
            return fused_linear_cross_entropy(out, w, labels, chunk) + aux, stats
        return cross_entropy_loss(out, labels) + aux, stats

    @functools.partial(jax.jit, donate_argnums=(0,))
    def train_step(state, batch):
        inputs, labels = batch
        bspec = jax.sharding.NamedSharding(
            mesh, resolve_spec(batch_pspec(), inputs.shape, mesh)
        )
        inputs = jax.lax.with_sharding_constraint(inputs, bspec)
        labels = jax.lax.with_sharding_constraint(labels, bspec)
        # Differentiate w.r.t. a compute-dtype copy of the params: gradients
        # then live in the policy's reduce dtype end-to-end (bf16 for the
        # bfSixteen preset, mirroring the reference's reduce_dtype=bf16,
        # ref:policies/mixed_precision.py:5-27) and the all-live grad tree
        # is half the size of fp32 grads. The fp32 upcast for Adam happens
        # per-leaf inside the optimizer chain.
        params_c = jax.tree.map(
            lambda p: p.astype(policy.compute_dtype), state["params"]
        )
        # named scopes bracket the trace so WindowedProfiler XPlane rows
        # attribute device time to fwd_bwd vs optimizer (docs/observability.md)
        with jax.named_scope("fwd_bwd"):
            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params_c, inputs, labels
            )
        if nan_fault is not None:
            # injected non-finite batch: poison loss AND grads for steps
            # [step, step+count) — the NaN-batch failure the guard below
            # must absorb (tests/test_resilience.py)
            at = int(nan_fault.get("step", 0))
            cnt = int(nan_fault.get("count", 1))
            s = state["step"] + start_step
            poison = jnp.where(
                (s >= at) & (s < at + cnt), jnp.float32(jnp.nan), jnp.float32(1.0)
            )
            loss = loss * poison
            grads = jax.tree.map(lambda g: g * poison.astype(g.dtype), grads)
        # Global-norm clip with the norm accumulated in fp32 regardless of
        # grad dtype — matches torch clip_grad_norm_ (ref:train_utils.py:96);
        # the pre-clip norm is the value the reference logs. Computed on
        # the RAW backward output, before any reduce wire round-trip:
        # the fp8_delayed wire clamps to the representable range, so an
        # inf grad leaf would otherwise be laundered to a finite value
        # here and the anomaly flag below would miss the poisoned batch
        # (while still rolling amax=inf into the delayed-scaling
        # history — permanently NaN-ing every later scale).
        gnorm = optax.global_norm(
            jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        )
        # on-device anomaly flag: loss or grad norm went non-finite (the
        # global norm folds every grad leaf, so one bad leaf trips it)
        nonfinite = jnp.logical_not(
            jnp.logical_and(jnp.isfinite(loss), jnp.isfinite(gnorm))
        )
        # Quantized gradient reduction (policy.reduce_quant): round-trip
        # the grad tree through the scale-carrying wire format exactly
        # where the reduce-dtype boundary sits. "none" skips the call
        # entirely — the traced program is bit-identical to the seed
        # step (pinned by tests/test_quant_parity.py). The clip below
        # uses the pre-wire norm (wire noise shifts it <1%; the guard
        # semantics above are what must never depend on the wire).
        new_quant = state.get("quant")
        if policy.reduce_quant != "none":
            with jax.named_scope("quant_reduce"):
                if bucket_plan is not None:
                    grads, new_quant = dcn_overlap.bucketed_quantized_grad_reduce(
                        grads, policy.reduce_quant, new_quant, bucket_plan
                    )
                else:
                    grads, new_quant = quantized_grad_reduce(
                        grads, policy.reduce_quant, new_quant
                    )
        clip_scale = jnp.minimum(1.0, cfg.grad_clip_thresh / (gnorm + 1e-6))
        if guard_updates:
            # zero poisoned grads with a true select — scaling by 0 would
            # NOT clear NaN (0*NaN=NaN). Also select the clip scale sane:
            # a NaN gnorm makes clip_scale NaN for every leaf otherwise.
            clip_scale = jnp.where(nonfinite, jnp.float32(1.0), clip_scale)
            grads = jax.tree.map(
                lambda g: jnp.where(nonfinite, jnp.zeros_like(g), g), grads
            )
        grads = jax.tree.map(lambda g: g * clip_scale.astype(g.dtype), grads)
        lr = schedule(state["step"])
        opt_state = state["opt_state"]._replace(
            hyperparams=dict(state["opt_state"].hyperparams, learning_rate=lr)
        )
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, opt_state, state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
        if guard_updates:
            # fully skip the update: even zeroed grads decay Adam moments
            # and apply weight decay — carry the old state forward. This
            # restore is the actual correctness guarantee; the grad
            # zeroing above only keeps the optimizer arithmetic finite.
            params = jax.tree.map(
                lambda new, old: jnp.where(nonfinite, old, new),
                params,
                state["params"],
            )
            opt_state = jax.tree.map(
                lambda new, old: jnp.where(nonfinite, old, new),
                opt_state,
                state["opt_state"],
            )
            if new_quant is not None:
                # a poisoned batch must not roll NaN (or a poisoned
                # amax) into the delayed-scaling history — carry the
                # old window forward like the moments
                new_quant = jax.tree.map(
                    lambda new, old: jnp.where(nonfinite, old, new),
                    new_quant,
                    state["quant"],
                )
        metrics = {
            "loss": loss,
            "gnorm": gnorm,
            "lr": lr,
            "nonfinite": nonfinite.astype(jnp.float32),
            **stats,
        }
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        if new_quant is not None:
            new_state["quant"] = new_quant
        return new_state, metrics

    return train_step
