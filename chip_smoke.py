"""Does the system still start on the chip? The quickest proof.

Drives the trainer and the serving engine once each through the entry
points a user calls — ``main_training_llama.main``, its Mamba and
Mixtral siblings, an in-process ``ServingEngine`` — at the published
widths of models the repo supports, with depth cut to what one 16 GB
TPU v5e holds and random weights made from ``--seed``. It checks what
comes out (finite falling losses, the flash kernel in the compiled
step, a resume that starts where the save stopped, kernel-vs-reference
logits in serving) and prints one JSON line per phase, then a last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and exits 0 — or ``"ok": false`` and a non-zero exit when no TPU
answers, a phase raises, or a check is false. Nothing here is a
benchmark: the times it prints say where a smoke run spends its wall
clock, not how fast the system is.

One process per chip. This parent never imports jax; it runs the
device check and then each phase as a child, one after another, so each
phase gets the whole chip and its own peak-memory reading. It sets no
platform, reads nothing outside the checkout, and writes only under
``chip_smoke_out/`` (git-ignored, cleared at start). The compile cache
goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to the checkout's
``.jax_cache/`` (fms_fsdp_tpu/utils/compile_cache.py): a second run in
the same checkout compiles far less, and each phase line carries the
entry counts that show it.

    python chip_smoke.py                   # one chip, the default phases
    python chip_smoke.py --phase serve     # one phase (repeatable)
    python chip_smoke.py --chips 4         # fsdp over 4 chips vs 1 device
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
        # every phase's control flow at a tiny size on the CPU, kernels
        # in interpret mode; ends "ok": false because no TPU answered
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearse --chips 4
"""

import argparse
import contextlib
import io
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out")

# in this order when the time limit cannot hold all of them
DEFAULT_PHASES = ("train", "serve", "train_mamba", "train_resume",
                  "train_mixtral")
FOUR_CHIP_PHASES = ("fsdp_1dev", "fsdp_4dev")
# run once by the builder, on request only
EXTRA_FOUR_CHIP_PHASES = ("mixtral_ep4",)
PHASE_TIMEOUT_S = 900

# Written tolerances (PERF.md "Findings", PR 21, has the measurements
# behind them), each as max |diff| <= TOL * max(1, max |reference|).
# serve: first-decode-step logits, kernel engine vs reference engine,
# bf16 compute through all 24 layers (measured 0.028)
SERVE_LOGITS_TOL = 0.1
# serve: the paged-attention kernel vs the reference gather on the same
# random bf16 pools at the engine's geometry, one call, no layers around
# it
SERVE_KERNEL_TOL = 0.02
# four chips: per-step loss of the fsdp run vs the one-device run, same
# seed and global batch (the reductions associate differently)
FSDP_LOSS_RTOL = 2e-2
# four chips: each device's share of the param + optimizer bytes
FSDP_SHARE_BOUNDS = (0.20, 0.30)

# ---------------------------------------------------------------------------
# sizes: published widths, depth cut to one chip (the memory_analysis()
# behind each cut is in CHANGES.md, PR 21). Rehearsal sizes walk the same
# code at a CPU-feasible size.
# ---------------------------------------------------------------------------

_TINY_LLAMA = {
    "LlamaConfig.nlayers": 1, "LlamaConfig.emb_dim": 256,
    "LlamaConfig.nheads": 2, "LlamaConfig.kvheads": 2,
    "LlamaConfig.src_vocab_size": 512, "LlamaConfig.multiple_of": 16,
    "LlamaConfig.max_expected_seq_len": 256,
}
_TINY_TRAIN = dict(seq_length=256, vocab_size=512,
                   # on a TPU "auto" means the flash kernel; off it, name
                   # the kernel so the rehearsal walks it in interpret mode
                   attention_kernel="pallas")


def train_spec(phase, rehearse):
    """(entry module, main() kwargs) of a training phase."""
    if phase in ("train", "fsdp_1dev", "fsdp_4dev"):
        # llama2_7b: emb 4096, 32 heads, ffn 11008, vocab 32000; 3 of 32
        # layers
        kw = dict(model_variant="llama2_7b", vocab_size=32000,
                  fsdp_activation_checkpointing=True,
                  selective_checkpointing=0.25,
                  **{"LlamaConfig.nlayers": 3})
        if phase != "train":
            # global batch 4 either way; the one-device side holds all
            # four rows, so both sides recompute every layer
            kw.update(selective_checkpointing=1,
                      batch_size=4 if phase == "fsdp_1dev" else 1)
        if rehearse:
            kw.update(_TINY_LLAMA, **_TINY_TRAIN)
        return "main_training_llama", kw
    if phase == "train_resume":
        # llama3_194m_4k whole: emb 1024, 8 heads, vocab 128256, all 10
        # layers
        kw = dict(model_variant="llama3_194m_4k", vocab_size=128256)
        if rehearse:
            kw.update(_TINY_LLAMA, **_TINY_TRAIN)
        return "main_training_llama", kw
    if phase == "train_mamba":
        # mamba_9.8b: d_model 4096, d_inner 8192, 128 heads x 64, d_state
        # 128, MLP 14336; 2 of 32 layers, the second an attention layer
        # (32 query / 8 kv heads x 128) so the hybrid's flash path runs;
        # vocab cut to 32000 so that two layers fit the chip
        kw = dict(vocab_size=32000, fsdp_activation_checkpointing=True,
                  selective_checkpointing=0.5,
                  **{"MambaConfig.n_layer": 2,
                     "MambaConfig.attn_layer_idx": (1,),
                     "MambaConfig.vocab_size": 32000})
        if rehearse:
            from fms_fsdp_tpu.models.configs import MambaAttnConfig

            kw.update(_TINY_TRAIN, **{
                "MambaConfig.d_model": 128, "MambaConfig.d_intermediate": 256,
                "MambaConfig.vocab_size": 512,
                "MambaConfig.attn_cfg": MambaAttnConfig(
                    num_heads=2, num_heads_kv=1, rotary_emb_dim=64),
            })
        return "main_training_mamba", kw
    if phase in ("train_mixtral", "mixtral_ep4"):
        # mixtral_8x7b: emb 4096, 32 query / 8 kv heads, experts 14336
        # wide, top-2; 1 of 32 layers. One chip holds 4 of the 8 experts
        # (fp32 params + Adam moments of 8 do not fit 16 GB); four chips
        # hold the published 8, two a chip
        kw = dict(vocab_size=32000, fsdp_activation_checkpointing=True,
                  selective_checkpointing=1,
                  **{"MixtralConfig.nlayers": 1,
                     "MixtralConfig.capacity_factor": 1.25})
        if phase == "mixtral_ep4":
            kw.update(expert_parallel_size=4, batch_size=1)
        else:
            kw["MixtralConfig.num_experts"] = 4
        if rehearse:
            kw.update(_TINY_TRAIN, **{
                "MixtralConfig.emb_dim": 256, "MixtralConfig.nheads": 2,
                "MixtralConfig.kvheads": 1, "MixtralConfig.hidden_dim": 256,
                "MixtralConfig.src_vocab_size": 512,
                "MixtralConfig.max_expected_seq_len": 256,
            })
        return "main_training_mixtral", kw
    raise ValueError(f"not a training phase: {phase}")


# ---------------------------------------------------------------------------
# child side: everything below here may import jax
# ---------------------------------------------------------------------------


class _Tee(io.TextIOBase):
    """stdout that also keeps what was written (the entries report by
    printing: ``start_step = N`` is read back from here)."""

    def __init__(self, stream):
        self.stream, self.kept = stream, []

    def write(self, s):
        self.kept.append(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


class _CompileMeter:
    """Counts and times what jax compiled (or fetched from the
    persistent cache) in this process, from jax's own monitoring
    events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def _peak_hbm_bytes():
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _spy_on_train(module, found):
    """Wrap the entry's ``train`` so the smoke can look at the very step
    function and state the entry built, then hand over unchanged: the
    optimized HLO of the compiled step (is the flash kernel in it? which
    collectives?) and where the state's bytes live."""
    import collections
    import itertools
    import re

    real_train = module.train

    def train(cfg, state, step_fn, rank, train_loader, *args, **kwargs):
        first = next(train_loader)
        hlo = step_fn.lower(state, first).compile().as_text()
        with open(os.path.join(OUT, f"{found['phase']}.hlo.txt"), "w") as f:
            f.write(hlo)  # too long for a JSON line, kept for diagnosis
        found["custom_calls"] = hlo.count("tpu_custom_call")
        # instructions by opcode. The v5e compiler leaves no instruction
        # called reduce-scatter in an fsdp step: attached to the chips it
        # turns the gradient reduction into collective-permute rings fused
        # with the matmuls, and compiling for a described topology it
        # fuses them into computations named %all-reduce-scatter.N
        found["collectives"] = dict(collections.Counter(re.findall(
            r" (all-gather|reduce-scatter|collective-permute|all-reduce"
            r"|all-to-all)(?:-start)?\(", hlo,
        )))
        found["reduce_scatter_fusions"] = len(
            re.findall(r"^%all-reduce-scatter", hlo, re.M)
        )
        per_device, total = {}, 0
        import jax

        for leaf in jax.tree.leaves(
            {"params": state["params"], "opt_state": state["opt_state"]}
        ):
            total += leaf.nbytes
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] = (
                    per_device.get(shard.device.id, 0) + shard.data.nbytes
                )
        found["state_bytes"] = total
        found["state_share_per_device"] = [
            round(per_device[d] / total, 4) for d in sorted(per_device)
        ]
        return real_train(
            cfg, state, step_fn, rank,
            itertools.chain([first], train_loader), *args, **kwargs
        )

    module.train = train


def _run_entry(phase, args, workdir, num_steps, spy=True, one_device=False):
    """One invocation of a training entry; returns what it reported."""
    import importlib
    import re

    module_name, kw = train_spec(phase, args.rehearse)
    kw = dict(
        dict(use_dummy_dataset=True, sharding_strategy="fsdp",
             seq_length=4096, batch_size=2, attention_kernel="auto",
             mixed_precision=True, report_interval=1,
             checkpoint_interval=10**9),
        **kw,
    )
    kw.update(
        num_steps=num_steps, seed=args.seed, obs_strict_schema=True,
        ckpt_save_path=os.path.join(workdir, "ckpt"),
        ckpt_load_path=os.path.join(workdir, "ckpt"),
        obs_dir=os.path.join(workdir, "obs"),
    )
    module = importlib.import_module(module_name)
    # the Mamba and Mixtral entries call the llama entry's main
    shared = importlib.import_module("main_training_llama")
    found = {"phase": phase}
    if spy:
        _spy_on_train(shared, found)
    if one_device:
        import jax

        from fms_fsdp_tpu.parallel.mesh import build_mesh

        # the comparison side of --chips 4: same entry, same machine, a
        # mesh over one of its devices
        shared.build_mesh = lambda mc: build_mesh(
            mc, devices=jax.devices()[:1]
        )
    obs_file = os.path.join(workdir, "obs", "metrics.jsonl")
    seen = 0
    if os.path.exists(obs_file):
        with open(obs_file) as f:
            seen = sum(1 for _ in f)
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        module.main(**kw)
    with open(obs_file) as f:
        records = [json.loads(line) for line in f][seen:]
    resumed = re.search(r"start_step = (\d+)", "".join(tee.kept))
    found.update(
        entry=f"{module_name}.main",
        losses=[r["loss"] for r in records],
        steps=[r["step"] for r in records],
        obs_device_platform=records[-1]["device_platform"] if records else None,
        start_step=int(resumed.group(1)) if resumed else None,
        sizes={k: v for k, v in kw.items()
               if not k.endswith("_path") and k != "obs_dir"},
    )
    return found


def _loss_checks(losses):
    import math

    return {
        "losses_finite": bool(losses)
        and all(x is not None and math.isfinite(x) for x in losses),
        "loss_fell": len(losses) >= 2
        and all(x is not None for x in losses)
        and losses[-1] < losses[0],
    }


def _train_phase(phase, args, workdir, meter=None):
    # (the schedule's first step has lr 0: loss can only fall from the
    # third report on)
    steps = 3 if args.rehearse and phase != "train" else 8
    one_device = phase == "fsdp_1dev"
    r = _run_entry(phase, args, workdir, steps, one_device=one_device)
    checks = _loss_checks(r["losses"])
    checks["reported_every_step"] = r["steps"] == list(range(1, steps + 1))
    # "auto" must have meant the flash kernel, not the XLA fallback
    checks["flash_kernel_in_compiled_step"] = r["custom_calls"] > 0
    checks["obs_record_names_tpu"] = r["obs_device_platform"] == "tpu"
    if phase == "fsdp_4dev":
        lo, hi = FSDP_SHARE_BOUNDS
        shares = r["state_share_per_device"]
        checks["state_a_quarter_per_device"] = len(shares) == 4 and all(
            lo <= s <= hi for s in shares
        )
        ops = r["collectives"]
        # parameters gathered, gradients reduced and scattered — under
        # whichever of its three names the compiler gave the latter
        checks["collectives_in_compiled_step"] = (
            ops.get("all-gather", 0) > 0
            and ops.get("reduce-scatter", 0) + ops.get("collective-permute", 0)
            + r["reduce_scatter_fusions"] > 0
        )
    if phase == "mixtral_ep4":
        checks["all_to_all_in_compiled_step"] = (
            r["collectives"].get("all-to-all", 0) > 0
        )
    return r, checks


def _resume_phase(phase, args, workdir, meter=None):
    first_steps, total = (3, 6) if args.rehearse else (4, 8)
    a = _run_entry(phase, args, workdir, first_steps, spy=False)
    b = _run_entry(phase, args, workdir, total, spy=False)
    start_step = b["start_step"]
    # four steps at lr 3e-4 do not move a 391M-parameter model's loss
    # reliably: the resume is judged on where it starts and that it
    # carries on finite, not on the loss falling
    checks = {
        "first_run_losses_finite": _loss_checks(a["losses"])["losses_finite"],
        "resumed_at_saved_step": start_step == first_steps,
        "resume_carried_on": b["steps"] == list(
            range(first_steps + 1, total + 1)
        ),
        "resume_losses_finite": _loss_checks(b["losses"])["losses_finite"],
    }
    out = {
        "entry": a["entry"], "sizes": a["sizes"],
        "start_step": start_step,
        "losses": a["losses"], "losses_after_resume": b["losses"],
    }
    return out, checks


def _paged_kernel_vs_reference(engine, cfg, seed):
    """The kernel against the reference gather, alone: random pools at
    the engine's page geometry, a ragged batch whose rows end at the
    start, the middle and the end of a page and of the cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fms_fsdp_tpu.ops.paged_attention import (
        paged_attention_kernel,
        paged_attention_reference,
    )

    ps, maxp = engine.page_size, engine.max_pages
    b = engine.serve_cfg.max_batch
    shape = (b * maxp + 2, ps, cfg.n_kv_heads, cfg.head_dim)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, cfg.nheads, cfg.head_dim), jnp.bfloat16)
    k_pages = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pages = jax.random.normal(kv, shape, jnp.bfloat16)
    table = jnp.asarray(
        np.arange(2, 2 + b * maxp, dtype=np.int32).reshape(b, maxp)
    )
    last = ps * maxp - 1
    lens = jnp.asarray(
        ([0, ps - 1, ps, ps + 1, last // 2, last - ps, last - 1, last] * b)[:b],
        jnp.int32,
    )
    ker = jax.jit(
        lambda *a: paged_attention_kernel(*a, block_kv=engine.block_kv)
    )(q, k_pages, v_pages, table, lens)
    ref = jax.jit(paged_attention_reference)(q, k_pages, v_pages, table, lens)
    ker, ref = np.asarray(ker, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(ker - ref))), max(1.0, float(np.max(np.abs(ref))))


def _serve_phase(phase, args, workdir, meter):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fms_fsdp_tpu.models.llama import init_llama_params
    from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
    from fms_fsdp_tpu.utils.config_utils import get_model_config

    # llama3_1.8b_4k at full depth: emb 2048, 16 query / 8 kv heads x
    # 128, 24 layers, vocab 128256
    cfg = get_model_config("llama3_1.8b_4k")
    scfg = ServeConfig(max_batch=8, max_seq_len=2048, prefill_bucket=256)
    prompt_lens = [64, 192, 320, 448, 576, 704, 832, 1024]
    new_tokens = 32
    if args.rehearse:
        cfg = dataclasses.replace(
            cfg, emb_dim=256, nheads=2, kvheads=1, nlayers=2,
            src_vocab_size=512, multiple_of=16,
        )
        # off a TPU "auto" means the reference: name the kernel so the
        # rehearsal walks it in interpret mode
        scfg = dataclasses.replace(
            scfg, max_batch=4, max_seq_len=256, prefill_bucket=32,
            attn_impl="kernel",
        )
        prompt_lens, new_tokens = [8, 24, 40, 64], 4
    params = jax.jit(
        lambda key: init_llama_params(key, cfg, dtype=jnp.bfloat16)
    )(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    def prompts():
        return [
            rng.integers(1, cfg.src_vocab_size, size=n).tolist()
            for n in prompt_lens
        ]

    def wave(engine, batch):
        """Submit a wave, run it dry; returns (requests, first decode
        step's logits row of the first stream)."""
        reqs = [engine.submit(p, new_tokens) for p in batch]
        # the first step admits and prefills stream 0 and dispatches its
        # first decode step; the second one commits it
        engine.last_logits = None
        while engine.last_logits is None:
            engine.step()
        first = np.asarray(engine.last_logits[0], np.float32)
        engine.run()
        return reqs, first

    engine = ServingEngine(params, cfg, scfg, seed=args.seed)
    warm, measured = prompts(), prompts()
    t0 = time.monotonic()
    wave(engine, warm)  # one request of each prefill shape: all compiles
    warm_s = time.monotonic() - t0
    compiles_before = meter.count
    t0 = time.monotonic()
    reqs, first_kernel = wave(engine, measured)
    wave_s = time.monotonic() - t0
    compiles_in_wave = meter.count - compiles_before

    ref_engine = ServingEngine(
        params, cfg, dataclasses.replace(scfg, attn_impl="reference"),
        seed=args.seed,
    )
    ref_reqs, first_ref = wave(ref_engine, measured)

    diff = float(np.max(np.abs(first_kernel - first_ref)))
    scale = max(1.0, float(np.max(np.abs(first_ref))))
    op_diff, op_scale = _paged_kernel_vs_reference(engine, cfg, args.seed)
    same = sum(
        int(x == y)
        for a, b in zip(reqs, ref_reqs)
        for x, y in zip(a.generated, b.generated)
    )
    total = sum(len(r.generated) for r in reqs)
    checks = {
        "all_requests_completed": all(
            r.state == "finished" and len(r.generated) == new_tokens
            for r in reqs
        ),
        "auto_resolved_to_kernel": engine.attn_impl == "kernel",
        "first_step_logits_finite": bool(np.isfinite(first_kernel).all()),
        "first_step_logits_within_tolerance": diff <= SERVE_LOGITS_TOL * scale,
        "kernel_matches_reference_op": op_diff <= SERVE_KERNEL_TOL * op_scale,
        "no_compile_after_warm_up": compiles_in_wave == 0,
    }
    out = {
        "entry": "fms_fsdp_tpu.serve.engine.ServingEngine",
        "sizes": {
            "model_variant": "llama3_1.8b_4k", "nlayers": cfg.nlayers,
            "emb_dim": cfg.emb_dim, "nheads": cfg.nheads,
            "kvheads": cfg.n_kv_heads, "vocab": cfg.src_vocab_size,
            "max_batch": scfg.max_batch, "max_seq_len": scfg.max_seq_len,
            "prefill_bucket": scfg.prefill_bucket,
            "prompt_lens": prompt_lens, "new_tokens": new_tokens,
        },
        "completed": sum(r.state == "finished" for r in reqs),
        "requests": len(reqs),
        "attn_impl": engine.attn_impl,
        "page_size": engine.page_size, "block_kv": engine.block_kv,
        "tuner_resolved": engine.tune_how,
        "first_step_logits_max_abs_diff": diff,
        "first_step_logits_max_abs_ref": scale,
        "tolerance": f"diff <= {SERVE_LOGITS_TOL} * max(1, max|ref|)",
        "kernel_vs_reference_op_max_abs_diff": op_diff,
        "kernel_vs_reference_op_max_abs_ref": op_scale,
        "kernel_tolerance": f"diff <= {SERVE_KERNEL_TOL} * max(1, max|ref|)",
        # random weights make whole-stream identity a coin toss: printed,
        # not decided on
        "token_agreement_share": round(same / max(1, total), 4),
        "compiles_in_measured_wave": compiles_in_wave,
        "warm_up_wave_s": round(warm_s, 2),
        "measured_wave_s": round(wave_s, 2),
    }
    return out, checks


# every other phase is one invocation of a training entry
_PHASE_FN = {"train_resume": _resume_phase, "serve": _serve_phase}


def child(args):
    """Run one phase in this process; write its result beside the log."""
    from fms_fsdp_tpu.utils.compile_cache import (
        cache_entry_count,
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    result_path = os.path.join(OUT, args.child + ".json")
    from fms_fsdp_tpu.utils.flops import device_info

    if args.child == "device":
        result = device_info()
    else:
        import jax

        meter = _CompileMeter()
        workdir = os.path.join(OUT, args.child)
        entries_before = cache_entry_count(cache_dir)
        t0 = time.monotonic()
        out, checks = _PHASE_FN.get(args.child, _train_phase)(
            args.child, args, workdir, meter
        )
        wall = time.monotonic() - t0
        result = {
            "phase": args.child,
            "rehearsal": args.rehearse,
            "ok": all(checks.values()),
            "checks": checks,
            **out,
            "wall_s": round(wall, 2),
            "compile_s": round(meter.seconds, 2),
            "run_s": round(wall - meter.seconds, 2),
            "compiles": meter.count,
            "peak_hbm_bytes": _peak_hbm_bytes(),
            "cache_dir": cache_dir,
            "cache_entries_before": entries_before,
            "cache_entries_after": cache_entry_count(cache_dir),
            "jax_version": jax.__version__,
            "device": device_info(),
        }
        # checkpoints of a 7B-width state are ~10 GB: do not keep them
        shutil.rmtree(os.path.join(workdir, "ckpt"), ignore_errors=True)
    with open(result_path, "w") as f:
        json.dump(result, f, default=str)


# ---------------------------------------------------------------------------
# parent side: never imports jax
# ---------------------------------------------------------------------------


def _run_child(name, args, timeout_s):
    """Run ``--child name`` to the end; returns its result dict, or one
    saying how it failed. Its output goes to chip_smoke_out/<name>.log."""
    argv = [sys.executable, os.path.abspath(__file__), "--child", name,
            "--seed", str(args.seed)]
    if args.rehearse:
        argv.append("--rehearse")
    log_path = os.path.join(OUT, name + ".log")
    result_path = os.path.join(OUT, name + ".json")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            argv, stdout=log, stderr=subprocess.STDOUT, cwd=HERE,
            start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            # the child's whole process group: nothing it started
            # (checkpoint writers, feeder threads' helpers) outlives it
            with contextlib.suppress(ProcessLookupError):
                os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    if rc == 0 and os.path.exists(result_path):
        with open(result_path) as f:
            return json.load(f)
    with open(log_path, errors="replace") as f:
        tail = f.read()[-1500:]
    why = f"timeout after {timeout_s}s" if rc is None else f"exit code {rc}"
    return {"phase": name, "ok": False, "error": why,
            "wall_s": round(time.monotonic() - t0, 2), "log_tail": tail}


def _compare_fsdp(one, four):
    """The four-chip run against the one-device run: per-step losses."""
    a, b = one.get("losses") or [], four.get("losses") or []
    rel = [
        abs(x - y) / max(abs(x), 1e-9) for x, y in zip(a, b)
        if x is not None and y is not None
    ]
    agree = bool(rel) and len(a) == len(b) == len(rel) and all(
        r <= FSDP_LOSS_RTOL for r in rel
    )
    return {
        "phase": "fsdp_4dev_vs_1dev", "ok": agree,
        "checks": {"losses_agree": agree},
        "tolerance": f"|a - b| <= {FSDP_LOSS_RTOL} * |a| at every step",
        "max_rel_diff": max(rel) if rel else None,
        "losses_1dev": a, "losses_4dev": b,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", action="append", default=[],
                    choices=DEFAULT_PHASES + FOUR_CHIP_PHASES
                    + EXTRA_FOUR_CHIP_PHASES)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child(args)
        return

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    tag = {"rehearsal": True} if args.rehearse else {}

    # the device check, before any phase: no TPU, no run
    device = _run_child("device", args, timeout_s=300)
    on_tpu = device.get("platform") == "tpu"
    refused = None
    if "platform" not in device:
        refused = device.get("error", "no device answered")
    elif not (on_tpu or args.rehearse):
        refused = f"jax found {device['platform']!r}, not a tpu"
    elif device["count"] != args.chips:
        refused = (f"--chips {args.chips} but jax reports "
                   f"{device['count']} device(s)")
    if refused:
        print(json.dumps(
            {"phase": "device", "ok": False, **tag, "error": refused, **device}
        ))
        print(json.dumps({"ok": False, "device": device}))
        sys.exit(1)

    phases = args.phase or (
        FOUR_CHIP_PHASES if args.chips == 4 else DEFAULT_PHASES
    )
    results = {}
    for name in phases:
        results[name] = _run_child(name, args, PHASE_TIMEOUT_S)
        print(json.dumps(results[name]), flush=True)
    if "fsdp_1dev" in results and "fsdp_4dev" in results:
        results["compare"] = _compare_fsdp(
            results["fsdp_1dev"], results["fsdp_4dev"]
        )
        print(json.dumps({**tag, **results["compare"]}), flush=True)

    ok = on_tpu and all(r.get("ok") for r in results.values())
    print(json.dumps({"ok": ok, "device": device}))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
