"""The device time of a phi4flash engine's two programs by the program's
own scopes: the decode step (``jit__step``) and the prefill of each
program length (``jit__prefill_<tokens>``), and beside each traced prefill
what the program itself counted for it.

The join is ``benchmark/program_scopes_jamba.py``'s, whose helpers are
used as they are (and ``program_scopes_kexaone.py``'s
``pair_with_done_spans`` and ``summary``, ``program_scopes_sarvam.py``'s
``prefill_ns`` and ``decode_unscoped_share``, which read a trace of this
shape): the programs are built again from the cell's two configs
(``serve/families/phi4flash.py::decode_program`` and ``prefill_program``),
lowered with the shapes of the engine's arrays (rings and slabs, the full
layer's pool) and compiled afresh with the persistent cache off;
``obs/scopes.py::scope_table`` turns the compiled text into ``{instruction
name: scope}``, and each device event inside an executed module's interval
takes the scope of its instruction.

**Two tables of one text.** ``diff_combine`` (the lambda, the difference
of the two softmaxes' outputs, the norm by head) lies inside whichever of
``attn_window``, ``attn_full`` and ``attn_cross`` it follows. Under
``PHI4FLASH_SCOPES`` it is named alone (the summary line's
``decode_device_ms_by_scope``); under ``PHI4FLASH_SCOPES_COARSE`` its
time stays with its attention, which is what the per-layer metrics sum
(``of(run).coarse``, a trace of the decode steps alone).

Computed once per run and kept on ``run`` (``of(run)``); one line
``phi4flash scopes: {...}`` is printed, with the decode step's unscoped
share. Against a program without these programs or scopes (another
family's run, a parent that lacks the family) every reader finds nothing
and returns ``None``.
"""

import json
import time

from benchmark import program_trace
from benchmark.program_scopes_jamba import (
    DECODE_MODULE,
    _compile_fresh,
    _padded_tokens,
    decode_ms,
    fill_from_users,
    modules,
    time_by_scope,
)
from benchmark.program_scopes_kexaone import (
    KExaoneTrace,
    pair_with_done_spans,
    summary,
)
from benchmark.program_scopes_lfm2 import decode_step_ms  # noqa: F401
from benchmark.program_scopes_sarvam import (  # noqa: F401
    decode_unscoped_share,
    prefill_ns,
)

SSM = ("ssm_in_proj", "ssm_conv", "ssm_params", "ssm_scan", "ssm_gate_out")
WINDOW_ATTN = ("win_write", "attn_window")
SHARED_KV_ATTN = ("attn_full", "attn_cross")
GMU = ("gmu",)


def scope_tables(run, prefill_lengths, decode=True):
    """-> ((the decode program's table, its coarse table) or None,
    {program length: table}), or ``None`` where the program offers no
    such programs."""
    if run.config.get("family") != "phi4flash":
        return None
    try:
        from fms_fsdp_tpu.obs.scopes import (
            PHI4FLASH_SCOPES, PHI4FLASH_SCOPES_COARSE, scope_table)
        from fms_fsdp_tpu.serve.families.phi4flash import (
            decode_program, page_geometry, pool_row, prefill_program,
            state_shapes)
    except ImportError:
        return None
    import jax
    import jax.numpy as jnp

    from benchmark import weights
    from benchmark.drivers.serve_hybrid import as_program_tree
    from fms_fsdp_tpu.serve.engine import ServeConfig

    c = run.config
    model_cfg = run.family.model_config(c)
    scfg = ServeConfig(**run.cell_file["engine"])
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[scfg.compute_dtype]
    page, block_kv, max_pages, num_pages = page_geometry(model_cfg, scfg)

    def S(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), dt)

    def _tables(lowered, *names):
        text = _compile_fresh(lowered)
        return [fill_from_users(text, scope_table(text, n)) for n in names]

    params = as_program_tree(weights.unflatten({
        p: S(s["shape"], dtype)
        for p, s in run.reference.param_spec(c).items()}))
    B = scfg.max_batch
    tables = None
    if decode:
        pool = (1, num_pages) + pool_row(model_cfg, page)
        tables = _tables(
            decode_program(model_cfg, scfg, page, block_kv, dtype).lower(
                params,
                {k: S(*s) for k, s in state_shapes(model_cfg, B, dtype).items()},
                {k: S(pool, dtype) for k in ("k", "v")},
                S((B, max_pages), jnp.int32), S((B,), jnp.int32),
                S((B,), jnp.int32), S((2,), jnp.uint32)),
            PHI4FLASH_SCOPES, PHI4FLASH_SCOPES_COARSE)
    prefill = {}
    for n in sorted(prefill_lengths):
        kv_len = -(-n // page) * page
        prefill[n], = _tables(
            prefill_program(model_cfg, scfg, n, kv_len, dtype).lower(
                params, S((1, n), jnp.int32), S((1,), jnp.int32)),
            PHI4FLASH_SCOPES)
    return tables, prefill


def of(run):
    """The run's trace by scope (``program_scopes_kexaone.KExaoneTrace``:
    the decode steps' and the counted prefills' ``{scope: device ns}``,
    and ``coarse``, such a trace of the decode steps under the coarse
    names, for ``decode_ms``), made at the first call, or ``None`` without
    a trace or without the programs."""
    if getattr(run, "phi4flash_trace", None) is not None:
        return run.phi4flash_trace
    pt = program_trace.of(run)
    if run.trace_data is None or pt is None:
        return None
    t = time.perf_counter()
    steps = modules(
        run.trace_data, lambda n: True if n.startswith(DECODE_MODULE) else None)
    prefills = modules(run.trace_data, _padded_tokens)
    counted = pair_with_done_spans(prefills, pt.spans)
    tables = scope_tables(
        run, {n for _, _, n, _ in counted}, decode=bool(steps))
    if tables is None:
        return None
    decode_tables, prefill_tables = tables
    ft = KExaoneTrace(prefill_modules=len(prefills))
    ft.coarse = KExaoneTrace()
    if decode_tables is not None:
        fine, coarse = decode_tables
        ft.decode_steps = [
            time_by_scope(lines, m, fine) for lines, m, _ in steps]
        ft.coarse.decode_steps = [
            time_by_scope(lines, m, coarse) for lines, m, _ in steps]
    ft.prefills = [
        (n, time_by_scope(lines, m, prefill_tables[n]), counts)
        for lines, m, n, counts in counted]
    ft.seconds = time.perf_counter() - t
    run.phi4flash_trace = ft
    if not run.rehearse:  # a CPU's times are not reported
        print("phi4flash scopes: " + json.dumps(summary(ft)), flush=True)
    return ft


def live(run):
    """What the driver reckoned of the window's decode-only steps
    (``drivers/serve_phi4flash.py::live_contexts``): mean live streams,
    cached positions and ring entries a step; ``None`` for another
    driver's run."""
    return run.facts.get("phi4flash_live")
