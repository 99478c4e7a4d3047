"""The device time of an lfm2 engine's two programs by the program's own
scopes: the decode step (``jit__step``) and the prefill of each program
length (``jit__prefill_<tokens>``), and beside each traced prefill what
the program itself counted for it.

The join is ``benchmark/program_scopes_jamba.py``'s, whose helpers are
used as they are (and ``program_scopes_kexaone.py``'s
``pair_with_done_spans`` and ``summary``, ``program_scopes_sarvam.py``'s
``live_means``, ``prefill_ns`` and ``decode_unscoped_share``, which read a
trace of this shape): the programs are built again from the cell's two
configs (``serve/families/lfm2.py::decode_program`` and
``prefill_program``), lowered with the shapes of the engine's arrays (the
convolution layers' windows, the attention layers' pools) and compiled
afresh with the persistent cache off; ``obs/scopes.py::scope_table`` over
``LFM2_SCOPES`` turns the compiled text into ``{instruction name:
scope}``, and each device event inside an executed module's interval
takes the scope of its instruction.

Computed once per run and kept on ``run`` (``of(run)``); one line ``lfm2
scopes: {...}`` is printed, with the decode step's unscoped share.
Against a program without these programs or scopes (another family's
run, a parent that lacks the family) every reader finds nothing and
returns ``None``.
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
from benchmark.program_scopes_sarvam import (  # noqa: F401
    decode_unscoped_share,
    live_means,
    prefill_ns,
)

MOE = ("moe_router", "moe_group", "moe_experts", "moe_combine")
CONV = ("conv_in", "short_conv", "conv_out")
ATTN_DECODE = ("qkv", "qk_norm", "rope", "kv_write", "kv_read", "attn_full",
               "attn_out")
ATTN_CORE = ("kv_read", "attn_full")


def scope_tables(run, prefill_lengths, decode=True):
    """-> (decode program's table or None, {program length: table}), or
    ``None`` where the program offers no such programs."""
    if run.config.get("family") != "lfm2":
        return None
    try:
        from fms_fsdp_tpu.obs.scopes import LFM2_SCOPES, scope_table
        from fms_fsdp_tpu.serve.families.kexaone import page_geometry
        from fms_fsdp_tpu.serve.families.lfm2 import (
            decode_program, prefill_program, window_shape)
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

    def _table(lowered):
        text = _compile_fresh(lowered)
        return fill_from_users(text, scope_table(text, LFM2_SCOPES))

    params = as_program_tree(weights.unflatten({
        p: S(s["shape"], dtype)
        for p, s in run.reference.param_spec(c).items()}))
    B = scfg.max_batch
    table = None
    if decode:
        from fms_fsdp_tpu.ops.paged_attention import (
            packed_row_width, tile_rows)

        nkv, hd = model_cfg.kvheads, model_cfg.head_dim
        pool = (len(model_cfg.attn_layers), num_pages,
                page * tile_rows(nkv, hd), packed_row_width(nkv, hd))
        table = _table(
            decode_program(model_cfg, scfg, page, block_kv, dtype).lower(
                params, {"z": S(window_shape(model_cfg, scfg), dtype)},
                {k: S(pool, dtype) for k in ("k", "v")},
                S((B, max_pages), jnp.int32), S((B,), jnp.int32),
                S((B,), jnp.int32), S((2,), jnp.uint32)))
    tables = {}
    for n in sorted(prefill_lengths):
        kv_len = -(-n // page) * page
        tables[n] = _table(
            prefill_program(model_cfg, scfg, n, kv_len, dtype).lower(
                params, S((1, n), jnp.int32), S((1,), jnp.int32)))
    return table, tables


def of(run):
    """The run's trace by scope (``program_scopes_kexaone.KExaoneTrace``:
    the decode steps' and the counted prefills' ``{scope: device ns}``),
    made at the first call, or ``None`` without a trace or without the
    programs."""
    if getattr(run, "lfm2_trace", None) is not None:
        return run.lfm2_trace
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
    decode_table, prefill_tables = tables
    lt = KExaoneTrace(prefill_modules=len(prefills))
    if decode_table is not None:
        lt.decode_steps = [
            time_by_scope(lines, m, decode_table) for lines, m, _ in steps]
    lt.prefills = [
        (n, time_by_scope(lines, m, prefill_tables[n]), counts)
        for lines, m, n, counts in counted]
    lt.seconds = time.perf_counter() - t
    run.lfm2_trace = lt
    if not run.rehearse:  # a CPU's times are not reported
        print("lfm2 scopes: " + json.dumps(summary(lt)), flush=True)
    return lt


def decode_step_ms(run):
    """Median device ms of the executed decode modules, or ``None``."""
    from benchmark import trace_reduce

    if run.trace_data is None:
        return None
    durs = sorted(trace_reduce.module_durations_ns(
        run.trace_data, (DECODE_MODULE,)))
    return durs[len(durs) // 2] / 1e6 if durs else None
