"""The device time of a kexaone engine's two programs by the program's
own scopes: the decode step (``jit__step``) and the prefill of each
padded prompt length (``jit__prefill_<tokens>``), and beside each traced
prefill what the program itself counted for it.

The join is ``benchmark/program_scopes_jamba.py``'s, whose helpers are
used as they are (and ``program_scopes_sarvam.py``'s ``live_means``,
``prefill_ns`` and ``decode_unscoped_share``, which read a trace of
this shape): the
programs are built again from the cell's two configs
(``serve/families/kexaone.py::decode_program`` and ``prefill_program``),
lowered with the shapes of the engine's arrays (the window layers' rings,
the full layers' pools) and compiled afresh with the persistent cache
off; ``obs/scopes.py::scope_table`` over ``KEXAONE_SCOPES`` turns the
compiled text into ``{instruction name: scope}``, and each device event
inside an executed module's interval takes the scope of its instruction.

**Counted, not expected.** A traced prefill module is counted only with
the ``serve/prefill.done`` span that follows it (the first that starts
after the module ended and before the next prefill module began), which
carries the positions computed and the request's id; the engine's
``serve/prefill`` span of the same ``rid`` gives the prompt's own length,
which is what a window layer's band is reckoned from.

Computed once per run and kept on ``run`` (``of(run)``); one line
``kexaone scopes: {...}`` is printed, with the decode step's unscoped
share. Against a program without these programs or scopes every reader
finds nothing and returns ``None``.
"""

import json
import time
from dataclasses import dataclass, field

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
from benchmark.program_scopes_sarvam import (  # noqa: F401
    decode_unscoped_share,
    live_means,
    prefill_ns,
)

FULL_ATTN_DECODE = ("kv_write", "kv_read", "attn_full")
FULL_ATTN_CORE_DECODE = ("kv_read", "attn_full")
WINDOW_ATTN_DECODE = ("win_write", "attn_window")
MOE_DECODE = ("moe_router", "moe_shared", "moe_experts", "moe_combine")
WINDOW_ATTN_PREFILL = ("attn_window",)


@dataclass
class KExaoneTrace:
    # one {scope: device ns} per executed decode module
    decode_steps: list = field(default_factory=list)
    # per traced prefill module that its ``done`` span followed:
    # (padded tokens, {scope: device ns}, {computed_tokens, prompt_tokens})
    prefills: list = field(default_factory=list)
    prefill_modules: int = 0  # all those the trace holds
    seconds: float = 0.0


def scope_tables(run, prefill_lengths, decode=True):
    """-> (decode program's table or None, {padded length: table}), or
    ``None`` where the program offers no such programs."""
    try:
        from fms_fsdp_tpu.obs.scopes import KEXAONE_SCOPES, scope_table
        from fms_fsdp_tpu.serve.families.kexaone import (
            decode_program, page_geometry, prefill_program, ring_shape)
    except ImportError:
        return None
    import jax
    import jax.numpy as jnp

    from benchmark import weights
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
        return fill_from_users(text, scope_table(text, KEXAONE_SCOPES))

    params = weights.unflatten({
        p: S(s["shape"], dtype)
        for p, s in run.reference.param_spec(c).items()})
    B = scfg.max_batch
    table = None
    if decode:
        ring = {k: S(ring_shape(model_cfg, scfg), dtype) for k in ("k", "v")}
        pool = (len(model_cfg.full_layers), num_pages, page,
                model_cfg.kvheads, model_cfg.head_dim)
        pools = {k: S(pool, dtype) for k in ("k", "v")}
        table = _table(
            decode_program(model_cfg, scfg, page, block_kv, dtype).lower(
                params, ring, pools, S((B, max_pages), jnp.int32),
                S((B,), jnp.int32), S((B,), jnp.int32), S((2,), jnp.uint32)))
    tables = {}
    for n in sorted(prefill_lengths):
        kv_len = -(-n // page) * page
        tables[n] = _table(
            prefill_program(model_cfg, scfg, n, kv_len, dtype).lower(
                params, S((1, n), jnp.int32), S((1,), jnp.int32)))
    return table, tables


def pair_with_done_spans(prefills, spans):
    """``prefills``: [(lines, module event, padded tokens), ...];
    ``spans``: the program's host spans. -> [(lines, module, padded,
    {computed_tokens, prompt_tokens}), ...] for the modules that a
    ``prefill.done`` span followed; the prompt's length from the
    ``prefill`` span of the same ``rid`` (the positions computed where
    there is none)."""
    done = sorted(
        (s for s in spans if s.name == "prefill.done"
         and "computed_tokens" in s.stats),
        key=lambda s: s.start_ns)
    prompt = {
        int(s.stats["rid"]): int(s.stats["prompt_tokens"]) for s in spans
        if s.name == "prefill" and "prompt_tokens" in s.stats
        and "rid" in s.stats}
    mods = sorted(prefills, key=lambda t: t[1].start_ns)
    out = []
    for i, (lines, m, n) in enumerate(mods):
        until = mods[i + 1][1].start_ns if i + 1 < len(mods) else float("inf")
        mine = next(
            (s for s in done if m.end_ns <= s.start_ns < until), None)
        if mine is None:
            continue
        computed = int(mine.stats["computed_tokens"])
        out.append((lines, m, n, {
            "computed_tokens": computed,
            "prompt_tokens": prompt.get(
                int(mine.stats.get("rid", -1)), computed)}))
    return out


def of(run):
    """The run's ``KExaoneTrace`` (made at the first call), or ``None``
    without a trace or without the programs."""
    if getattr(run, "kexaone_trace", None) is not None:
        return run.kexaone_trace
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
    kt = KExaoneTrace(prefill_modules=len(prefills))
    if decode_table is not None:
        kt.decode_steps = [
            time_by_scope(lines, m, decode_table) for lines, m, _ in steps]
    kt.prefills = [
        (n, time_by_scope(lines, m, prefill_tables[n]), counts)
        for lines, m, n, counts in counted]
    kt.seconds = time.perf_counter() - t
    run.kexaone_trace = kt
    if not run.rehearse:  # a CPU's times are not reported
        print("kexaone scopes: " + json.dumps(summary(kt)), flush=True)
    return kt


def summary(kt):
    names = sorted({s for by in kt.decode_steps for s in by})
    total = prefill_ns(kt)
    by_scope = {}
    for _, by, _ in kt.prefills:
        for s, v in by.items():
            by_scope[s] = by_scope.get(s, 0.0) + v
    return {
        "decode_steps": len(kt.decode_steps),
        "decode_device_ms_by_scope": {
            (n or "(unscoped)"): decode_ms(kt, (n,)) for n in names},
        "decode_unscoped_share": decode_unscoped_share(kt),
        "prefill_modules_in_trace": kt.prefill_modules,
        "prefills_counted": [
            (n, c["prompt_tokens"], c["computed_tokens"])
            for n, _, c in kt.prefills],
        "prefill_device_ms": total / 1e6,
        "prefill_device_share_by_scope": {
            (s or "(unscoped)"): v / total
            for s, v in sorted(by_scope.items(), key=lambda kv: -kv[1])
        } if total else {},
        "read_s": kt.seconds,
    }
