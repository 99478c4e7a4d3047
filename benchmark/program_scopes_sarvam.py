"""The device time of a sarvam engine's two programs by the program's
own scopes: the decode step (``jit__step``) and the prefill of each
padded prompt length (``jit__prefill_<tokens>``), and beside each traced
prefill what the program itself counted for it.

The join is ``benchmark/program_scopes_jamba.py``'s, whose helpers are
used as they are: the programs are built again from the cell's two
configs (``serve/families/sarvam.py::decode_program`` and
``prefill_program``), lowered with the shapes of the engine's arrays and
compiled afresh with the persistent cache off;
``obs/scopes.py::scope_table`` over ``SARVAM_SCOPES`` turns the compiled
text into ``{instruction name: scope}``, and each device event inside an
executed module's interval takes the scope of its instruction.

**Counted, not expected.** The prefill program returns how many (token,
choice) pairs landed on held experts, and the adapter puts that on the
``serve/prefill.done`` span with the positions computed
(``computed_tokens``, ``moe_pairs_held``, ``moe_pairs_routed``). A traced
prefill module is counted only with the ``done`` span that follows it
(the first that starts after the module ended and before the next
prefill module began), so time and counts are of the same prefills; a
module whose span fell outside the trace is left out of both.

Computed once per run and kept on ``run`` (``of(run)``); one line
``sarvam scopes: {...}`` is printed. Against a program without these
programs or scopes every reader finds nothing and returns ``None``.
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
    median,
    modules,
    time_by_scope,
)

ATTN_DECODE = ("mla_q", "mla_kv_down", "latent_write", "latent_gather",
               "mla_absorb", "attn", "attn_out")
ATTN_CORE_DECODE = ("latent_gather", "mla_absorb", "attn")
MOE_DECODE = ("moe_router", "moe_shared", "moe_experts", "moe_combine")
ATTN_PREFILL = ("mla_q", "mla_kv_down", "latent_write", "mla_expand",
                "attn", "attn_out")
MOE_GROUPED = ("moe_group", "moe_experts", "moe_combine")


@dataclass
class SarvamTrace:
    # one {scope: device ns} per executed decode module
    decode_steps: list = field(default_factory=list)
    # per traced prefill module that its ``done`` span followed:
    # (padded tokens, {scope: device ns}, the span's counts)
    prefills: list = field(default_factory=list)
    prefill_modules: int = 0  # all those the trace holds
    chunk: int = 0  # positions a trip of the prefill's loop takes
    seconds: float = 0.0


def scope_tables(run, prefill_lengths, decode=True):
    """-> (decode program's table or None, {padded length: table}, the
    program's ``prefill_chunk``), or ``None`` where the program offers no
    such programs."""
    try:
        from fms_fsdp_tpu.models.sarvam import pool_width, prefill_chunk
        from fms_fsdp_tpu.obs.scopes import SARVAM_SCOPES, scope_table
        from fms_fsdp_tpu.serve.families.sarvam import (
            decode_program, page_geometry, prefill_program)
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
    page, max_pages, num_pages = page_geometry(model_cfg, scfg)

    def S(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), dt)

    def _table(lowered):
        text = _compile_fresh(lowered)
        return fill_from_users(text, scope_table(text, SARVAM_SCOPES))

    params = weights.unflatten({
        p: S(s["shape"], dtype)
        for p, s in run.reference.param_spec(c).items()})
    B = scfg.max_batch
    table = None
    if decode:
        pools = {"latent": S(
            (model_cfg.nlayers, num_pages, page, pool_width(model_cfg)), dtype)}
        table = _table(
            decode_program(model_cfg, scfg, page, dtype).lower(
                params, pools, S((B, max_pages), jnp.int32),
                S((B,), jnp.int32), S((B,), jnp.int32), S((2,), jnp.uint32)))
    tables = {}
    for n in sorted(prefill_lengths):
        kv_len = -(-n // page) * page
        tables[n] = _table(
            prefill_program(model_cfg, scfg, n, kv_len, dtype).lower(
                params, S((1, n), jnp.int32), S((1,), jnp.int32)))
    return table, tables, prefill_chunk


def pair_with_done_spans(prefills, spans):
    """``prefills``: [(lines, module event, padded tokens), ...];
    ``spans``: the program's host spans. -> [(lines, module, padded, the
    counts of the ``prefill.done`` span that followed it), ...], modules
    that none followed left out."""
    done = sorted(
        (s for s in spans if s.name == "prefill.done"
         and "moe_pairs_held" in s.stats),
        key=lambda s: s.start_ns)
    mods = sorted(prefills, key=lambda t: t[1].start_ns)
    out = []
    for i, (lines, m, n) in enumerate(mods):
        until = mods[i + 1][1].start_ns if i + 1 < len(mods) else float("inf")
        mine = next(
            (s for s in done if m.end_ns <= s.start_ns < until), None)
        if mine is not None:
            out.append((lines, m, n, {
                k: int(mine.stats[k]) for k in
                ("computed_tokens", "moe_pairs_held", "moe_pairs_routed")}))
    return out


def of(run):
    """The run's ``SarvamTrace`` (made at the first call), or ``None``
    without a trace or without the programs."""
    if getattr(run, "sarvam_trace", None) is not None:
        return run.sarvam_trace
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
    decode_table, prefill_tables, prefill_chunk = tables
    st = SarvamTrace(prefill_modules=len(prefills))
    if decode_table is not None:
        st.decode_steps = [
            time_by_scope(lines, m, decode_table) for lines, m, _ in steps]
    st.prefills = [
        (n, time_by_scope(lines, m, prefill_tables[n]), counts)
        for lines, m, n, counts in counted]
    if counted:
        st.chunk = prefill_chunk(counted[0][2])
    st.seconds = time.perf_counter() - t
    run.sarvam_trace = st
    if not run.rehearse:  # a CPU's times are not reported
        print("sarvam scopes: " + json.dumps(summary(st)), flush=True)
    return st


def decode_unscoped_share(st):
    if not st.decode_steps:
        return None
    return median(
        100.0 * by.get("", 0.0) / sum(by.values())
        for by in st.decode_steps if by)


def live_means(run):
    """Mean live streams and mean cached tokens over the window's engine
    steps that ran no prefill (the driver's log of ``engine.step()``s),
    or ``None``."""
    log = run.facts.get("steps_log")
    steps = [(n, kv) for s, e, n, kv, pf in log or () if pf == 0 and n > 0]
    if not steps:
        return None
    return (sum(a for a, _ in steps) / len(steps),
            sum(b for _, b in steps) / len(steps))


def prefill_ns(st, scopes=None):
    """Device ns of the counted prefill modules under ``scopes`` (all
    scopes, and none, when ``None``)."""
    return sum(
        sum(v for s, v in by.items() if scopes is None or s in scopes)
        for _, by, _ in st.prefills)


def prefill_counts(st):
    """The counted prefills' sums: computed tokens, pairs held, pairs
    routed, and trips of the prefill's loop."""
    out = {k: sum(c[k] for _, _, c in st.prefills) for k in
           ("computed_tokens", "moe_pairs_held", "moe_pairs_routed")}
    out["chunks"] = sum(
        -(-c["computed_tokens"] // st.chunk) for _, _, c in st.prefills
    ) if st.chunk else 0
    return out


def summary(st):
    names = sorted({s for by in st.decode_steps for s in by})
    total = prefill_ns(st)
    by_scope = {}
    for _, by, _ in st.prefills:
        for s, v in by.items():
            by_scope[s] = by_scope.get(s, 0.0) + v
    return {
        "decode_steps": len(st.decode_steps),
        "decode_device_ms_by_scope": {
            (n or "(unscoped)"): decode_ms(st, (n,)) for n in names},
        "decode_unscoped_share": decode_unscoped_share(st),
        "prefill_modules_in_trace": st.prefill_modules,
        "prefills_counted": [n for n, _, _ in st.prefills],
        "prefill_counts": prefill_counts(st),
        "prefill_device_ms": total / 1e6,
        "prefill_device_share_by_scope": {
            (s or "(unscoped)"): v / total
            for s, v in sorted(by_scope.items(), key=lambda kv: -kv[1])
        } if total else {},
        "read_s": st.seconds,
    }
