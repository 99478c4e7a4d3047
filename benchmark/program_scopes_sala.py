"""The device time of a minicpm_sala engine's two programs by the
program's own scopes: the decode step (``jit__step``) and the prefill of
each program length (``jit__prefill_<tokens>``), and beside each traced
prefill what the program itself counted for it.

The join is ``benchmark/program_scopes_jamba.py``'s, whose helpers are
used as they are (and ``program_scopes_sarvam.py``'s ``prefill_ns`` and
``decode_unscoped_share``, which read a trace of this shape): the programs are built again from the cell's two configs
(``serve/families/minicpm_sala.py::decode_program`` and
``prefill_program``; a conditional's own event is taken out first,
``without_conditionals``), lowered with the shapes of the engine's arrays (the
lightning layers' states, the sparse layers' pools of pages and of index
rows) and compiled afresh with the persistent cache off;
``obs/scopes.py::scope_table`` over ``SALA_SCOPES`` turns the compiled
text into ``{instruction name: scope}``, and each device event inside an
executed module's interval takes the scope of its instruction.

**Counted, not expected.** A traced prefill module is counted only with
the ``serve/prefill.done`` span that follows it (the first that starts
after the module ended and before the next prefill module began), which
carries what the program counted: the positions computed
(``computed_tokens``), those of them that chose their blocks
(``chose_tokens``), the blocks they chose and the blocks they chose from
(``chosen_blocks``, ``context_blocks``).

Computed once per run and kept on ``run`` (``of(run)``); one line ``sala
scopes: {...}`` is printed, with the decode step's unscoped share.
Against a program without these programs or scopes every reader finds
nothing and returns ``None``.
"""

import json
import time
from dataclasses import dataclass, field

from benchmark import program_trace, trace_reduce
from benchmark.program_scopes_jamba import (
    DECODE_MODULE,
    _compile_fresh,
    _padded_tokens,
    decode_ms,
    fill_from_users,
    modules,
    time_by_scope,
)
from benchmark.program_scopes_sarvam import decode_unscoped_share, prefill_ns

SPARSE_ATTN_DECODE = ("sparse_select", "sparse_attn", "index_write", "kv_write")
SPARSE_ATTN_CORE_DECODE = ("sparse_select", "sparse_attn")
LIN_ATTN_DECODE = ("lin_step", "lin_gate")
SPARSE_ATTN_PREFILL = ("sparse_compress", "sparse_select", "sparse_attn", "attn")
SPARSE_ATTN_CORE_PREFILL = ("sparse_select", "sparse_attn", "attn")
LIN_ATTN_PREFILL = ("lin_scan", "lin_gate")
LIN_SCAN_PREFILL = ("lin_scan",)
COUNTS = ("computed_tokens", "chose_tokens", "chosen_blocks", "context_blocks")


def without_conditionals(lines):
    """A device's lines with the conditionals' own events taken out of
    the operations: a prefill chunk's sparse layer is a ``lax.cond`` (dense
    walk or chosen blocks) whose event, like a loop's, spans the events of
    the branch it ran, which are listed too; ``time_by_scope`` leaves
    ``trace_reduce.WRAPPERS`` out and a ``cond.<n>`` is not among them."""
    ops = lines.get(trace_reduce.OPS_LINE, [])
    return {**lines, trace_reduce.OPS_LINE: [
        e for e in ops if not e.name.startswith("cond")]}


@dataclass
class SalaTrace:
    # one {scope: device ns} per executed decode module
    decode_steps: list = field(default_factory=list)
    # per traced prefill module that its ``done`` span followed:
    # (program length, {scope: device ns}, {the counts of COUNTS})
    prefills: list = field(default_factory=list)
    prefill_modules: int = 0  # all those the trace holds
    chunk: int = 0  # positions a trip of the prefill's loop takes
    seconds: float = 0.0


def scope_tables(run, prefill_lengths, decode=True):
    """-> (decode program's table or None, {program length: table}, the
    prefill's chunk), or ``None`` where the program offers no such
    programs."""
    try:
        from fms_fsdp_tpu.models.minicpm_sala import prefill_chunk
        from fms_fsdp_tpu.obs.scopes import SALA_SCOPES, scope_table
        from fms_fsdp_tpu.serve.families.minicpm_sala import (
            decode_program, page_geometry, prefill_program, state_shape)
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
        return fill_from_users(text, scope_table(text, SALA_SCOPES))

    params = weights.unflatten({
        p: S(s["shape"], dtype)
        for p, s in run.reference.param_spec(c).items()})
    B = scfg.max_batch
    table = None
    if decode:
        state = {"S": S(state_shape(model_cfg, scfg), jnp.float32)}
        L = len(model_cfg.sparse_layers) * model_cfg.kvheads
        H = model_cfg.head_dim
        pools = {
            "k": S((L, num_pages, page, 1, H), dtype),
            "v": S((L, num_pages, page, 1, H), dtype),
            "kc": S((L, num_pages, model_cfg.sparse.per_block, H), dtype),
        }
        table = _table(
            decode_program(model_cfg, scfg, page, block_kv, dtype).lower(
                params, state, pools, S((B, max_pages), jnp.int32),
                S((B,), jnp.int32), S((B,), jnp.int32), S((2,), jnp.uint32)))
    tables = {
        n: _table(prefill_program(model_cfg, scfg, n, dtype).lower(
            params, S((1, n), jnp.int32), S((1,), jnp.int32)))
        for n in sorted(prefill_lengths)}
    chunk = prefill_chunk(max(1, scfg.prefill_bucket), model_cfg)
    return table, tables, chunk


def pair_with_done_spans(prefills, spans):
    """``prefills``: [(lines, module event, program length), ...];
    ``spans``: the program's host spans. -> [(lines, module, length,
    {the counts of COUNTS}), ...] for the modules that a ``prefill.done``
    span carrying those counts followed."""
    done = sorted(
        (s for s in spans if s.name == "prefill.done"
         and all(k in s.stats for k in COUNTS)),
        key=lambda s: s.start_ns)
    mods = sorted(prefills, key=lambda t: t[1].start_ns)
    out = []
    for i, (lines, m, n) in enumerate(mods):
        until = mods[i + 1][1].start_ns if i + 1 < len(mods) else float("inf")
        mine = next(
            (s for s in done if m.end_ns <= s.start_ns < until), None)
        if mine is not None:
            out.append(
                (lines, m, n, {k: int(mine.stats[k]) for k in COUNTS}))
    return out


def of(run):
    """The run's ``SalaTrace`` (made at the first call), or ``None``
    without a trace or without the programs."""
    if getattr(run, "sala_trace", None) is not None:
        return run.sala_trace
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
    decode_table, prefill_tables, chunk = tables
    st = SalaTrace(prefill_modules=len(prefills), chunk=chunk)
    plain = {}  # a device's lines, once

    def time_by(lines, m, table):
        if id(lines) not in plain:
            plain[id(lines)] = without_conditionals(lines)
        return time_by_scope(plain[id(lines)], m, table)

    if decode_table is not None:
        st.decode_steps = [
            time_by(lines, m, decode_table) for lines, m, _ in steps]
    st.prefills = [
        (n, time_by(lines, m, prefill_tables[n]), counts)
        for lines, m, n, counts in counted]
    st.seconds = time.perf_counter() - t
    run.sala_trace = st
    if not run.rehearse:  # a CPU's times are not reported
        print("sala scopes: " + json.dumps(summary(st)), flush=True)
    return st


def live_choice(run):
    """The window's means over the engine steps that ran no prefill, as
    the driver reckoned them from each live stream's context
    (``drivers/serve_sala.py``): ``{"streams", "attended", "scored"}``,
    the live streams, the positions they attend in a sparse layer and the
    compressed keys those of them that choose score; or ``None``."""
    return run.facts.get("sala_live")


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
        "prefills_counted": [(n, c) for n, _, c in st.prefills],
        "prefill_device_ms": total / 1e6,
        "prefill_device_share_by_scope": {
            (s or "(unscoped)"): v / total
            for s, v in sorted(by_scope.items(), key=lambda kv: -kv[1])
        } if total else {},
        "read_s": st.seconds,
    }
