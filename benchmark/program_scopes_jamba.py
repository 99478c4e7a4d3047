"""The device time of a hybrid engine's two programs by the program's
own scopes: the decode step (``jit__step``) and the prefill of each
padded prompt length (``jit__prefill_<tokens>``), for the configurations
of the ``jamba`` family.

``program_trace.decode_scope_table`` builds Mixtral's decode program and
no other, so the join for this family lives here, by the same means: the
same programs are built again from the cell's two configs
(``serve/families/mamba.py::decode_program`` and ``prefill_program``),
lowered with the shapes of the engine's arrays and compiled afresh with
the persistent cache off (a cached executable may be another tree's,
with that tree's ``op_name``s); ``obs/scopes.py::scope_table`` turns the
compiled text into ``{instruction name: scope}`` (``fill_from_users``
adds the compiler's own weight prefetches, which carry no name), and
each device event inside an executed module's interval takes the scope
of its instruction. Only the prefill lengths that the trace holds are compiled
(about 12 s each, after the window, in a traced run only).

Computed once per run and kept on ``run`` (``of(run)``); one line
``hybrid scopes: {...}`` is printed. Against a program without these
programs or scopes every reader finds nothing and returns ``None``.
"""

import bisect
import json
import re
import time
from dataclasses import dataclass, field

from benchmark import trace_reduce

DECODE_MODULE = "jit__step"
PREFILL_MODULE = re.compile(r"^jit__prefill_(\d+)")
UNSCOPED = ""


@dataclass
class HybridTrace:
    # one {scope: device ns} per executed decode module
    decode_steps: list = field(default_factory=list)
    # one (padded tokens, {scope: device ns}) per executed prefill module
    prefills: list = field(default_factory=list)
    seconds: float = 0.0


def modules(trace, match):
    """[(module event, what ``match`` gave for its name), ...] over all
    devices; ``match(name)`` returns ``None`` for a module to leave out."""
    out = []
    for lines in trace.devices.values():
        for m in lines.get(trace_reduce.MODULES_LINE, []):
            got = match(m.name)
            if got is not None:
                out.append((lines, m, got))
    return out


def time_by_scope(lines, module, scopes):
    """``{scope: ns}`` of the device operations inside one executed
    module's interval; a loop's own event spans its body's events, which
    are listed too, so wrappers are left out."""
    ops = lines.get(trace_reduce.OPS_LINE, [])
    i = bisect.bisect_left([e.start_ns for e in ops], module.start_ns)
    by = {}
    while i < len(ops) and ops[i].start_ns < module.end_ns:
        e = ops[i]
        i += 1
        if e.name.startswith(trace_reduce.WRAPPERS):
            continue
        scope = scopes.get(e.name, UNSCOPED)
        by[scope] = by.get(scope, 0.0) + min(e.end_ns, module.end_ns) - e.start_ns
    return by


_LHS = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_REF = re.compile(r"%([\w.\-]+)")


def fill_from_users(text, table):
    """The compiler streams a weight into fast memory ahead of the
    product that reads it (``slice-start``/``slice-done``, a copy's
    ``custom-call``); such an instruction has no ``op_name`` and reads a
    parameter, so ``scope_table`` gives it no scope. Its time is the read
    of that product's weight: give an instruction without a scope the
    scope of the first instruction that consumes it (through a chain of
    such instructions). -> a new table."""
    users = {}
    for line in text.splitlines():
        m = _LHS.match(line)
        if m is None:
            continue
        for operand in _REF.findall(line, m.end()):
            users.setdefault(operand, []).append(m.group(1))
    out = dict(table)
    for _ in range(4):  # start -> done -> copy -> product
        changed = False
        for name, scope in list(out.items()):
            if scope:
                continue
            got = next((out[u] for u in users.get(name, ()) if out.get(u)), "")
            if got:
                out[name], changed = got, True
        if not changed:
            break
    return out


def _compile_fresh(lowered):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def scope_tables(run, prefill_lengths, decode=True):
    """-> (decode program's table or None, {padded length: table}), or
    ``None`` where the program offers no such programs."""
    try:
        from fms_fsdp_tpu.models.mamba import init_mamba_decode_state
        from fms_fsdp_tpu.obs.scopes import HYBRID_SCOPES, scope_table
        from fms_fsdp_tpu.serve.families.mamba import (
            decode_program, page_geometry, prefill_program)
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
    page, max_pages, num_pages = page_geometry(model_cfg, scfg)

    def S(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), dt)

    def _table(lowered):
        text = _compile_fresh(lowered)
        return fill_from_users(text, scope_table(text, HYBRID_SCOPES))

    params = as_program_tree(weights.unflatten({
        p: S(s["shape"], dtype)
        for p, s in run.reference.param_spec(c).items()}))
    B, a = scfg.max_batch, model_cfg.attn_cfg
    table = None
    if decode:
        state = jax.eval_shape(
            lambda: init_mamba_decode_state(model_cfg, B, dtype))
        pools = {
            k: S((len(model_cfg.attn_layer_idx), num_pages, page,
                  a.num_heads_kv, a.head_dim), dtype)
            for k in ("k", "v")}
        table = _table(
            decode_program(model_cfg, scfg, page, dtype).lower(
                params, state, pools, S((B, max_pages), jnp.int32),
                S((B,), jnp.int32), S((B,), jnp.int32), S((2,), jnp.uint32)))
    tables = {}
    for n in sorted(prefill_lengths):
        kv_len = -(-n // page) * page
        tables[n] = _table(
            prefill_program(model_cfg, scfg, n, kv_len, dtype).lower(
                params, S((1, n), jnp.int32), S((1,), jnp.int32)))
    return table, tables


def _padded_tokens(module_name):
    m = PREFILL_MODULE.match(module_name)
    return int(m.group(1)) if m else None


def of(run):
    """The run's ``HybridTrace`` (made at the first call), or ``None``
    without a trace or without the programs."""
    if getattr(run, "hybrid_trace", None) is not None:
        return run.hybrid_trace
    if run.trace_data is None:
        return None
    t = time.perf_counter()
    steps = modules(
        run.trace_data, lambda n: True if n.startswith(DECODE_MODULE) else None)
    prefills = modules(run.trace_data, _padded_tokens)
    tables = scope_tables(
        run, {n for _, _, n in prefills}, decode=bool(steps))
    if tables is None:
        return None
    decode_table, prefill_tables = tables
    ht = HybridTrace()
    if decode_table is not None:
        ht.decode_steps = [
            time_by_scope(lines, m, decode_table) for lines, m, _ in steps]
    ht.prefills = [
        (n, time_by_scope(lines, m, prefill_tables[n]))
        for lines, m, n in prefills]
    ht.seconds = time.perf_counter() - t
    run.hybrid_trace = ht
    if not run.rehearse:  # a CPU's times are not reported
        print("hybrid scopes: " + json.dumps(summary(ht)), flush=True)
    return ht


def median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def ssm_scopes():
    from fms_fsdp_tpu.obs.scopes import SSM_SCOPES

    return SSM_SCOPES


def decode_ms(ht, scopes):
    """Median over the executed decode modules of the device ms under
    ``scopes``; ``None`` without any."""
    if not ht.decode_steps:
        return None
    return median(
        sum(by.get(s, 0.0) for s in scopes) / 1e6 for by in ht.decode_steps)


def prefill_ns(ht, scopes=None):
    """Device ns of all traced prefill modules under ``scopes`` (all
    scopes, and none, when ``None``)."""
    return sum(
        sum(v for s, v in by.items() if scopes is None or s in scopes)
        for _, by in ht.prefills)


def prefill_tokens(ht):
    return sum(n for n, _ in ht.prefills)


def summary(ht):
    names = sorted({s for by in ht.decode_steps for s in by})
    total = prefill_ns(ht)
    by_scope = {}
    for _, by in ht.prefills:
        for s, v in by.items():
            by_scope[s] = by_scope.get(s, 0.0) + v
    return {
        "decode_steps": len(ht.decode_steps),
        "decode_device_ms_by_scope": {
            (n or "(unscoped)"): decode_ms(ht, (n,)) for n in names},
        "prefills": [n for n, _ in ht.prefills],
        "prefill_device_ms": total / 1e6,
        "prefill_device_share_by_scope": {
            (s or "(unscoped)"): v / total
            for s, v in sorted(by_scope.items(), key=lambda kv: -kv[1])
        } if total else {},
        "read_s": ht.seconds,
    }
