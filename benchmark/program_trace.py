"""What the program itself wrote into a traced run's ``.xplane.pb``, and
the decode program's device time by the program's own scopes.

Two things ``trace_reduce`` does not read, both put there by the program
(no file that was in ``benchmark/`` before is edited for them):

- **Host spans named ``serve/<phase>``** (``fms_fsdp_tpu/obs/spans.py``):
  ``ServingEngine.submit`` and ``step`` and, inside a step, ``expire``,
  ``admit``, ``prefill`` (with ``prefill.dispatch``,
  ``prefill.write_pages``, ``prefill.sample``), ``prefill_chunk``,
  ``grow``, ``decode`` (with ``decode.table``, ``decode.dispatch``,
  ``decode.wait``, ``decode.commit``) and ``publish``, each with its
  counts as the event's stats. They are on the clock of the device's
  events, so the chip's idle time can be put down to a phase.
  ``run.trace_data`` keeps only ``bench/`` host events, so the file
  named by ``run.facts["trace_file"]`` is read again here, host planes
  only.
- **The scope of each device operation of the decode program.** A device
  event carries its instruction's name and no ``op_name``; the compiled
  program's HLO text carries both. So the same program is built again
  from the cell's two configs
  (``serve/families/mixtral.py::decode_program``), lowered with the
  shapes of the engine's arrays, compiled, and its text read by
  ``obs/scopes.py::scope_table`` into ``{instruction name: scope}``. This
  happens after the window, in a traced run only, and only where the
  trace holds executions of the decode module (``jit__step``). The
  compile does not read the persistent cache: a metadata-blind cache key
  could hand back an executable that another tree compiled, with that
  tree's ``op_name``\\ s.

Everything is computed once per run and kept on ``run`` (``of(run)``), and
one line ``program spans: {...}`` is printed: the device's idle seconds by
innermost ``serve/*`` span and the decode program's device ms a step by
scope (PERF.md section 5 copies it). Against a program that has no such
spans or no ``decode_program`` (the parent of the PR that added this
file), every reader finds nothing and returns ``None``.
"""

import bisect
import json
import time
from dataclasses import dataclass, field

from benchmark import trace_reduce
from benchmark.trace_reduce import Event

SPAN_PREFIX = "serve/"
DECODE_MODULE = "jit__step"
# the per-layer metrics' groups of scopes (fms_fsdp_tpu/obs/scopes.py)
ATTN_SCOPES = ("qkv", "kv_write", "kv_gather", "attn", "attn_out")
MOE_GATHER_SCOPES = ("moe_gather",)
MOE_EXPERT_SCOPES = ("moe_router", "moe_experts", "moe_combine")
UNSCOPED = ""


@dataclass
class ProgramTrace:
    spans: list  # the program's host spans ("serve/" stripped), by start
    # per executed decode module: {scope: device ns}, wrappers left out
    decode_steps: list = field(default_factory=list)
    joined_share: float = None  # of the decode module's events, by count
    idle_by_span: dict = field(default_factory=dict)  # seconds
    idle_s: float = 0.0
    seconds: float = 0.0  # what reading all this took


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


def serve_spans(pd):
    """The program's spans of a ``ProfileData``: host events named
    ``serve/...`` with their stats, by start (outermost first on ties)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append(Event(
                        e.name[len(SPAN_PREFIX):], float(e.start_ns),
                        float(e.duration_ns), dict(e.stats)))
    out.sort(key=lambda ev: (ev.start_ns, -ev.dur_ns))
    return out


def children(spans, parent):
    """The spans that lie inside ``parent`` (itself left out)."""
    return [
        s for s in spans
        if s is not parent and s.start_ns >= parent.start_ns
        and s.end_ns <= parent.end_ns]


def host_ms_of_decode_steps(spans):
    """Per ``step`` span that decoded and held no prefill of any kind:
    its duration less its ``decode.wait``, in ms."""
    out = []
    for step in (s for s in spans if s.name == "step"):
        inside = children(spans, step)
        if any(s.name.startswith("prefill") for s in inside):
            continue
        waits = [s.dur_ns for s in inside if s.name == "decode.wait"]
        if waits:
            out.append((step.dur_ns - sum(waits)) / 1e6)
    return out


def innermost_segments(spans):
    """The spans' time cut into ``[(start, end, name), ...]`` that do not
    overlap, by start: each instant under the innermost span that covers
    it (a span's own time is its interval less its children's; the spans
    of one thread nest). The ``.done`` markers count with their parent."""
    segs, stack, at = [], [], 0.0

    def close(upto):
        nonlocal at
        if stack and upto > at:
            segs.append((at, upto, stack[-1].name))
        at = max(at, upto)

    for s in sorted(
            (s for s in spans if not s.name.endswith(".done")),
            key=lambda ev: (ev.start_ns, -ev.dur_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            close(stack[-1].end_ns)
            stack.pop()
        close(s.start_ns)
        stack.append(s)
    while stack:
        close(stack[-1].end_ns)
        stack.pop()
    return segs


def idle_by_innermost_span(spans, idle):
    """``idle`` is ``[(start, end), ...]`` in ns, by start. -> ({name:
    seconds}, total seconds): each idle instant goes to the innermost
    span that covers it, ``"(outside)"`` where none does."""
    segs = innermost_segments(spans)
    ends = [hi for _, hi, _ in segs]
    by, total = {}, 0.0
    for lo, hi in idle:
        total += hi - lo
        left = hi - lo
        i = bisect.bisect_right(ends, lo)
        while i < len(segs) and segs[i][0] < hi:
            s_lo, s_hi, name = segs[i]
            cover = min(hi, s_hi) - max(lo, s_lo)
            if cover > 0:
                by[name] = by.get(name, 0.0) + cover
                left -= cover
            i += 1
        if left > 0:
            by["(outside)"] = by.get("(outside)", 0.0) + left
    return {k: v / 1e9 for k, v in by.items()}, total / 1e9


def device_idle(trace, spans):
    """The first device's idle intervals between the start of the first
    and the end of the last ``step`` span (a step that was under way when
    the profiler started has no span: the stretch before the first one is
    left out, not counted as the caller's)."""
    ops = trace_reduce.device_ops(trace)
    steps = [s for s in spans if s.name == "step"]
    if not ops or not steps:
        return []
    evs = next(iter(ops.values()))
    lo = max(evs[0].start_ns, steps[0].start_ns)
    hi = min(max(e.end_ns for e in evs), steps[-1].end_ns)
    if hi <= lo:
        return []
    return trace_reduce.gaps(evs, lo, hi)


# ---------------------------------------------------------------------------
# the decode program's device time by scope
# ---------------------------------------------------------------------------


def decode_time_by_scope(trace, scopes):
    """-> (one ``{scope: ns}`` per executed decode module, share of that
    module's events whose instruction the table knows). Events are taken
    inside the intervals of the ``jit__step`` events of "XLA Modules";
    a loop's own event spans its body's events, which are listed too, so
    wrappers are left out."""
    steps, known, seen = [], 0, 0
    for lines in trace.devices.values():
        mods = [
            m for m in lines.get(trace_reduce.MODULES_LINE, [])
            if m.name.startswith(DECODE_MODULE)]
        ops = lines.get(trace_reduce.OPS_LINE, [])
        starts = [e.start_ns for e in ops]
        for m in mods:
            by = {}
            i = bisect.bisect_left(starts, m.start_ns)
            while i < len(ops) and ops[i].start_ns < m.end_ns:
                e = ops[i]
                i += 1
                if e.name.startswith(trace_reduce.WRAPPERS):
                    continue
                seen += 1
                known += e.name in scopes
                scope = scopes.get(e.name, UNSCOPED)
                by[scope] = by.get(scope, 0.0) + min(e.end_ns, m.end_ns) - e.start_ns
            steps.append(by)
    return steps, (known / seen if seen else None)


def decode_scope_table(run):
    """``{instruction name: scope}`` of the cell's decode program, built
    again from the cell's configs and compiled here; ``None`` where the
    program offers no ``decode_program`` or is of another family."""
    try:
        from fms_fsdp_tpu.obs.scopes import scope_table
        from fms_fsdp_tpu.serve.families.mixtral import (
            decode_program, page_geometry)
    except ImportError:
        return None
    if run.config.get("family") != "mixtral":
        return None
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from benchmark import weights
    from fms_fsdp_tpu.serve.engine import ServeConfig

    c = run.config
    model_cfg = run.family.model_config(c)
    scfg = ServeConfig(**run.cell_file["engine"])
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[scfg.compute_dtype]
    page, _, _, max_pages, num_pages = page_geometry(model_cfg, scfg)

    def S(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), dt)

    params = weights.unflatten({
        p: S(s["shape"], dtype)
        for p, s in run.reference.param_spec(c).items()})
    B = scfg.max_batch
    pools = {
        k: S((c["num_hidden_layers"], num_pages, page, model_cfg.n_kv_heads,
              model_cfg.head_dim), dtype)
        for k in ("k", "v")}
    lowered = decode_program(model_cfg, scfg, page, dtype).lower(
        params, pools, S((B, max_pages), jnp.int32), S((B,), jnp.int32),
        S((B,), jnp.int32), S((2,), jnp.uint32))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    return scope_table(text)


# ---------------------------------------------------------------------------
# once per run
# ---------------------------------------------------------------------------


def of(run):
    """The run's ``ProgramTrace`` (made at the first call), or ``None``
    without a trace."""
    if getattr(run, "program_trace", None) is not None:
        return run.program_trace
    path = run.facts.get("trace_file")
    if run.trace_data is None or path is None:
        return None
    from jax.profiler import ProfileData

    t = time.perf_counter()
    pt = ProgramTrace(serve_spans(ProfileData.from_file(path)))
    scopes = None
    if trace_reduce.module_durations_ns(run.trace_data, (DECODE_MODULE,)):
        scopes = decode_scope_table(run)
    if scopes is not None:
        pt.decode_steps, pt.joined_share = decode_time_by_scope(
            run.trace_data, scopes)
    pt.idle_by_span, pt.idle_s = idle_by_innermost_span(
        pt.spans, device_idle(run.trace_data, pt.spans))
    pt.seconds = time.perf_counter() - t
    run.program_trace = pt
    if not run.rehearse:  # a CPU's times are not reported
        print("program spans: " + json.dumps(summary(pt)), flush=True)
    return pt


def median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def decode_ms(pt, scopes):
    """Median over the executed decode modules of the device ms on events
    whose scope is one of ``scopes``; ``None`` without a table."""
    if not pt.decode_steps:
        return None
    return median(
        sum(by.get(s, 0.0) for s in scopes) / 1e6 for by in pt.decode_steps)


def unscoped_share(pt):
    if not pt.decode_steps:
        return None
    return median(
        100.0 * by.get(UNSCOPED, 0.0) / sum(by.values())
        for by in pt.decode_steps if by)


def outside_share(pt):
    """Share of the device's idle time outside every ``step`` and
    ``submit`` span, in %; ``None`` without spans or idle time."""
    if pt.idle_s <= 0 or not any(s.name == "step" for s in pt.spans):
        return None
    return 100.0 * pt.idle_by_span.get("(outside)", 0.0) / pt.idle_s


def summary(pt):
    names = sorted({s for by in pt.decode_steps for s in by})
    host = host_ms_of_decode_steps(pt.spans)
    phases = {}
    for s in pt.spans:
        if not s.name.endswith(".done"):
            phases.setdefault(s.name, []).append(s.dur_ns / 1e6)
    return {
        "spans": len(pt.spans),
        "span_ms_median": {k: median(v) for k, v in sorted(phases.items())},
        "host_ms_decode_step_median": median(host),
        "device_idle_s": pt.idle_s,
        "device_idle_s_by_span": dict(sorted(
            pt.idle_by_span.items(), key=lambda kv: -kv[1])),
        "decode_steps": len(pt.decode_steps),
        "decode_device_ms_by_scope": {
            (n or "(unscoped)"): decode_ms(pt, (n,)) for n in names},
        "decode_events_joined_share": pt.joined_share,
        "read_s": pt.seconds,
    }
