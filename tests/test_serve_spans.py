"""The serving engine's host spans (obs/spans.py) and the way from a
device event back to a model scope (obs/scopes.py::scope_table).

Tiny engines on the CPU. The spans are read back from a real
``jax.profiler`` session with ``jax.profiler.ProfileData``, the same way
``benchmark/program_trace.py`` reads a chip trace; what is pinned here is
what that reader and docs/observability.md "Tracing a serving replica"
rely on: the names, the nesting, the counts as the event's stats, the
registry counters that sum them, and the names of the jitted programs.
"""

import contextlib
import glob
import itertools
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from fms_fsdp_tpu.models.configs import LlamaConfig, MixtralConfig
from fms_fsdp_tpu.models.llama import init_llama_params
from fms_fsdp_tpu.models.mixtral import init_mixtral_params, mixtral_prefill
from fms_fsdp_tpu.obs.scopes import DECODE_SCOPES, scope_table
from fms_fsdp_tpu.obs.spans import PREFIX
from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
from fms_fsdp_tpu.serve.families.mixtral import decode_program, page_geometry
from fms_fsdp_tpu.serve.scheduler import RequestRejected

TINY_MIXTRAL = MixtralConfig(
    src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
    hidden_dim=128, num_experts=4, top_k=2, max_expected_seq_len=64,
)
TINY_LLAMA = LlamaConfig(
    src_vocab_size=128, emb_dim=64, nheads=4, kvheads=2, nlayers=2,
    max_expected_seq_len=64,
)
SCFG = ServeConfig(
    max_batch=2, max_seq_len=64, page_size=8, prefill_bucket=8,
    attn_impl="reference", compute_dtype="float32",
)
# (prompt length, max_new_tokens): three requests over two slots, so the
# third waits in the queue; none finishes inside its own prefill
REQUESTS = ((5, 4), (9, 3), (12, 5))

# every span of the engine and the adapter: name -> the span it lies in
PARENT = {
    "submit": None,
    "step": None,
    "expire": "step",
    "admit": "step",
    "prefill": "admit",
    "prefill.dispatch": "prefill",
    "prefill.write_pages": "prefill",
    "grow": "step",
    "decode": "step",
    "decode.table": "decode",
    "decode.dispatch": "decode",
    "decode.wait": "decode",
    "decode.commit": "decode",
    # the landing of an admission, behind the decode step's dispatch: the
    # read of the first token and the counts of the prefill program
    "prefill.land": "step",
    "prefill.sample": "prefill.land",
    "publish": "step",
    # counts known only at the end of their span
    "submit.done": "submit",
    "expire.done": "expire",
    "admit.done": "admit",
    "grow.done": "grow",
    "decode.commit.done": "decode.commit",
    "prefill.done": "prefill.land",
}
# the chunked path (llama only) adds one name; the last chunk's first
# token lands like a whole prompt's, and the positions are counted where
# they are staged
CHUNKED_PARENT = {"prefill_chunk": "step", "prefill.land": "step",
                  "prefill.sample": "prefill.land",
                  "prefill.done": "prefill",
                  "prefill.write_pages": "prefill_chunk",
                  "prefill.dispatch": "prefill_chunk"}


class Span:
    def __init__(self, e):
        self.name = e.name[len(PREFIX):]
        self.start, self.end = e.start_ns, e.start_ns + e.duration_ns
        self.stats = dict(e.stats)

    def inside(self, other):
        return other.start <= self.start and self.end <= other.end


def read_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    spans = [
        Span(e)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith(PREFIX)]
    return sorted(spans, key=lambda s: (s.start, -s.end))


def serve(params, cfg, scfg, trace_dir=None):
    """Serve REQUESTS (and one submit that is rejected) to the end, under
    a profiler session when ``trace_dir`` is given. -> (engine, the
    requests, the spans read back or None)."""
    engine = ServingEngine(params, cfg, scfg, seed=3)
    session = contextlib.nullcontext()
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        session = jax.profiler.trace(str(trace_dir), profiler_options=opts)
    with session:
        reqs = [
            engine.submit([1 + (i + j) % 100 for j in range(p)], new)
            for i, (p, new) in enumerate(REQUESTS)]
        with pytest.raises(RequestRejected):
            engine.submit([1] * 60, 10)
        engine.run()
    spans = None if trace_dir is None else read_spans(str(trace_dir))
    return engine, reqs, spans


@pytest.fixture(scope="module")
def mixtral_params():
    return init_mixtral_params(jax.random.PRNGKey(0), TINY_MIXTRAL)


@pytest.fixture(scope="module")
def traced(mixtral_params, tmp_path_factory):
    return serve(mixtral_params, TINY_MIXTRAL, SCFG,
                 tmp_path_factory.mktemp("trace"))


@pytest.fixture(scope="module")
def traced_chunked(tmp_path_factory):
    params = init_llama_params(jax.random.PRNGKey(1), TINY_LLAMA)
    scfg = ServeConfig(**{**SCFG.__dict__, "prefill_chunk_tokens": 8})
    return serve(params, TINY_LLAMA, scfg, tmp_path_factory.mktemp("chunked"))


def named(spans, name):
    return [s for s in spans if s.name == name]


# -- (a) the spans of a traced engine ----------------------------------------


@pytest.mark.parametrize("name", sorted(PARENT))
def test_span_is_written_inside_its_parent(traced, name):
    _, _, spans = traced
    mine = named(spans, name)
    assert mine, f"no serve/{name} span in the trace"
    if PARENT[name] is not None:
        parents = named(spans, PARENT[name])
        for s in mine:
            assert any(s.inside(p) for p in parents), (name, s.stats)


@pytest.mark.parametrize("name", sorted(CHUNKED_PARENT))
def test_chunked_prefill_spans(traced_chunked, name):
    _, reqs, spans = traced_chunked
    assert all(r.state == "finished" for r in reqs)
    parents = named(spans, CHUNKED_PARENT[name])
    inside = [s for s in named(spans, name)
              if any(s.inside(p) for p in parents)]
    assert inside, f"no serve/{name} inside serve/{CHUNKED_PARENT[name]}"
    assert all("rid" in s.stats for s in inside)


def test_a_prefill_that_does_not_stop_short_computes_its_bucket(
        traced, traced_chunked):
    """``serve.prefill_computed_tokens`` equals the padded tokens for
    the families whose prefill covers the bucket, staged in chunks
    (llama) or whole (mixtral); the hybrid's is in tests/test_jamba.py."""
    for engine, _, _ in (traced, traced_chunked):
        reg = engine.registry
        assert reg.counter("serve.prefill_computed_tokens").value == (
            reg.counter("serve.prefill_padded_tokens").value) > 0


def test_counts_come_back_as_the_events_stats(traced):
    """The keyword counts of a span are the event's stats on this jax
    (they are not left in its name): what benchmark/program_trace.py
    reads."""
    _, _, spans = traced
    assert all("#" not in s.name for s in spans)
    step = named(spans, "step")[0]
    assert {"step", "queued", "busy"} <= set(step.stats)
    assert step.stats["step"] == 1 and step.stats["queued"] == 3
    (rejected,) = [s for s in named(spans, "submit.done")
                   if s.stats["rejected"]]
    assert rejected.stats["reason"] == "too_large"


def test_decode_dispatch_names_the_moe_form(traced):
    """Which loop the routed decode program runs over the experts is a
    static fact of the engine; every ``decode.dispatch`` span carries it
    (2 slots x top-2 against 4 experts: every expert is streamed)."""
    engine, _, spans = traced
    assert engine.adapter.moe_form == "all_experts"
    assert {s.stats["moe_form"] for s in named(spans, "decode.dispatch")} == {
        "all_experts"}


@pytest.fixture(scope="module")
def traced_sarvam(tmp_path_factory):
    """A tiny latent-attention MoE (values narrower than keys: 24 and 16)
    served under a profiler session, the einsum attention of a CPU."""
    from fms_fsdp_tpu.models.configs import SarvamConfig
    from fms_fsdp_tpu.models.sarvam import init_sarvam_params

    cfg = SarvamConfig(
        src_vocab_size=128, emb_dim=64, nheads=4, nlayers=2, first_k_dense=1,
        hidden_dim=128, moe_hidden_dim=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=4, top_k=2, max_expected_seq_len=64,
    )
    params = init_sarvam_params(jax.random.PRNGKey(2), cfg)
    return cfg, params, serve(
        params, cfg, SCFG, tmp_path_factory.mktemp("sarvam"))


def test_prefill_dispatch_names_the_attention_form(traced_sarvam, traced):
    """What the prefill program's attention runs is a fact of the program
    (the backend, its chunk, the head widths): every ``prefill.dispatch``
    span of the latent-attention family carries it, and a gauge set at
    build holds the value width it runs at. Other families add no field."""
    cfg, params, (engine, _, spans) = traced_sarvam
    dispatched = named(spans, "prefill.dispatch")
    assert len(dispatched) == len(REQUESTS)
    assert {s.stats["attn_form"] for s in dispatched} == {"einsum"}
    assert {s.stats["built"] for s in dispatched} == {0, 1}
    width = engine.registry.gauge("serve.prefill_attn_value_width").value
    assert width == cfg.v_head_dim == 16 != cfg.q_head_dim
    # the same engine told to take the kernels: a bucket the flash blocks
    # tile runs it with two widths, an odd one the einsum
    kernel = ServingEngine(
        params, cfg, ServeConfig(**{**SCFG.__dict__, "attn_impl": "kernel"}))
    fields = kernel.adapter._prefill_fields
    assert fields((256, 256)) == {"attn_form": "flash_two_width"}
    assert fields((24, 24)) == {"attn_form": "einsum"}
    assert all(
        "attn_form" not in s.stats
        for s in named(traced[2], "prefill.dispatch"))
    assert traced[0].adapter._prefill_fields((16, 16, True)) == {}


def test_a_sala_prefill_names_its_form_and_counts_what_it_multiplied(
        tmp_path, monkeypatch):
    """The sparse-and-linear family: ``attn_form`` on ``prefill.dispatch``
    says what the program's chunks past ``dense_len`` run (the band and
    the kernel over each query's chosen blocks), and ``prefill.done``
    carries the program's four counts, ``multiplied_blocks`` the last;
    the counter sums it."""
    from fms_fsdp_tpu.models import minicpm_sala as M
    from fms_fsdp_tpu.models.configs import minicpm_sala_config

    monkeypatch.setattr(M, "PREFILL_CHUNK", 4)
    cfg = minicpm_sala_config({
        "model_type": "minicpm_sala", "hidden_size": 32, "head_dim": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2,
        "mixer_types": ["minicpm4", "lightning-attn"],
        "intermediate_size": 64, "lightning_nh": 4, "lightning_nkv": 4,
        "lightning_head_dim": 8, "lightning_use_rope": True,
        "lightning_scale": "1/sqrt(d)", "attn_use_rope": False,
        "qk_norm": True, "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True, "scale_emb": 12, "scale_depth": 1.4,
        "dim_model_base": 16, "vocab_size": 128, "hidden_act": "silu",
        "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "sparse_config": {
            "kernel_size": 4, "kernel_stride": 2, "block_size": 4, "topk": 4,
            "init_blocks": 1, "window_size": 8, "dense_len": 8},
    })
    params = M.init_sala_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    scfg = ServeConfig(**{**SCFG.__dict__, "page_size": 0})
    engine, reqs, spans = serve(params, cfg, scfg, tmp_path)
    assert M.chunk_forms(16, cfg) == ["dense", "dense", "chosen", "chosen"]
    forms = {s.stats["rid"]: s.stats["attn_form"]
             for s in named(spans, "prefill.dispatch")}
    # prompts of 5, 9 and 12: programs of 8, 16 and 16 positions
    assert [forms[r.rid] for r in reqs] == [
        "einsum", "einsum+chosen_blocks", "einsum+chosen_blocks"]
    done = {s.stats["rid"]: s.stats for s in named(spans, "prefill.done")}
    for req, p, n in zip(reqs, (5, 9, 12), (8, 16, 16)):
        chose, chosen, context = M.prefill_choices(p, cfg)
        assert chose == max(0, p - 8)
        got = done[req.rid]
        assert (got["chose_tokens"], got["chosen_blocks"],
                got["context_blocks"]) == (chose, chosen, context)
        # the first block and the band of 2 (a tile of one block has no
        # corner); positions 8-11 stand in block 2 and have no free block
        assert got["multiplied_blocks"] == M.prefill_multiplied(
            p, n, cfg) == chose * 3
    count = engine.registry.counter
    assert count("serve.sparse_multiplied_blocks").value == (1 + 4) * 3
    assert count("serve.sparse_context_blocks").value == 3 + 4 * 3


def test_every_span_of_a_step_carries_its_step(traced):
    engine, _, spans = traced
    steps = named(spans, "step")
    assert [s.stats["step"] for s in steps] == list(
        range(1, engine.iterations + 1))
    for step in steps:
        inside = [s for s in spans if s is not step and s.inside(step)]
        assert len(inside) >= 5
        # the adapter's spans (decode.table, decode.dispatch, decode.wait,
        # prefill.dispatch, prefill.write_pages) get their step from the
        # engine's span they lie in
        assert {s.stats["step"] for s in inside if "step" in s.stats} == {
            step.stats["step"]}


def test_the_spans_of_a_request_share_its_rid(traced):
    _, reqs, spans = traced
    prefills = named(spans, "prefill")
    assert sorted(s.stats["rid"] for s in prefills) == sorted(
        r.rid for r in reqs)
    for pf in prefills:
        kids = [s for s in spans if s is not pf and s.inside(pf)]
        assert {s.name for s in kids} == {
            "prefill.dispatch", "prefill.write_pages"}
        assert {s.stats["rid"] for s in kids} == {pf.stats["rid"]}
    # the landing half: a span a request, in the step of its admission
    # and behind that step's decode dispatch
    lands = named(spans, "prefill.land")
    assert sorted(s.stats["rid"] for s in lands) == sorted(
        r.rid for r in reqs)
    for land in lands:
        (pf,) = [s for s in prefills if s.stats["rid"] == land.stats["rid"]]
        assert land.stats["step"] == pf.stats["step"] and pf.end <= land.start
        kids = [s for s in spans if s is not land and s.inside(land)]
        assert [s.name for s in kids] == ["prefill.sample", "prefill.done"]
        assert {s.stats["rid"] for s in kids} == {land.stats["rid"]}
        assert kids[0].stats["step"] == land.stats["step"]
        assert kids[0].stats["overlapped"] == 1
        # one decode step was dispatched between the two halves
        assert len([
            s for s in named(spans, "decode.dispatch")
            if pf.end <= s.start and s.end <= land.start]) == 1
    accepted = [s.stats["rid"] for s in named(spans, "submit.done")
                if not s.stats["rejected"]]
    assert accepted == [r.rid for r in reqs]


def _steps_with_prefill(spans):
    return sum(
        any(s.name in ("prefill", "prefill_chunk") and s.inside(step)
            for s in spans)
        for step in named(spans, "step"))


COUNTERS = {
    # registry counter -> the same number from the spans' counts
    "serve.steps": lambda sp: len(named(sp, "step")),
    "serve.steps_with_prefill": _steps_with_prefill,
    "serve.prefill_padded_tokens": lambda sp: sum(
        s.stats["padded_tokens"] for s in named(sp, "prefill")),
    "serve.prefill_tokens": lambda sp: sum(
        s.stats["prompt_tokens"] for s in named(sp, "prefill")),
    # an adapter whose prefill does not stop short computes its bucket
    "serve.prefill_computed_tokens": lambda sp: sum(
        s.stats["computed_tokens"] for s in named(sp, "prefill.done")),
    "serve.prefill_programs_built": lambda sp: sum(
        s.stats["built"] for s in named(sp, "prefill.dispatch")),
    "serve.page_table_uploads": lambda sp: sum(
        s.stats["uploaded"] for s in named(sp, "decode.table")),
    "serve.decode_live_slots": lambda sp: sum(
        s.stats["live"] for s in named(sp, "decode")),
    "serve.decode_tokens": lambda sp: sum(
        s.stats["tokens"] for s in named(sp, "decode.commit.done")),
    "serve.requests_completed": lambda sp: sum(
        s.stats["finished"] for s in named(sp, "decode.commit.done")),
    "serve.requests_submitted": lambda sp: sum(
        1 - s.stats["rejected"] for s in named(sp, "submit.done")),
    "serve.requests_rejected.too_large": lambda sp: sum(
        s.stats["rejected"] for s in named(sp, "submit.done")),
    "serve.requests_evicted": lambda sp: sum(
        s.stats["evicted"] for s in named(sp, "grow.done")),
    "serve.requests_expired": lambda sp: sum(
        s.stats["expired"] for s in named(sp, "expire.done")),
}


@pytest.mark.parametrize("counter", sorted(COUNTERS))
def test_registry_counter_is_the_sum_of_the_spans_counts(traced, counter):
    engine, _, spans = traced
    assert engine.registry.counter(counter).value == COUNTERS[counter](spans)


def test_counters_read_what_happened(traced):
    engine, reqs, spans = traced
    reg = engine.registry
    assert reg.counter("serve.prefill_padded_tokens").value == 8 + 16 + 16
    assert reg.counter("serve.prefill_computed_tokens").value == 8 + 16 + 16
    # three shapes, each prefilled once: (8, not full), (16, x), (16, x)
    assert reg.counter("serve.prefill_programs_built").value == 2
    assert reg.counter("serve.steps_with_prefill").value == 3
    assert reg.counter("serve.decode_live_slots").value == sum(
        new - 1 for _, new in REQUESTS)
    assert sum(s.stats["admitted"] for s in named(spans, "admit.done")) == 3
    decodes = named(spans, "decode")
    kv = [s.stats["kv_tokens"] for s in decodes if s.stats["live"]]
    assert kv[0] == 5 and all(k > 0 for k in kv)
    # the last iteration dispatches nothing: it commits the step in flight
    assert [s.stats["live"] for s in decodes].count(0) == 1
    assert decodes[-1].stats["live"] == 0


def test_a_family_without_the_paged_kernel_walks_no_blocks(traced):
    """Mixtral's decode step gathers its pages and attends in XLA: the
    field, the counter and the gauge of the ragged paged kernel's walk
    (ops/paged_attention.py) read 0."""
    engine, _, spans = traced
    decodes = named(spans, "decode")
    assert decodes and all(s.stats["attn_blocks"] == 0 for s in decodes)
    assert all(r["attn_blocks"] == 0 for r in engine.step_log)
    assert engine.registry.counter("serve.decode_attn_blocks").value == 0
    assert engine.registry.gauge("serve.decode_attn_grid_blocks").value == 0


# -- (b) tracing off is no profiler session ----------------------------------


def test_no_session_same_tokens_and_nothing_written(
        traced, mixtral_params, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    engine, reqs, spans = serve(mixtral_params, TINY_MIXTRAL, SCFG)
    assert spans is None
    assert [r.generated for r in reqs] == [r.generated for r in traced[1]]
    assert all(r.state == "finished" for r in reqs)
    assert list(tmp_path.iterdir()) == []
    # the step log needs no session: a record a step, the same counts as
    # the traced engine's (tests/test_engine_step_log.py has the rest)
    log, was = list(engine.step_log), list(traced[0].step_log)
    assert len(log) == engine.iterations == len(was)
    for field in ("admitted", "admit_stopped", "live", "kv_tokens", "tokens",
                  "padded_tokens", "computed_tokens", "pages_in_use"):
        assert [r[field] for r in log] == [r[field] for r in was], field
    assert all(r["wall_us"] > 0 for r in log)


# -- (c) the request's own timeline -------------------------------------------


def test_admit_time_and_queue_wait(mixtral_params):
    tick = itertools.count()
    engine = ServingEngine(
        mixtral_params, TINY_MIXTRAL, SCFG, clock=lambda: float(next(tick)))
    reqs = [engine.submit([1 + j for j in range(p)], new)
            for p, new in REQUESTS]
    assert all(r.admit_time is None for r in reqs)
    engine.run()
    for r in reqs:
        assert r.submit_time < r.admit_time < r.first_token_time
    waits = engine.registry.hist("serve.queue_wait_s").samples
    assert sorted(waits) == sorted(r.admit_time - r.submit_time for r in reqs)
    # the third request waited for a slot
    assert reqs[2].admit_time > reqs[0].first_token_time


# -- (d) scope_table ------------------------------------------------------------


def _toy_text():
    def f(x, w, idx):
        def body(c, wl):
            with jax.named_scope("moe_gather"):
                g = wl[idx]
            with jax.named_scope("moe_experts"):
                c = jnp.tanh(c @ g[0]) @ g[1]
            return c * 2.0 + 1.0, None

        return jax.lax.scan(body, x, w)[0]

    args = (jnp.ones((4, 8)), jnp.ones((3, 5, 8, 8)), jnp.array([0, 2]))
    return jax.jit(f).lower(*args).compile().as_text()


@pytest.mark.parametrize("op,scope", [
    (" gather(", "moe_gather"), (" dot(", "moe_experts"),
    (" multiply(", ""), (" while(", ""),
])
def test_scope_table_on_a_toy_scan(op, scope):
    text = _toy_text()
    table = scope_table(text)
    names = [
        re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line).group(1)
        for line in text.splitlines() if op in line and " = " in line]
    assert names, f"the compiled toy has no{op.rstrip('(')}"
    assert {table.get(n, "") for n in names} == {scope}


def test_scope_table_takes_the_innermost_declared_name():
    text = (
        '  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_type="x" op_name="jit(_step)/layers/while/body/'
        'closed_call/moe_router/attn/dot_general" source_file="a.py"}\n'
        '  %copy.1 = f32[4]{0} copy(%fusion.3)\n'
        '  ROOT %add.2 = f32[4]{0} add(%copy.1, %copy.1), '
        'metadata={op_name="jit(_step)/add"}\n')
    # copy.1 has no op_name: it takes the scope of what it consumes;
    # add.2 has one that names no scope and runs in no other's computation
    assert scope_table(text) == {
        "fusion.3": "attn", "copy.1": "attn", "add.2": ""}
    assert scope_table(text, names=("layers",)) == {
        "fusion.3": "layers", "copy.1": "layers", "add.2": ""}


def test_an_instruction_without_a_scope_takes_its_callers():
    """What the TPU compiler makes of a gather: a ``while`` that carries
    the gather's ``op_name`` over a body of slices that carry none."""
    text = """HloModule jit__step

%fused_computation.7 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %negate.1 = f32[4]{0} negate(%param_0)
}

%wide.while_body.3.sunk (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %slice_fusion.9 = f32[4]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.7
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%arg, %slice_fusion.9)
}

%cond.4 (arg.1: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.5 = pred[] compare(%arg.1, %arg.1), direction=LT
}

ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %while.6 = (s32[], f32[4]{0}) while(%p), condition=%cond.4, body=%wide.while_body.3.sunk, metadata={op_name="jit(_step)/layers/while/body/closed_call/moe_gather/gather"}
  %copy.8 = f32[4]{0} copy(%p)
  %get-tuple-element.7 = f32[4]{0} get-tuple-element(%while.6), index=1
  %copy.10 = f32[4]{0} copy(%get-tuple-element.7)
  ROOT %add.9 = f32[4]{0} add(%copy.8, %copy.10), metadata={op_name="jit(_step)/lm_head/add"}
}
"""
    table = scope_table(text)
    assert table["while.6"] == "moe_gather"
    assert table["slice_fusion.9"] == "moe_gather"  # in the loop's body
    assert table["negate.1"] == "moe_gather"  # in that fusion's computation
    assert table["lt.5"] == "moe_gather"  # in the loop's condition
    # glue after the loop, no op_name: what it consumes came from the loop
    assert table["copy.10"] == "moe_gather"
    assert table["copy.8"] == "" and table["p"] == ""
    assert table["add.9"] == "lm_head"


# -- (e) the names the benchmark's readers depend on --------------------------


def _shapes(cfg, scfg, dtype=jnp.bfloat16):
    """The decode program's arguments: float32 weights (so that the
    whole-tree cast to the compute dtype is there), ``dtype`` pools."""
    page, _, _, max_pages, num_pages = page_geometry(cfg, scfg)

    def S(shape, dt=dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dt)

    params = jax.eval_shape(
        lambda k: init_mixtral_params(k, cfg), jax.random.PRNGKey(0))
    B = scfg.max_batch
    pools = {k: S((cfg.nlayers, num_pages, page, cfg.n_kv_heads,
                   cfg.head_dim)) for k in ("k", "v")}
    return page, (
        params, pools, S((B, max_pages), jnp.int32), S((B,), jnp.int32),
        S((B,), jnp.int32), S((2,), jnp.uint32))


@pytest.fixture(scope="module")
def decode_text():
    page, args = _shapes(TINY_MIXTRAL, SCFG)
    return decode_program(
        TINY_MIXTRAL, SCFG, page, jnp.bfloat16).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def prefill_text():
    params = jax.eval_shape(
        lambda k: init_mixtral_params(k, TINY_MIXTRAL), jax.random.PRNGKey(0))
    jitted = jax.jit(partial(
        mixtral_prefill, cfg=TINY_MIXTRAL, max_seq_len=16,
        compute_dtype=jnp.float32, full_logits=True))
    return jitted.lower(
        params, jax.ShapeDtypeStruct((1, 16), jnp.int32)).compile().as_text()


def test_the_programs_keep_their_names(decode_text, prefill_text):
    """``decode_device_ms`` and ``moe_decode_roofline`` find the decode
    module in the trace as ``jit__step``. The prefill programs are jitted
    ``functools.partial``s, which this jax names ``jit__unknown`` (not
    ``jit_mixtral_prefill``): no reader goes by that name, and a rename
    would change what the persistent compile cache is keyed on."""
    assert decode_text.startswith("HloModule jit__step,")
    assert prefill_text.startswith("HloModule jit__unknown,")


# the routed mixtral step holds every decode scope but the dense FFNs
MIXTRAL_DECODE_SCOPES = [
    s for s in DECODE_SCOPES if s not in ("ffn", "moe_dense")]


@pytest.mark.parametrize("scope", MIXTRAL_DECODE_SCOPES)
def test_decode_program_holds_the_scope(decode_text, scope):
    assert scope in set(scope_table(decode_text).values())
    assert re.search(rf'op_name="jit\(_step\)/[^"]*\b{scope}/', decode_text)


@pytest.mark.parametrize(
    "scope", ["embed", "qkv", "attn", "attn_out", "moe_dense", "lm_head"])
def test_prefill_program_holds_the_scope(prefill_text, scope):
    assert scope in set(scope_table(prefill_text).values())


def test_every_operation_with_an_op_name_lies_under_a_scope(decode_text):
    """What runs as a device event of its own (a fusion, a dot, a copy, a
    gather, a slice) and carries an ``op_name`` lies under some scope:
    no line of the model's code is outside every scope. (An instruction
    that the compiler made, with no ``op_name`` and in no other's
    computation, has none; plumbing does not count.)"""
    table = scope_table(decode_text)
    work = re.compile(
        r"\s*(?:ROOT )?%?([\w.\-]+) = \S+ "
        r"(fusion|dot|copy|gather|scatter|dynamic-slice|"
        r"dynamic-update-slice|custom-call|convolution)\(.*op_name=")
    found = [m.group(1) for m in map(work.match, decode_text.splitlines()) if m]
    assert len(found) > 50
    assert [n for n in found if table[n] == ""] == []


def _program_only(text):
    """An HLO text without what does not make the program: the
    ``metadata={...}`` of each instruction, and the tables of file names
    and stack frames that the metadata points into."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return "\n".join(
        line for line in text.splitlines()
        if not re.match(
            r"(\d+ |FileNames$|FunctionNames$|FileLocations$|StackFrames$)",
            line))


def test_scopes_change_the_metadata_and_nothing_else(decode_text, monkeypatch):
    """The decode program compiled with every ``named_scope`` a no-op is
    the same text once the metadata is stripped: same instructions, same
    names, same order."""
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext())
    page, args = _shapes(TINY_MIXTRAL, SCFG)
    bare = decode_program(
        TINY_MIXTRAL, SCFG, page, jnp.bfloat16).lower(*args).compile().as_text()
    assert "moe_gather" in decode_text and "moe_gather" not in bare
    assert _program_only(bare) == _program_only(decode_text)
