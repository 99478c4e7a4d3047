"""The serving engine: checkpoint -> continuous-batching decode loop.

First slice of the serving story (ROADMAP item 1): single-chip,
CPU-deterministic, one fixed-shape jitted decode step serving a
changing request population. The pieces:

- params restored from a training checkpoint (``from_checkpoint`` ->
  utils/checkpointing.py::load_params_only — a params pickle, a
  step_N_ckp dir, or a checkpoints/ root; optimizer state is never
  read);
- a :class:`~fms_fsdp_tpu.serve.kv_cache.PagedKVCache` pool whose page
  size resolves through the kernel-tuning table
  (tune/lookup.py::resolve_paged_decode) at engine build — table or
  cost model, never a timing sweep;
- the :class:`~fms_fsdp_tpu.serve.scheduler.ContinuousBatchingScheduler`
  deciding admission / expiry / eviction each iteration;
- one jitted ragged decode step (serve/decode.py) over the ``max_batch``
  slots, pools donated so the update is in-place; prefills run
  interleaved (at most ``max_prefill_per_step`` per iteration) through
  models/generation.py::prefill, whose cache scatters into the pages.

Since PR 17 the family-specific device work — decode-state allocation,
prefill, the jitted ragged decode step, checkpoint resolution — lives
in a per-family adapter (serve/families/): llama keeps its paged-KV +
ragged-kernel path verbatim, mamba decodes from a constant-size
recurrent slab, mixtral routes each token through its top-k experts
over paged attention. The engine proper is family-agnostic: admission,
continuous batching, LIFO eviction, sampling, metrics.

Greedy decode on the reference impls is bit-identical to each family's
jitted dense full-forward walk — the parity anchors
(tests/test_serving.py, tests/test_serving_families.py). Metrics land
on the engine's MetricRegistry under ``serve.*`` and fold into the obs
record's schema-v14 ``serving`` map via
:meth:`ServingEngine.serving_stats`.

PR 19 raw-speed additions, both parity-preserving: chunked prefill
(``prefill_chunk_tokens``) streams long prompts in slices interleaved
with decode, and speculative serving (``speculator_path``) commits
multiple greedy tokens per verify step through the family adapter's
``decode_spec``.

Since PR 30 the plain decode loop keeps one step in flight: ``step()``
dispatches decode step n+1 from step n's token output, which stays on
the device, and only then reads step n's tokens and commits them, so the
host's work between two steps runs while the device computes. Lengths
advance at dispatch, tokens become visible at the commit; whatever takes
a stream away or reads its tokens outside the commit first collects the
step in flight (``_collect``). docs/serving.md "The decode loop" has the
order and what it means for an end-of-sequence token. The speculative
path stays dispatch-then-collect inside one ``step()``: how many tokens
a stream gains there is known only from the result.

Since PR 42 an admission reads nothing either: its prefill program and
its sampler are dispatched, the first token stays on the device and goes
into the next decode step's token row there, and the host reads it
behind that step's dispatch, at the end of the same ``step()``
(``_land``: TTFT, the adapter's counts and the ``prefill.done`` marker
are written there). From the prefill program's end to the decode step's
start the device has its work queued. ``_collect`` lands what is pending
too, so it stays the one door; a prefill replica, a speculative engine
and a mesh need the token on the host at once and land it where it is
sampled.
"""

import json
import logging
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from fms_fsdp_tpu.models.generation import sample_token
from fms_fsdp_tpu.obs.registry import MetricRegistry
from fms_fsdp_tpu.obs.spans import ADMIT_STOPPED, SLOT, StepLog, done, span
from fms_fsdp_tpu.serve.families import FAMILY_CODES, resolve_adapter
from fms_fsdp_tpu.serve.scheduler import (
    FINISHED,
    REJECT_DEADLINE_UNMEETABLE,
    REJECT_OVERLOADED,
    REJECT_TOO_LARGE,
    ContinuousBatchingScheduler,
    Request,
    RequestRejected,
)

logger = logging.getLogger("fms_fsdp_tpu.serve")

# the fields of a step's record that no span carries, written here
_BUSY_AFTER_ADMIT = SLOT["busy_after_admit"]
_ADMIT_STOPPED = SLOT["admit_stopped"]
_PAGES_IN_USE = SLOT["pages_in_use"]
_HBM = (SLOT["hbm_in_use"], SLOT["hbm_largest_free"])
# what the runtime's ``memory_stats()`` calls those two
_HBM_KEYS = ("bytes_in_use", "largest_free_block_bytes")

_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
}


@dataclass
class _InFlight:
    """A decode step that was dispatched and whose tokens the host has
    not read yet."""

    toks: object  # (B,) int32, on the device
    logits: object  # (B, V), on the device
    streams: List[Tuple[int, Request]]  # (slot, request) it decodes


@dataclass
class _Admission:
    """An admission whose prefill program and sampler were dispatched
    and whose first token the host has not read yet."""

    req: Request
    slot: int
    tok: object  # () int, on the device
    prompt_tokens: int
    # a decode step was dispatched over the stream: the token went into
    # the step's row on the device and the host reads it behind the step
    rode: bool = False


@dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (docs/serving.md has the full table)."""

    max_batch: int = 8  # decode slots (the fixed jit batch shape)
    max_seq_len: int = 2048  # per-sequence cache capacity
    num_pages: int = 0  # pool size; 0 = max_batch*max_seq_len + reserved
    page_size: int = 0  # 0 = resolve via the tuning table / cost model
    kv_quant: str = "none"  # "none" | "int8" | "fp8" page storage
    attn_impl: str = "auto"  # "reference" | "kernel" | "auto"
    compute_dtype: str = "bfloat16"
    # prompt lengths round up to a multiple of this before prefill
    # (bounds jit recompiles under diverse lengths); 1 = exact lengths,
    # which keeps strict dense bit-parity
    prefill_bucket: int = 1
    max_prefill_per_step: int = 1  # prefill-decode interleave bound
    # chunked prefill: prompts longer than this split into chunk-sized
    # slices advanced one per engine step, interleaved with decode — a
    # long prompt no longer head-of-line-blocks every running stream's
    # next token. Chunked logits are bit-identical to whole-prompt prefill
    # (decode_chunk and prefill run the same attention op-for-op over
    # the same zero-initialized cache). 0 = whole-prompt, the exact v1
    # code path
    prefill_chunk_tokens: int = 0
    # overload protection at admission: queued requests beyond this are
    # rejected typed (RequestRejected reason="overloaded") instead of
    # growing an unbounded queue; 0 = unbounded (the v1 behavior —
    # fleet routers front their replicas with a bounded queue instead)
    max_queue: int = 0
    # deadline admission estimator: with a nonzero floor rate (tokens/s
    # the operator guarantees), a submit whose deadline cannot be met
    # even by an IDLE engine (max_new_tokens / rate > deadline_s) is
    # rejected typed (reason="deadline_unmeetable") at the door rather
    # than admitted, computed, and expired; 0 disables the estimate
    min_decode_tokens_per_s: float = 0.0
    eos_token: Optional[int] = None
    # sampling (greedy default — the parity mode)
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 10
    # speculative serving: path to a save_speculator checkpoint
    # (models/speculator.py). When set, llama decode runs a batched
    # draft-then-verify step — the speculator proposes k tokens per
    # row, one jitted verify forward scores them, and the longest
    # greedy-matching prefix commits; the greedy accept rule keeps the
    # emitted stream token-identical to non-speculative greedy. "" off
    speculator_path: str = ""
    # cap on draft tokens per verify step (the checkpoint's n_predict
    # chain is sliced to this many heads); 0 = use n_predict
    spec_draft_tokens: int = 0
    # mixtral decode FFN: "routed" gathers each token's top-k experts
    # (O(top_k/E) of the dense FLOPs, within one gather-einsum ulp of
    # dense); "dense" replays the training-path full mixture, which is
    # the strict bit-parity mode. Other families ignore this.
    moe_impl: str = "routed"
    # serving parallel layout: "" = single-chip (the v1 path, every
    # parity anchor); "tp=2" / "tp=2,fsdp=2" spans one replica over a
    # mesh — params per the family rulebook, KV pools sharded over
    # kv-heads (parallel/sharding.py::serve_kv_pool_specs)
    serve_layout: str = ""
    # disaggregation role: "unified" serves end-to-end; "prefill" packs
    # a PageHandoff after the first token instead of decoding;
    # "decode" additionally accepts submit_handoff() resumes (it can
    # still prefill — eviction recompute needs that)
    role: str = "unified"
    # a prefill engine rejects (too_large) any request whose packed
    # handoff could exceed this many bytes; 0 = unbounded
    handoff_max_bytes: int = 0


class ServingEngine:
    def __init__(
        self,
        params,
        model_cfg,
        serve_cfg: Optional[ServeConfig] = None,
        registry: Optional[MetricRegistry] = None,
        clock: Callable[[], float] = time.monotonic,
        seed: int = 0,
    ):
        scfg = serve_cfg or ServeConfig()
        self.params = params
        self.model_cfg = model_cfg
        self.serve_cfg = scfg
        self.registry = registry or MetricRegistry()
        self.clock = clock
        self.compute_dtype = _DTYPES[scfg.compute_dtype]

        from fms_fsdp_tpu.serve.disagg import ROLES

        if scfg.role not in ROLES:
            raise ValueError(
                f"unknown serving role {scfg.role!r}: expected one of "
                f"{ROLES} (docs/serving.md \"Sharded replicas & "
                f"disaggregation\")"
            )

        # family-specific device work (cache/slab, prefill + decode
        # jits, page accounting) — resolved from the model config, with
        # the params tree validated against it; what happens inside the
        # adapter it counts into this registry itself
        self.adapter = resolve_adapter(
            params, model_cfg, scfg, self.compute_dtype, self.registry
        )
        self.family = self.adapter.family
        # one fact per engine, not a rate: set where the program is built
        self.registry.gauge("serve.moe_expert_reads_per_layer").set(
            self.adapter.moe_expert_reads_per_layer
        )
        self.registry.gauge("serve.ssm_layers").set(self.adapter.ssm_layers)
        self.registry.gauge("serve.decode_attn_grid_blocks").set(
            self.adapter.attn_grid_blocks
        )
        self.registry.gauge("serve.ssm_state_bytes_per_stream").set(
            self.adapter.state_bytes_per_stream
        )
        if scfg.role != "unified" and not self.adapter.supports_handoff:
            raise ValueError(
                f"role={scfg.role!r} needs page handoff, which the "
                f"{self.family} family does not support (its decode "
                f"state is not pure KV pages) — run {self.family} "
                f"replicas unified"
            )
        if scfg.serve_layout and not self.adapter.supports_layout:
            raise ValueError(
                f"serve_layout={scfg.serve_layout!r} is not supported "
                f"for the {self.family} family yet — run it single-chip"
            )
        if (
            scfg.prefill_chunk_tokens
            and not self.adapter.supports_chunked_prefill
        ):
            raise ValueError(
                f"prefill_chunk_tokens={scfg.prefill_chunk_tokens} is "
                f"not supported for the {self.family} family yet — "
                f"unset it (whole-prompt prefill)"
            )
        # back-compat surface (tests, benches, fleet introspection):
        # llama/mixtral expose their PagedKVCache here; pure-mamba has
        # no pages, so cache is None and page_size 0
        self.cache = self.adapter.cache
        self.page_size = self.adapter.page_size
        self.max_pages = self.adapter.max_pages
        self.attn_impl = self.adapter.attn_impl
        self.block_kv = self.adapter.block_kv
        self.tune_how = self.adapter.tune_how

        self.scheduler = ContinuousBatchingScheduler(
            scfg.max_batch,
            max_prefill_per_step=scfg.max_prefill_per_step,
            clock=clock,
        )

        self._slots: List[Optional[Request]] = [None] * scfg.max_batch
        self._admit_order: List[Request] = []
        self._tokens = np.zeros((scfg.max_batch,), np.int32)
        self._lens = np.zeros((scfg.max_batch,), np.int32)
        # the plain decode loop's token row lives on the device, in the
        # adapter (a step's output is the next step's input); ``_tokens``
        # holds what the host knows, and ``_fresh`` marks the slots whose
        # host value the next dispatch must write into the row (a new
        # stream's first token). The speculative path reads ``_tokens``
        # alone.
        self._fresh = np.zeros((scfg.max_batch,), bool)
        self._inflight: Optional[_InFlight] = None
        # admissions of the open step() whose first token is on the
        # device, unread (``_land``); empty outside a step()
        self._pending: List[_Admission] = []
        # the host needs a first token before the next decode step's
        # dispatch: a prefill replica packs it into the handoff, the
        # speculative step reads its tokens on the host, and on a mesh
        # the sampler's token lies on one device, the step's row on all
        self._lands_at_once = (
            scfg.role == "prefill"
            or self.adapter.speculative
            or self.adapter.mesh is not None
        )
        self._key = jax.random.PRNGKey(seed)
        # one record per step() (obs/spans.py): the spans' host times
        # and what the engine writes beside them; ``_rec`` is the open
        # one, None outside a step
        self.step_log = StepLog()
        self._rec = None
        self._device = jax.local_devices()[0]  # whose memory a record samples
        self._finished_buf: List[Request] = []
        # handoff imports that failed typed AFTER admission (the
        # adapter freed its allocations): the replica loop drains these
        # via take_failed() and rejects them back to the router
        self._failed_buf: List[Request] = []
        # (B, V) of the last decode step whose tokens were committed: the
        # collected step's, not the one in flight (debug)
        self.last_logits = None
        self.iterations = 0  # engine step() count (health + fault ctx)
        self._draining = False
        # disaggregation accounting (obs schema v13 serving map)
        self._handoff_bytes = 0  # wire bytes packed out + imported in
        self._handoff_wall = 0.0  # seconds spent packing/scattering
        # chunked prefill + speculative accounting (obs schema v14)
        self._chunking: Dict[int, tuple] = {}  # rid -> (req, slot)
        self._prefill_chunks = 0
        self._spec_draft_total = 0  # draft tokens offered to verify
        self._spec_accept_total = 0  # draft tokens accepted

    # -- construction ------------------------------------------------------

    @classmethod
    def from_checkpoint(
        cls, path: str, model_cfg, serve_cfg: Optional[ServeConfig] = None,
        **kw,
    ) -> "ServingEngine":
        """Restore params from a training checkpoint (params pickle,
        step_N_ckp dir, or a checkpoints/ root — the Checkpointer's
        committed layout) and build the engine around them. The params
        initializer resolves from the model config's family
        (serve/families/) — llama, mamba and mixtral checkpoints all
        restore through this one path."""
        from fms_fsdp_tpu.serve.families import init_params_for
        from fms_fsdp_tpu.utils.checkpointing import load_params_only

        params = load_params_only(path, init_params_for(model_cfg))
        return cls(params, model_cfg, serve_cfg, **kw)

    # -- request side ------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        deadline_s: Optional[float] = None,
    ) -> Request:
        """Queue one request. ``deadline_s`` is relative to now; a
        request still queued past it is expired unserved.

        Raises :class:`RequestRejected` (a ValueError subclass) with a
        machine-readable ``reason`` — ``too_large`` / ``overloaded`` /
        ``deadline_unmeetable`` — and bumps the per-reason
        ``serve.requests_rejected.<reason>`` counter. Typed raises, not
        asserts: these validate USER input and must survive python -O —
        an accepted never-fits request would head-of-line-block the
        FIFO queue forever."""
        with span("submit", prompt_tokens=len(prompt)):
            req = self._submit(prompt, max_new_tokens, deadline_s)
            done("submit", rid=req.rid, rejected=0)
        return req

    def _submit(self, prompt, max_new_tokens, deadline_s) -> Request:
        deadline = None if deadline_s is None else self.clock() + deadline_s
        # a speculative verify step writes up to spec_draft_tokens
        # positions past the committed length before the accept rule
        # rolls back — those in-flight draft slots must exist, so the
        # cache budget tightens by draft-1 tokens
        slack = max(0, self.adapter.spec_draft_tokens - 1)
        if len(prompt) + max_new_tokens + slack > self.serve_cfg.max_seq_len:
            extra = f" + {slack} draft headroom" if slack else ""
            self._reject(
                REJECT_TOO_LARGE,
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}){extra} exceeds max_seq_len "
                f"({self.serve_cfg.max_seq_len})",
            )
        err = self.adapter.admission_error(len(prompt), max_new_tokens)
        if err is not None:
            self._reject(REJECT_TOO_LARGE, err)
        if (
            self.serve_cfg.role == "prefill"
            and self.serve_cfg.handoff_max_bytes
            and self.adapter.cache is not None
        ):
            # a prefill engine's output is the packed page set: bound it
            # at the door so one pathological prompt cannot jam the
            # handoff stream (the estimate is pure page bytes; the
            # header adds O(prompt) ints on top)
            cache = self.adapter.cache
            need = cache.pages_needed(self.adapter._padded(len(prompt)))
            page_bytes = sum(
                int(pool.nbytes) // cache.num_pages
                for pool in cache.pools.values()
            )
            est = need * page_bytes
            if est > self.serve_cfg.handoff_max_bytes:
                self._reject(
                    REJECT_TOO_LARGE,
                    f"packed handoff would carry ~{est} bytes of KV "
                    f"pages ({need} pages), over handoff_max_bytes="
                    f"{self.serve_cfg.handoff_max_bytes} — shrink the "
                    f"prompt or raise the cap",
                )
        if (
            self.serve_cfg.max_queue
            and self.scheduler.queue_depth() >= self.serve_cfg.max_queue
        ):
            self._reject(
                REJECT_OVERLOADED,
                f"queue holds {self.scheduler.queue_depth()} requests "
                f"(max_queue={self.serve_cfg.max_queue}): shedding at "
                f"admission — back off and retry",
            )
        rate = self.serve_cfg.min_decode_tokens_per_s
        if deadline_s is not None and rate > 0:
            floor_s = max_new_tokens / rate
            if deadline_s < floor_s:
                self._reject(
                    REJECT_DEADLINE_UNMEETABLE,
                    f"deadline {deadline_s:.3f}s < {floor_s:.3f}s floor "
                    f"({max_new_tokens} tokens at the configured "
                    f"min_decode_tokens_per_s={rate:g}) — unmeetable "
                    f"even by an idle engine",
                )
        if self._draining:
            self._reject(
                REJECT_OVERLOADED,
                "engine is draining: not admitting new requests",
            )
        req = self.scheduler.submit(
            Request(list(prompt), max_new_tokens, deadline)
        )
        self.registry.counter("serve.requests_submitted").add()
        return req

    def _reject(self, reason: str, msg: str):
        self.registry.counter(f"serve.requests_rejected.{reason}").add()
        done("submit", rejected=1, reason=reason)
        raise RequestRejected(reason, msg)

    def submit_handoff(
        self,
        data: bytes,
        max_new_tokens: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> Request:
        """Admit a request by resuming a packed PageHandoff (the wire
        bytes a prefill-role engine produced) instead of prefilling:
        the header restores the stream's position (prompt, generated,
        seq_len) and its KV pages scatter bit-exact into this pool at
        admission. ``max_new_tokens``/``deadline_s`` default to the
        header's values (the deadline the ROUTER tracks — it re-derives
        the remaining budget when it forwards a handoff).

        Raises :class:`~fms_fsdp_tpu.serve.disagg.HandoffError` (a
        ValueError) on malformed or geometry-mismatched bytes and
        :class:`RequestRejected` on admission failure, same contract as
        :meth:`submit`."""
        with span("submit", handoff_bytes=len(data)):
            req = self._submit_handoff(data, max_new_tokens, deadline_s)
            done("submit", rid=req.rid, rejected=0)
        return req

    def _submit_handoff(self, data, max_new_tokens, deadline_s) -> Request:
        from fms_fsdp_tpu.serve.disagg import unpack_handoff

        if not self.adapter.supports_handoff:
            raise ValueError(
                f"the {self.family} family does not support page "
                f"handoff — route its requests to unified replicas"
            )
        if self.adapter.speculative:
            raise ValueError(
                "a speculative engine cannot resume handoffs: the "
                "draft state (the last base hidden state) is not part "
                "of the page handoff — route resumes to "
                "non-speculative replicas"
            )
        header, arrays = unpack_handoff(data)
        self.adapter.check_handoff_header(header)
        prompt = [int(t) for t in header["prompt"]]
        generated = [int(t) for t in header["generated"]]
        mnt = int(
            header["max_new_tokens"]
            if max_new_tokens is None
            else max_new_tokens
        )
        deadline = None if deadline_s is None else self.clock() + deadline_s
        if len(prompt) + mnt > self.serve_cfg.max_seq_len:
            self._reject(
                REJECT_TOO_LARGE,
                f"handoff prompt ({len(prompt)}) + max_new_tokens "
                f"({mnt}) exceeds max_seq_len "
                f"({self.serve_cfg.max_seq_len})",
            )
        err = self.adapter.admission_error(len(prompt), mnt)
        if err is not None:
            self._reject(REJECT_TOO_LARGE, err)
        if (
            self.serve_cfg.max_queue
            and self.scheduler.queue_depth() >= self.serve_cfg.max_queue
        ):
            self._reject(
                REJECT_OVERLOADED,
                f"queue holds {self.scheduler.queue_depth()} requests "
                f"(max_queue={self.serve_cfg.max_queue}): shedding at "
                f"admission — back off and retry",
            )
        if self._draining:
            self._reject(
                REJECT_OVERLOADED,
                "engine is draining: not admitting new requests",
            )
        req = Request(prompt, mnt, deadline)
        req.generated = generated
        req.handoff_in = (header, arrays, len(data))
        self.scheduler.submit(req)
        # the first token was already served (by the prefill engine):
        # this stream must never expire as "unserved queued work", and
        # its TTFT was recorded where it was paid
        req.first_token_time = req.submit_time
        self.registry.counter("serve.requests_submitted").add()
        self.registry.counter("serve.handoffs_accepted").add()
        return req

    # -- prefill -----------------------------------------------------------

    def _prefill_request(self, req: Request, slot: int) -> None:
        """One admission's device work, under the ``prefill`` span: a
        handoff import (no prefill program: ``padded_tokens`` 0), the
        staging of a chunked prefill, or the whole-prompt prefill."""
        handoff = req.handoff_in is not None
        prompt = req.prompt if handoff else req.resume_prompt()
        p = len(prompt)
        padded = 0 if handoff else self.adapter._padded(p)
        chunk = self.serve_cfg.prefill_chunk_tokens
        with span(
            "prefill",
            step=self.iterations,
            rid=req.rid,
            prompt_tokens=p,
            padded_tokens=padded,
        ):
            if handoff:
                self._import_handoff(req, slot)
            elif chunk and p > chunk and self.adapter.supports_chunked_prefill:
                # chunked prefill: allocate + stage now, advance one chunk
                # per step() interleaved with decode — the slot is held but
                # joins the decode batch only once the whole prompt is in
                self.adapter.prefill_start(req.rid, slot, prompt)
                self._slots[slot] = req
                self._chunking[req.rid] = (req, slot)
            else:
                # the adapter allocates the stream's decode state (pages
                # and/or slab slice), dispatches the family prefill and
                # hands back the (V,) logits row of the last real prompt
                # position, unread; sampling stays here so every family
                # shares one rng stream and one sampler
                row = self.adapter.prefill(req.rid, slot, prompt)
                self._sample_first(req, slot, row, p)
        self.registry.counter("serve.prefill_padded_tokens").add(padded)

    def _sample_first(self, req: Request, slot: int, row, p: int) -> None:
        """Shared tail of whole-prompt and chunked prefill, the dispatch
        half: sample the first token from the last real prompt
        position's logits row and promote the stream into the decode
        batch. Nothing is read: the token stays on the device for the
        next decode step to take, and ``_land`` reads it behind that
        step's dispatch (at once where the host needs it first)."""
        self._key, sub = jax.random.split(self._key)
        tok = sample_token(
            row[None],
            sub,
            self.serve_cfg.temperature,
            self.serve_cfg.top_k,
            self.serve_cfg.do_sample,
        )[0]
        self._slots[slot] = req
        self._admit_order.append(req)
        self._lens[slot] = p
        self._pending.append(_Admission(req, slot, tok, p))
        if self._lands_at_once:
            self._land()

    def _land(self) -> bool:
        """The landing half of the admissions dispatched and not yet
        read, oldest first: read the first token (the wait for the
        prefill program), record TTFT, read the adapter's counts
        (``prefill.done``), hand the token to its stream. False where
        none is pending. ``step()`` lands behind its decode step's
        dispatch; whatever needs the token or the stream's state earlier
        comes through here first (``_collect``). A stream that ends at
        its first token behind a step dispatched over it rode that step:
        the commit drops the step's token for it, as for an
        ``eos_token`` found at a commit."""
        pending, self._pending = self._pending, []
        it = self.iterations
        reg = self.registry
        for adm in pending:
            req, slot = adm.req, adm.slot
            with span("prefill.land", step=it, rid=req.rid):
                with span(
                    "prefill.sample",
                    step=it,
                    rid=req.rid,
                    overlapped=int(adm.rode),
                ):
                    tok = int(adm.tok)
                now = self.clock()
                # the program has ended: its counts wait for nothing
                self.adapter.count_prefill(req.rid)
                if req.first_token_time is None:
                    req.first_token_time = now
                    reg.hist("serve.ttft_s").record(now - req.submit_time)
                req.generated.append(tok)
                reg.counter("serve.prefill_tokens").add(adm.prompt_tokens)
                if adm.rode:
                    reg.counter("serve.admissions_overlapped").add()
                if self._finish_if_done(req, slot, now=now):
                    continue
                if not adm.rode:
                    # the next dispatch writes it into the step's row
                    self._tokens[slot] = tok
                    self._fresh[slot] = True
                if self.serve_cfg.role == "prefill":
                    # disaggregation: a prefill engine's job ends at the
                    # first token — pack the stream's pages + sampling
                    # state into wire bytes and retire the request; the
                    # replica loop emits it as a "handoff" message
                    # instead of "done"
                    self._export_handoff(req, slot)
        return bool(pending)

    def _import_handoff(self, req: Request, slot: int) -> None:
        """The decode half of a handoff admission: scatter the shipped
        pages into this pool and restore the stream's decode position —
        no prefill compute at all, which is the disaggregation win (a
        long-prompt prefill never stalls this engine's decode step)."""
        from fms_fsdp_tpu.serve.disagg import HandoffError

        header, arrays, nbytes = req.handoff_in
        done("prefill", rid=req.rid, computed_tokens=0)  # no program ran
        t0 = self.clock()
        try:
            ok = self.adapter.import_handoff(req.rid, slot, header, arrays)
        except HandoffError as e:
            # the frame passed the submit-time header check but failed
            # mid-import (corrupt leaves, geometry drift). The adapter
            # freed every page and slab slice it allocated — pool
            # accounting is back to its pre-import value — so fail the
            # request typed instead of crashing the replica; the
            # router clears the journaled frame and requeues it for
            # re-prefill
            req.handoff_in = None
            req.state = "failed"
            req.fail_reason = f"handoff_error: {e}"
            self._failed_buf.append(req)
            self.registry.counter("serve.handoffs_failed").add()
            return
        assert ok, "admission checked capacity; scatter cannot fail here"
        self._handoff_wall += self.clock() - t0
        self._handoff_bytes += nbytes
        self.registry.counter("serve.handoffs_imported").add()
        self.registry.counter("serve.handoff_bytes").add(nbytes)
        req.handoff_in = None  # eviction after this point recomputes
        self._slots[slot] = req
        self._admit_order.append(req)
        self._tokens[slot] = req.generated[-1]
        self._fresh[slot] = True
        self._lens[slot] = int(header["seq_len"])
        self._finish_if_done(req, slot)

    def _export_handoff(self, req: Request, slot: int) -> None:
        """The prefill half: gather the stream's pages, pack them with
        the sampling state (prompt, generated, position) into
        deterministic wire bytes, then retire the stream — its pages
        free only AFTER the gather read them."""
        from fms_fsdp_tpu.serve.disagg import pack_handoff

        t0 = self.clock()
        header, arrays = self.adapter.export_handoff(req.rid, slot)
        header.update(
            prompt=[int(t) for t in req.prompt],
            generated=[int(t) for t in req.generated],
            seq_len=int(self._lens[slot]),
            max_new_tokens=int(req.max_new_tokens),
        )
        req.handoff_out = pack_handoff(header, arrays)
        self._handoff_wall += self.clock() - t0
        self._handoff_bytes += len(req.handoff_out)
        self.registry.counter("serve.handoffs_exported").add()
        self.registry.counter("serve.handoff_bytes").add(
            len(req.handoff_out)
        )
        self.scheduler.mark_finished(req)
        self._release_slot(req, slot)
        self._finished_buf.append(req)

    # -- lifecycle helpers -------------------------------------------------

    def _finish_if_done(self, req: Request, slot: int, now=None) -> bool:
        done = len(req.generated) >= req.max_new_tokens or (
            self.serve_cfg.eos_token is not None
            and req.generated
            and req.generated[-1] == self.serve_cfg.eos_token
        )
        if not done:
            return False
        self.scheduler.mark_finished(req, now=now)
        if self._slots[slot] is req:  # else released at its last dispatch
            self._release_slot(req, slot)
        self._finished_buf.append(req)
        self.registry.counter("serve.requests_completed").add()
        self.registry.hist("serve.request_latency_s").record(req.latency)
        return True

    def _release_slot(self, req: Request, slot: int) -> None:
        self._chunking.pop(req.rid, None)
        self.adapter.release(req.rid, slot)
        self._slots[slot] = None
        if req in self._admit_order:
            self._admit_order.remove(req)
        self._tokens[slot] = 0
        self._fresh[slot] = False
        self._lens[slot] = 0

    def _evict(self, victim: Request) -> None:
        slot = self._slots.index(victim)
        self._release_slot(victim, slot)
        self.scheduler.mark_evicted(victim)
        self.registry.counter("serve.requests_evicted").add()

    # -- the engine iteration ----------------------------------------------

    def step(self) -> List[Request]:
        """One continuous-batching iteration: expire, admit (+prefill,
        dispatched and unread), dispatch one ragged decode step, collect
        and commit the step dispatched by the previous iteration, then
        land the admissions: read their first tokens behind the step
        just dispatched. Returns the requests that finished during this
        iteration. The first ``step()`` of an idle engine therefore
        returns with its admissions' first tokens and before its decode
        step's tokens are visible; the next one brings them.

        Every phase runs under a host span (obs/spans.py: ``serve/step``
        and its children, each carrying ``step=<iterations>``), which
        also times itself into the step's record (``step_log``); a
        profiler session, where one runs, is handed the record at the
        end, behind the decode step in flight."""
        self.iterations += 1
        it = self.iterations
        reg = self.registry
        reg.counter("serve.steps").add()
        now = self.clock()
        queued = self.scheduler.queue_depth()
        busy = self._busy()
        rec = self._rec = self.step_log.open(it, now, queued, busy)
        try:
            with span("step", step=it, queued=queued, busy=busy):
                with span("expire", step=it):
                    expired = self._expire(now)
                    done("expire", step=it, expired=expired)
                with span("admit", step=it):
                    admitted = self._admit()
                    done("admit", step=it, admitted=admitted)
                chunks = self._advance_chunks(it)
                if admitted or chunks:
                    reg.counter("serve.steps_with_prefill").add()
                    self._sample_memory(rec)
                with span("grow", step=it):
                    evicted = self._grow()
                    done("grow", step=it, evicted=evicted)
                self._decode(it)
                self._land()
                with span("publish", step=it):
                    pages = rec[_PAGES_IN_USE] = self.adapter.pages_in_use
                    reg.gauge("serve.kv_pages_in_use").set(pages)
                    peak = reg.gauge("serve.kv_pages_peak")
                    if pages > peak.value:
                        peak.set(pages)
        finally:
            self._rec = None
            self.step_log.close(rec)
        if self.step_log.is_slow(rec):
            self._log_slow_step()
        if TraceAnnotation.is_enabled():
            self._replay()
        else:
            self.step_log.session_over()
        out, self._finished_buf = self._finished_buf, []
        return out

    def _sample_memory(self, rec) -> None:
        """The device's bytes in use and largest free block into
        ``rec``, as far as its ``memory_stats()`` gives them (a CPU
        gives none)."""
        stats = self._device.memory_stats() or {}
        for slot, key in zip(_HBM, _HBM_KEYS):
            rec[slot] = stats.get(key, -1)

    def _log_slow_step(self) -> None:
        """A step that stood still (``StepLog.is_slow``) writes itself
        out: one WARNING line, a JSON object with its record, the three
        before it and the device's memory now, and a count."""
        self.registry.counter("serve.steps_slow").add()
        log = self.step_log
        stats = self._device.memory_stats() or {}
        *before, slow = (log[i] for i in range(-min(4, len(log)), 0))
        logger.warning(json.dumps({
            "slow_step": slow,
            "before": before,
            "memory": {
                k: stats[k] for k in _HBM_KEYS + (
                    "peak_bytes_in_use", "bytes_limit") if k in stats
            } or "the device gives no memory_stats",
        }))

    def _replay(self) -> None:
        """Hand the running profiler session this step's record and the
        earlier ones it has not been given (``StepLog.unwritten``: as
        many as the step's wait for the device leaves room for), each a
        zero-length ``serve/step.log`` span whose counts are the fields,
        with the two facts of the engine a reader sizes them by."""
        slots, total = self.serve_cfg.max_batch, self.adapter.pages_total
        for fields in self.step_log.unwritten():
            with span("step.log", slots=slots, pages_total=total, **fields):
                pass

    def _expire(self, now: float) -> int:
        """Deadline expiry at the step boundary -> requests expired."""
        queued = self.scheduler.expire_queued(now)
        for r in queued:
            self.registry.counter("serve.requests_expired").add()
        # in flight too: a running request past its deadline frees its
        # slot and pages NOW — decoding tokens nobody can use any more
        # starves streams that can still meet theirs
        running = [r for r in self._slots if r is not None]
        if self.scheduler.past_deadline(running, now) and self._collect():
            # an expired stream keeps what the step in flight made for
            # it, and may turn out to have ended there
            running = [r for r in self._slots if r is not None]
        inflight = self.scheduler.expire_inflight(running, now)
        for r in inflight:
            self._release_slot(r, self._slots.index(r))
            self.registry.counter("serve.requests_expired_inflight").add()
        return len(queued) + len(inflight)

    def _admit(self) -> int:
        """The admission loop -> requests admitted (each prefilled, or
        staged for chunked prefill, or imported from a handoff)."""

        def can_fit(req: Request) -> bool:
            if req.handoff_in is not None:
                # a handoff admission allocates the shipped page set,
                # not a padded prefill; seq_len is the position the
                # pages cover
                return self.adapter.can_admit(
                    req.rid, int(req.handoff_in[0]["seq_len"])
                )
            return self.adapter.can_admit(
                req.rid, len(req.resume_prompt())
            )

        # admit ONE at a time, prefilling (and so allocating) before the
        # next can_fit evaluation — a single batched admit would check
        # every candidate against the pre-prefill pool and over-admit
        # when two requests each fit alone but not together. Slots are
        # recounted live too: a request that ends at its first token
        # gives its slot back when that token lands, so a step's further
        # admissions land the one before them first (the last one's
        # token is read behind the decode step).
        admitted = 0
        stopped = "draining" if self._draining else "budget"
        for _ in range(0 if self._draining else
                       self.serve_cfg.max_prefill_per_step):
            self._land()
            if self._slots.count(None) <= 0:
                stopped = "no_slot"
                break
            got = self.scheduler.admit(1, can_fit)
            if not got:
                # the head of the queue did not fit, or there is none
                stopped = "no_pages" if self.scheduler.queue else "queue_empty"
                break
            req = got[0]
            if req.evictions == 0:
                self.registry.hist("serve.queue_wait_s").record(
                    req.admit_time - req.submit_time
                )
            admitted += 1
            self._prefill_request(req, self._slots.index(None))
        self.registry.counter(f"serve.admit_stopped.{stopped}").add()
        self._rec[_ADMIT_STOPPED] = ADMIT_STOPPED.index(stopped)
        self._rec[_BUSY_AFTER_ADMIT] = self._busy()
        return admitted

    def _advance_chunks(self, it: int) -> int:
        """Advance each staged chunked prefill by ONE chunk, interleaved
        with the decode that follows: the chunk advance does not consume
        the admit budget, so short requests keep admitting (and every
        running stream keeps decoding) while a long prompt streams in.
        -> chunks advanced."""
        chunks = 0
        for rid in list(self._chunking):
            req, slot = self._chunking[rid]
            with span("prefill_chunk", step=it, rid=rid):
                row = self.adapter.prefill_chunk(rid)
                chunks += 1
                self._prefill_chunks += 1
                self.registry.counter("serve.prefill_chunks").add()
                if row is not None:
                    del self._chunking[rid]
                    self._sample_first(
                        req, slot, row, len(req.resume_prompt())
                    )
        return chunks

    def _grow(self) -> int:
        """Token-granular state growth; evict (LIFO) when the pool is
        dry. Constant-state families (mamba slab) always grow free — the
        loop never spins for them. Speculative streams reserve draft
        headroom: the verify step writes spec_draft_tokens positions
        past the committed length before rollback. -> requests evicted.
        """
        evicted = 0
        draft = self.adapter.spec_draft_tokens
        for slot in range(len(self._slots)):
            req = self._slots[slot]
            if req is None or req.rid in self._chunking:
                continue
            need = int(self._lens[slot]) + 1 + draft
            while not self.adapter.grow(req.rid, need):
                # a victim's resume prompt must hold its last token (its
                # first, where it is the stream just admitted), and the
                # commit may free pages (or end ``req``) by itself
                if self._collect():
                    if self._slots[slot] is not req:
                        break
                    continue
                victim = self.scheduler.evict_victim(self._admit_order)
                assert victim is not None, "no victim but pool exhausted"
                self._evict(victim)
                evicted += 1
                if victim is req:
                    break
        return evicted

    def _decode(self, it: int) -> None:
        """The decode phase of an iteration: dispatch a step over the
        live slots, then collect and commit the one dispatched before it
        (speculative: one draft-and-verify step, dispatched and
        committed here)."""
        if self.adapter.speculative:
            return self._decode_spec(it)
        active = self._active()
        if not active and self._inflight is None:
            return
        with span(
            "decode",
            step=it,
            live=len(active),
            kv_tokens=int(sum(self._lens[slot] for slot, _ in active)),
            attn_blocks=self._attn_blocks(active),
        ):
            prev, self._inflight = self._inflight, None
            if active:
                self._inflight = self._dispatch(active, prev is not None)
            if prev is not None:
                self._commit(prev)

    def _attn_blocks(self, active) -> int:
        """The blocks the ragged paged kernel walks in a decode step over
        ``active`` (the adapter's count, 0 without that kernel), counted
        into ``serve.decode_attn_blocks`` too."""
        blocks = self.adapter.attn_blocks(
            [self._lens[slot] for slot, _ in active]
        )
        self.registry.counter("serve.decode_attn_blocks").add(blocks)
        return blocks

    def _busy(self) -> int:
        """Slots that hold a stream."""
        return sum(r is not None for r in self._slots)

    def _active(self) -> List[Tuple[int, Request]]:
        """(slot, request) of the streams a decode step serves."""
        return [
            (slot, r)
            for slot, r in enumerate(self._slots)
            if r is not None and r.rid not in self._chunking
        ]

    def _slot_rids(self, active) -> List[Optional[int]]:
        """Per slot the rid of its stream in ``active``, else None."""
        rids: List[Optional[int]] = [None] * len(self._slots)
        for slot, req in active:
            rids[slot] = req.rid
        return rids

    def _dispatch(self, active, overlapped: bool) -> _InFlight:
        """Dispatch one decode step over ``active`` without reading its
        result, and advance the host's lengths past it: the page growth
        and the table of the next step need them. A stream whose token
        in flight is its ``max_new_tokens``-th gives its slot, slab row
        and pages back now (on the device the release is ordered behind
        the step by the slab and the pools, which every program takes
        donated), so the next admission comes when it always came."""
        reg = self.registry
        self._key, sub = jax.random.split(self._key)
        # first tokens still on the device go into the step's row there
        first = []
        for adm in self._pending:
            adm.rode = True
            first.append((adm.slot, adm.tok))
        # copies: the host's arrays change before the device has run
        toks, logits = self.adapter.decode_dispatch(
            self._slot_rids(active),
            self._lens.copy(),
            self._tokens.copy(),
            sub,
            self._fresh.copy(),
            in_flight=int(overlapped),
            first=first,
        )
        self._fresh[:] = False
        reg.counter("serve.decode_live_slots").add(len(active))
        if overlapped:
            reg.counter("serve.decode_steps_overlapped").add()
        for slot, req in active:
            # the cache holds the prompt and all of the stream's tokens
            # but the newest, the one in flight counted
            self._lens[slot] += 1
            tokens = int(self._lens[slot]) - len(req.prompt) + 1
            if tokens >= req.max_new_tokens:
                self._release_slot(req, slot)
        return _InFlight(toks, logits, active)

    def _collect(self) -> bool:
        """Wait for the decode step in flight and commit its tokens,
        then land the admissions whose first token is unread; False
        where there was neither. The door for everything that takes a
        stream away, or looks at its tokens, outside the commit and the
        landing at the end of ``step()``."""
        flight, self._inflight = self._inflight, None
        if flight is not None:
            self._commit(flight)
        return self._land() or flight is not None

    def _commit(self, flight: _InFlight) -> None:
        """Read a dispatched step's tokens (the wait for the device) and
        hand each to its stream. A stream that ended by ``eos_token`` at
        the commit before rode this step too: its token is dropped."""
        it = self.iterations
        reg = self.registry
        finished = len(self._finished_buf)
        toks = self.adapter.decode_collect(flight.toks)
        self.last_logits = flight.logits
        with span("decode.commit", step=it):
            live = [sr for sr in flight.streams if sr[1].state != FINISHED]
            reg.counter("serve.decode_tokens").add(len(live))
            if len(live) < len(flight.streams):
                reg.counter("serve.decode_tokens_discarded").add(
                    len(flight.streams) - len(live)
                )
            for slot, req in live:
                req.generated.append(int(toks[slot]))
                self._finish_if_done(req, slot)
            done(
                "decode.commit",
                step=it,
                finished=len(self._finished_buf) - finished,
                tokens=len(live),
            )

    def _decode_spec(self, it: int) -> None:
        """One draft-and-verify step over the live slots and the commit
        of its tokens, nothing left in flight."""
        active = self._active()
        if not active:
            return
        reg = self.registry
        finished = len(self._finished_buf)
        with span(
            "decode",
            step=it,
            live=len(active),
            kv_tokens=int(sum(self._lens[slot] for slot, _ in active)),
            attn_blocks=self._attn_blocks(active),
        ):
            emit, counts, logits = self.adapter.decode_spec(
                self._slot_rids(active), self._lens, self._tokens
            )
            self.last_logits = logits
            with span("decode.commit", step=it):
                draft = self.adapter.spec_draft_tokens
                tokens = 0
                for slot, req in active:
                    self._spec_draft_total += draft
                    self._spec_accept_total += int(counts[slot]) - 1
                    # commit the accepted prefix token-by-token: eos
                    # and max_new checks run per token, so truncation
                    # matches the non-speculative stream exactly
                    for j in range(int(counts[slot])):
                        self._lens[slot] += 1
                        tok = int(emit[slot, j])
                        req.generated.append(tok)
                        self._tokens[slot] = tok
                        tokens += 1
                        if self._finish_if_done(req, slot):
                            break
                reg.counter("serve.decode_tokens").add(tokens)
                done(
                    "decode.commit",
                    step=it,
                    finished=len(self._finished_buf) - finished,
                    tokens=tokens,
                )
        reg.counter("serve.decode_live_slots").add(len(active))

    def run(self, max_steps: int = 100000) -> None:
        """Drive step() until queue and slots drain (or max_steps)."""
        for _ in range(max_steps):
            if not self.has_work():
                return
            self.step()

    def has_work(self) -> bool:
        """Queued or running requests, a dispatched decode step whose
        tokens are not committed yet, or finished requests that no
        ``step()`` has returned yet (they ended at a collect outside one:
        ``drain``'s, ``pack_stream``'s): keep stepping while true."""
        return (
            bool(self.scheduler.queue)
            or any(r is not None for r in self._slots)
            or self._inflight is not None
            or bool(self._finished_buf)
        )

    # -- fleet hooks (docs/serving.md "Fleet resilience") ------------------

    def drain(self) -> None:
        """Stop admitting: queued and new requests are refused, running
        streams finish. The fleet router drains a replica before a
        planned stop so in-flight work completes instead of requeueing;
        ``drained`` flips once the slots empty. The decode step in
        flight is collected first, so the running streams hold their
        last tokens; a stream that ended there is the next ``step()``'s
        to return, and ``has_work()`` stays true until it has."""
        self._collect()
        self._draining = True

    def take_failed(self) -> List[Request]:
        """Requests that failed typed after admission (a handoff
        import rejected mid-apply) — the replica loop emits these as
        ``handoff_error`` rejects so the router requeues them for
        re-prefill instead of counting them served."""
        out, self._failed_buf = self._failed_buf, []
        return out

    def live_requests(self) -> List[Request]:
        """The running (slot-holding) streams, admission order — what
        drain-and-migrate must pack before the process exits."""
        return [r for r in self._admit_order if r in self._slots]

    def pack_stream(self, req: Request) -> Optional[bytes]:
        """Pack a LIVE decode stream's state into handoff wire bytes
        WITHOUT retiring it — the drain-and-migrate read: a SIGTERM'd
        replica packs each running stream and ships it to a sibling so
        a planned eviction costs zero recompute (the stream resumes
        mid-decode there via ``submit_handoff``). Returns None for
        streams that cannot travel: mid-chunked-prefill (the staged
        prompt is not in the frame) or a speculative engine (the draft
        state is not in the frame) — those fall back to the router's
        requeue/recompute path."""
        from fms_fsdp_tpu.serve.disagg import pack_handoff

        if not self.adapter.supports_handoff or self.adapter.speculative:
            return None
        self._collect()  # the frame holds the stream's last token
        if req.rid in self._chunking or req not in self._slots:
            return None
        slot = self._slots.index(req)
        header, arrays = self.adapter.export_handoff(req.rid, slot)
        header.update(
            prompt=[int(t) for t in req.prompt],
            generated=[int(t) for t in req.generated],
            seq_len=int(self._lens[slot]),
            max_new_tokens=int(req.max_new_tokens),
        )
        data = pack_handoff(header, arrays)
        self._handoff_bytes += len(data)
        self.registry.counter("serve.handoffs_exported").add()
        self.registry.counter("serve.handoff_bytes").add(len(data))
        return data

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        return (
            self._draining
            and self._inflight is None
            and all(r is None for r in self._slots)
            and not self._finished_buf
        )

    def health(self) -> Dict[str, float]:
        """One flat liveness snapshot (the replica loop's heartbeat
        payload): iteration count proves forward progress, the rest
        sizes the replica's load for the router's dispatch choice."""
        return {
            "iterations": float(self.iterations),
            "slots_busy": float(self._busy()),
            "queue_depth": float(self.scheduler.queue_depth()),
            "kv_pages_in_use": float(self.adapter.pages_in_use),
            "draining": float(self._draining),
        }

    # -- obs ---------------------------------------------------------------

    def serving_stats(self) -> Dict[str, float]:
        """The schema-v9 ``serving`` map (flat str->number): headline
        serving health for one obs record. Registry counters/gauges
        additionally ride a record's ``extra`` via MetricRegistry
        snapshot as usual."""
        ttft = self.registry.hist("serve.ttft_s").reduce(clear=False)
        # true p99 from the latency window (Hist.reduce only derives
        # mean/p50/p90/max — max would alarm on a single outlier)
        lat = sorted(self.registry.hist("serve.request_latency_s").samples)
        p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat else 0.0
        return {
            # decode tokens committed over the engine's clock, across
            # the steps the step log holds
            "tokens_per_s": self.step_log.tokens_per_s(),
            "ttft_s": ttft.get("mean", 0.0),
            "queue_depth": float(self.scheduler.queue_depth()),
            "kv_pages_in_use": float(self.adapter.pages_in_use),
            "requests_completed": float(self.scheduler.completed),
            "requests_evicted": float(self.scheduler.evicted),
            "requests_expired": float(self.scheduler.expired),
            "requests_expired_inflight": float(
                self.scheduler.expired_inflight
            ),
            "p99_latency_s": p99,
            # v12: numeric family code (serve/families/FAMILY_CODES)
            # + the constant per-stream recurrent-state bytes (0 for
            # paged-KV families, whose state rides kv_pages_in_use)
            "family": float(FAMILY_CODES[self.family]),
            "state_bytes_per_stream": float(
                self.adapter.state_bytes_per_stream
            ),
            # v13: disaggregation + serving layout — numeric role code
            # (serve/disagg/ROLE_CODES), the layout as 100*tp + fsdp
            # (0 = single-chip), and cumulative handoff wire traffic
            "role": float(_role_code(self.serve_cfg.role)),
            "serve_layout": float(
                _layout_code(self.serve_cfg.serve_layout)
            ),
            "handoff_bytes": float(self._handoff_bytes),
            "handoff_s": float(self._handoff_wall),
            # v14: speculative serving + chunked prefill + how paged
            # attention decoded (0 = reference gather, 1 = kernel with
            # one full-width page per cell, 2 = kernel with multi-page
            # cells and/or native quantized reads)
            "spec_accept_rate": (
                self._spec_accept_total / self._spec_draft_total
                if self._spec_draft_total
                else 0.0
            ),
            "spec_draft_tokens": float(self.adapter.spec_draft_tokens),
            "prefill_chunks": float(self._prefill_chunks),
            "paged_kernel_impl": float(self._paged_kernel_impl()),
            # v15: drain-and-migrate — 1.0 once a draining engine's
            # slots have emptied (its streams finished or were packed
            # and migrated to siblings)
            "drained": float(self.drained),
        }

    def _paged_kernel_impl(self) -> int:
        if self.attn_impl != "kernel":
            return 0
        if self.serve_cfg.kv_quant != "none" or (
            self.block_kv and self.page_size
            and self.block_kv != self.page_size
        ):
            return 2
        return 1


def _role_code(role: str) -> int:
    from fms_fsdp_tpu.serve.disagg import ROLE_CODES

    return ROLE_CODES[role]


def _layout_code(layout: str) -> int:
    from fms_fsdp_tpu.parallel.sharding import serve_layout_code

    return serve_layout_code(layout)
