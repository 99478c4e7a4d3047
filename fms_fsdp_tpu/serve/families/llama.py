"""Llama family adapter: the paged-KV serving path.

The tuner-resolved page size, the PagedKVCache pool, the prefill
programs keyed on (p_len, s_pad, full_logits), the donated ragged decode
step and the page-table upload cache are the skeleton's
(serve/families/__init__.py); what is llama's own is the decode program
over the ragged paged-attention kernel, chunked prefill and speculative
decode. The engine's bit-parity anchor (tests/test_serving.py) holds the
ops and their order.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from fms_fsdp_tpu.models.generation import decode_chunk, prefill, sample_token
from fms_fsdp_tpu.models.speculative import speculator_propose
from fms_fsdp_tpu.obs.spans import span
from fms_fsdp_tpu.serve.decode import paged_decode_step, paged_verify_step
from fms_fsdp_tpu.serve.families import PagedAdapter


class LlamaAdapter(PagedAdapter):
    family = "llama"
    supports_handoff = True
    supports_layout = True
    supports_chunked_prefill = True
    _model_prefill = staticmethod(prefill)

    def _setup(self) -> None:
        cfg, scfg = self.model_cfg, self.scfg
        # serve_layout: build the serving mesh + shard params (tp over
        # heads/ffn, fsdp ZeRO-style — the train rulebook). No-op when
        # unset, keeping the single-chip bit-parity anchor byte-exact.
        self._init_layout(scfg)
        self._init_pages(
            int(self.params["layers"]["wq"].shape[0]),
            cfg.nheads,
            cfg.n_kv_heads,
            cfg.head_dim,
            quant=scfg.kv_quant,
        )
        page_size = self.page_size
        impl = scfg.attn_impl
        if impl == "auto":
            impl = "reference" if jax.default_backend() != "tpu" else "kernel"
        # the kernel reads quantized pools natively (scales applied in
        # VMEM) — no reference fallback on the TPU path
        self.attn_impl = impl
        self._chunk_state: dict = {}  # rid -> staged incremental prefill

        def _step(params, pools, page_table, seq_lens, tokens, key):
            logits, _, pools = paged_decode_step(
                params,
                pools,
                page_table,
                seq_lens,
                tokens,
                cfg,
                page_size=page_size,
                compute_dtype=self.compute_dtype,
                quant=scfg.kv_quant,
                attn_impl=impl,
                block_kv=self.block_kv,
            )
            tok = sample_token(
                logits, key, scfg.temperature, scfg.top_k, scfg.do_sample
            )
            return tok.astype(jnp.int32), logits, pools

        # pools donated: the step's cache update is in-place, never a
        # pool copy per token
        self._decode_fn = jax.jit(_step, donate_argnums=(1,))

        if scfg.speculator_path:
            self._init_speculative(scfg, cfg, impl)

    # -- speculative serving (ServeConfig.speculator_path) -----------------

    def _init_speculative(self, scfg, cfg, impl) -> None:
        from fms_fsdp_tpu.models.speculator import load_speculator

        if scfg.do_sample:
            raise ValueError(
                "speculative serving is greedy-only: the accept rule "
                "compares drafts against the base model's argmax — set "
                "do_sample=False or unset speculator_path"
            )
        if scfg.role != "unified":
            raise ValueError(
                f"speculative serving is unified-only (role="
                f"{scfg.role!r}): the draft state (the last base "
                f"hidden state) is not part of the page handoff"
            )
        spec_params, spec_cfg = load_speculator(scfg.speculator_path)
        if (
            spec_cfg.emb_dim != cfg.emb_dim
            or spec_cfg.vocab_size != cfg.src_vocab_size
        ):
            raise ValueError(
                f"speculator geometry (emb_dim={spec_cfg.emb_dim}, "
                f"vocab={spec_cfg.vocab_size}) does not match the base "
                f"model (emb_dim={cfg.emb_dim}, "
                f"vocab={cfg.src_vocab_size})"
            )
        n = spec_cfg.n_predict
        if scfg.spec_draft_tokens:
            if scfg.spec_draft_tokens > spec_cfg.n_predict:
                raise ValueError(
                    f"spec_draft_tokens={scfg.spec_draft_tokens} "
                    f"exceeds the checkpoint's n_predict="
                    f"{spec_cfg.n_predict}"
                )
            n = scfg.spec_draft_tokens
        self.speculative = True
        self.spec_draft_tokens = n
        self._spec_params = spec_params
        self._spec_cfg = spec_cfg
        # the draft chain's input: each slot's last base hidden state
        # (the embed that produced the slot's pending token); prefill
        # and decode_spec keep it current, in compute dtype so the jit
        # never retraces on a dtype flip
        self._spec_embed = np.zeros(
            (scfg.max_batch, cfg.emb_dim), np.dtype(self.compute_dtype)
        )

        def _spec_step(
            params, spec_params, pools, page_table, seq_lens, tokens, embed
        ):
            # propose with the FULL checkpoint config (the variance-
            # preserving state/emb weights depend on n_predict), then
            # slice: each head only feeds on the previous ones, so a
            # truncated chain equals the full chain's prefix
            props = speculator_propose(
                spec_params, embed, tokens, spec_cfg
            )[:, :n]
            b = tokens.shape[0]
            cand = jnp.concatenate([tokens[:, None], props], axis=1)
            logits, embeds, pools = paged_verify_step(
                params,
                pools,
                page_table,
                seq_lens,
                cand,
                cfg,
                page_size=self.page_size,
                compute_dtype=self.compute_dtype,
                quant=scfg.kv_quant,
                attn_impl=impl,
            )
            base_next = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            match = jnp.cumprod(
                (props == base_next[:, :-1]).astype(jnp.int32), axis=1
            )
            k = match.sum(axis=1)  # (B,) accepted drafts, 0..n
            rows = jnp.arange(b)
            bonus = base_next[rows, k]  # the base's own pick at the
            # first mismatch (or the position after a full accept)
            prop_pad = jnp.concatenate(
                [props, jnp.zeros((b, 1), jnp.int32)], axis=1
            )
            emit = jnp.where(
                jnp.arange(n + 1)[None, :] == k[:, None],
                bonus[:, None],
                prop_pad,
            )
            return (
                emit,
                (k + 1).astype(jnp.int32),
                logits[rows, k],
                embeds[rows, k],
                pools,
            )

        self._spec_fn = jax.jit(_spec_step, donate_argnums=(2,))

    def decode_spec(self, slot_rids, lens, tokens):
        """One draft-then-verify step over all slots: propose
        ``spec_draft_tokens`` tokens per row, score them in one jitted
        verify forward, commit the longest greedy-matching prefix.
        Returns (emit (B, n+1) np.int32, counts (B,) np.int32, logits
        (B, V) of each row's committed position) — row b's new tokens
        are ``emit[b, :counts[b]]``."""
        self._upload_table(slot_rids)
        with span("decode.dispatch", in_flight=0):
            emit, counts, logits, embeds, pools = self._spec_fn(
                self.params,
                self._spec_params,
                self.cache.pools,
                self._table_dev,
                self._dev(lens),
                self._dev(tokens),
                self._dev(self._spec_embed),
            )
            self.cache.pools = pools
        with span("decode.wait"):
            # np.array (not asarray): prefill writes rows in place when a
            # new stream lands in a slot, so the host copy must be
            # writable
            self._spec_embed = np.array(embeds)
            emit, counts = np.asarray(emit), np.asarray(counts)
        return emit, counts, logits

    def _release_state(self, rid: int, slot: int) -> None:
        self._chunk_state.pop(rid, None)

    def _seed_draft(self, slot: int, embed) -> None:
        """Seed the slot's draft chain with the hidden state that
        produced its stream's first token."""
        if self.speculative:
            self._spec_embed[slot] = np.asarray(embed)

    def prefill(self, rid: int, slot: int, prompt):
        # the skeleton's, and the draft chain's seed after the page write
        row = super().prefill(rid, slot, prompt)
        self._seed_draft(slot, self._draft_seed)
        return row

    # -- chunked prefill (ServeConfig.prefill_chunk_tokens) ----------------

    def _build_chunk(self, key):
        return jax.jit(
            partial(
                decode_chunk,
                cfg=self.model_cfg,
                compute_dtype=self.compute_dtype,
            ),
            donate_argnums=(1,),
        )

    def prefill_start(self, rid: int, slot: int, prompt) -> None:
        """Stage ``prompt`` for incremental prefill: allocate the full
        page budget up front (so admission capacity stays honest), then
        advance through a zero-initialized dense mini-cache one chunk
        per ``prefill_chunk``. decode_chunk runs the same attention
        einsum as whole-prompt ``prefill`` over the same zeroed cache,
        so the chunked logits — and the k/v written to pages at the
        end — are bit-identical to the whole-prompt path."""
        p = len(prompt)
        p_pad = self._padded(p)
        s_pad = self.cache.pages_needed(p_pad) * self.page_size
        ok = self.cache.ensure(rid, p_pad)
        assert ok, "admission checked capacity; ensure cannot fail here"
        toks = np.zeros((1, p_pad), np.int32)
        toks[0, :p] = prompt
        self._count_prefill(rid, p_pad)  # the chunks cover the bucket
        nlayers = int(self.params["layers"]["wq"].shape[0])
        # mini-cache length p_pad, NOT s_pad: whole-prompt prefill's
        # attention reduces over exactly p_pad key positions, and
        # matching that reduction length is what keeps the chunked
        # logits bit-identical; the page-granular tail is padded with
        # zeros only at the final write (same bytes the whole path's
        # zero-initialized cache tail carries)
        shape = (
            nlayers,
            1,
            p_pad,
            self.model_cfg.n_kv_heads,
            self.model_cfg.head_dim,
        )
        self._chunk_state[rid] = {
            "slot": slot,
            "toks": toks,
            "p": p,
            "p_pad": p_pad,
            "s_pad": s_pad,
            "pos": 0,
            "cache": {
                "k": jnp.zeros(shape, self.compute_dtype),
                "v": jnp.zeros(shape, self.compute_dtype),
            },
            "row": None,
            "embed": None,
        }

    def prefill_chunk(self, rid: int):
        """Advance a staged prefill by one chunk. Returns None while
        incomplete; on the final chunk, commits the state and returns
        the (V,) logits row of the last real prompt position —
        bit-identical to what whole-prompt ``prefill`` returns."""
        st = self._chunk_state[rid]
        pos = st["pos"]
        m = min(self.scfg.prefill_chunk_tokens, st["p_pad"] - pos)
        fn, built = self._program(
            ("chunk", m, st["p_pad"]), self._build_chunk
        )
        with span("prefill.dispatch", rid=rid, built=built):
            logits, embeds, st["cache"] = fn(
                self.params,
                st["cache"],
                self._dev(st["toks"][:, pos : pos + m]),
                pos,
            )
        last = st["p"] - 1
        if pos <= last < pos + m:
            # the chunk holding the last REAL prompt position carries
            # the first token's logits (padding chunks past it only
            # complete the bucketed cache write)
            st["row"] = logits[0, last - pos]
            st["embed"] = embeds[0, last - pos]
        st["pos"] = pos + m
        if st["pos"] < st["p_pad"]:
            return None
        tail = st["s_pad"] - st["p_pad"]
        pad = ((0, 0), (0, 0), (0, tail), (0, 0), (0, 0))
        cache = {n: jnp.pad(a, pad) for n, a in st["cache"].items()}
        with span("prefill.write_pages", rid=rid):
            self.cache.write_prompt(rid, cache["k"][:, 0], cache["v"][:, 0])
        self._seed_draft(st["slot"], st["embed"])
        row = st["row"]
        del self._chunk_state[rid]
        return np.asarray(row) if self.mesh is not None else row
