"""A serving cell of the phi4flash family: a list of layers of five kinds
under a tied head, two values carried across the reference's walk, rings
and slabs beside one layer's pages, a prefill program a doubling of the
bucket.

A cell takes this driver (``"kind": "serve_phi4flash"``) because none of
the other five walks its reference: every one of them hands a layer its
input and takes its output, and here two values go across layers: layer
16's scan output ``M`` to the seven gated memory units, layer 17's keys
and values to the seven cross layers (``reference/phi4flash.py::block``
takes and returns ``shared``), and an attention layer is told
``lambda_init`` of its own index. Everything else is imported: the
seeded tree, the engine, the leaf maker and the tied head's gather
(``drivers/serve_hybrid.py::build``, ``Reference``), the warm-up of a
program a doubling of the bucket and the order of the requests
(``drivers/serve_sala.py::warm_up``, ``ordered_schedule``: every seed the
same sequence of prompt buckets and output lengths), the open loop, the
window, the sample of finished requests, the two numbers that decide
``correct`` (mean logit gap of the served token below the reference's
best, and the share of tokens more than 0.05 below), the float8 control.
The collector is frozen over the window as ``drivers/serve_lfm2.py``
does, for its reason (the generator's prompts are not the server's to
walk).

**What a decode step reads**, for the rooflines: after the window the
driver reckons, for every engine step that ran no prefill, each live
stream's context from its request's own record, and from it the ring
entries it attends (``min(context, sliding_window)``); the means go to
``run.facts["phi4flash_live"]``.
"""

import gc
import time

import numpy as np

from benchmark.drivers import serve_hybrid
from benchmark.drivers.serve import (
    TRACE_SECONDS,
    drive,
    gap_stats,
    percentile,
    sample_requests,
    summarize,
)
from benchmark.drivers.serve_sala import ordered_schedule, warm_up
from benchmark.harness import memory_peak_bytes

COUNTERS = (
    "serve.prefill_computed_tokens", "serve.prefill_self_positions",
    "serve.prefill_cross_positions", "serve.decode_live_slots",
    "serve.decode_tokens")


def live_contexts(run, recs, steps_log, seconds):
    """Means over the window's engine steps that ran no prefill: live
    streams, their cached positions, the ring entries they attend in a
    window layer. A stream's context at a step is its prompt and the
    tokens it had when the step returned."""
    window = run.config["sliding_window"]
    at = {}  # a step's end -> [context of each stream that gained a token]
    for rec in recs:
        have = len(rec["prompt"])
        for t, n in rec["token_times"]:
            have += n
            at.setdefault(t, []).append(have)
    rows = [
        (len(ctx), sum(ctx), sum(min(n, window) for n in ctx))
        for _, e, active, _, prefilled in steps_log
        if not prefilled and active and e <= seconds
        for ctx in (at.get(e, ()),)]
    if not rows:
        return None
    streams, kv, ring = (sum(r[i] for r in rows) / len(rows) for i in range(3))
    return {"streams": streams, "kv_tokens": kv, "ring_positions": ring,
            "steps": len(rows)}


def run(run):
    import jax

    engine, scfg, spec, key = serve_hybrid.build(run)
    seconds = float(run.args.seconds)
    schedule = ordered_schedule(
        run.args.seed, run.traffic, seconds, run.config["vocab_size"],
        max(1, scfg.prefill_bucket))
    programs = warm_up(run, engine, schedule)
    print(f"{len(schedule)} requests due in {seconds} s, prefill programs "
          f"{programs}, {sum(len(p) for _, p, _ in schedule)} prompt tokens, "
          f"{sum(n for _, _, n in schedule)} output tokens", flush=True)
    before = {n: engine.registry.counter(n).value for n in COUNTERS}

    compiles_before = run.meter.count
    gc.collect()
    gc.freeze()  # the generator's prompts are not the server's to walk
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start
    try:
        recs, steps_log, t_end = drive(
            run, engine, schedule, seconds, t0,
            trace_from=(
                max(0.0, seconds - TRACE_SECONDS) if run.trace else None))
    finally:
        gc.unfreeze()
    compiles_in_window = run.meter.count - compiles_before
    peak = memory_peak_bytes()

    gaps, late, tokens = summarize(recs, seconds)
    rejected = sum(1 for rec in recs if rec["req"] is None)
    admitted = sum(1 for rec in recs if rec["admitted"] is not None)
    finished = [
        (rec["prompt"], list(rec["req"].generated)) for rec in recs
        if rec["req"] is not None and rec["req"].state == "finished"
    ]
    errored = sum(
        1 for rec in recs
        if rec["req"] is not None and rec["req"].state == "failed")
    e2e = {"serve_tokens_per_s": tokens / seconds, "setup_s": setup_s}
    itl_p95_ms = 1e3 * percentile(gaps, 95) if gaps else None
    counted = {
        n.split(".", 1)[1]: engine.registry.counter(n).value - before[n]
        for n in COUNTERS}
    live = live_contexts(run, recs, steps_log, seconds)
    run.facts.update(
        window=(t0, t0 + seconds), window_s=seconds, setup_s=setup_s,
        steps_log=steps_log, itl_p95_ms=itl_p95_ms, phi4flash_live=live,
        **counted)
    prefilled = sum(pf for _, e, _, _, pf in steps_log if e <= seconds)
    longest = sorted(steps_log, key=lambda st: st[0] - st[1])[:3]
    cache = engine.cache
    print(f"window {seconds} s (loop left at {t_end:.2f} s): {len(recs)} "
          f"requests due, {rejected} rejected, {errored} failed, {admitted} "
          f"admitted, {len(finished)} finished, "
          f"{len(recs) - admitted - rejected} still queued at the close, "
          f"{tokens} tokens in window, {e2e['serve_tokens_per_s']:.2f} "
          f"tokens/s, {prefilled} prompt tokens prefilled, "
          f"{counted['prefill_self_positions']:.0f} positions through the "
          f"first half of the stack and "
          f"{counted['prefill_cross_positions']:.0f} through the second, "
          f"{counted['decode_tokens']:.0f} decoded tokens, live means {live}, "
          f"{len(gaps)} token gaps (p50 "
          f"{1e3 * percentile(gaps or [0.0], 50):.1f} ms, p95 "
          f"{itl_p95_ms or 0.0:.1f} ms), generator late p95 "
          f"{1e3 * percentile(late, 95):.2f} ms max {1e3 * max(late):.2f} ms, "
          f"{len(steps_log)} engine steps (the longest, as seconds at "
          f"second with prompt tokens prefilled: "
          + ", ".join(f"{e - s0:.2f} at {s0:.1f} with {pf}"
                      for s0, e, _, _, pf in longest)
          + f"), pages in use at the close {cache.pages_in_use} of "
          f"{cache.num_pages}, failed allocations {cache.failed_allocs}, "
          f"set-up {setup_s:.2f} s, compiles in window {compiles_in_window}",
          flush=True)

    # -- free the engine's weights, pools, rings and slabs; then the reference
    del recs
    for leaf in jax.tree.leaves(
            (engine.params, engine.cache.pools, engine.adapter._state)):
        leaf.delete()
    del engine
    check(run, finished, spec, key)
    run.check("requests_rejected_or_failed", rejected + errored, 0)
    return {
        "end_to_end": e2e,
        "attempted": admitted + rejected,
        "failed": rejected + errored,
        "compiles_in_window": compiles_in_window,
        "memory_peak_bytes": peak,
    }


# ---------------------------------------------------------------------------
# correct: served tokens against the float32 reference, layer by layer
# ---------------------------------------------------------------------------


class Reference(serve_hybrid.Reference):
    """``drivers/serve_hybrid.py``'s reference (a leaf maker a shape, a
    layer's leaves made again from the seed, the tied head) with a walk
    that carries ``shared`` across layers (``M`` and the full layer's keys
    and values, each request's own) and tells an attention layer
    ``lambda_init`` of its index; one jitted block a kind of layer, the
    family's LayerNorm before the head."""

    def __init__(self, run, spec, key):
        import jax

        # the parent's ``blocks`` and ``final`` are made lazily traced
        # programs of another signature: replaced before they are called
        super().__init__(run, spec, key)
        ref, c = run.reference, run.config
        self.blocks = {
            kind: jax.jit(lambda x, layer, lam, shared, kind=kind: ref.block(
                x, layer, c, kind, lam, shared))
            for kind in {
                ref.layer_kind(i, c) for i in range(c["num_hidden_layers"])}
        }
        self.final = jax.jit(
            lambda x, norm, emb: ref.layer_norm(
                x, norm, c["layer_norm_eps"]) @ emb.T)

    def logits(self, sample):
        """Float32 logits at every served position of every sampled
        request: ``[(n_generated, vocab) array, ...]``, the weights made
        again from the seed one layer at a time."""
        import jax
        import jax.numpy as jnp

        ref, c = self.run.reference, self.run.config
        pad_to = serve_hybrid.PAD_TO
        with jax.default_matmul_precision("highest"):
            emb = self.leaf("embedding")
            xs, rows = [], []
            for prompt, generated in sample:
                toks = list(prompt) + list(generated[:-1])
                pad = -(-len(toks) // pad_to) * pad_to
                xs.append(emb[jnp.asarray(toks + [0] * (pad - len(toks)))][None])
                rows.append((len(prompt) - 1, len(prompt) - 1 + len(generated)))
            shared = [{} for _ in xs]
            for i in range(c["num_hidden_layers"]):
                layer = self.layer(i)
                block = self.blocks[ref.layer_kind(i, c)]
                lam = jnp.float32(ref.lambda_init(i))
                xs, shared = map(list, zip(*(
                    block(x, layer, lam, s) for x, s in zip(xs, shared))))
                jax.block_until_ready(xs)
                del layer
            norm = {n: self.leaf("norm_f/" + n) for n in ("weight", "bias")}
            return [
                np.asarray(self.final(x[0, lo:hi], norm, emb))
                for x, (lo, hi) in zip(xs, rows)]


def check(run, finished, spec, key):
    """As ``drivers/serve.py::check``: by how much a served token's logit
    lies below the reference's best at that position, over a sample of
    finished requests (the longest first: its prompt crossed chunks of the
    prefill's loop, its output wrapped the ring and read pages far behind
    the window); the mean, and the share more than 0.05 below."""
    c = run.cell_file["check"]
    sample = sample_requests(
        finished, run.args.seed, int(c["tokens"]), int(c["requests_at_most"]))
    if not sample:
        run.check("finished_requests_to_compare", 0, 1, ok=False)
        return
    t = time.perf_counter()
    logits = Reference(run, spec, key).logits(sample)
    stats = gap_stats(np.concatenate([
        l.max(axis=-1) - l[np.arange(len(served)), np.asarray(served)]
        for l, (_, served) in zip(logits, sample)]))
    print(f"reference took {time.perf_counter() - t:.2f} s over "
          f"{len(sample)} of {len(finished)} finished requests, longest "
          f"{len(sample[0][0])}+{len(sample[0][1])} tokens; logit gap of the "
          f"served token below the reference's best: {stats}", flush=True)
    run.check("served_token_logit_gap_mean", stats["mean"],
              run.limit("logit_gap_mean"))
    run.check("served_token_logit_gap_share_over", stats["share_over"],
              run.limit("logit_gap_share_over"))
