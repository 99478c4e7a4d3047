"""A serving cell of the minicpm_sala family: layers of two kinds of mixer
held as two stacks, an untied head, pages and an index cache for one kind
and a state a slot for the other, and the family's scaled embedding and
logits.

A cell takes this driver (``"kind": "serve_sala"``) because none of the
other three walks its reference or warms its programs:
``drivers/serve_sarvam.py`` walks two stacks as this family needs, but
embeds without ``scale_emb``, ends without the division of the logits
(its gaps would read 16 times too large here) and frees no state;
``drivers/serve.py``'s warm-up sends a request a padded length, where this
family's adapter builds a program a doubling of the bucket
(``serve/families/minicpm_sala.py::program_len``): a 64k deployment has
six prefill programs, not 32, and one request of each warms them.
Everything else is imported: the seeded tree and the engine
(``drivers/serve.py::build``), the open loop, the window, the sample of
finished requests, the two numbers that decide ``correct`` (mean logit
gap of the served token below the reference's best, and the share of
tokens more than 0.05 below), the float8 control, and
``drivers/serve_sarvam.py``'s walk over the stacks.

**The order of the requests** (``ordered_schedule``).
``serve_sarvam.stratified_schedule`` gives every seed the same sequence
of prompt buckets and of output strata (32 by rank) and lets the seed
draw the member of each stratum. Here that is not enough: a prompt of
65536 positions is 5 s of prefill, the prefills are 92% of a window, and
the decode steps that make the window's tokens run in what is left of
the 45 s, about 8 s, so one part in a hundred more prefill before the
window's end is 4.6 in a hundred fewer tokens. Six seeds of the
stratified order read 337.27-352.20 tokens/s on the chip, a spread of
3.2% where half the bound is 1.75%, with the same 59 requests admitted
and the same 835584 positions computed in every run (my chip runs,
PR 39): what differed was which outputs ended when, and so which
prefills fell before the window's end. So the sequence of output
lengths is the same for every seed too (``balanced_order`` of the
mix's own set, as the prompts' buckets are), and the seed draws each
prompt's length within its bucket, the gaps and the token ids (and the
weights): which contexts the streams stand at and when requests arrive,
not how much work a window is offered or in what order. In
``traffic.serve_schedule``'s plain order the prompt positions of the
59 requests a window reaches differ by seed with a spread of 7-21% over
four sets of six seeds (arithmetic over the mix's own set, PERF.md
section 6, PR 39).

**What a decode step reads**, for the rooflines: after the window the
driver reckons, for every engine step that ran no prefill, each live
stream's context from its request's own record, and from it the
positions the stream attends in a sparse layer and the compressed keys
it scores (``costs_sala.attended_positions``, ``index_rows``); the means
go to ``run.facts["sala_live"]``.
"""

import time

import numpy as np

from benchmark import costs_sala, traffic
from benchmark.drivers import serve_sarvam
from benchmark.drivers.serve import (
    build,
    drive,
    gap_stats,
    percentile,
    sample_requests,
    summarize,
)
from benchmark.drivers.serve_hybrid import balanced_order
from benchmark.harness import memory_peak_bytes

# the profiler runs over the window's last seconds: ten here, where the
# other drivers take three. The last three of this cell's window hold one
# prefill of 5 s and two or three decode steps (my chip runs, PR 39),
# too few steps for the engine to hand the session its step log of the
# 42 s before (64 to 256 records a step, ``obs/spans.py::StepLog``), so
# the six metrics read from it found nothing; ten seconds hold two
# hundred decode steps and half a dozen prefills
TRACE_SECONDS = 10.0

COUNTERS = (
    "serve.prefill_computed_tokens", "serve.sparse_chose_tokens",
    "serve.sparse_chosen_blocks", "serve.sparse_context_blocks",
    "serve.sparse_decode_chose", "serve.decode_live_slots")


def ordered_schedule(seed, mix, seconds, vocab_size, bucket):
    """``traffic.serve_schedule``'s set of requests, from the same laws by
    the same functions, the prompts' buckets and the outputs in
    ``balanced_order``'s order, the same for every seed; the seed draws
    each prompt's length among its bucket's, the gaps and the token ids:
    see the module's docstring. ``bucket``: the engine's
    ``prefill_bucket``."""
    rate, at_open = float(mix["rate_per_s"]), int(mix["queued_at_open"])
    arrivals = max(1, int(round(rate * seconds)))
    n = at_open + arrivals
    fixed = np.random.default_rng(0)
    prompts = balanced_order(
        fixed, traffic.lognormal_lengths(n, mix["prompt_tokens"]), 2)
    outputs = balanced_order(
        fixed, traffic.lognormal_lengths(n, mix["output_tokens"]), 3)
    rng = np.random.default_rng(int(seed))
    buckets = -(-prompts // bucket)
    for b in np.unique(buckets):
        at = np.flatnonzero(buckets == b)
        prompts[at] = prompts[rng.permutation(at)]
    gaps = rng.permutation(traffic.exponential_gaps(arrivals, rate))
    due = np.concatenate([np.zeros(at_open), np.cumsum(gaps)])
    return [
        (float(t), rng.integers(1, vocab_size, size=int(p)).tolist(), int(o))
        for t, p, o in zip(due, prompts, outputs)]


def warm_up(run, engine, schedule):
    """One request of each prefill program the schedule needs (the
    adapter's own rule says which a prompt takes), as long as the program
    is: its loop takes every trip once, and the writes of its pages, its
    index rows and its state have its shapes."""
    adapter = engine.adapter
    lengths = sorted({adapter.program_len_of(len(p)) for _, p, _ in schedule})
    rng = np.random.default_rng(0)
    vocab = run.config["vocab_size"]
    for n in lengths:
        with run.span("warm_up"):
            engine.submit(rng.integers(1, vocab, size=n - 1).tolist(), 3)
            engine.run()
    return lengths


def live_choice(run, recs, steps_log, seconds):
    """Means over the window's engine steps that ran no prefill: live
    streams, the positions they attend in a sparse layer, the compressed
    keys those that choose score. A stream's context at a step is its
    prompt and the tokens it had when the step returned."""
    c = run.config
    at = {}  # a step's end -> [context of each stream that gained a token]
    for rec in recs:
        have = len(rec["prompt"])
        for t, n in rec["token_times"]:
            have += n
            at.setdefault(t, []).append(have)
    rows = []
    for _, e, active, _, prefilled in steps_log:
        if prefilled or not active or e > seconds:
            continue
        ctx = at.get(e, ())
        rows.append((
            len(ctx),
            sum(costs_sala.attended_positions(c, n) for n in ctx),
            sum(costs_sala.index_rows(c, n) for n in ctx
                if costs_sala.chooses(c, n))))
    if not rows:
        return None
    streams, attended, scored = (
        sum(r[i] for r in rows) / len(rows) for i in range(3))
    return {"streams": streams, "attended": attended, "scored": scored,
            "steps": len(rows)}


def run(run):
    import jax

    engine, scfg, spec, key = build(run)
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
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start
    recs, steps_log, t_end = drive(
        run, engine, schedule, seconds, t0,
        trace_from=max(0.0, seconds - TRACE_SECONDS) if run.trace else None)
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
    live = live_choice(run, recs, steps_log, seconds)
    run.facts.update(
        window=(t0, t0 + seconds), window_s=seconds, setup_s=setup_s,
        steps_log=steps_log, itl_p95_ms=itl_p95_ms, sala_live=live, **counted)
    prefilled = sum(pf for _, e, _, _, pf in steps_log if e <= seconds)
    longest = sorted(steps_log, key=lambda st: st[0] - st[1])[:3]
    chosen_share = counted["sparse_chosen_blocks"] / max(
        1, counted["sparse_context_blocks"])
    print(f"window {seconds} s (loop left at {t_end:.2f} s): {len(recs)} "
          f"requests due, {rejected} rejected, {errored} failed, {admitted} admitted, "
          f"{len(finished)} finished, {len(recs) - admitted - rejected} still "
          f"queued at the close, {tokens} tokens in window, "
          f"{e2e['serve_tokens_per_s']:.2f} tokens/s, {prefilled} prompt "
          f"tokens prefilled, {counted['prefill_computed_tokens']:.0f} "
          f"positions computed, {counted['sparse_chose_tokens']:.0f} of them "
          f"chose {counted['sparse_chosen_blocks']:.0f} of "
          f"{counted['sparse_context_blocks']:.0f} blocks a kv head and layer "
          f"({chosen_share:.4f}), {counted['sparse_decode_chose']:.0f} of "
          f"{counted['decode_live_slots']:.0f} decoded positions chose, "
          f"live means {live}, {len(gaps)} token gaps "
          f"(p50 {1e3 * percentile(gaps or [0.0], 50):.1f} ms, p95 "
          f"{itl_p95_ms or 0.0:.1f} ms), generator late p95 "
          f"{1e3 * percentile(late, 95):.2f} ms max {1e3 * max(late):.2f} ms, "
          f"{len(steps_log)} engine steps (the longest, as seconds at "
          f"second with prompt tokens prefilled: "
          + ", ".join(f"{e - s0:.2f} at {s0:.1f} with {pf}"
                      for s0, e, _, _, pf in longest)
          + f"), set-up {setup_s:.2f} s, compiles "
          f"in window {compiles_in_window}", flush=True)

    # -- free the engine's weights, pools and states, then the reference ----
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


class Reference(serve_sarvam.Reference):
    """``drivers/serve_sarvam.py``'s reference (a jitted block a kind of
    layer, the walk over both stacks) with the family's own ends: the
    embedding's rows times ``scale_emb`` (``h0 = scale_emb * E[id]``:
    the leaf is scaled, the walk's gather is the parent's) and the logits
    over ``hidden_size / dim_model_base`` (``reference.logits``)."""

    def __init__(self, run, spec, key):
        import jax

        super().__init__(run, spec, key)
        ref, c = run.reference, run.config
        self.final = jax.jit(
            lambda x, norm, head: ref.logits(x, norm, head, c))

    def leaf(self, path, index=0):
        w = super().leaf(path, index)
        return w * self.run.config["scale_emb"] if path == "embedding" else w


def check(run, finished, spec, key):
    """As ``drivers/serve.py::check``: by how much a served token's logit
    lies below the reference's best at that position, over a sample of
    finished requests (the longest first); the mean, and the share more
    than 0.05 below. The longest has to stand past ``dense_len``: its
    compared tokens were then decoded through chosen pages."""
    c = run.cell_file["check"]
    sample = sample_requests(
        finished, run.args.seed, int(c["tokens"]), int(c["requests_at_most"]))
    if not sample:
        run.check("finished_requests_to_compare", 0, 1, ok=False)
        return
    dense_len = run.config["sparse_config"]["dense_len"]
    run.check("longest_compared_prompt_past_dense_len", len(sample[0][0]),
              dense_len, ok=len(sample[0][0]) > dense_len)
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
