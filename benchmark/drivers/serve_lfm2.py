"""A serving cell of the lfm2 family: a list of layers of four kinds
under a tied head, windows a slot beside the pages, a prefill program a
doubling of the bucket, and the decode program's own counts.

A cell takes this driver (``"kind": "serve_lfm2"``) because none of the
other four does all of it. ``drivers/serve_hybrid.py`` walks this
family's reference as it stands (a list of layers, ``layer_kind``,
``layer_paths``, ``block``, the tied head: its ``Reference`` is used
here, with the family's own key for the norms' epsilon), but it warms a
program a padded length, where this family's adapter builds one a
doubling of the bucket (``drivers/serve_sala.py::warm_up`` warms those),
it frees a ``slab`` the adapter does not have, and it reads no counter:
``moe_experts_touched_share`` needs ``serve.moe_experts_touched`` and
``serve.moe_steps`` before and after the window. Everything else is
imported: the seeded tree and the engine (``drivers/serve_hybrid.py::
build``), the open loop, the window, the sample of finished requests, the
two numbers that decide ``correct`` (mean logit gap of the served token
below the reference's best, and the share of tokens more than 0.05
below), the float8 control.

**The order of the requests** is ``drivers/serve_sala.py::
ordered_schedule``'s: every seed the same sequence of prompt buckets and
of output lengths (``balanced_order`` of the mix's own set), and from
the seed each prompt's length within its bucket, the gaps and the token
ids (and the weights). In ``traffic.serve_schedule``'s plain order, each
seed its own, six seeds read 3711.29-3816.36 tokens/s on the chip, a
spread of 1.38% where half the bound is 1.75% and the issue asked for
0.7% (my chip runs, PR 41).

**The collector is frozen over the window.** The fixed order alone read
no better (3725.56-3808.18, 1.85%): the runs fell into three groups by
the number of decode steps a window held, 1372-1377, 1390 and 1400-1401,
and the short ones held a decode-only step of 0.13-0.29 s where a step is
17 ms. That pause is Python's cyclic collector: this cell's generator
holds 886 prompts as lists of Python ints (620k of them) beside the
engine's own objects, and each collection of the oldest generation walks
them all. The generator's objects are not the server's, so before the
window the driver collects once and moves what stands then to the
permanent generation (``gc.freeze()``), and lets it go after
(``gc.unfreeze()``): six seeds then read 3808.18-3819.60, four of them
3813.89, with no step over the 0.10 s of a 4096-position prefill (the
cell's ``why`` has the readings). The other drivers do not do this yet
(PERF.md section 7).
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
    "serve.prefill_computed_tokens", "serve.moe_experts_touched",
    "serve.moe_pairs", "serve.moe_steps", "serve.conv_windows_written",
    "serve.decode_live_slots")


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
    run.facts.update(
        window=(t0, t0 + seconds), window_s=seconds, setup_s=setup_s,
        steps_log=steps_log, itl_p95_ms=itl_p95_ms, **counted)
    prefilled = sum(pf for _, e, _, _, pf in steps_log if e <= seconds)
    c = run.config
    could = (c["num_hidden_layers"] - c["num_dense_layers"]) * c[
        "num_experts"] * max(1, counted["moe_steps"])
    longest = sorted(steps_log, key=lambda st: st[0] - st[1])[:3]
    cache = engine.cache
    print(f"window {seconds} s (loop left at {t_end:.2f} s): {len(recs)} "
          f"requests due, {rejected} rejected, {errored} failed, {admitted} admitted, "
          f"{len(finished)} finished, {len(recs) - admitted - rejected} still "
          f"queued at the close, {tokens} tokens in window, "
          f"{e2e['serve_tokens_per_s']:.2f} tokens/s, {prefilled} prompt "
          f"tokens prefilled, {counted['prefill_computed_tokens']:.0f} "
          f"positions computed, {counted['moe_steps']:.0f} decode steps "
          f"with {counted['decode_live_slots'] / max(1, counted['moe_steps']):.1f}"
          f" live slots a step touched {counted['moe_experts_touched']:.0f} "
          f"of {could} (layer, expert) pairs "
          f"({counted['moe_experts_touched'] / could:.4f}) for "
          f"{counted['moe_pairs']:.0f} routed pairs, {len(gaps)} token gaps "
          f"(p50 {1e3 * percentile(gaps or [0.0], 50):.1f} ms, p95 "
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

    # -- free the engine's weights, pools and windows, then the reference ---
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
    """``drivers/serve_hybrid.py``'s reference (a jitted block a kind of
    layer, a leaf maker a shape, the walk over the list of layers, the
    tied head) under the family's own key for the norms' epsilon."""

    def __init__(self, run, spec, key):
        import jax

        # the parent's constructor reads ``rms_norm_eps`` lazily, inside
        # ``final``'s trace: replaced here before it is ever called
        super().__init__(run, spec, key)
        ref, c = run.reference, run.config
        self.final = jax.jit(
            lambda x, norm, emb: ref.rms_norm(x, norm, c["norm_eps"]) @ emb.T)


def check(run, finished, spec, key):
    """As ``drivers/serve.py::check``: by how much a served token's logit
    lies below the reference's best at that position, over a sample of
    finished requests (the longest first: with a prompt past one chunk of
    the prefill's loop its windows crossed a chunk boundary); the mean,
    and the share more than 0.05 below."""
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
