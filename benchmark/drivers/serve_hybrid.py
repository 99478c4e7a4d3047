"""A serving cell of a hybrid model: layers of more than one kind, a
recurrent slab beside the pages.

A cell takes this driver (``"kind": "serve_hybrid"``) instead of
``drivers/serve.py`` when its family's reference offers ``layer_kind(i,
c)``, ``layer_paths(spec, i)`` and ``block(x, layer, c, kind)``: the
layers differ, so the program's parameter tree holds them as a list
(``layers/<i>/...`` in ``param_spec``, no stacked leaf) and the reference
is walked layer by layer with the kind of each. Everything else is
``drivers/serve.py``'s, imported from there: the open loop, the warm-up,
the window, the sample of finished requests, the two numbers that decide
``correct`` (mean logit gap of the served token below the reference's
best, and the share of tokens more than 0.05 below), the float8 control.
Beside the engine's weights and pools this driver frees the adapter's
slab before the reference runs.

One thing more is its own: the order of the requests (``balanced_schedule``).
``traffic.serve_schedule`` gives every seed the same set of lengths in an
order drawn from the seed, which is enough where a window serves nearly
all of the set. A cell above the knee whose time goes to prefills serves
about 160 of the 229 requests due, the first in the order, and the tokens
per second follow the ratio of padded prompt tokens to output tokens among
them: over six seeds that read 510.6-533.1 tokens/s, a spread of 3.5%
where half the bound is 1.75% (my chip runs, PR 27). So the same set, from
the same laws by the same functions of ``traffic.py``, is put in an order
whose every prefix holds all quantiles of both lengths alike (a van der
Corput sequence in base 2 for the prompts and base 3 for the outputs, each
rotated by a shift drawn from the seed); the gaps and the token ids are
drawn as before. The seed still decides which requests meet; it no longer
decides how much work a window is offered.
"""

import time

import numpy as np

from benchmark import traffic, weights
from benchmark.drivers.serve import (
    TRACE_SECONDS,
    drive,
    gap_stats,
    percentile,
    sample_requests,
    summarize,
    through_fp8,
    warm_up,
)
from benchmark.harness import memory_peak_bytes

PAD_TO = 1024  # the reference pads a checked sequence to a multiple of this


def _van_der_corput(i, base):
    x, f = 0.0, 1.0 / base
    while i:
        x += (i % base) * f
        i //= base
        f /= base
    return x


def balanced_order(rng, values, base):
    """``values`` in an order whose every prefix holds all their quantiles
    alike: request ``i`` takes the quantile that the van der Corput
    sequence of ``base``, rotated by a shift drawn from ``rng``, points
    at."""
    v = np.sort(np.asarray(values))
    keys = (np.array([_van_der_corput(i, base) for i in range(len(v))])
            + rng.random()) % 1.0
    return v[np.argsort(np.argsort(keys))]


def balanced_schedule(seed, mix, seconds, vocab_size):
    """``traffic.serve_schedule`` with the lengths in a balanced order:
    the same counts, laws, gaps and ids; see the module's docstring."""
    rate, at_open = float(mix["rate_per_s"]), int(mix["queued_at_open"])
    arrivals = max(1, int(round(rate * seconds)))
    n = at_open + arrivals
    rng = np.random.default_rng(int(seed))
    prompts = balanced_order(
        rng, traffic.lognormal_lengths(n, mix["prompt_tokens"]), 2)
    outputs = balanced_order(
        rng, traffic.lognormal_lengths(n, mix["output_tokens"]), 3)
    gaps = rng.permutation(traffic.exponential_gaps(arrivals, rate))
    due = np.concatenate([np.zeros(at_open), np.cumsum(gaps)])
    return [
        (float(t), rng.integers(1, vocab_size, size=int(p)).tolist(), int(o))
        for t, p, o in zip(due, prompts, outputs)]


def as_program_tree(tree):
    """``weights.unflatten`` makes dicts only; the program holds
    ``layers`` as a list."""
    layers = tree["layers"]
    return {**tree, "layers": [layers[str(i)] for i in range(len(layers))]}


def make_params(key, spec, dtype):
    """The whole seeded tree, shaped as the program's; call it under
    ``jax.jit`` to make it on the device."""
    return as_program_tree(weights.make_tree(key, spec, dtype))


def build(run):
    import jax
    import jax.numpy as jnp

    from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
    from fms_fsdp_tpu.serve.families import init_params_for

    c = run.config
    model_cfg = run.family.model_config(c)
    spec = run.reference.param_spec(c)
    key = weights.seed_key(run.args.seed)
    scfg = ServeConfig(**run.cell_file["engine"])
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[scfg.compute_dtype]
    mine = jax.eval_shape(lambda k: make_params(k, spec, dtype), key)
    theirs = jax.eval_shape(init_params_for(model_cfg), key)
    weights.require_same_tree(mine, theirs, c["family"])
    with run.span("init_weights"):
        params = jax.jit(lambda k: make_params(k, spec, dtype))(key)
        if run.control:
            params = jax.tree.map(
                jax.jit(through_fp8, donate_argnums=0), params)
        jax.block_until_ready(params)
    engine = ServingEngine(
        params, model_cfg, scfg, clock=time.perf_counter,
        seed=int(run.args.seed) % (2**31))
    return engine, scfg, spec, key


def run(run):
    import jax

    engine, scfg, spec, key = build(run)
    seconds = float(run.args.seconds)
    schedule = balanced_schedule(
        run.args.seed, run.traffic, seconds, run.config["vocab_size"])
    shapes = warm_up(run, engine, scfg, schedule)
    print(f"{len(schedule)} requests due in {seconds} s, prefill shapes "
          f"{shapes}, {sum(len(p) for _, p, _ in schedule)} prompt tokens, "
          f"{sum(n for _, _, n in schedule)} output tokens", flush=True)

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
    run.facts.update(
        window=(t0, t0 + seconds), window_s=seconds, setup_s=setup_s,
        steps_log=steps_log, itl_p95_ms=itl_p95_ms)
    prefilled = sum(pf for _, e, _, _, pf in steps_log if e <= seconds)
    print(f"window {seconds} s (loop left at {t_end:.2f} s): {len(recs)} "
          f"requests due, {rejected} rejected, {errored} failed, {admitted} admitted, "
          f"{len(finished)} finished, {len(recs) - admitted - rejected} still "
          f"queued at the close, {tokens} tokens in window, "
          f"{e2e['serve_tokens_per_s']:.2f} tokens/s, {prefilled} prompt "
          f"tokens prefilled, {len(gaps)} token gaps "
          f"(p50 {1e3 * percentile(gaps or [0.0], 50):.1f} ms, p95 "
          f"{itl_p95_ms or 0.0:.1f} ms), generator late p95 "
          f"{1e3 * percentile(late, 95):.2f} ms max {1e3 * max(late):.2f} ms, "
          f"{len(steps_log)} engine steps, set-up {setup_s:.2f} s, compiles "
          f"in window {compiles_in_window}", flush=True)

    # -- free the engine: weights, pools and slab; then the reference -------
    del recs
    for leaf in jax.tree.leaves(
            (engine.params, engine.cache.pools, engine.adapter.slab)):
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


class Reference:
    """The family's float32 blocks, one jitted program per kind of layer
    (its weights an argument, so one program serves every layer of that
    kind), a leaf maker per distinct (shape, kind, scale), the final norm
    and the tied head."""

    def __init__(self, run, spec, key):
        import jax
        import jax.numpy as jnp

        self.run, self.spec, self.key = run, spec, key
        ref, c = run.reference, run.config
        self._makers = {}
        self._served = jnp.bfloat16
        self.blocks = {
            kind: jax.jit(lambda x, layer, kind=kind: ref.block(x, layer, c, kind))
            for kind in {ref.layer_kind(i, c) for i in range(c["num_hidden_layers"])}
        }
        self.final = jax.jit(
            lambda x, norm, emb: ref.rms_norm(x, norm, c["rms_norm_eps"]) @ emb.T)

    def leaf(self, path):
        """One leaf as the configuration states it: rounded to the served
        dtype first (they are the same weights), then float32."""
        import jax
        import jax.numpy as jnp

        s = self.spec[path]
        sig = (tuple(s["shape"]), s["kind"], s.get("scale", 1.0))
        if sig not in self._makers:
            self._makers[sig] = jax.jit(lambda k: weights.make_leaf_from(
                k, s, self._served).astype(jnp.float32))
        return self._makers[sig](weights.leaf_key(self.key, path))

    def layer(self, i):
        at = f"layers/{i}/"
        return weights.unflatten({
            p[len(at):]: self.leaf(p)
            for p in self.run.reference.layer_paths(self.spec, i)})

    def logits(self, sample):
        """Float32 logits at every served position of every sampled
        request: ``[(n_generated, vocab) array, ...]``, the weights made
        again from the seed one layer at a time."""
        import jax
        import jax.numpy as jnp

        ref, c = self.run.reference, self.run.config
        with jax.default_matmul_precision("highest"):
            emb = self.leaf("embedding")
            xs, rows = [], []
            for prompt, generated in sample:
                toks = list(prompt) + list(generated[:-1])
                pad = -(-len(toks) // PAD_TO) * PAD_TO
                xs.append(emb[jnp.asarray(toks + [0] * (pad - len(toks)))][None])
                rows.append((len(prompt) - 1, len(prompt) - 1 + len(generated)))
            for i in range(c["num_hidden_layers"]):
                layer = self.layer(i)
                block = self.blocks[ref.layer_kind(i, c)]
                xs = [block(x, layer) for x in xs]
                jax.block_until_ready(xs)
                del layer
            norm = self.leaf("norm_f")
            return [
                np.asarray(self.final(x[0, lo:hi], norm, emb))
                for x, (lo, hi) in zip(xs, rows)]


def check(run, finished, spec, key):
    """As ``drivers/serve.py::check``: by how much a served token's logit
    lies below the reference's best at that position, over a sample of
    finished requests; the mean, and the share more than 0.05 below."""
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
