"""A serving cell: one in-process ``ServingEngine`` under an open loop.

Weights come from the seed in one jitted call on the device, in the dtype
they are served in. Requests are due at the times ``traffic.py`` draws;
the loop submits what is due, steps the engine, and reads from outside,
after each step, which requests gained tokens: a token counts on the
benchmark's own clock at the instant the step that made it returned, and
how late the loop was against each due time is printed. The window closes
at ``--seconds``
and nothing drains: what the queue still holds then was offered beyond
what the engine completes, which is the cell's design.

The warm-up sends one request of each prefill shape that this run's
schedule holds (and so compiles each prefill program, the decode program
and the sampler) and no others.

Afterwards the engine and its weights are freed and a sample of the
finished requests (the longest among them) is checked against the float32
reference, layer by layer: see ``check`` below. With ``--control 1`` the
engine serves weights that went through float8, and the same check has to
fail them.
"""

import math
import time

import numpy as np

from benchmark import traffic, weights
from benchmark.harness import memory_peak_bytes

PAD_TO = 256  # the reference pads a checked sequence to a multiple of this
TRACE_SECONDS = 3.0  # the profiler runs over the end of the window


def percentile(values, q):
    """The q-th percentile (0-100), nearest rank: a value that occurred."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))]


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
    mine = jax.eval_shape(lambda k: weights.make_tree(k, spec, dtype), key)
    theirs = jax.eval_shape(init_params_for(model_cfg), key)
    weights.require_same_tree(mine, theirs, c["family"])
    with run.span("init_weights"):
        params = jax.jit(lambda k: weights.make_tree(k, spec, dtype))(key)
        if run.control:
            params = jax.tree.map(
                jax.jit(through_fp8, donate_argnums=0), params)
        jax.block_until_ready(params)
    engine = ServingEngine(
        params, model_cfg, scfg, clock=time.perf_counter,
        seed=int(run.args.seed) % (2**31))
    return engine, scfg, spec, key


def warm_up(run, engine, scfg, schedule):
    """One request of each prefill shape the schedule holds: the padded
    length, and whether the prompt fills it (the adapter compiles a
    variant for each)."""
    bucket = max(1, scfg.prefill_bucket)
    shapes = sorted({
        (-(-len(p) // bucket) * bucket, len(p) % bucket == 0)
        for _, p, _ in schedule
    })
    rng = np.random.default_rng(0)
    vocab = run.config["vocab_size"]
    for padded, exact in shapes:
        n = padded if exact else padded - 1
        with run.span("warm_up"):
            engine.submit(rng.integers(1, vocab, size=n).tolist(), 3)
            engine.run()
    return shapes


def drive(run, engine, schedule, seconds, t0, trace_from=None):
    """The open loop: submit what is due, step the engine, note after
    each step which requests gained tokens. -> (one record per due
    request, one entry per engine step, seconds from ``t0`` at the end).
    """
    from fms_fsdp_tpu.serve.scheduler import RequestRejected

    recs = []  # one per due request
    live = []  # submitted, not finished
    steps_log = []  # (start, end, active streams, kv tokens, prefill tokens)
    nxt = 0
    while True:
        now = time.perf_counter() - t0
        if (trace_from is not None and not run.tracing
                and run.trace_data is None and now >= trace_from):
            run.start_trace()
        while nxt < len(schedule) and schedule[nxt][0] <= now:
            due, prompt, max_new = schedule[nxt]
            rec = dict(due=due, submitted=now, prompt=prompt, max_new=max_new,
                       req=None, seen=0, admitted=None, token_times=[])
            with run.span("submit"):
                try:
                    rec["req"] = engine.submit(prompt, max_new)
                    live.append(rec)
                except RequestRejected as e:
                    rec["rejected"] = e.reason
            recs.append(rec)
            nxt += 1
        if now >= seconds:
            break
        if engine.has_work():
            s = time.perf_counter() - t0
            with run.span("engine_step"):
                engine.step()
            e = time.perf_counter() - t0
            active = kv = prefilled = 0
            for rec in live:
                n = len(rec["req"].generated)
                if rec["admitted"] is None and rec["req"].state != "queued":
                    rec["admitted"] = s
                if n > rec["seen"]:
                    if not rec["token_times"]:
                        prefilled += len(rec["prompt"])
                    rec["token_times"].append((e, n - rec["seen"]))
                    rec["seen"] = n
                    active += 1
                    kv += len(rec["prompt"]) + n
            steps_log.append((s, e, active, kv, prefilled))
            live = [r for r in live if r["req"].state != "finished"]
        elif nxt < len(schedule):
            with run.span("idle_wait"):
                time.sleep(max(0.0, min(0.002, schedule[nxt][0] - now)))
        else:
            break
    t_end = time.perf_counter() - t0
    if run.tracing:
        run.stop_trace()
    return recs, steps_log, t_end


def summarize(recs, seconds):
    """-> (token gaps, generator lateness, tokens emitted), all inside the
    window: a token counts at the instant the ``engine.step()`` that made
    it returned, a gap is between two such instants of one request."""
    gaps, late, tokens = [], [], 0
    for rec in recs:
        late.append(rec["submitted"] - rec["due"])
        times = [(t, n) for t, n in rec["token_times"] if t <= seconds]
        gaps.extend(b[0] - a[0] for a, b in zip(times, times[1:]))
        tokens += sum(n for _, n in times)
    return gaps, late, tokens


def run(run):
    import jax

    engine, scfg, spec, key = build(run)
    seconds = float(run.args.seconds)
    schedule = traffic.serve_schedule(
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
    print(f"window {seconds} s (loop left at {t_end:.2f} s): {len(recs)} "
          f"requests due, {rejected} rejected, {errored} failed, {admitted} admitted, "
          f"{len(finished)} finished, {len(recs) - admitted - rejected} still "
          f"queued at the close, {tokens} tokens in window, "
          f"{e2e['serve_tokens_per_s']:.2f} tokens/s, {len(gaps)} token gaps "
          f"(p50 {1e3 * percentile(gaps or [0.0], 50):.1f} ms, p95 "
          f"{itl_p95_ms or 0.0:.1f} ms), generator late p95 "
          f"{1e3 * percentile(late, 95):.2f} ms max {1e3 * max(late):.2f} ms, "
          f"{len(steps_log)} engine steps, set-up {setup_s:.2f} s, compiles "
          f"in window {compiles_in_window}", flush=True)

    # -- free the engine and its weights, then the reference ----------------
    del recs
    for leaf in jax.tree.leaves((engine.params, engine.cache.pools)):
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
# correct: served tokens against the float32 reference
# ---------------------------------------------------------------------------


def sample_requests(finished, seed, want_tokens, at_most):
    """The longest finished request and others drawn from the seed, until
    they hold ``want_tokens`` served tokens or number ``at_most``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rng = np.random.default_rng(int(seed))
    rest = [int(i) for i in rng.permutation(order[1:])]
    picked, tokens = [], 0
    for i in [order[0]] + rest:
        picked.append(finished[i])
        tokens += len(finished[i][1])
        if tokens >= want_tokens or len(picked) >= at_most:
            break
    return picked


class Reference:
    """The family's float32 blocks, jitted once and used for every pass:
    a leaf maker for each path (its layer index an argument), the block
    (its weights an argument, so one program serves every layer), the
    final norm and head."""

    def __init__(self, run, spec, key):
        import jax
        import jax.numpy as jnp

        self.run, self.spec, self.key = run, spec, key
        ref, c = run.reference, run.config
        served = jnp.bfloat16

        def maker(s):
            return jax.jit(lambda k, i: weights.make_leaf_from(
                k, s, served, index=i if s.get("stacked") else None
            ).astype(jnp.float32))

        self.make = {p: maker(s) for p, s in spec.items()}
        self.block = jax.jit(lambda x, layer: ref.block(x, layer, c))
        self.final = jax.jit(
            lambda x, norm, head: ref.rms_norm(x, norm, c["rms_norm_eps"]) @ head)

    def leaf(self, path, index=0):
        """The weights of one leaf (of one layer) as the configuration
        states them: rounded to the served dtype first (they are the same
        weights), then float32."""
        return self.make[path](weights.leaf_key(self.key, path), index)

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
            del emb
            for i in range(c["num_hidden_layers"]):
                layer = {
                    p.split("/", 1)[1]: self.leaf(p, i)
                    for p in ref.layer_paths(self.spec)}
                xs = [self.block(x, layer) for x in xs]
                jax.block_until_ready(xs)
                del layer
            norm = self.leaf("norm")
            head = self.leaf("lm_head")
            return [
                np.asarray(self.final(x[0], norm, head))[lo:hi]
                for x, (lo, hi) in zip(xs, rows)]


def fp8_weights(w):
    """Through float8 (e4m3: 3 bits of mantissa, exponents down to 2^-6,
    then fixed steps of 2^-9; largest 448) and back, scaled by the largest
    magnitude of each output channel: the step below bfloat16 that would
    tempt a later PR. Rounded by arithmetic and not by a cast: a v5e has
    no float8 and its compiler drops a cast there and back."""
    import jax.numpy as jnp

    top = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(top > 0, top / 448.0, 1.0)
    a = jnp.abs(w / scale)
    _, ex = jnp.frexp(a)  # a = m * 2^ex, m in [0.5, 1)
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(ex - 1, -6) - 3)
    q = jnp.minimum(jnp.round(a / step) * step, 448.0)
    return jnp.sign(w) * q * scale


def through_fp8(w):
    """The control's weights: every matrix of the served tree through
    float8 and back to its own dtype, one matrix at a time (a stacked
    leaf's float32 copy would not fit beside the weights)."""
    import jax
    import jax.numpy as jnp

    if w.ndim < 2:
        return w

    def one(m):
        return fp8_weights(m.astype(jnp.float32)).astype(w.dtype)

    if w.ndim == 2:
        return one(w)
    return jax.lax.map(one, w.reshape((-1,) + w.shape[-2:])).reshape(w.shape)


GAP_THAT_COUNTS = 0.05  # logit units; see check()


def gap_stats(gaps):
    g = np.sort(np.asarray(gaps, np.float64))
    return {
        "n": int(g.size), "max": float(g[-1]), "mean": float(g.mean()),
        "p90": float(g[int(0.9 * (g.size - 1))]),
        "p99": float(g[int(0.99 * (g.size - 1))]),
        "share_over": float(np.mean(g > GAP_THAT_COUNTS)),
    }


def check(run, finished, spec, key):
    """By how much a served token's logit lies below the reference's best
    at that position, over a sample of finished requests. Greedy serving
    picks the program's best token; where the program computes what the
    configuration states, that token is the reference's best too, or all
    but. Two numbers are compared: the mean gap, and the share of served
    tokens more than ``GAP_THAT_COUNTS`` below the best. (The widest gap
    does not separate a lower precision from the stated one here: where
    two experts' router logits all but tie, bfloat16 takes the other one,
    and that one token lands anywhere.)"""
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
