"""Time the prefills cost per thousand prompt tokens prefilled, over the
whole window: over the engine steps that ran a prefill (a step prefills
and then decodes), their wall time beyond the median wall time of a
decode-only step, over the prompt tokens those steps prefilled (as
sent, not as padded to the bucket). From the benchmark's own spans
around ``engine.step()``, so it holds the prefill program's device time
and the host's work around it.

Not from the device trace: the profiler runs over the window's last
seconds only, and with some seeds no prefill falls into them, so a
reader of the trace found nothing to read in those runs."""


def read(run):
    log = run.facts.get("steps_log")
    if not log:
        return None
    hi = run.facts["window_s"]
    decode = sorted(e - s for s, e, n, kv, pf in log if pf == 0 and n > 0 and e <= hi)
    mixed = [(e - s, pf) for s, e, n, kv, pf in log if pf > 0 and e <= hi]
    if not decode or not mixed:
        return None
    base = decode[len(decode) // 2]
    tokens = sum(pf for _, pf in mixed)
    return 1e3 * sum(max(0.0, w - base) for w, _ in mixed) / (tokens / 1e3)
