"""Share of the window that prefills held the decode batch up: over the
engine steps that ran a prefill, their wall time beyond the median wall
time of a decode-only step, over the window. From the benchmark's own
spans around ``engine.step()``."""


def read(run):
    log = run.facts.get("steps_log")
    if not log:
        return None
    hi = run.facts["window_s"]
    decode = sorted(e - s for s, e, n, kv, pf in log if pf == 0 and n > 0 and e <= hi)
    mixed = [e - s for s, e, n, kv, pf in log if pf > 0 and e <= hi]
    if not decode or not mixed:
        return None
    base = decode[len(decode) // 2]
    return 100.0 * sum(max(0.0, w - base) for w in mixed) / hi
