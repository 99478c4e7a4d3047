"""Median wall time of the ``engine.step()`` calls that decoded and ran
no prefill: the benchmark's own span around the call, inside the window.
"""


def read(run):
    log = run.facts.get("steps_log")
    if not log:
        return None
    hi = run.facts["window_s"]
    walls = sorted(
        e - s for s, e, active, kv, prefilled in log
        if prefilled == 0 and active > 0 and e <= hi)
    if not walls:
        return None
    return 1e3 * walls[len(walls) // 2]
