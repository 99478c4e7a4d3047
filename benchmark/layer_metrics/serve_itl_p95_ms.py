"""95th percentile (nearest rank) of the gaps between consecutive output
tokens of one request, over all gaps inside the window: a token counts at
the instant the ``engine.step()`` that made it returned, on the
benchmark's clock. Above the knee this tail sits between a decode-only
step and a step that also ran a prefill, and swings between the two with
the share of steps that prefill, so it is read beside the tokens per
second and carries no bound of its own."""


def read(run):
    return run.facts.get("itl_p95_ms")
