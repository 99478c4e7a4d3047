"""How sparse the traffic really ran: over the window's prefilled
positions that chose their blocks (``t + 1 > dense_len``), the blocks
they chose over the blocks of their context, from the program's own
counts (the counters ``serve.sparse_chosen_blocks`` and
``serve.sparse_context_blocks``, which the driver reads before and
after the window). 100: nothing was left out (no prompt past
``dense_len`` and 64 blocks); 64 blocks of a 65536-position context's
1024 read 6%."""


def read(run):
    chosen = run.facts.get("sparse_chosen_blocks")
    context = run.facts.get("sparse_context_blocks")
    if not chosen or not context:
        return None
    return 100.0 * chosen / context
