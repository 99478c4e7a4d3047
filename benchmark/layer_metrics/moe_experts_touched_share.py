"""Of the (layer, expert) pairs a decode step could stream, the share
that some live stream chose: the program's own count over the window
(counter ``serve.moe_experts_touched``, returned by the decode program
with its tokens) over expert layers x experts held x the decode steps so
counted (``serve.moe_steps``), which the driver reads before and after
the window. Near 100 with the slots full: the ``all_experts``
form then streams nothing that no one asked for."""


def read(run):
    touched = run.facts.get("moe_experts_touched")
    steps = run.facts.get("moe_steps")
    if not touched or not steps:
        return None
    c = run.config
    layers = c["num_hidden_layers"] - c["num_dense_layers"]
    return 100.0 * touched / (layers * c["num_experts"] * steps)
