"""Generate a learnable REAL arrow corpus on disk for the train ->
eval_ppl leg — the same generator the e2e tests
use (fms_fsdp_tpu/data/synth.py), scaled up, so EVAL.json exercises
arrow streaming -> training -> falling perplexity through the
production entry points instead of the in-memory dummy stream.

Usage:
    python scripts/gen_arrow_data.py /tmp/eval_data \
        --n_shards=4 --docs_per_shard=2500 --doc_len=1000 --vocab=4096
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from fms_fsdp_tpu.data.synth import build_arrow_corpus


def main(argv):
    assert argv and not argv[0].startswith("--"), (
        "first arg must be the output root directory"
    )
    root, kwargs = argv[0], {}
    for a in argv[1:]:
        assert a.startswith("--") and "=" in a, f"bad arg {a!r}"
        k, v = a[2:].split("=", 1)
        kwargs[k] = float(v) if k == "noise" else int(v)
    out = build_arrow_corpus(root, **kwargs)
    n = kwargs.get("n_shards", 3)
    d = kwargs.get("docs_per_shard", 60)
    ln = kwargs.get("doc_len", 90)
    print(f"wrote {n} shards x {d} docs x {ln} tokens under {out}")


if __name__ == "__main__":
    main(sys.argv[1:])
