"""One cell, one run, one last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` for the cell (its configuration, its chips, the
metrics it reports), ``benchmark/workloads/<cell>.json`` for the traffic
and the deployment, the configuration's file for the sizes, and hands
them to ``benchmark/drivers/<kind>.py``. Everything runs in this one
process, which is the one that holds the chips. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.

``--rehearse`` walks the same code at the tiny sizes the cell's file gives
under ``rehearse``, on whatever jax finds (the CPU in the tests); its last
line is labelled a rehearsal, carries no metric, says ``correct: false``
and the exit code is 1. ``--control 1`` runs the cell's control, the next
lower precision, whose last line has to say ``correct: false``
(benchmark/README.md); the driver's runs never pass it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness

    return harness.run(args, ROOT, T_START)


if __name__ == "__main__":
    sys.exit(main())
