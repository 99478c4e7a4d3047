"""bench.py plumbing smoke: the driver-facing JSON contract.

Runs the real parent->probe->row-subprocess pipeline at tiny CPU shapes
(BENCH_SMOKE) over the headline row and its bf16 sibling (BENCH_ROWS)
and asserts the schema the judge reads: the bf16 number and the MFU
convention string ride in the SAME top-level object as the int8
headline (a lone int8 headline vs a bf16 baseline invites an
apples-to-oranges reading). This is the one mode in which bench.py runs
off a TPU; it reports step times and no MFU.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_smoke_schema():
    env = dict(os.environ)
    env.update(
        BENCH_SMOKE="1",
        BENCH_FORCE_CPU="1",
        BENCH_ROWS="0,1",
        BENCH_PROBE_TIMEOUT_S="300",
        BENCH_ROW_TIMEOUT_S="300",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=900,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-2000:]
    line = [
        ln for ln in proc.stdout.splitlines() if ln.startswith("{")
    ][-1]
    out = json.loads(line)

    # driver contract
    for key in ("metric", "value", "unit", "vs_baseline", "rows"):
        assert key in out, (key, out)
    assert out["unit"] == "MFU"
    assert out.get("smoke") is True

    # the bf16 sibling + convention string ride at top level
    assert "bf16_mfu" in out and "bf16_vs_baseline" in out, out
    assert "bf16 peak" in out["mfu_convention"]

    # both selected rows actually ran (no error entries at tiny shapes);
    # the ran-at-all signals are throughput and step time. Off a TPU
    # there is no peak: a CPU rate is never written as a share of a chip
    assert len(out["rows"]) == 2, out["rows"]
    for row in out["rows"]:
        assert "error" not in row, row
        assert row["tokens_per_sec_per_chip"] > 0
        assert row["step_time_s"] > 0
        assert row["mfu"] is None and row["hfu"] is None
        # tuned-vs-default is a per-row first-class output: every row
        # states its tuning mode and the kernel tiles it resolved
        assert row["kernel_tuning"] in ("auto", "off"), row
        assert isinstance(row["tuning"], dict), row

    # a run whose rows ran is never degraded
    assert not out.get("degraded"), out
    assert out["bf16_mfu"] is None and out["bf16_vs_baseline"] is None
    # the fp8 sibling fields always ride at top level (null when the
    # fp8 row is outside the BENCH_ROWS selection, as here)
    assert "fp8_mfu" in out and "fp8_vs_baseline" in out


def test_fp8_sibling_located_structurally():
    """The fp8 headline sibling is found by kwargs identity (minus
    quant), like the bf16 one — reordering ROWS can't mislabel it."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    label = bench._fp8_sibling_label()
    assert label is not None and "fp8" in label
    kw = dict(next(kw for lb, kw in bench.ROWS if lb == label))
    head = dict(bench.ROWS[0][1])
    assert kw.pop("quant") in ("fp8", "fp8_dgrad")
    head.pop("quant")
    assert kw == head
