"""The host's own time in a decode step: median, over the traced
``serve/step`` spans that decoded and held no prefill of any kind
(``serve/prefill``, ``serve/prefill_chunk``), of the step's duration
less its ``serve/decode.wait`` (the blocking read of the sampled tokens,
during which the device works). From the program's own spans
(``fms_fsdp_tpu/obs/spans.py``), read by ``benchmark/program_trace.py``;
the trace's last seconds hold about 17 such steps whether or not a
prefill fell into them."""

from benchmark import program_trace


def read(run):
    pt = program_trace.of(run)
    if pt is None:
        return None
    return program_trace.median(program_trace.host_ms_of_decode_steps(pt.spans))
