"""The paged decode kernel's share of its roofline (%): the least time its
calls need (per call the larger of FLOPs over peak and bytes over HBM
bandwidth; bytes are the live K/V pages of the slots each decode step
served) over the kernel's summed device time in the trace."""
from bench.harness.measures import paged_decode_need_s
from bench.harness.trace import kernel_seconds


def read(run):
    if run.trace is None or run.peak is None:
        return None
    _, t = kernel_seconds(run.trace, "jit_decode",
                          "paged_decode_attention_pallas")
    return 100.0 * paged_decode_need_s(run) / t if t else None
