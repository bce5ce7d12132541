"""The ragged prefill kernel's share of its roofline (%): the least time its
calls need (per chunk the larger of FLOPs over peak and bytes over HBM
bandwidth, for the causal prefix of the chunk's live rows) over the
kernel's summed device time in the trace."""
from bench.harness.measures import ragged_prefill_need_s
from bench.harness.trace import kernel_seconds


def read(run):
    if run.trace is None or run.peak is None:
        return None
    need = ragged_prefill_need_s(run)
    _, t = kernel_seconds(run.trace, "jit_cprefill",
                          "ragged_prefill_attention_pallas")
    return 100.0 * need / t if t and need is not None else None
