"""Median time to first token (ms), over every request due in the window,
from when it was due; one with no first token at the close enters with its
wait so far."""
from bench.harness.measures import percentile, ttft_s


def read(run):
    v = percentile(ttft_s(run.window), 50)
    return None if v is None else v * 1e3
