"""95th percentile of the gaps between successive tokens a request received
(ms), open gaps at the close included."""
from bench.harness.measures import itl_s, percentile


def read(run):
    v = percentile(itl_s(run.window), 95)
    return None if v is None else v * 1e3
