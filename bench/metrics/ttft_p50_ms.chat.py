"""Median time to first token (ms) in the open-loop chat cell, as
``ttft_p50_ms`` reads it; a per-layer reading there because a window of
about 20 requests leaves its median too unsteady for a bound."""
from bench.harness.measures import percentile, ttft_s


def read(run):
    v = percentile(ttft_s(run.window), 50)
    return None if v is None else v * 1e3
