"""Model FLOP/s utilisation of the whole step (%): the FLOPs the window's
served work needs (receiver prefill and decode with attention, logits once
per request and per decoded token, transmitter prefill and fuser for each
C2C request), counted by the benchmark, per second of the window, over the
chip's bf16 peak."""
from bench.harness.measures import served_flops


def read(run):
    flops = served_flops(run)
    if run.peak is None or flops is None:
        return None
    return 100.0 * flops / run.window.seconds / run.peak["bf16_flops"]
