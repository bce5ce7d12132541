"""Output tokens delivered in the window over the window's length."""
from bench.harness.measures import tokens


def read(run):
    return tokens(run.window) / run.window.seconds
