"""Mean number of requests each decode step of the window served."""
from bench.harness.measures import active_at, window_steps


def read(run):
    w = run.window
    n = [len(active_at(w, k)) for k, _ in window_steps(w)]
    return sum(n) / len(n) if n else None
