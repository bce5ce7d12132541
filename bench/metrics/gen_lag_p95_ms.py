"""95th percentile of how late the loop submitted each request due in the
window (ms): submit start minus due time; one never submitted enters with
its wait until the close."""
from bench.harness.measures import percentile


def read(run):
    w = run.window
    lags = [(r.submit_t0 if r.submit_t0 is not None else w.w_end) - r.due_t
            for r in w.due_in_window()]
    v = percentile(lags, 95)
    return None if v is None else v * 1e3
