"""Mean host time of one engine step (ms): admission, prefill chunk, decode
and the step's own synchronisation, on the harness clock."""
from bench.harness.measures import mean_step_ms as read  # noqa: F401
