"""Mean device time of one execution of the engine's decode program
(jit(decode)), from the trace."""
from bench.harness.measures import program_ms


def read(run):
    return program_ms(run, "jit_decode")
