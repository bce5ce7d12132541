"""Seconds from the start of the run to the window's first due request:
building the weights and the engine, compiling (or reading back) every
program, and the warm-up that serves one block of the mix."""


def read(run):
    return run.setup_s
