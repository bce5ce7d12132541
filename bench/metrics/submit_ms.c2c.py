"""Mean host time of one C2C submit (ms): the federation front end's eager
transmit side (transmitter prefill, wire, fuser, gate) and the engine's
queueing of the request, on the harness clock."""


def read(run):
    w = run.window
    t = [r.submit_t1 - r.submit_t0 for r in w.requests
         if r.protocol == "c2c" and r.submit_t1 is not None
         and r.submit_t0 >= w.w0]
    return sum(t) / len(t) * 1e3 if t else None
