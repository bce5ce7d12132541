"""Share of the window's prompt tokens served from the prefix cache (%):
the engine's radix-matched tokens over matched plus prefilled tokens."""


def read(run):
    w = run.window
    hit = w.stats1["radix_matched_tokens"] - w.stats0["radix_matched_tokens"]
    fresh = w.stats1["prefill_tokens"] - w.stats0["prefill_tokens"]
    return 100.0 * hit / (hit + fresh) if hit + fresh else None
