"""Time per output token after the first, ms: the sum over requests of
(last token's time - first token's time) over the sum of (tokens - 1).
Stalls between tokens count."""


def read(run):
    return run.window["tpot_s"] * 1e3
