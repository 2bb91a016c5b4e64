"""Time to first token at the 90th percentile, ms: over every request of
the window, timed from when the open-loop schedule made it due until the
``step`` that produced its first token returned. A request that never got
one counts as a miss and sorts last; a p90 that falls on a miss fails the
run."""

import math

from bench.harness import percentile


def read(run):
    p90 = percentile(run.window["ttft_s"], 90)
    if math.isinf(p90):
        raise RuntimeError("the 90th percentile of time to first token is a miss")
    return p90 * 1e3
