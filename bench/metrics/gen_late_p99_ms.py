"""How late the open-loop generator submitted, ms, at the 99th percentile:
submit time minus due time. The engine's ``step`` blocks the generator's
one thread, so a long step shows here."""

from bench.harness import percentile


def read(run):
    late = run.window.get("late_s")
    return percentile(late, 99) * 1e3 if late else None
