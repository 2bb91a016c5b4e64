"""Seconds of ``restore`` onto the chip until every leaf is ready, mean over
the restores of the window."""


def read(run):
    restores = run.window["restore_s"]
    return sum(restores) / len(restores)
