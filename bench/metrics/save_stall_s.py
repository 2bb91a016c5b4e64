"""Seconds a caller is blocked in ``BlobCheckpointer.save``, mean over the
saves of the window."""


def read(run):
    saves = run.window["save_s"]
    return sum(saves) / len(saves)
