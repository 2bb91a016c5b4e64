"""Set-up, s: from the start of the process until the window opens:
loading, state made on the device, the peer's publishes where the mix has
any, warm-up and any compilation."""


def read(run):
    return run.setup_s
