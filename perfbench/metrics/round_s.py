"""Seconds per simulated round: the window's host-clock seconds, ended by
``block_until_ready`` on the server's parameters, over the whole rounds
run in it."""


def read(ctx):
    if "trace" in ctx:
        return None
    return ctx["window_s"] / ctx["rounds"]
