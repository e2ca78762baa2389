"""Set-up seconds: process start to the first timed round (backend start,
weights and data from the seed, compile or cache load, the cell's first
rounds).  Host clock."""


def read(ctx):
    return ctx["setup_s"]
