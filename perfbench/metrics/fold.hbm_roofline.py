"""The local fold's share of its HBM roofline, in percent: the bytes the
fold needs (each client's update read once in its own dtype, the fp32
accumulator read and written once) over (HBM bytes per second x the fold's
device time).  The count is the same whatever implements the fold."""


def read(ctx):
    ms = ctx.value("fold.ms_per_round")
    if ms is None or ctx["peaks"] is None:
        return None
    return 100.0 * ctx["fold_bytes_per_round"] / (
        ms * 1e-3 * ctx["peaks"]["hbm_bytes_per_s"])
