"""Coupled atom-steps per second per chip: atoms x steps completed in the
window over the window's seconds (ended on ``block_until_ready``), over the
chips of the cell."""


def read(ctx):
    run = ctx["run"]
    return run["atoms"] * run["steps"] / run["window_s"] / run["chips"]
