"""Neighbour-table builds per 1,000 steps: the engine's in-scan rebuild
counter (``Engine.n_rebuilds``) plus one build per episode restart."""


def read(ctx):
    run = ctx["run"]
    if not run["steps"]:
        return None
    return 1e3 * (run["rebuilds"] + run["restarts"]) / run["steps"]
