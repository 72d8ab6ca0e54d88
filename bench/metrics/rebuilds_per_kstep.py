"""Neighbour-table builds per 1,000 steps: the engine's in-scan rebuild
counter (``Engine.n_rebuilds``) plus the builds of the episode restarts
(one each where the engine restarts from the swapped state, none where it
is handed the carry built in set-up)."""


def read(ctx):
    run = ctx["run"]
    if not run["steps"]:
        return None
    return 1e3 * (run["rebuilds"] + run["restart_builds"]) / run["steps"]
