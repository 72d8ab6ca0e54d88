"""Device time of ``repro.rebuild`` ops (reorder, linked-cell table build,
neighbour gather) per step of the traced window [ms/step]."""


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["run"]["steps"]:
        return None
    return 1e3 * t["scopes"].get("rebuild", 0.0) / ctx["run"]["steps"]
