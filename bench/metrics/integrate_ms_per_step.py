"""Device time of ``repro.integrate`` ops outside the force call (kicks,
drift, spin rotations, thermostats, the dr refresh) per step [ms/step]."""


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["run"]["steps"]:
        return None
    return 1e3 * t["scopes"].get("integrate", 0.0) / ctx["run"]["steps"]
