"""Device time of ``repro.force`` ops per step [ms/step]: the integrator's
force call and the re-evaluation after each rebuild or restart."""


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["run"]["steps"]:
        return None
    return 1e3 * t["scopes"].get("force", 0.0) / ctx["run"]["steps"]
