"""Seconds from process start to the first timed step: imports, state and
weights, engine construction (compile or cache load), the warm chunk and
the warm restart."""


def read(ctx):
    return ctx["run"]["setup_s"]
