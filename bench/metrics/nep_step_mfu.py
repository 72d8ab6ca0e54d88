"""Whole-step model flops utilization [%]: the NEP-SPIN flops one step
requires (one force call per step; the rebuild's re-evaluation is
recomputation and does not count) times atom-steps/s, over the chips'
peak."""
from bench import work


def read(ctx):
    run = ctx["run"]
    cfg = run["config"]
    if cfg["kind"] != "nep_spin" or not ctx["trace"]:
        return None
    need = work.nep_force_work(cfg["spec"], cfg["lattice"], run["atoms"])
    peak = work.load_peaks(run["device_kind"])["flops_per_s"]
    rate = run["atoms"] * run["steps"] / run["window_s"]
    return 100.0 * need["flops_per_atom"] * rate / (peak * run["chips"])
