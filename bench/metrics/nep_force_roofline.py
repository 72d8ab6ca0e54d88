"""The NEP-SPIN force call's share of its roofline [%]: the least time the
chip could take for the work the call requires (``bench/work.py``, peaks
from ``bench/peaks.json``) over the measured ``repro.force`` device time
per call (one per step, one per rebuild, one per restart)."""
from bench import work


def read(ctx):
    run, t = ctx["run"], ctx["trace"]
    cfg = run["config"]
    if cfg["kind"] != "nep_spin" or not t or not t["scopes"].get("force"):
        return None
    need = work.nep_force_work(cfg["spec"], cfg["lattice"], run["atoms"])
    least, _ = work.roofline_seconds(need, work.load_peaks(run["device_kind"]))
    return 100.0 * least / (t["scopes"]["force"] / run["force_calls"])
