"""One run of a root's cell on a CPU host forced to four devices, with one
of the sharded path's faults planted underneath, for the benchmark's tests.
It runs in a process of its own, since JAX fixes the device count when it
starts:

    python3 bench/tests/sharded_run.py ROOT WORKLOAD FAULT

prints the result line of :func:`bench.harness.run_cell`, with the
window's compile count and in-scan rebuilds under ``window``.  FAULT is
``none`` or a key of :data:`FAULTS`; :func:`run` starts such a process.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def run(root: str, workload: str, fault: str = "none",
        cache: str | None = None) -> dict:
    """The result line of one such run in a process of its own; runs that
    name one ``cache`` directory share the programs they compile alike (a
    fault's own programs differ, and so miss)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    if cache is not None:
        env.update(JAX_COMPILATION_CACHE_DIR=cache,
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    out = subprocess.run([sys.executable, os.path.abspath(__file__), root,
                          workload, fault], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def halo_zeroed():
    """The adjoint fold drops what the halo's ghost slots received."""
    import repro.parallel.halo as halo

    def core(x, axis_names, dims=(0, 1, 2), width=1, tag=None,
             allgather=False):
        return x[width:-width, width:-width, width:-width]

    halo.fold_halo = core


def halo_local():
    """The forward halo exchange leaves the other devices out: each
    device's ghost cells are its own block wrapped round."""
    import repro.parallel.halo as halo

    exchange_axis = halo.exchange_axis

    def local(x, dim, axis_name, width=1, allgather=False):
        return exchange_axis(x, dim, None, width)

    halo.exchange_axis = local


def device0_key():
    """Every device draws its random numbers from device 0's key."""
    import jax

    import repro.md.engine as engine

    class Random:
        def __getattr__(self, name):
            return getattr(jax.random, name)

        @staticmethod
        def fold_in(key, data):
            return jax.random.fold_in(key, 0)

    class Jax:
        random = Random()

        def __getattr__(self, name):
            return getattr(jax, name)

    engine.jax = Jax()


def no_migration():
    """The in-scan rebuild leaves every atom in the cell it was in."""
    import jax.numpy as jnp

    import repro.parallel.domain as domain

    def stay(dspec, local_shape, pos, vel, spin, types, aid,
             allgather=False):
        z = jnp.zeros((), jnp.int32)
        return pos, vel, spin, types, aid, z, z

    domain.migrate_cells = stay


FAULTS = {"halo_zeroed": halo_zeroed, "halo_local": halo_local,
          "device0_key": device0_key,
          "no_migration": no_migration}


def main(root: str, workload: str, fault: str) -> int:
    from bench import harness
    from repro.telemetry.metrics import CompileWatchdog

    if fault != "none":
        FAULTS[fault]()
    seen = {}
    window = harness.Episodes.window

    def counted(self, seconds):
        wd = CompileWatchdog()
        c0 = wd.count
        rec = window(self, seconds)
        seen.update(compiles=wd.count - c0, rebuilds=rec["rebuilds"])
        return rec

    harness.Episodes.window = counted
    rc, res = harness.run_cell(root, workload, 3_000_000_019, 0.5, False,
                               require_accelerator=False)
    if res is not None:
        res["window"] = seen
        print(json.dumps(res), flush=True)
    return rc


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    sys.exit(main(*sys.argv[1:4]))
