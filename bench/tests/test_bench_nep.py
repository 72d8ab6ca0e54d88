"""The NEP-SPIN cell's comparison at a small size on the CPU: the fused
kernels (``xla_tiled`` executor here) agree with the plain reference
within the limits, and the reference in bfloat16 does not."""
import json
import os

from bench import check, control


def test_nep_program_passes_and_control_fails(small_root):
    path = os.path.join(small_root, "bench", "configs", "fege-nep-spin.json")
    with open(path) as f:
        cfg = json.load(f)
    # smaller widths keep the CPU compile of the kernels short
    cfg["spec"].update(basis_size=6, n_rad=4, n_ang=2, l_max=2, n_spin=2,
                       hidden=16)
    with open(path, "w") as f:
        json.dump(cfg, f)
    lim = check.limits(small_root, "fege-nep-spin")
    (seed, prog, ctrl), = control.readings(
        small_root, "nep-fc-64k", [7], require_accelerator=False)
    assert check.verdict(prog, lim), prog
    assert not check.verdict(ctrl, lim), ctrl
