#!/usr/bin/env python3
"""Readings that the limits of the output comparison are set from.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 ... \
        [--faults half_batch wire_altered]

For each seed, in one process: the program's sound readings of the
checked steps against the float32 reference (the lower reading of each
limit); for the first `CONTROL_SEEDS` seeds, the control, i.e. the
reference computed and kept in bfloat16 at default precision, put in
the program's place (its upper reading), and beside it the reference
computed in bfloat16 over float32 master weights; and for the first
`FAULT_SEEDS` seeds, each fault of `chipbench.faults` planted in the
program. Each reading names the client and parameter leaf that its
``grad`` and ``update`` come from. One JSON line per reading. The
benchmark's own runs never run this; it needs no measured window, only
the checked steps.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CONTROL_SEEDS = 3
FAULT_SEEDS = 3


def readings(workload: str, seed: int, fault=None, overrides=None):
    """(program gaps, reference readings, checked steps, cell) for one
    seed; ``fault`` is planted while the program is built and stepped."""
    import contextlib

    from chipbench import cell as C
    from chipbench import faults
    from chipbench import reference as R

    ctx = faults.planted(fault) if fault else contextlib.nullcontext()
    with ctx:
        cell = C.Cell(workload, seed, overrides=overrides)
        cell.build()
        checked = cell.checked_steps()
        cell.algo = None
    gc.collect()
    ref = cell.reference().run(checked.inputs)
    return R.gaps(checked.readings, ref), ref, checked, cell


def _control(cell, checked, param_dtype=None):
    import jax
    import jax.numpy as jnp

    return cell.reference(dtype=jnp.bfloat16,
                          precision=jax.lax.Precision.DEFAULT,
                          param_dtype=param_dtype).run(checked.inputs)


def control_gaps(cell, checked, ref):
    """The control's gaps: the reference computed and kept in bfloat16."""
    from chipbench import reference as R

    return R.gaps(_control(cell, checked), ref)


def where(prog, ref, names):
    """The client and leaf of the worst ``grad`` and ``update`` gap, with
    that leaf's reference norm and the client's median leaf's."""
    import numpy as np

    from chipbench import reference as R

    out = {}
    for key, gap in R.gap_matrices(prog, ref).items():
        norms = ref.grad_norms if key == "grad" else ref.change_norms
        i, j = np.unravel_index(int(np.argmax(gap)), gap.shape)
        out[key] = {"client": int(i), "leaf": names[j],
                    "gap": float(gap[i, j]), "ref_norm": float(norms[i, j]),
                    "median_norm": float(np.median(norms[i]))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)
    from repro.common.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax.numpy as jnp

    from chipbench import reference as R

    for n, seed in enumerate(args.seeds):
        gaps, ref, checked, cell = readings(args.workload, seed)
        names = cell.leaf_names()
        line = {"seed": seed, "distilled": checked.distilled, "sound": gaps,
                "sound_where": where(checked.readings, ref, names)}
        if n < CONTROL_SEEDS:
            for key, param_dtype in (("control", None),
                                     ("bf16_compute", jnp.float32)):
                ctl = _control(cell, checked, param_dtype)
                line[key] = R.gaps(ctl, ref)
                line[key + "_where"] = where(ctl, ref, names)
                del ctl
        print(json.dumps(line), flush=True)
        del ref, checked, cell
        gc.collect()
        if n < FAULT_SEEDS:
            for fault in args.faults:
                gaps = readings(args.workload, seed, fault)[0]
                print(json.dumps({"seed": seed, "fault": fault,
                                  "gaps": gaps}), flush=True)
                gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
