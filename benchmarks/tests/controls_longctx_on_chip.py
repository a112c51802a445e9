"""The controls of a cell whose model keeps pages by layer kind and holds
a share of its experts (benchmarks/arch/afmoe.py), read on the chip at
the cell's own sizes (PERF.md, "How correct is decided"): for each seed
the cell runs once, and the answers its timed window produced are judged
by the plain reference as it is and by the reference with a fault
planted in it, each compared as a run is: every matrix product in float8
(the nearest precision below the bfloat16 the configuration states), 3
experts of 4, the window left off the sliding layers, rotary embeddings
on the full layer too, the attention's gate left out; and in bfloat16,
which has to pass where the others each have to fail a limit. (The
selection bias left in the gate weights moves them by a hundredth, which
bfloat16 hides: that fault is the CPU's, tests/test_afmoe.py, in
float32.)

    python3 benchmarks/tests/controls_longctx_on_chip.py \\
        --workload longctx-saturated --seeds 1,2 --seconds 15

One line a seed. `--only a,b` judges the sound reference and those
variants alone (`--only sound`: the sound readings of many seeds). With
`--dump DIR` every checked request's per-token gaps are kept as
DIR/<seed>.npz, and `--rejudge DIR` reduces and judges those again, with
no chip, by the architecture's `judged` and the mix's limits as they are
now.
"""

import argparse
import dataclasses
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def variants(dims):
    import jax.numpy as jnp

    return {
        "sound": {},
        "bfloat16": {"lowp": jnp.bfloat16},
        "float8": {"lowp": jnp.float8_e4m3fn},
        "one_expert_less": {"top_k": dims.top_k - 1},
        "window_off": {"no_window": True},
        "rope_on_full": {"rope_full": True},
        "gate_out": {"no_gate": True},
    }


def line(seed, limits, read, **more):
    """One seed's line: each variant's numbers and whether they pass."""
    from benchmarks.lib import harness

    out = dict(seed=seed, limits=limits, **more)
    for name, got in read.items():
        out[name] = dict(got, passes=harness.judge(
            [(k, got[k], limits[k]) for k in limits]))
    print(json.dumps(out), flush=True)


def rejudge(cell, limits, directory):
    import numpy as np

    for fn in sorted(os.listdir(directory)):
        if not fn.endswith(".npz"):
            continue
        kept, read = np.load(os.path.join(directory, fn)), {}
        for key in kept.files:         # <variant>/<request>
            name, j = key.split("/")
            read.setdefault(name, {})[int(j)] = kept[key]
        line(int(fn[:-4]), limits, {
            name: cell.arch.judged([gaps[j] for j in sorted(gaps)])
            for name, gaps in read.items()}, rejudged=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--only", default="")
    p.add_argument("--dump", default="")
    p.add_argument("--rejudge", default="")
    args = p.parse_args()

    from benchmarks.lib import spec

    cell = spec.cell(args.workload)
    limits = cell.traffic["limits"][cell.config_name]
    if args.rejudge:
        return rejudge(cell, limits, args.rejudge)
    import numpy as np

    run = spec.driver(cell)
    chosen = {k: v for k, v in variants(cell.dims).items()
              if not args.only or k in ("sound", *args.only.split(","))}
    real_gaps = cell.arch.served_gaps
    for seed in (int(s) for s in args.seeds.split(",")):
        read, kept = {}, {}

        def compare(c, seed, sample, read=read, kept=kept):
            for name, fault in chosen.items():
                t, n = time.monotonic(), [0]

                def keeping(*a, name=name, n=n, **kw):
                    out = real_gaps(*a, **kw)
                    kept[f"{name}/{n[0]}"] = out
                    n[0] += 1
                    return out

                cell.arch.served_gaps = keeping
                try:
                    judged, _ = cell.arch.compare_served(
                        c, seed, sample, **fault)
                finally:
                    cell.arch.served_gaps = real_gaps
                read[name] = dict(judged, seconds=time.monotonic() - t)
            return {k: read["sound"][k] for k in limits}, {}

        arch = types.SimpleNamespace(**{
            k: getattr(cell.arch, k) for k in dir(cell.arch)
            if not k.startswith("__")})
        arch.__name__ = cell.arch.__name__
        arch.compare_served = compare
        res = run(dataclasses.replace(cell, arch=arch), seed, args.seconds,
                  False, time.monotonic())
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            np.savez_compressed(os.path.join(args.dump, f"{seed}.npz"), **kept)
        line(seed, limits, read, correct=res["correct"],
             out_tok_per_s=res["metrics"]["out_tok_per_s"]["value"])


if __name__ == "__main__":
    main()
