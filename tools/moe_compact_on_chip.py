#!/usr/bin/env python3
"""A prompt rung's mixture layer alone, on the chip, for the two
configurations that hold a share of their experts: `dropless_mlp` over
every routed pair's row (what it did before it compacted) against the
same call compacted to the held pairs (`ops/moe.py:compact_bound`), the
same rows and the time each takes.

    chiprun -- python tools/moe_compact_on_chip.py

Shapes: `ax-k1-serve` (12 of 192 experts of [7168, 2048], 8 a token,
rungs 2,048 to 8,192) and `trinity-large-serve` (32 of 256 of
[3072, 3072], 4 a token, rungs 4,096 to 16,384), a tick of each beside
them (64 and 32 rows: the rule leaves it whole, and the line says so).
The router is a seeded top-k of normal scores over every expert, a fifth
of the rows dead at the front as a padded prompt's are; each grouped
matmul is the kernel's or `ragged_dot`'s as `grouped_matmul.use_kernel`
answers for the rows that side works on. `--held-share X` routes that
share of the pairs here instead of the mean (2.5 x the mean overruns the
window and takes a second). One JSON line a shape: the held pairs, the
bound, the largest difference over the largest value (both sides round
one float32 sum to bfloat16: 2**-7 bounds it), the milliseconds of each.
Exits 69 when there is no TPU, 1 on a difference over the bound.
"""

import argparse
import json
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.grouped_matmul_on_chip import TOLERANCE, timed  # noqa: E402

# name: (d, d_ff, experts held, experts, a token, rows of a tick, rungs)
CONFIGS = {
    "ax-k1-serve": (7168, 2048, 12, 192, 8, 64, (2048, 4096, 6144, 8192)),
    "trinity-large-serve": (3072, 3072, 32, 256, 4, 32,
                            (4096, 8192, 12288, 16384)),
}


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops import grouped_matmul, moe
    from kubeflow_tpu.ops.flash_attention import interpret_mode
    from kubeflow_tpu.runtime.metrics import device_info

    p = argparse.ArgumentParser()
    p.add_argument("--configs", default=",".join(CONFIGS))
    p.add_argument("--held-share", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=36)
    a = p.parse_args(argv)
    if interpret_mode():
        print(f"moe_compact_on_chip: no TPU: JAX found {device_info()}",
              file=sys.stderr)
        return 69
    cfg = SimpleNamespace(dtype=jnp.bfloat16)
    ok = True
    for name in a.configs.split(","):
        d, f, e, e_all, k, tick, rungs = CONFIGS[name]
        keys = jax.random.split(jax.random.PRNGKey(a.seed), 5)
        w = [jax.random.normal(key, shape, jnp.bfloat16) * shape[1] ** -0.5
             for key, shape in zip(keys, ((e, d, f), (e, d, f), (e, f, d)))]
        for t in (tick,) + rungs:
            x = jax.random.normal(keys[3], (t, d), jnp.bfloat16)
            scores = jax.random.normal(jax.random.fold_in(keys[4], t),
                                       (t, e_all), jnp.float32)
            if a.held_share:
                # lift the held experts' scores until that share of the
                # pairs is theirs
                lift = jnp.linspace(0.0, 6.0, 61)
                shares = jnp.stack([(jax.lax.top_k(
                    scores.at[:, :e].add(s), k)[1] < e).mean() for s in lift])
                scores = scores.at[:, :e].add(
                    lift[jnp.argmax(shares >= a.held_share)])
            gate_vals, gate_idx = jax.lax.top_k(jax.nn.sigmoid(scores), k)
            live = jnp.arange(t) >= (t // 5 if t > tick else 0)
            bound = moe.compact_bound(t * k, e, e_all)
            sides = {}
            for side, rows, window in (("whole", t * k, None),
                                       ("compacted", t * k * e // e_all,
                                        bound)):
                streamed = grouped_matmul.use_kernel(rows, d, f, e, cfg.dtype)
                fn = jax.jit(lambda x, gv, gi, *w, s=streamed, b=window:
                             moe.dropless_mlp(cfg, x, gv, gi, *w, live, s, 0,
                                              b))
                y, counts = fn(x, gate_vals, gate_idx, *w)
                sides[side] = (np.asarray(y, np.float32), np.asarray(counts),
                               timed(lambda *args: fn(*args)[0], x, gate_vals,
                                     gate_idx, *w), streamed)
            whole, compacted = sides["whole"], sides["compacted"]
            err = float(np.abs(whole[0] - compacted[0]).max()
                        / np.abs(whole[0]).max())
            ok &= err <= TOLERANCE and bool((whole[1] == compacted[1]).all())
            print(json.dumps({
                "config": name, "rows": t, "pairs": t * k,
                "held_pairs": int(whole[1].sum()), "bound": bound,
                "max_err_rel_to_max": round(err, 6),
                "whole_ms": round(whole[2], 4), "whole_kernel": whole[3],
                "compacted_ms": round(compacted[2], 4),
                "compacted_kernel": compacted[3]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
