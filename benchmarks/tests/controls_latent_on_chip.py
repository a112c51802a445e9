"""The controls of a cell whose model attends over latents and chooses
its experts inside the best groups (benchmarks/arch/axk1.py), read on the
chip at the cell's own sizes (PERF.md, "How correct is decided"): for
each seed the cell runs once, and the answers its timed window produced
are judged by the plain reference as it is and by the reference with a
fault planted in it, each compared as a run is: every matrix product in
float8 (the nearest precision below the bfloat16 the configuration
states), 7 experts of 8, a plain top-8 in the place of the grouped
choice, YaRN left out (plain rotary), the mscale factor left out of the
softmax scale, the kv latent's RMSNorm left out; and in bfloat16, which
has to pass where the others each have to fail a limit. (The selection
bias left in the gate weights moves them by a hundredth, which bfloat16
hides: that fault is the CPU's, tests/test_axk1.py, in float32.)

    python3 benchmarks/tests/controls_latent_on_chip.py \\
        --workload latent-saturated --seeds 1,2 --seconds 15

The options, the lines and `--dump` / `--rejudge` are those of
controls_longctx_on_chip.py, whose machinery this runs with its own
variants.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.tests import controls_longctx_on_chip as base  # noqa: E402


def variants(dims):
    import jax.numpy as jnp

    return {
        "sound": {},
        "bfloat16": {"lowp": jnp.bfloat16},
        "float8": {"lowp": jnp.float8_e4m3fn},
        "one_expert_less": {"top_k": dims.top_k - 1},
        "plain_top_k": {"no_groups": True},
        "yarn_out": {"no_yarn": True},
        "mscale_out": {"no_mscale": True},
        "kv_norm_out": {"no_kv_norm": True},
    }


if __name__ == "__main__":
    base.variants = variants
    base.main()
