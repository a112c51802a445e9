#!/usr/bin/env python3
"""The compiled flash kernel against the reference, on the chip.

tests/test_flash_attention.py judges the Pallas INTERPRETER (the test
conftest forces the CPU); this is the same comparison for the kernel
Mosaic compiles, which only a TPU can run:

    chiprun -- python tools/flash_on_chip.py

One shape, fixed: b=2, l=2048, h=16, d=64 (gpt-350m's heads at the smoke's
sequence length), causal. Inputs are bf16, as in training. The reference is `reference_attention`
on the same values in float32 under matmul precision "highest". For the
output and each gradient it reports max|kernel - reference| over
max|reference| and fails above TOLERANCE = 2**-6: bf16 keeps 8 bits of
mantissa (rounding 2**-8 of the value), and the kernel rounds twice on
the way (probabilities to bf16 before the PV matmul, then the output),
so four roundings' worth is the bound set beforehand from the dtype.
Exits 69 when there is no TPU: it never judges the interpreter.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOLERANCE = 2.0 ** -6
SHAPE_BLHD = (2, 2048, 16, 64)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.attention import reference_attention
    from kubeflow_tpu.ops.flash_attention import flash_attention, interpret_mode
    from kubeflow_tpu.runtime.metrics import device_info

    device = device_info()
    if interpret_mode():
        print(f"flash_on_chip: no TPU: JAX found {device}", file=sys.stderr)
        return 69
    q, k, v, g = (jax.random.normal(key, SHAPE_BLHD, jnp.bfloat16)
                  for key in jax.random.split(jax.random.PRNGKey(0), 4))

    def run(fn, *xs):
        # one vjp against a fixed cotangent: output and all three gradients
        out, pull = jax.vjp(fn, *xs)
        return (out,) + pull(g.astype(out.dtype))

    got = jax.jit(lambda *xs: run(flash_attention, *xs))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *xs: run(reference_attention, *xs))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    errors = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a = a.astype(jnp.float32)
        errors[name] = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        if not bool(jnp.all(jnp.isfinite(a))):
            errors[name] = float("inf")
    ok = all(e <= TOLERANCE for e in errors.values())
    print(json.dumps({
        "ok": ok, "device": device,
        "shape_blhd": SHAPE_BLHD, "dtype": "bfloat16", "causal": True,
        "tolerance_rel_to_max": TOLERANCE,
        "max_err_rel_to_max": {k: round(e, 5) for k, e in errors.items()}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
