#!/usr/bin/env python3
"""The paged prefill behind a prefix hit, on the chip.

No cell of the benchmark sends a shared prefix, so its traffic never takes
the second case of a rung's program (`Attention._decode_paged`: the slot's
pages before the rung, then the rung's own keys, through the flash
kernel). This does, at `chat-saturated`'s own size:

    chiprun -- python tools/prefill_hit_on_chip.py [--seed N] [--prefix 2048]

It serves two requests that share their first `--prefix` tokens through
the cell's server (`benchmarks/lib/serve.py:Served`), one after the other,
so that the second hits, and gives both to the cell's own comparison
(`compare_served`, the plain reference). One JSON line: what the decoder
counted (`prefill_flash`, `prefill_behind_hit`, `prefix_hit_pages`,
`prefill_tokens_computed`), `served_logit_gap` beside the mix's limit,
`ok`. Exit 1 where the second request did not attend behind its hit or
the gap is over the limit; 69 with no TPU.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

COUNTED = ("admitted", "prefill_flash", "prefill_behind_hit",
           "prefix_hit_pages", "prefill_tokens_computed")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="chat-saturated")
    p.add_argument("--seed", type=int, default=2147480034)
    p.add_argument("--prefix", type=int, default=2048)
    p.add_argument("--tail", type=int, default=300)
    p.add_argument("--new", type=int, default=64)
    args = p.parse_args(argv)

    import numpy as np

    from benchmarks.lib import harness, serve, spec

    cell = spec.cell(args.workload)
    try:
        devices = harness.devices_for(cell.chips)
    except harness.NoDevice as e:
        print(e, file=sys.stderr)
        return 69
    harness.configure_cache()
    rng = np.random.default_rng(args.seed)
    prefix = rng.integers(1, cell.dims.vocab, args.prefix).tolist()
    prompts = [prefix + rng.integers(1, cell.dims.vocab, args.tail).tolist()
               for _ in range(2)]
    served = serve.Served(cell, args.seed, devices, {})
    before = served.decoder.stats()
    sample = []
    for prompt in prompts:
        ok, got = served.ask(prompt, args.new)
        if not ok:
            print(f"request failed: {got}", file=sys.stderr)
            return 1
        sample.append({"prompt": prompt, "prediction": got})
    after = served.decoder.stats()
    served.close_and_free()
    judged, beside = cell.arch.compare_served(cell, args.seed, sample)
    limits = cell.traffic["limits"][cell.config_name]
    counted = {k: after[k] - before[k] for k in COUNTED}
    ok = (counted["prefill_flash"] == counted["admitted"] == 2
          and counted["prefill_behind_hit"] == 1
          and all(v <= limits[k] for k, v in judged.items()))
    print(json.dumps({
        "device": harness.device_line(devices, None), "workload": cell.name,
        "prefix": args.prefix, "tail": args.tail, **counted, **judged,
        **beside, "limits": limits, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
