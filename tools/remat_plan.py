"""Remat memory planner: per-policy saved-residual accounting.

The round-3 hardware ledger showed a hole between remat policies: "mlp"
(save-anything-except-wide) OOMs at bs>=16 on llama-1b while "full"
(nothing saveable) pays ~33% recompute and hits an XLA spill cliff on
gpt-760m. This tool makes the tradeoff measurable BEFORE spending chip
time: for each policy it traces one LM train-loss forward on the host
(jax.ad_checkpoint.saved_residuals — abstract tracing, no execution, no
TPU needed) and reports the bytes of residuals the backward will hold,
alongside the analytic recompute tax in block-MAC terms.

Usage:
  python tools/remat_plan.py --model llama-1b --batch 16 [--seq 2048]

CALIBRATION (round-5 hardware ledger): these numbers bound the saved
RESIDUAL bytes only — XLA's compile-time HLO temps amplify the real
footprint well past them (llama-1b bs8 dots: planner said comfortable,
AOT compile needed 19.3G against 15.75G HBM; gpt-760m bs8 slim missed
by 50MB). Use the report to ORDER candidate policies, never to conclude
a config fits; an ahead-of-time compile (tools/aot_tpu.py) is the ground
truth.
"""

from __future__ import annotations

import argparse
import os
import sys

# an analysis tool traces on the host and must never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

POLICIES = ["none", "slim", "mlp", "dots", "full"]


def recompute_tax(cfg, policy: str, seq: int) -> float:
    """Replay MACs as a fraction of one block forward (analytic)."""
    d, dff = cfg.d_model, cfg.d_ff
    hd = cfg.head_dim
    proj = d * hd * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)  # q,k,v,o
    mlp = 3 * d * dff
    attn = 2 * cfg.n_heads * hd * seq / 2                   # causal avg
    block = proj + mlp + attn
    if policy == "none":
        return 0.0
    if policy == "full":
        return 1.0
    if policy == "dots":
        # dot outputs + the flash out/lse residuals (named inside the
        # custom_vjp fwd rule) are saved: replay is elementwise only
        return 0.0
    if policy == "mlp":
        return (2 * d * dff) / block
    if policy == "slim":
        # gate/up matmuls replay; flash does not (attn_flash saved)
        return (2 * d * dff) / block
    raise ValueError(policy)


def residual_bytes(model, tokens, policy: str, xent_chunks: int = 8):
    # public alias dropped from jax.ad_checkpoint in this jax version;
    # the implementation is still shipped
    from jax._src.ad_checkpoint import saved_residuals

    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, train=True))
    from flax.core import meta

    variables = meta.unbox(variables)
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), variables)

    if xent_chunks > 1:
        # mirror the production loss (runtime/trainer.py chunked_head):
        # the [B, L, V] logits pair must not count against the policy
        from kubeflow_tpu.ops.xent import chunked_lm_xent

        def loss(params, tokens):
            hidden = model.apply(params, tokens, train=True,
                                 return_hidden=True)
            y = jnp.roll(tokens, -1, axis=-1)
            l, _ = chunked_lm_xent(hidden, params["params"]["lm_head"]["kernel"],
                                   y, xent_chunks)
            return l
    else:
        def loss(params, tokens):
            logits = model.apply(params, tokens, train=True)
            return jnp.mean(logits.astype(jnp.float32) ** 2)

    res = saved_residuals(loss, params, tokens)
    tot = 0
    items = []
    for aval, descr in res:
        if "from the argument" in descr:
            continue  # parameters/inputs, not activation residuals
        nb = aval.size * aval.dtype.itemsize
        items.append((nb, str(aval.shape), str(aval.dtype), descr))
        tot += nb
    return tot, items


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-1b")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--top", type=int, default=0,
                    help="also print the N largest residuals per policy")
    ap.add_argument("--attention", default="flash",
                    help="attention_impl to trace (flash = the hardware "
                         "path; its custom_vjp residuals q/k/v/out/lse "
                         "are what the backward actually holds)")
    ap.add_argument("--xent-chunks", type=int, default=8)
    args = ap.parse_args()

    from kubeflow_tpu.models.registry import get_model

    rows = []
    for policy in POLICIES:
        kw = {} if policy == "none" else dict(remat=True, remat_policy=policy)
        model = get_model(args.model, max_seq_len=args.seq,
                          attention_impl=args.attention, **kw)
        tokens = jnp.zeros((args.batch, args.seq), jnp.int32)
        tot, items = residual_bytes(model, tokens, policy, args.xent_chunks)
        tax = recompute_tax(model.cfg, policy, args.seq)
        rows.append((policy, tot, tax))
        print(f"{policy:>6}: residuals {tot / 2**30:7.2f} GiB   "
              f"block replay {tax * 100:5.1f}% of fwd MACs")
        if args.top:
            for nb, shape, dt, descr in sorted(items, reverse=True)[:args.top]:
                print(f"         {nb / 2**20:9.1f} MiB  {shape:>22} {dt:>9}  "
                      f"{descr[:80]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
