"""Driver-contract dryrun at the n=16 tier (slow).

The driver itself validates dryrun_multichip(8); this covers the larger
tier the driver does not run: a 16-device virtual mesh where the composed
4-factor config G (dcn x dp x pp x tp, pp >= 2 guaranteed) exists. The
wrapper's partitioner-warning gate applies, so this also asserts every
config compiles without GSPMD involuntary rematerialization/replication. Runs in a subprocess (the wrapper re-execs with
JAX_PLATFORMS=cpu and the 16-device flag before jax initializes).
"""

import pytest

import __graft_entry__ as graft


@pytest.mark.slow
def test_dryrun_multichip_16_green_and_warning_clean():
    graft.dryrun_multichip(16)


def test_spmd_equivalence_parity():
    """The self-certifying SPMD statement: one
    model/seed/batch reaches the same loss under dp, dp·tp·sp and
    fsdp·accum layouts — forward parity at step 1, gradient-path parity
    at step 2."""
    graft.assert_spmd_parity(graft.spmd_equivalence_losses(8))


def test_moe_dispatch_equivalence_parity():
    """EP contract: the sparse sort+all_to_all dispatch must match the
    dense one-hot-einsum oracle on the same model/seed/batch — logits,
    post-update params and losses (measured spread ~6e-8 in f32)."""
    graft.assert_spmd_parity(graft.moe_equivalence_losses(8))


def test_moe_equivalence_catches_dropped_all_to_all(monkeypatch):
    """Neutering the expert all_to_all (each shard silently keeps its
    own capacity buffers — shapes intact, tokens routed to the wrong
    experts' weights) must trip the parity assertion."""
    import jax

    monkeypatch.setattr(
        jax.lax, "all_to_all",
        lambda x, axis_name, split_axis, concat_axis, tiled=False: x)
    losses = graft.moe_equivalence_losses(8)
    with pytest.raises(AssertionError, match="SPMD parity violated"):
        graft.assert_spmd_parity(losses)


def test_spmd_equivalence_catches_dropped_collective(monkeypatch):
    """The contract must FAIL when a sharding bug is injected: neutering
    ring attention's ppermute (each shard silently attends only its local
    K/V — shapes intact, numbers wrong) has to trip the parity
    assertion. Guards against the contract degenerating into
    'execution succeeded'."""
    import jax

    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, axis_name, perm: x)
    losses = graft.spmd_equivalence_losses(8)
    with pytest.raises(AssertionError, match="SPMD parity violated"):
        graft.assert_spmd_parity(losses)
