"""Reader of how much of the page table the decode ticks walk: one
counter's growth over the window as a share of another's
(`SlotDecoder.stats()`: `kv_pages_walked` over `kv_pages_tabled`). A
program without the counters (a commit before the paged attention
kernel) reads as None, never as an error."""

from benchmarks.metrics.spans import _delta


def growth_share(ctx, part, whole):
    """100 x the growth of `part` over the growth of `whole` between the
    window's two snapshots; None where either lacks a key or `whole`
    stood still."""
    grown, of = _delta(ctx, part), _delta(ctx, whole)
    if grown is None or of is None or of <= 0:
        return None
    return 100.0 * grown / of
