"""`train_fit`: one Trainer and one state through `Trainer.fit`; the job
is the configuration's `trainer` block, the mix carries only the limits.
Reports `train_tok_per_s`."""

from benchmarks.lib.train import run  # noqa: F401
