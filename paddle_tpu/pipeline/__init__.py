"""paddle_tpu.pipeline — micro-batch pipeline parallelism + elastic
sharded checkpoints.

The reference framework's Gen-1 model parallelism placed whole layers on
numbered devices (`ParallelNeuralNetwork` device attrs, PAPER §Gen-1);
its Go pserver survived worker churn via etcd-backed checkpoint
recovery. This package is both capabilities, TPU-shaped:

- partition: split a training Program's forward block into K stages at
  `stage_boundary()` markers or automatic cost-balanced cuts.
- schedule: `PipelineExecutor` runs the K-stage, M-microbatch GPipe
  grid as ONE jitted lax.scan over ticks (backward drain = the reverse
  scan, free via jax.value_and_grad).
- elastic: background sharded checkpoint commits on the trainer's
  writer-thread double buffer, and resume-with-resharding onto a
  different mesh shape or chip count.

Quickstart:

    exe = pipeline.PipelineExecutor(num_stages=2, num_microbatches=8)
    exe.run(main_program, feed={...}, fetch_list=[loss])

or from the CLI: `paddle_tpu train --mesh dp2,pp2 --microbatches 8`.
"""

from .elastic import (  # noqa: F401
    declare_reshard_counter,
    load_checkpoint_resharded,
    reshard_scope_to_mesh,
    snapshot_scope,
    submit_sharded_save,
)
from .partition import (  # noqa: F401
    Stage,
    StagedProgram,
    split_program,
    stage_boundary,
)
from .schedule import PipelineExecutor  # noqa: F401

__all__ = [
    "PipelineExecutor",
    "Stage",
    "StagedProgram",
    "split_program",
    "stage_boundary",
    "declare_reshard_counter",
    "load_checkpoint_resharded",
    "reshard_scope_to_mesh",
    "snapshot_scope",
    "submit_sharded_save",
]
