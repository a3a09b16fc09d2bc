"""repro.plan — the unified scheduling API behind every Pallas kernel.

One `Schedule` dataclass (grid, blocks, halo, modeled HBM words, VMEM
working set), one `Planner` protocol with per-op implementations that
encode the paper's capacity argument against a `MachineModel` (MANTICORE
or TPU_V5E), and one `pallas_op` registry that owns the wrapper
boilerplate.  Planners constructed with a `MeshSpec` additionally emit
`ShardedSchedule`s: the device partitioning (Alg 3's ring, Alg 4's psum,
batch/stack data parallelism) becomes a planner output with the modeled
words split into per-mesh HBM and interconnect counts.  See DESIGN.md
Secs. 3-5.
"""

from repro.plan.planners import (
    PLANNERS,
    AttentionPlanner,
    ConvDgradPlanner,
    ConvPlanner,
    ConvWgradPlanner,
    GroupedMatmulDwPlanner,
    GroupedMatmulDxPlanner,
    GroupedMatmulPlanner,
    Im2colConvPlanner,
    MatmulDwPlanner,
    MatmulDxPlanner,
    MatmulPlanner,
    MoeFfnPlanner,
    Planner,
    ShardablePlanner,
    TransformerBlockPlanner,
    conv_strip_words,
    conv_wgrad_words,
    planner_for,
)
from repro.plan.registry import (
    PallasOp,
    default_interpret,
    freeze_schedules,
    get_op,
    pad_dim,
    pallas_op,
    registered_ops,
    require_fit,
    with_reference_vjp,
)
from repro.plan.schedule import Blocks, Schedule, to_roofline
from repro.plan.sharded import (
    MeshSpec,
    ShardCandidate,
    ShardedSchedule,
    local_schedule,
    mesh_spec,
    partition_specs,
    validate_sharded_plan,
)
# The autotuner (repro.plan.autotune: tune/resolve/set_policy/AutotuneCache)
# is deliberately NOT imported here: it is its own CLI entry point
# (`python -m repro.plan.autotune`), and importing it from the package
# __init__ would shadow that runpy execution.  Import the submodule
# directly: ``from repro.plan import autotune``.

__all__ = [
    "AttentionPlanner",
    "Blocks",
    "ConvDgradPlanner",
    "ConvPlanner",
    "ConvWgradPlanner",
    "GroupedMatmulDwPlanner",
    "GroupedMatmulDxPlanner",
    "GroupedMatmulPlanner",
    "Im2colConvPlanner",
    "MatmulDwPlanner",
    "MatmulDxPlanner",
    "MatmulPlanner",
    "MeshSpec",
    "MoeFfnPlanner",
    "PLANNERS",
    "PallasOp",
    "Planner",
    "Schedule",
    "ShardCandidate",
    "ShardablePlanner",
    "ShardedSchedule",
    "TransformerBlockPlanner",
    "conv_strip_words",
    "conv_wgrad_words",
    "default_interpret",
    "freeze_schedules",
    "get_op",
    "local_schedule",
    "mesh_spec",
    "pad_dim",
    "pallas_op",
    "partition_specs",
    "planner_for",
    "registered_ops",
    "require_fit",
    "to_roofline",
    "validate_sharded_plan",
    "with_reference_vjp",
]
