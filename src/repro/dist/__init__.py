"""Distribution subsystem: layout rules, layout search.

``repro.dist.sharding`` holds the parameter/cache/batch/activation
PartitionSpec rules consumed by the models, the launch stack, and the
dry-run coster; ``repro.dist.planner`` searches over those rules'
axis-role assignments with the shared roofline cost model (pass
``layout="auto"`` to the dry-run, hillclimb, or serve engine).

:class:`LogicalMesh` (re-exported from the planner) is the abstract
``.shape``/``.axis_names`` mesh stand-in every layout consumer accepts —
including the fusion planner's ``Traced.plan(layout=...)``, which uses it
to select hybrid local/distributed fused-operator plans from a CPU
container with no devices attached.
"""

from .planner import LogicalMesh  # noqa: F401  (abstract mesh stand-in)
