"""paddle_tpu.analysis — static verification of the Program IR.

The layer the reference keeps in ``framework/ir/``: a def-use graph over
Program/Block/Operator (graph.py), a pass registry with concrete checkers
(passes.py), and structured diagnostics (diagnostics.py). Opt in at run
time with ``PADDLE_TPU_VERIFY=1`` (or ``Executor.run(verify=True)``): the
verifier runs once per compiled executable, pre-lowering, and raises on
ERROR findings. Standalone linting: ``python tools/lint_program.py``.
"""

from paddle_tpu.analysis.diagnostics import (  # noqa: F401
    DiagnosticReport,
    Finding,
    Severity,
    VerificationError,
)
from paddle_tpu.analysis.graph import (  # noqa: F401
    Graph,
    OpNode,
    VarNode,
    build_graph,
)
from paddle_tpu.analysis.passes import (  # noqa: F401
    DEFAULT_PASSES,
    PASS_REGISTRY,
    AnalysisContext,
    Pass,
    default_passes,
    register_pass,
    run_passes,
    verify_graph,
    verify_program,
)
from paddle_tpu.analysis.transforms import (  # noqa: F401
    TRANSFORM_PIPELINE,
    TransformContext,
    TransformPass,
    TransformReport,
    optimize_program,
    transform_passes,
)
from paddle_tpu.analysis.memory import (  # noqa: F401
    LivenessReport,
    analyze_liveness,
)
from paddle_tpu.analysis.spmd import (  # noqa: F401
    Collective,
    SpmdReport,
    analyze_spmd,
    hlo_collectives,
    measured_collectives,
)

__all__ = [
    "AnalysisContext", "DEFAULT_PASSES", "DiagnosticReport",
    "Finding", "Graph", "LivenessReport",
    "OpNode", "PASS_REGISTRY", "Pass",
    "Severity", "TRANSFORM_PIPELINE", "TransformContext",
    "TransformPass", "TransformReport", "VarNode", "VerificationError",
    "Collective", "SpmdReport", "analyze_spmd", "hlo_collectives",
    "measured_collectives",
    "analyze_liveness", "build_graph", "default_passes",
    "optimize_program", "register_pass",
    "transform_passes", "run_passes",
    "verify_graph", "verify_program",
]
