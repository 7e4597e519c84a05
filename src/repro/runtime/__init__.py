# Runtime: ExecutionPlan strategies (scan epoch engine + per-batch reference
# loop) behind the compile-step API, the phase-program trainer (TrainProgram
# over a project-once ActivationStore), fault-tolerant training loop
# (checkpoint/restart, stragglers, elastic restore), and the serving
# subsystem (ServiceConfig -> InferenceService -> ServePlan: batched /
# fused slot-batched decode / streaming), with the async engine
# (continuous batching + futures), latency telemetry, the Router
# serving fabric (per-tenant SLO scheduling over N engines), and the
# continual-learning tier (online Hebbian updates under live traffic with
# per-tenant adapters, drift detection, and snapshot/rollback) on top.
from repro.runtime.activations import ActivationStore, store_for
from repro.runtime.engine import AsyncEngine, EngineStopped, QueueFull
from repro.runtime.epoch_engine import (
    epoch_sharding,
    gather_batch,
    hidden_epoch_fn,
    readout_epoch_fn,
    sgd_epoch_fn,
    stack_epoch,
)
from repro.runtime.metrics import (
    Counter,
    DriftWindow,
    Gauge,
    Histogram,
    RouterMetrics,
    ServiceMetrics,
    TenantMetrics,
    format_latency_line,
)
from repro.runtime.plans import BatchPlan, ExecutionPlan, ScanPlan, make_plan
from repro.runtime.router import (
    DeadlineExceeded,
    NoEngineAvailable,
    Router,
    RouterConfig,
    RouterError,
    RouterStopped,
    TenantConfig,
    TenantQueueFull,
)
from repro.runtime.program import (
    BcpnnReadoutPhase,
    HiddenPhase,
    SgdReadoutPhase,
    TrainProgram,
    compile_program,
    run_program,
)
from repro.runtime.service import (
    SERVE_PLANS,
    BatchedPlan,
    Completion,
    DecodePlan,
    DecodeSession,
    InferenceService,
    Request,
    ServePlan,
    ServiceConfig,
    StreamingPlan,
    pad_cache_like,
    serve_fleet,
    serve_model,
)
from repro.runtime.serve_loop import ServeSession
from repro.runtime.trace import (
    DeadlineShed,
    EngineRestart,
    EventJournal,
    MergeApplied,
    RecompileRebaseline,
    RollbackApplied,
    SpanRecord,
    TenantShed,
    TraceConfig,
    Tracer,
    build_tracer,
)
from repro.runtime.export import (
    MetricsServer,
    OpenMetricsError,
    parse_openmetrics,
    render_openmetrics,
)
from repro.runtime.train_loop import TrainLoopConfig, TrainLoopResult, train_loop

# The continual tier imports repro.core.compiled (NetworkState,
# build_forward), and core.compiled imports repro.runtime.plans — an
# eager import here would re-enter core.compiled while it is still
# initializing.  PEP 562 defers the continual names until first access.
_CONTINUAL_NAMES = (
    "ContinualConfig", "ContinualPlan", "DriftDetected", "Feedback",
    "MERGE_STRATEGIES",
)


def __getattr__(name):
    if name in _CONTINUAL_NAMES:
        from repro.runtime import continual

        return getattr(continual, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "ActivationStore", "store_for",
    "AsyncEngine", "EngineStopped", "QueueFull",
    "ContinualConfig", "ContinualPlan", "DriftDetected", "Feedback",
    "MERGE_STRATEGIES",
    "Counter", "DriftWindow", "Gauge", "Histogram", "ServiceMetrics",
    "TenantMetrics", "RouterMetrics", "format_latency_line",
    "Router", "RouterConfig", "RouterError", "RouterStopped", "TenantConfig",
    "TenantQueueFull", "DeadlineExceeded", "NoEngineAvailable",
    "epoch_sharding", "gather_batch", "hidden_epoch_fn", "readout_epoch_fn",
    "sgd_epoch_fn", "stack_epoch",
    "BatchPlan", "ExecutionPlan", "ScanPlan", "make_plan",
    "BcpnnReadoutPhase", "HiddenPhase", "SgdReadoutPhase",
    "TrainProgram", "compile_program", "run_program",
    "TrainLoopConfig", "TrainLoopResult", "train_loop",
    "SERVE_PLANS", "BatchedPlan", "Completion", "DecodePlan", "DecodeSession",
    "InferenceService", "Request", "ServePlan", "ServiceConfig",
    "StreamingPlan", "pad_cache_like", "serve_model", "serve_fleet",
    "ServeSession",
    # Observability (repro.runtime.trace / repro.runtime.export).  The
    # trace module's DriftDetected *event* is deliberately not re-exported:
    # the continual tier's exception keeps that name here.
    "TraceConfig", "Tracer", "build_tracer", "SpanRecord", "EventJournal",
    "EngineRestart", "MergeApplied", "RollbackApplied",
    "RecompileRebaseline", "DeadlineShed", "TenantShed",
    "MetricsServer", "OpenMetricsError", "parse_openmetrics",
    "render_openmetrics",
]
