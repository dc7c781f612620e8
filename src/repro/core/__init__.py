"""RAPID-Serve core: scheduler/executor split serving engine + policies.

Serving API v2 (see README "Serving API v2"): ``Engine`` drives a pure
``Scheduler`` policy and an ``Executor`` pricing backend on the injected
event loop and emits a typed request-lifecycle event stream.
"""
from repro.core.engines import (  # noqa: F401
    BaseEngine, DisaggEngine, Engine, HybridEngine, RapidEngine,
    drive, kv_pool_blocks, make_engine,
)
from repro.core.events import (  # noqa: F401
    CancelledEvent, EventStream, FinishedEvent, PhaseEvent, RejectedEvent,
    TokenEvent,
)
from repro.core.executor import (  # noqa: F401
    DeviceExecutor, Executor, PerfModelExecutor, StepOutputs,
    configure_compile_cache,
)
from repro.core.preemption import (  # noqa: F401
    DEFAULT_PREEMPTION, PreemptionPolicy,
)
from repro.core.request import Request, State  # noqa: F401
from repro.core.resource_manager import (  # noqa: F401
    AdaptiveResourceManager, Allocation, DecodeProfile,
    build_decode_profile,
)
from repro.core.scheduler import (  # noqa: F401
    SCHEDULERS, DisaggScheduler, HybridScheduler, RapidScheduler,
    SchedView, Scheduler, StepPlan, Wake, make_scheduler,
)
