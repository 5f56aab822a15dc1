from repro.serve.engine import ServeEngine, ServeConfig  # noqa: F401
from repro.serve.kv_pool import PagedKVPool  # noqa: F401
from repro.serve.sampling import (  # noqa: F401
    GREEDY, SamplingParams, masked_logits, request_base_key, sample_tokens)
from repro.serve.scheduler import (  # noqa: F401
    BEST_EFFORT, ContinuousScheduler, DrainReport, InvalidRequest, LATENCY,
    PRIORITIES, Request, SchedulerConfig, ShedError, STANDARD)
from repro.serve.faults import (  # noqa: F401
    FaultEvent, FaultInjector, FaultPlan, run_chaos)
