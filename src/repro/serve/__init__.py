"""Serving: model families (paged transformer, recurrent RWKV6/Mamba),
the family protocol, continuous batching with SLA-aware admission and
preemption, and the chaos/fault-injection layer.

Layering: ``family`` defines the :class:`ServableFamily` protocol the
scheduler speaks; ``kv`` owns the paged KV pool; ``paged_lm`` binds the
transformer engine to it; ``recurrent_lm`` serves fixed-size-state models
out of donated state pools; ``scheduler`` drives any family; ``faults``
injects chaos and checks invariants — family-agnostically.
"""
from .drafter import Drafter, NGramDrafter, TinyLMDrafter
from .family import OutOfPages, ServableFamily
from .kv import PagedKVCache
from .paged_lm import PagedFamily, PagedLM, static_batch_generate
from .recurrent_lm import (
    RecurrentFamily,
    RecurrentLM,
    RecurrentStatePool,
    recurrent_reference_generate,
)
from .faults import (
    FaultPlan,
    InvariantViolation,
    check_scheduler_invariants,
    terminal_states,
)
from .scheduler import (
    TERMINAL_STATES,
    PrefixIndex,
    RejectReason,
    Request,
    RequestRejected,
    RequestState,
    Scheduler,
    SchedulerStalledError,
    ServeStats,
    build_prefill_rows,
)
