"""Core packed-irregular-stream library (the paper's contribution, in JAX).

Public surface:

* :mod:`repro.core.streams` -- stream descriptors (the AXI-Pack request form).
* :mod:`repro.core.packing` -- functional pack/unpack semantics + traffic
  accounting (the reference semantics of the beat packer).
* :mod:`repro.core.busmodel` -- analytical BASE/PACK/IDEAL cycle model.
* :mod:`repro.core.banksim` -- cycle-approximate banked endpoint simulator.

These reproduce the paper's figures (``benchmarks/``).  The serving stack
(:mod:`repro.serve`) imports none of them: what a served kernel moves is
counted by the chip benchmark (``bench/``) from the model's configuration.
"""
from .streams import (
    BurstKind,
    ContiguousStream,
    IndirectStream,
    StridedStream,
    beats_for,
    elements_per_beat,
)
from .packing import (
    Traffic,
    indirect_traffic,
    pack_indirect,
    pack_strided,
    strided_traffic,
    unpack_indirect,
    unpack_strided,
)
from .busmodel import (
    BusConfig,
    System,
    WorkloadModel,
    Iteration,
    indirect_utilization_ceiling,
    stream_cycles,
)
from .banksim import (
    BankConfig,
    SimResult,
    crossbar_area_kge,
    indirect_utilization,
    simulate_stream,
    strided_utilization,
)
