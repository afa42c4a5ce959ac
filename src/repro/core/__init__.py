"""repro.core — the paper's contribution: paged virtual tensor memory.

Layers (DESIGN.md §3):
  vmem            page tables, frame allocator, translation, traces
  tlb             tree-PLRU TLB + shared-MMU trace simulator
  faults          precise page faults, vstart resume protocol
  context_switch  preemption spill/restore of vector state
  counters        perf counters, snapshot FIFO, host spans
  costmodel       AraOS cycle constants + TPU roofline constants
"""

from repro.core.context_switch import ContextSwitcher, SpilledState, SwitchStats
from repro.core.costmodel import CostModel
from repro.core.counters import PerfCounters
from repro.core.faults import OutOfPagesError, PageFault, ResumeCursor
from repro.core.tlb import (
    SCALAR,
    VECTOR,
    AccessEvent,
    OverheadReport,
    SharedMMUSimulator,
    TLB,
    interleave,
)
from repro.core.vmem import (
    INVALID_PAGE,
    PagePool,
    SeqState,
    VMemConfig,
    VirtualMemory,
    burst_trace,
    element_trace,
    gather_pages,
    logical_to_physical,
)

__all__ = [
    "AccessEvent",
    "ContextSwitcher",
    "CostModel",
    "INVALID_PAGE",
    "OutOfPagesError",
    "OverheadReport",
    "PageFault",
    "PagePool",
    "PerfCounters",
    "ResumeCursor",
    "SCALAR",
    "SeqState",
    "SharedMMUSimulator",
    "SpilledState",
    "SwitchStats",
    "TLB",
    "VECTOR",
    "VMemConfig",
    "VirtualMemory",
    "burst_trace",
    "element_trace",
    "gather_pages",
    "interleave",
    "logical_to_physical",
]
