from repro.serving.engine import (AllocatorInvariantError, EngineCrashError,
                                  EngineStallError, IterStats, PapiEngine,
                                  ServeRequest, ServeResult, TokenEvent)
from repro.serving.faults import FaultInjector, parse_fault_specs
from repro.serving.journal import (FinishedRequest, Journal, RecoveredRequest,
                                   RecoveredState, read_records, recover,
                                   replay, write_snapshot)
from repro.serving.kv_pages import (BlockTables, PageAllocator, PagedKVManager,
                                    PageStats)
from repro.serving.metrics import latency_summary, percentile
from repro.serving.sampler import greedy, sample
from repro.serving.telemetry import (NULL_TRACER, Event, NullTracer, Tracer,
                                     export_chrome, export_jsonl,
                                     export_prometheus, phase, write_trace)

__all__ = ["AllocatorInvariantError", "BlockTables", "EngineCrashError",
           "EngineStallError", "Event", "FaultInjector", "FinishedRequest",
           "IterStats", "Journal", "NULL_TRACER", "NullTracer",
           "PageAllocator", "PagedKVManager", "PageStats", "PapiEngine",
           "RecoveredRequest", "RecoveredState", "ServeRequest",
           "ServeResult", "TokenEvent", "Tracer", "export_chrome",
           "export_jsonl", "export_prometheus", "greedy", "latency_summary",
           "parse_fault_specs", "percentile", "phase", "read_records",
           "recover", "replay", "sample", "write_snapshot", "write_trace"]
