"""ECI-Cache core: URD/TRD analysis, MRC partitioning, write policies.

Public API re-exports for the paper's primary contribution.
"""
from repro.core.baselines import GlobalLRUManager, make_manager
from repro.core.batch_sim import (reuse_distances_fast, simulate_batch,
                                  simulate_many, stack_distances)
from repro.core.characterize import (PhaseDetector, PhaseEvent,
                                     WindowFeatures, characterize_trace,
                                     characterize_windows)
from repro.core.device_pipeline import (DeviceWindowPipeline, WindowDecision,
                                        greedy_walk_device,
                                        monitor_window_device,
                                        transfer_sanitizer)
from repro.core.faults import (FAULT_KINDS, FaultPlan, FaultSpec,
                               InjectedFault)
from repro.core.guard import GuardReport, validate_decision
from repro.core.manager import (AnalyzerDecision, DegradeEvent,
                                ECICacheManager, ReconfigEvent, TenantState)
from repro.core.monitor import MonitorResult, analyze_windows
from repro.core.mrc import (BatchedHitRatioFunctions, HitRatioFunction,
                            build_hit_ratio_function,
                            build_hit_ratio_functions)
from repro.core.partitioner import (PartitionResult, aggregate_latency,
                                    greedy_allocate, pgd_solve,
                                    two_level_solve)
from repro.core.reuse_distance import (RDResult, auto_sample_rate, max_rd,
                                       reuse_distances,
                                       reuse_distances_vectorized,
                                       sampled_reuse_distances, shards_salt,
                                       urd_cache_blocks)
from repro.core.simulator import (LRUCache, SimResult, rebalance_levels,
                                  simulate)
from repro.core.stage_profile import StageProfile
from repro.core.trace import (AccessClass, Trace, TraceError,
                              classify_accesses, request_type_mix,
                              total_cache_writes_wb, validate_trace,
                              validate_trace_arrays)
from repro.core.write_policy import (WritePolicy, assign_write_policy,
                                     assign_write_policy_levels, write_ratio)

__all__ = [
    "AccessClass", "AnalyzerDecision", "BatchedHitRatioFunctions",
    "DegradeEvent", "DeviceWindowPipeline", "ECICacheManager",
    "FAULT_KINDS", "FaultPlan", "FaultSpec", "GlobalLRUManager",
    "GuardReport", "HitRatioFunction", "InjectedFault", "LRUCache",
    "MonitorResult", "PartitionResult",
    "PhaseDetector", "PhaseEvent", "RDResult", "ReconfigEvent", "SimResult",
    "StageProfile", "TenantState", "Trace", "TraceError", "WindowDecision",
    "WindowFeatures", "WritePolicy",
    "aggregate_latency",
    "analyze_windows", "assign_write_policy", "assign_write_policy_levels",
    "auto_sample_rate", "build_hit_ratio_function",
    "build_hit_ratio_functions", "characterize_trace",
    "characterize_windows", "classify_accesses",
    "greedy_allocate", "greedy_walk_device", "make_manager", "max_rd",
    "monitor_window_device", "pgd_solve",
    "rebalance_levels", "request_type_mix", "reuse_distances",
    "reuse_distances_fast", "reuse_distances_vectorized",
    "sampled_reuse_distances", "shards_salt",
    "simulate", "simulate_batch", "simulate_many", "stack_distances",
    "total_cache_writes_wb", "transfer_sanitizer", "two_level_solve",
    "urd_cache_blocks",
    "validate_decision", "validate_trace", "validate_trace_arrays",
    "write_ratio",
]
