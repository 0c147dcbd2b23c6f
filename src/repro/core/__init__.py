# DINOMO's contribution, reproduced: ownership partitioning, adaptive
# caching, selective replication, log-structured writes w/ async merge,
# and the M-node policy engine -- on real data structures with exact RT
# accounting (the JAX/Pallas data plane lives in clht.py / log.py and
# src/repro/kernels; the serving integration in src/repro/kvcache).
from .cluster import (CLOVER, DINOMO, DINOMO_N, DINOMO_S, VARIANTS,
                      ArrayCloverCache, BatchResult, CloverCache,
                      DinomoCluster, VariantConfig)
from .dac import ArrayDAC, ArrayStaticCache, DAC, StaticCache
from .dpm_pool import DPMPool, FencedWrite
from .faults import (ALL_POINTS, ARMABLE_POINTS, CRASH_POINTS,
                     FaultPlane, KNCrash, LOG_MERGE_POINTS, Partition,
                     SlowSpec)
from .hashring import HashRing, stable_hash
from .linearizability import Op, check_history, check_key_history
from .mnode import Action, EpochStats, PolicyConfig, PolicyEngine
from .netmodel import (ArrivalProcess, DEFAULT_MODEL, NetModel,
                       PhasedArrival)
from .ownership import OwnershipMap, ReconfigEvent
from .requestplane import (OpRecord, RequestPlane, RequestPlaneConfig,
                           RequestPlaneResult)
from .simulate import TimedSimulation
from .telemetry import Telemetry
from .transition import (DacWindowPlan, StaticWindowPlan,
                         CloverReadPlan, plan_clover_reads,
                         plan_dac_window, plan_static_window)

__all__ = [
    "DinomoCluster", "VariantConfig", "BatchResult", "DINOMO",
    "DINOMO_S", "DINOMO_N",
    "CLOVER", "VARIANTS", "DAC", "ArrayDAC", "ArrayStaticCache",
    "StaticCache", "CloverCache", "ArrayCloverCache", "DPMPool",
    "FaultPlane", "KNCrash", "CRASH_POINTS", "ALL_POINTS",
    "ARMABLE_POINTS", "LOG_MERGE_POINTS", "FencedWrite", "Partition",
    "SlowSpec",
    "HashRing",
    "stable_hash", "Op", "check_history", "check_key_history", "Action",
    "EpochStats", "PolicyConfig", "PolicyEngine", "NetModel",
    "DEFAULT_MODEL", "ArrivalProcess", "PhasedArrival", "OpRecord",
    "RequestPlane", "RequestPlaneConfig", "RequestPlaneResult",
    "OwnershipMap", "ReconfigEvent", "TimedSimulation",
    "Telemetry", "DacWindowPlan", "StaticWindowPlan", "CloverReadPlan",
    "plan_dac_window", "plan_static_window", "plan_clover_reads",
]
