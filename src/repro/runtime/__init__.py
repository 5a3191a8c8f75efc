"""JAX streaming runtime: operators, micro-batch streams, an executor that
enacts a planned Schedule on real JAX devices (the "Storm" substrate of the
reproduction), deterministic fault injection, and the live enactment layer
mirroring FleetController deltas onto running executors."""

from .operators import OPERATORS
from .stream import MicroBatch, SyntheticSource, VirtualClock, WallClock
from .chaos import (Fault, FaultEvent, FaultInjector, FaultKind, FaultPlan,
                    FaultTimeline, InjectedOperatorError, null_injector)
from .executor import (ExecutionReport, RebindInfo, RobustnessPolicy,
                       StreamExecutor)
from .enact import (EnactRecord, EnactmentLog, LiveFleet, transplant_map)

__all__ = [k for k in dir() if not k.startswith("_")]
