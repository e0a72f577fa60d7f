"""Cost-card fleet simulator (docs/simulator.md).

Discrete-event simulation of the serving fleet at 100–1000-replica
scale: the REAL policy stack (router dispatch/health/failover,
admission ladder, engine autotuner, fleet autoscaler, rollout
controller) runs unmodified over :class:`~easyparallellibrary_tpu.sim.
replica.SimReplica` members whose device step is a configured
:class:`~easyparallellibrary_tpu.sim.replica.CostModel` charge on a
virtual clock — policy search in seconds instead of cluster-hours,
with replay fidelity against a recorded real-fleet episode pinned in
CI (tests/test_sim_replay.py).
"""

from easyparallellibrary_tpu.sim.arrivals import (  # noqa: F401
    Workload, make_workload)
from easyparallellibrary_tpu.sim.engine import (  # noqa: F401
    EventQueue, SimClock, XorShift)
from easyparallellibrary_tpu.sim.faults import (  # noqa: F401
    FaultEvent, FaultInjector, death_and_recovery)
from easyparallellibrary_tpu.sim.fleet import (  # noqa: F401
    SimFleet, actuation_sequence)
from easyparallellibrary_tpu.sim.replica import (  # noqa: F401
    CostModel, SimReplica, SimReplicaDead)
