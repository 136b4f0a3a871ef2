"""The paper's primary contribution: MPIX Threadcomm adapted to JAX.

- comm.py:        the unified ``Comm`` API — root ThreadComm, split/dup
                  sub-communicators, Request-based nonblocking ops, and
                  stream-bound contexts (MPIX stream analogue)
- threadcomm.py:  back-compat facade over comm.py
- schedules.py:   dissemination/binomial/ring/recursive-doubling schedules
- collectives.py: executable shard_map collectives (explicit + fused + 2-level)
- p2p.py:         rank-addressed messaging w/ eager|1-copy protocol selection
- protocol.py:    the Fig.3 latency/bandwidth protocol model
- compat.py:      shard_map/make_mesh facade (all-auto mesh axes)
"""

from repro.core.comm import (AxisComm, Comm, CommError, CommStream,  # noqa: F401
                             Group, GroupComm, Request, ThreadComm,
                             ThreadCommError, threadcomm_init, testall,
                             waitall)
from repro.core import collectives, p2p, protocol, schedules  # noqa: F401
