"""Degrade-and-heal resilience supervisor.

Turns the PR-1 static fault policies into an adaptive loop:

- ``breaker``: the closed/open/half-open ``CircuitBreaker`` state
  machine with decayed failure windows (injectable clock);
- ``domains``: ``FaultDomain`` tracking keyed (subsystem, backend,
  file identity), the ``DemotionLadder`` that demotes decode planes
  native -> zlib mid-run (byte-identical results) and heals
  back via half-open probes, and the upgraded quarantine circuit;
- ``chaos``: named fault points past the byte-source layer (pool
  submission, native decode, deflate workers, transport disconnects) with seed-derived deterministic schedules.

Everything here is host-local policy — no jax, no collectives — so it
is safe to consult from pool workers, the serve dispatcher, and client
threads alike.
"""
from hadoop_bam_tpu.resilience.breaker import (       # noqa: F401
    CLOSED, HALF_OPEN, OPEN, CircuitBreaker, DecayingWindow,
)
from hadoop_bam_tpu.resilience.domains import (       # noqa: F401
    PLANES, DemotionLadder, FaultDomain, FaultDomainRegistry,
    check_quarantine_gate, decode_ladder, file_ident, quarantine_breaker,
    quarantine_run_ok, registry, reset,
)
from hadoop_bam_tpu.resilience import chaos           # noqa: F401
