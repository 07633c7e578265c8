"""Test support: deterministic fault injection for the storage / I-O /
pipeline stack (every failure mode the self-healing path claims to
handle is drivable from tests and the chaos soak), and the numpy oracles
that the soak and the on-chip smoke check the engine against."""
from repro.testing.faults import FaultInjector, FaultyBlockStore
from repro.testing.oracles import oracle_average, oracle_stock

__all__ = ["FaultInjector", "FaultyBlockStore", "oracle_average",
           "oracle_stock"]
