from repro.runtime import faults
from repro.runtime import racecheck

__all__ = ["faults", "racecheck"]
