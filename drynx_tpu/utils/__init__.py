"""Host-side utilities: phase timers, config, data generation."""
from .timers import PhaseTimers  # noqa: F401
