"""One import point for the property-test modules' ``hypothesis`` API:
``given`` / ``settings`` / ``st``."""

from hypothesis import given, settings
from hypothesis import strategies as st

__all__ = ["given", "settings", "st"]
