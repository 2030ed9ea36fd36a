"""Test bootstrap: property tests replay one fixed example sequence
(derandomized, no example database), so every run draws the same cases
and nothing is written into the checkout."""

from hypothesis import settings

settings.register_profile("repro", derandomize=True, database=None)
settings.load_profile("repro")
