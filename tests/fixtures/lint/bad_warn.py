"""Seeded P001 fixture. The path passed to the linter in the test
carries a ``src/repro/`` prefix so the in-repro rules apply. NEVER
imported — parsed by the lint tests only."""
from jax.experimental import pallas as pl                    # P001
