"""Launchers: mesh construction, HLO collective analysis, and the
production-scale dry-run of the sharded closure.

NOTE: ``closure_dryrun`` must be run as the process entry point (it sets
XLA_FLAGS before jax initializes); do not import it from an
already-initialized process and expect 512 devices.
"""
from .mesh import make_production_mesh, make_test_mesh

__all__ = ["make_production_mesh", "make_test_mesh"]
