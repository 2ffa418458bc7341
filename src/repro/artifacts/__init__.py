"""Persistent program artifacts: synthesize once, start warm forever.

:class:`ArtifactStore` persists converged synthesis results — plan,
graph, modes, audit reports, prepared weights, and (where ``jax.export``
supports the platform) serialized Stage-D executables — keyed by the
program fingerprint.  ``synthesize(artifact_store=...)`` and the serving
tier's :class:`~repro.serving.program_cache.ProgramCache` use it to skip
the fixed-point loop and Stage-D compiles on restart (DESIGN.md §13).
"""
from .codec import ArtifactCodecError
from .store import (ARTIFACT_SCHEMA_VERSION, ArtifactError, ArtifactStore,
                    synthesis_request_key)

__all__ = [
    "ARTIFACT_SCHEMA_VERSION",
    "ArtifactCodecError",
    "ArtifactError",
    "ArtifactStore",
    "synthesis_request_key",
]
