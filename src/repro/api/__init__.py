"""Public deployment facade: ``compile() -> Deployment`` plus the spec
and artifact layers.  Re-exported at top level as ``repro.compile`` /
``repro.Deployment`` / ``repro.PlanSpec`` / ...

Only the lightweight pieces (the specs) import eagerly;
:func:`compile`/:class:`Deployment` and the artifact codecs load on
first touch so ``repro.core`` stays importable without JAX and
free of import cycles.
"""

from ._compat import lazy_exports
from .specs import (OBJECTIVE_PRESETS, SPEC_VERSION, DeploySpec, ExecSpec,
                    FleetSpec, ObjectiveSpec, PlanSpec, spec_from_dict)

_LAZY = {
    "compile": ("repro.api.deployment", "compile"),
    "Deployment": ("repro.api.deployment", "Deployment"),
    "artifacts": ("repro.api.artifacts", None),
    "SCHEMA_VERSION": ("repro.api.artifacts", "SCHEMA_VERSION"),
}

__all__ = ["PlanSpec", "ExecSpec", "DeploySpec", "FleetSpec",
           "ObjectiveSpec", "OBJECTIVE_PRESETS", "spec_from_dict",
           "SPEC_VERSION", "SCHEMA_VERSION", "compile", "Deployment",
           "artifacts"]

__getattr__, __dir__ = lazy_exports(__name__, globals(), _LAZY)
