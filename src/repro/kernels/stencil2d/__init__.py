"""Engine-driven fused rolling-buffer stencil interpreter (Pallas TPU).

The spec dataclasses formerly defined here live in
:mod:`repro.core.plan` (the KernelPlan IR); this package holds the pure
interpreter of that IR."""
from .kernel import build_call
from .ops import run_fused_stencil
from .ref import run_unfused_reference
