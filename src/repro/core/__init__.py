"""HFAV core: the paper's fusion/vectorization engine as a JAX module."""

#: Build stamp folded into on-disk plan-cache keys and entry headers
#: (repro.core.plancache): bump alongside behavior changes that should
#: invalidate persisted plans without a schema change.
__version__ = "0.9.0"

from .codegen_jax import Generated
from .codegen_pallas import PallasGenerated, plan_pallas
from .engine import (BACKENDS, BatchedGenerated, clear_compile_cache,
                     compile_batched, compile_cache_size,
                     compile_program, explain, pallas_auto_viable,
                     plan_cache_cap, plan_cache_size, program_signature,
                     register_pallas_split_win, set_plan_cache_cap)
from .fusion import FusedSchedule, Unfusable, fuse_inest_dag
from .infer import IDAG, InferenceError, infer
from .dataflow import build_dataflow
from .interpreters import (InterpreterSpec, PlanUnsupported, execute_plan,
                           get_interpreter, register_interpreter,
                           registered_interpreters, unregister_interpreter)
from .layoutapply import (APPLY_MODES, HANDLED_HINTS, LayoutApplyResult,
                          apply_layout, render_apply, resolve_apply_mode)
from .plan import (PLAN_FEATURES, SCHEMA_VERSION, CallPlan, KernelPlan,
                   LanePass, LayoutHint, PallasUnsupported,
                   PlanSerializationError, VecLoadPlan, fn_key,
                   register_step_builder, unregister_step_builder)
from .plancache import PlanCache, program_plan_key
from .plancheck import (Diagnostic, PlanCheckError, PlanCheckWarning,
                        check_plan, has_errors, sizes_from_arrays,
                        vmem_bytes, vmem_report)
from .tiling import pad_to_lane
from .vecscan import (ACCESS_CLASSES, AccessSite, VecReport,
                      attach_layout_hints, auto_vec_reject, render_vec,
                      scan_plan)
from .reuse import analyze_storage, reuse_graph, reuse_order
from .rules import Extent, KernelRule, Program, axiom, goal, kernel
from .terms import Term, parse_term, unify_term

__all__ = [
    "ACCESS_CLASSES", "APPLY_MODES", "AccessSite",
    "BACKENDS", "BatchedGenerated", "CallPlan", "Diagnostic", "Generated",
    "HANDLED_HINTS", "compile_batched",
    "InterpreterSpec",
    "KernelPlan", "LanePass", "LayoutApplyResult", "LayoutHint",
    "PallasGenerated", "PallasUnsupported", "PlanCache", "PlanCheckError",
    "PlanCheckWarning", "PlanSerializationError", "PlanUnsupported",
    "PLAN_FEATURES",
    "SCHEMA_VERSION", "VecLoadPlan", "VecReport", "apply_layout",
    "attach_layout_hints",
    "auto_vec_reject", "check_plan", "clear_compile_cache",
    "compile_cache_size", "execute_plan", "get_interpreter", "has_errors",
    "pad_to_lane", "register_interpreter", "registered_interpreters",
    "render_apply", "render_vec", "resolve_apply_mode", "scan_plan",
    "sizes_from_arrays",
    "unregister_interpreter", "vmem_bytes",
    "vmem_report",
    "compile_program", "fn_key",
    "pallas_auto_viable", "plan_cache_cap", "plan_cache_size", "plan_pallas",
    "program_plan_key", "program_signature", "register_pallas_split_win",
    "register_step_builder", "set_plan_cache_cap", "unregister_step_builder",
    "explain", "FusedSchedule", "Unfusable",
    "fuse_inest_dag", "IDAG", "InferenceError", "infer", "build_dataflow",
    "analyze_storage", "reuse_graph", "reuse_order", "Extent", "KernelRule",
    "Program", "axiom", "goal", "kernel", "Term", "parse_term", "unify_term",
]
