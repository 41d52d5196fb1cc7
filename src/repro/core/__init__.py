"""Covenant compiler core — the paper's contribution.

Pipeline: ``library`` Codelets -> named pass pipeline (``pipeline``:
placement, compute mapping, Algorithm-1 tiling, transfer insertion,
vectorize / unroll / pack, macro-mnemonic ``codegen``) -> ``stream``
execution, with ``interp`` (functional) and ``cost`` (analytic cycles) as
cross-checks.  ``targets`` holds the predefined ACGs; ``driver`` is the
user-facing ``repro.compile()`` entry point with the content-addressed
compile cache, schedule ``search`` (beam, with exhaustive as the oracle,
materialising candidates through the pipeline) and the disk-backed
``store``.
``scheduler.schedule`` / ``codegen.generate`` remain as thin stable
wrappers over the pipeline stages.
"""
from . import (acg, codegen, codelet, cost, covenant, driver, dtypes, interp,
               library, passes, pipeline, scheduler, search, semantics, spec,
               store, stream, targets)
from .acg import ACG, Capability, ComputeNode, Edge, MemoryNode, cap, ospec
from .codelet import Codelet, Compute, Loop, Ref, Surrogate, Transfer, ref, v
from .covenant import (CovenantError, CovenantViolation, check_covenant,
                       validate_acg)
from .driver import (CompiledArtifact, available_targets, cache_stats,
                     clear_cache, compile, compile_many, register_target)
from .dtypes import Dtype, dt
from .pipeline import CompileOptions, PassContext, Pipeline, PipelineError
from .scheduler import ScheduleConfig, schedule
from .search import SearchOptions, SearchResult
from .spec import ACGSpec, SpecError, acg_spec, validate_spec
from .store import ArtifactStore
from .targets import get_spec, get_target, list_targets, register_spec

__all__ = [
    "ACG", "ACGSpec", "ArtifactStore", "Capability", "Codelet",
    "CompileOptions", "CompiledArtifact", "Compute", "ComputeNode",
    "CovenantError", "CovenantViolation", "Dtype", "Edge", "Loop",
    "MemoryNode", "PassContext", "Pipeline", "PipelineError", "Ref",
    "ScheduleConfig", "SearchOptions", "SearchResult", "SpecError",
    "Surrogate", "Transfer", "acg", "acg_spec", "available_targets",
    "cache_stats", "cap", "check_covenant", "clear_cache", "codegen",
    "codelet", "compile", "compile_many", "cost", "covenant", "driver",
    "dt", "dtypes", "get_spec", "get_target", "interp", "library",
    "list_targets", "ospec", "passes", "pipeline", "ref", "register_spec",
    "register_target", "schedule", "scheduler", "search", "semantics",
    "spec", "store", "stream", "targets", "v", "validate_acg",
    "validate_spec",
]
