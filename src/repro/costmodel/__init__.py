"""MAESTRO-style analytical DNN-accelerator cost model (the ConfuciuX Env).

Public API:
  LayerSpec / layers_to_array   -- workload descriptors
  evaluate / evaluate_batch     -- latency/energy/area/power for design points
  soft_evaluate / soft_model_cost -- differentiable relaxation (see maestro)
  content_hash                  -- cache-versioning hash of the model sources
  PE_LEVELS / KT_LEVELS         -- the paper's L=12 coarse action tables
  workloads                     -- paper DNNs + assigned-architecture lowering
"""
from repro.costmodel.layers import (
    LayerSpec,
    layers_to_array,
    CONV,
    DWCONV,
    GEMM,
    BMM,
    EXPERTS,
    NUM_FIELDS,
)
from repro.costmodel.dataflows import (
    DLA,
    EYE,
    SHI,
    DATAFLOW_NAMES,
    pe_levels,
    kt_levels,
    PE_LEVELS,
    KT_LEVELS,
)
from repro.costmodel.maestro import (
    CostOut,
    content_hash,
    evaluate,
    evaluate_point,
    model_cost,
    soft_evaluate,
    soft_model_cost,
)

__all__ = [
    "LayerSpec",
    "layers_to_array",
    "CONV",
    "DWCONV",
    "GEMM",
    "BMM",
    "EXPERTS",
    "NUM_FIELDS",
    "DLA",
    "EYE",
    "SHI",
    "DATAFLOW_NAMES",
    "pe_levels",
    "kt_levels",
    "PE_LEVELS",
    "KT_LEVELS",
    "CostOut",
    "content_hash",
    "evaluate",
    "evaluate_point",
    "model_cost",
    "soft_evaluate",
    "soft_model_cost",
]
