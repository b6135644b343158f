from repro.parallel.sharding import (LOGICAL_RULES, MeshCtx, ParamSpec,
                                     global_shape_dtypes, infer_shardings,
                                     pad_to_multiple, padded, smap,
                                     spec_pspecs)

__all__ = ["LOGICAL_RULES", "MeshCtx", "ParamSpec", "global_shape_dtypes",
           "infer_shardings", "pad_to_multiple", "padded", "smap",
           "spec_pspecs"]
