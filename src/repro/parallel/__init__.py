from repro.parallel.sharding import (batch_pspecs, param_pspecs,
                                     shard_ctx_for_mesh)

__all__ = ["param_pspecs", "batch_pspecs", "shard_ctx_for_mesh"]
