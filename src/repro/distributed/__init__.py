"""Distribution: sharding rules, steps, fault tolerance, compression."""

from .sharding import (param_pspecs, batch_pspec, cache_pspecs,  # noqa: F401
                       named_shardings, key_shard_mesh,
                       stacked_store_sharding, vary_like)
