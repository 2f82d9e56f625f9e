from repro.sharding.specs import (shard, use_mesh, spec_for, named_sharding,
                                  logical_to_mesh, partitioned)

__all__ = ["shard", "use_mesh", "spec_for", "named_sharding", "logical_to_mesh",
           "partitioned"]
