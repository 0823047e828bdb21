from repro.distributed import fault_tolerance, sharding
