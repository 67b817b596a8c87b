"""Traffic scenarios, one file each: ``generate(key, cdf, n_objects, length,
params) -> (length,) int32`` ids, a pure function of the PRNG key."""
