"""The yardstick: traffic generation, client stamps, window arithmetic,
trace and counter reduction, seeded weights and the correctness check."""
