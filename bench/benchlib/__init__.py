"""The on-chip benchmark's yardstick: traffic generation, seeded weights,
counts of operations and bytes, the peaks table, trace reduction and the
lookup of cells, configurations, traffic mixes, jobs and metric readers
by the names ``BENCHMARK.json`` gives them."""
