"""The benchmark of the served path (BENCHMARK.json, PERF.md): yardstick code
and data only. Nothing under gome_tpu/ imports from here."""
