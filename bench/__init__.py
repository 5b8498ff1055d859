"""The chip benchmark: cells of BENCHMARK.json served through ServeEngine."""
