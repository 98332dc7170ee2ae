"""The benchmark of cake-tpu's served path. See PERF.md and BENCHMARK.json.

Everything that decides a number lives here, where later PRs cannot change
it: traffic generation, the client's clock, percentiles, the table of peaks,
the functions that count a step's bytes, the reduction from the profiler's
trace to metrics, the plain reference and the comparison behind ``correct``.
From the program the benchmark takes the server (``cake_tpu.cli.main``), its
HTTP routes and the names the compiler gives its device programs.
"""
