"""The chip benchmark: cells of BENCHMARK.json run by ``python bench/run.py``.

Everything the benchmark measures with lives here: traffic generators, the
plain references and their comparison, the reduction of profiler traces,
the table of peaks and the per-layer metric readers.  See ``README.md``.
"""
