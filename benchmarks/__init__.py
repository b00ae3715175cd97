"""The benchmark: one command runs one cell once (``python3 benchmarks/run.py``).

Everything that decides a number lives under this directory, where a PR that
claims a gain cannot change it: the traffic generator, the reduction from
trace and spans to metrics, the table of peaks, the operation and byte counts,
the plain reference and the comparison that decides ``correct``. From the
program it takes the system under test (``Learner``, ``run_anakin_train``),
its stage spans and counters, and the names of its scopes and kernels.

A cell, a configuration (with the plain reference and the scope table it
names), a traffic mix, a runner or a per-layer metric is a file found by the
name ``BENCHMARK.json`` or a data file gives it; adding one edits no file that
is here. ``PERF.md`` says why each exists.
"""
