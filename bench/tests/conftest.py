"""Tests of the benchmark's own code, on the CPU."""
import repro  # noqa: F401  (x64 on, as the harness has it)
