"""Tests of the benchmark's own code; they run on the CPU."""
