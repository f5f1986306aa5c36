"""Serving benchmark harness: drives ``DisaggEngine`` under seeded traffic
on one TPU and reduces host stamps and a profiler trace to metrics."""
