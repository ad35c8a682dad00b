"""Shared pieces of the benchmark: the registry that finds configs,
traffic mixes, cells and metrics by name; the device and span
helpers; the trace reduction; the peaks table; the traffic generator;
the weights maker; the plain SHARK reference; the serve and train
tasks."""
