"""The benchmark's own yardstick: generators, references, the trace
reduction, the work counts of each kernel and step, and the plumbing that
turns one cell of ``BENCHMARK.json`` into one result line.

Nothing here is imported by the program under test, and nothing here
imports the program except the runners under ``bench/kinds/``, which
hold the system under test and read its spans and counters."""
