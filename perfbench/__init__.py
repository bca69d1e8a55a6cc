"""perfbench: the repo's benchmark (BENCHMARK.json at the root names its cells).

Everything here is the yardstick: traffic generation, the reduction from
traces and spans to metrics, the table of peaks, the operation counts, the
plain references and the comparison that decides ``correct``.  From the
program it takes only the system under test.  README.md says how to add a
configuration, a traffic mix, a metric or a cell as files.
"""
