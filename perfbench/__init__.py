"""perfbench: the benchmark of dryad_tpu, driven by ``BENCHMARK.json``.

Everything that defines a measurement lives in this directory: data
generation, the traffic generator, the reduction from trace to metrics,
the table of peaks, the byte counts of the rooflines, the plain numpy
references and the comparison that decides ``correct``.  From the
program it takes only the system under test (``Context``, ``Dataset``,
``sql``) and its events.  See PERF.md.
"""
