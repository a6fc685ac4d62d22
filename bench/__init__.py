"""The chip benchmark of the DiverseFL engine (see ``BENCHMARK.json``).

Everything the benchmark measures with lives here and nowhere else:
the traffic generator, the configurations with their plain references,
the per-layer metric readers, the table of peaks and the comparison
that decides ``correct``.  From the program under ``src/`` it takes
only the system under test: ``Federation``, ``RoundEngine`` and the
models it trains.
"""
