"""Drivers: the code that puts the system under test on the clock.  One per
kind of entry point (``sim``: the in-mesh simulator behind ``FedMLRunner``;
a cell of another entry point brings its own); a traffic file names its kind
under ``driver`` and the harness imports ``benchmark.drivers.<kind>``.  These
are the only files of the benchmark that import the program."""
