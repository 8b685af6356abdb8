"""Drivers of the systems under test, one module per ``system`` that a
configuration file names. Each exposes ``run(job) -> Outcome``."""
