"""The benchmark's yardstick: traffic generation, the plain reference,
metric readers and the trace reduction. Nothing here is imported by the
program, and nothing here imports the program except ``client.py`` and
``harness.py``, which drive the system under test."""
