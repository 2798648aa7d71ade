"""The yardstick: BENCHMARK.json's command, its traffic, references, peaks,
trace reduction and per-layer readers. Nothing here is imported by the
program, and only this directory's runners import the program."""
