"""The benchmark of tpu-llm-trainer: one command runs one cell once on the chip.

Everything a later PR may not change lives here: traffic generation, the
reduction from traces and spans to metrics, the table of peaks, the FLOP and
byte counts, the plain reference and the comparison that decides ``correct``.
From the program (``tpu_trainer``) it takes only the system under test.
See ``perf/README.md``.
"""
