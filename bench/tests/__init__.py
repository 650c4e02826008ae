"""CPU tests of the benchmark under ``bench/``."""
