"""Whole SFNO step: ``step_mfu``'s reading on the configuration's counts
(``bench/counts/sfno.py``): the algorithm's operations per member-step
times the member-steps completed, over the time to the last completion
and the chip's peak, as a percentage."""

from bench.metrics.step_mfu import read  # noqa: F401
