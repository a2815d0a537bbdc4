"""One small reader for each metric: a function of a finished ``Run``
that returns the number, or None where there is nothing to read (the
harness then leaves the metric out of the line).  A metric's file under
``chipbench/layers/`` names its reader as ``<module>:<function>``."""
