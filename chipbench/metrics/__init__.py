"""One reader per per-layer metric: ``<metric>.py`` defines ``read(run)``,
which returns the number or None where the run holds nothing to read.
``serving.py`` holds what the serving readers share."""
