"""Target positions trained a second a chip (each has its source position
beside it): the median over the window's blocks."""
from benchmark.readers import samples_per_s_per_chip as read  # noqa: F401
