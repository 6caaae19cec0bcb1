"""Images trained a second a chip: the median over the window's blocks."""
from benchmark.readers import samples_per_s_per_chip as read  # noqa: F401
