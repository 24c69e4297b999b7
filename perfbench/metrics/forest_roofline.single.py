"""``forest_roofline`` over the calls of the one-row cell: the forest's
share of its roofline on calls of one or a few rows, which moves the
median latency there and not a rate."""
from perfbench.readings import reader

read = reader("forest_roofline")
