"""``device_idle_share.batch`` in the one-row cells: the share of the traced
window in which no operation ran on the chip. The offered load is fixed,
so the device's work is too: a faster walk, or fewer calls for the same
rows, leaves the chip idle longer."""
from perfbench.readings import reader

read = reader("device_idle_share.batch")
