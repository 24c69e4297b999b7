"""``rows_per_dispatch.batch`` in the one-row cells: how many one-row
requests the frontend merged into each batched replica call. At a fixed
offered load requests merge only while they queue behind a busy
dispatcher, so a faster host path brings it down towards 1."""
from perfbench.readings import reader

read = reader("rows_per_dispatch.batch")
