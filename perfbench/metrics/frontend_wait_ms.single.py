"""``frontend_wait_ms.batch`` in the one-row cells: the mean admission-queue
wait of the requests the frontend dispatched, which a one-row request
pays on top of its call."""
from perfbench.readings import reader

read = reader("frontend_wait_ms.batch")
