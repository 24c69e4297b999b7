"""Rows answered over the time they took: every request sent in the
window, from the window's start to the last answer. Counting only the
requests answered inside the window would drop or keep a whole request of
up to 1024 rows by a hair's breadth at its end."""


def read(run):
    return run["rows_ok"] / run["seconds"]
