"""Process start to the first request the window may send: the forest's
fit from the seed, the server's start, backend build, compile-cache hits,
warm-up of every batch shape the mix reaches, and the clients' connects."""


def read(run):
    return run["setup_s"]
