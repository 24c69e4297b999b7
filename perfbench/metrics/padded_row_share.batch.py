"""Share of the rows sent to the backend that were padding: ``EngineStats``
padded_rows over backend_rows plus padded_rows, between two snapshots taken
inside the traced window."""


def read(run):
    eng = run["counters"]["engine"]
    if "padded_rows" not in eng:
        return None
    sent = eng["backend_rows"] + eng["padded_rows"]
    if not sent:
        return None
    return 100.0 * eng["padded_rows"] / sent
