"""Share of the traced window in which no XLA operation ran on a device,
in %, averaged over the cell's devices; nothing without a trace."""


def read(record):
    trace = record["trace"]
    if not trace:
        return None
    shares = [d["idle_share"] for d in trace["devices"].values()]
    return 100.0 * sum(shares) / len(shares)
