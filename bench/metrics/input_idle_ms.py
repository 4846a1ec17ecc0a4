"""Device idle ms a window step while the host loads the batch (the
program's ``train.load`` and ``loader.*`` spans); nothing without a
trace."""


def read(record):
    return (record.get("phases") or {}).get("input_idle_ms")
