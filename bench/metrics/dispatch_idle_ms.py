"""Device idle ms a window step while the host dispatches the jitted
step (the program's ``train.dispatch`` span); nothing without a
trace."""


def read(record):
    return (record.get("phases") or {}).get("dispatch_idle_ms")
