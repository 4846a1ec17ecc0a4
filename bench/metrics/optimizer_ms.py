"""Device ms a window step in the optimizer's updates and the gradient
mean (scope ``optimizer``), self time of its ops; nothing without a
trace."""


def read(record):
    return (record.get("phases") or {}).get("optimizer_ms")
