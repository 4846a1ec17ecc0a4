"""Device ms a window step in the final norm, LM head, loss and their
gradients (scope ``head``), self time of its ops; nothing without a
trace."""


def read(record):
    return (record.get("phases") or {}).get("head_ms")
