"""Device ms a window step in the stage forward (scope ``fwd``: each
tick's F phase), self time of its ops; nothing without a trace."""


def read(record):
    return (record.get("phases") or {}).get("fwd_ms")
