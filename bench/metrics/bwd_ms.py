"""Device ms a window step in the stage backward with its recompute and
gradient accumulation (scope ``bwd``), self time of its ops; nothing
without a trace."""


def read(record):
    return (record.get("phases") or {}).get("bwd_ms")
