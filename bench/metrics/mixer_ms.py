"""Device ms a window step under any of the sequence mixer's scopes (its
architecture module's ``SCOPES``: ``attention``, ``wkv``), in any
phase, self time of its ops; nothing without a trace or where the mixer
names no scope."""


def read(record):
    return (record.get("phases") or {}).get("mixer_ms")
