"""Peak device memory over the allocator's limit, in %, on the fullest
device, read once the window has closed and before any of the
benchmark's readers or its reference runs: the state made from the seed
and the program's steps.  The peak is that of the allocator's buffers
plus that of the memory the runtime reserves apart for XLA's
temporaries (``peak_bytes_reserved`` on a TPU), so it bounds the true
peak from above where the two peaks fall at different times."""


def read(record):
    fracs = [(s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0))
             / s["bytes_limit"]
             for s in record["memory"]
             if s.get("peak_bytes_in_use") and s.get("bytes_limit")]
    return 100.0 * max(fracs) if fracs else None
