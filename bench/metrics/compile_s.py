"""Host-clock seconds of the first call of the jitted step less the
median step of the window: compilation or the compile cache's load."""
import statistics


def read(record):
    if not record["step_times"]:
        return None
    return record["first_step_s"] - statistics.median(record["step_times"])
