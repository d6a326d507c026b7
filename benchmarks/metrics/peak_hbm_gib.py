"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window."""

import runlog

UNIT = "GiB"


def read(run):
    peaks = [e["peak_bytes"] for e in runlog.of(run, "end")
             if e.get("peak_bytes") is not None]
    return max(peaks) / 2 ** 30 if peaks else None
