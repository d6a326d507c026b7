"""End of the restore to the end of the first resumed step: the step
program read from the compile cache, loaded and run once."""

import runlog

UNIT = "s"


def read(run):
    restored, fetches = runlog.first(run, "restored"), runlog.resumed_fetches(run)
    if restored is None or not fetches:
        return None
    return fetches[0]["t"] - restored["t"]
