"""Host clock around ``load_checkpoint`` in the resumed process."""

import runlog

UNIT = "s"


def read(run):
    restored = runlog.first(run, "restored")
    return restored["seconds"] if restored else None
