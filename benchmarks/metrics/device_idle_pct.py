"""One minus the union of the intervals in which an operation ran on the
device over the traced span, averaged over the devices."""

from reduce import xplane

UNIT = "%"


def read(run):
    if not run["reduced"]:
        return None
    busy = xplane.mean_over_devices(run["reduced"], "busy_s")
    span = xplane.mean_over_devices(run["reduced"], "span_s")
    return None if not span else 100.0 * (1.0 - busy / span)
