"""Collective-operation time during which no other operation ran on that
device, over the traced span; the worst of the devices."""

UNIT = "%"


def read(run):
    if not run["reduced"]:
        return None
    shares = [100.0 * d["collective_exposed_s"] / d["span_s"]
              for d in run["reduced"]["devices"].values() if d["span_s"] > 0]
    return max(shares) if shares else None
