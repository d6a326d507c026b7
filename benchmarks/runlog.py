"""What a metric reader may ask of a finished run.

A run is a dict the driver fills: ``events`` (the workers' JSON lines, in
file order), ``reduced`` (the reduced trace of a ``--trace 1`` run, else
None), ``cell``, ``config``, ``params``, ``seconds``, ``t0`` (when the
harness process started), ``t_kill`` (when the driver killed the worker,
else None) and ``peak`` (the attached device's row of ``peaks.json``, None
off the table).  A reader returns a number, or None where it finds nothing
to read; the harness then leaves the metric out.
"""

import statistics


def of(run, ev, **match):
    return [e for e in run["events"] if e["ev"] == ev
            and all(e.get(k) == v for k, v in match.items())]


def first(run, ev, **match):
    found = of(run, ev, **match)
    return found[0] if found else None


def window_open(run):
    return first(run, "window_open")


def window_fetches(run):
    """The loss fetches between the window's opening and its close."""
    opened = window_open(run)
    if opened is None:
        return []
    return [e for e in of(run, "fetch")
            if e["t"] > opened["t"] and not e.get("warm")]


def tokens_per_step(run):
    return run["config"]["batch"] * run["config"]["seq"]


def blocks(run, traced=False):
    """(steps, seconds) of the window's blocks: from its opening, which
    follows a fetch, to the first fetch, then fetch to fetch; with
    ``traced`` False, without the blocks that shared their time with the
    profiler."""
    opened, out = window_open(run), []
    if opened is None:
        return out
    prev_t, prev_step = opened["t"], opened["step"]
    for e in window_fetches(run):
        if traced or not e.get("traced"):
            out.append((e["step"] - prev_step, e["t"] - prev_t))
        prev_t, prev_step = e["t"], e["step"]
    return out


def mean_tokens_per_s(run):
    """Tokens over seconds of the window's blocks the profiler did not
    share: every block in a run that was not traced."""
    untraced = blocks(run)
    steps = sum(n for n, _s in untraced)
    seconds = sum(s for _n, s in untraced)
    return steps * tokens_per_step(run) / seconds if seconds else None


def window_saves(run):
    """(save event, staged event or None) of the saves the engine
    accepted inside the window."""
    opened = window_open(run)
    if opened is None:
        return []
    staged = {e["step"]: e for e in of(run, "staged")}
    return [(s, staged.get(s["step"])) for s in of(run, "save", accepted=True)
            if not s.get("blocking") and s["t_call"] >= opened["t"]]


def whole_saves(run):
    """Those of ``window_saves`` that shared memory held before the window
    closed: the rest finished their drain with no step running."""
    closed = first(run, "window_close")
    if closed is None:
        return []
    return [(s, g) for s, g in window_saves(run)
            if g is not None and g["t"] <= closed["t"]]


def resumed_fetches(run):
    return of(run, "fetch", resumed=True)


def median(values):
    values = list(values)
    return statistics.median(values) if values else None
