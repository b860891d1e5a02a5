"""Engine decode tick: live slots per decode step, averaged over the decode
steps of the window: the tokens the slots decoded over the engine's
``decode_steps`` counter."""


def read(run):
    steps = [s for s in run.window_steps() if s["steps"]]
    total = sum(s["steps"] for s in steps)
    if not total:
        return None
    return sum(n for s in steps for _, n in s["live"]) / total
