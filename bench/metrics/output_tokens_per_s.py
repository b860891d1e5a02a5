"""Output tokens delivered in the window over the window's length (host
clock). A token is delivered when the engine step that produced it returns;
the window is whole engine steps (``Run.window``), so a step cut by either
end neither drops its tokens nor counts them over less than its time."""


def read(run):
    return sum(s["tokens"] for s in run.window_steps()) / run.window_s()
