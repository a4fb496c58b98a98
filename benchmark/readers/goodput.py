"""Output tokens of the requests that completed in the window, a second."""


def read(ctx):
    run = ctx["run"]
    if not run.get("finished"):
        return None
    return sum(f["out"] for f in run["finished"]) / run["window_s"]
