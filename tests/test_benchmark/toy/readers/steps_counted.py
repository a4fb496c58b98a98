"""A reader the toy benchmark brings itself: the window's steps."""


def read(ctx, scale=1):
    steps = ctx["run"].get("steps")
    return None if steps is None else float(steps) * scale
