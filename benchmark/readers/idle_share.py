"""1 - union of device-op intervals / traced window, in percent."""


def read(ctx):
    share = ctx["trace"].idle_share
    return None if share is None else 100.0 * share
