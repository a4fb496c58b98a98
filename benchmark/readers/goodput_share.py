"""The goodput ledger's own account of the loop: ``productive`` over
``productive + host_blocked``, in percent (set-up and compile are booked
in buckets of their own and left out)."""


def read(ctx):
    try:
        from rocket_tpu.observe.ledger import get_goodput
    except ImportError:
        return None
    snap = get_goodput().snapshot()
    both = snap.get("productive_s", 0.0) + snap.get("host_blocked_s", 0.0)
    if both <= 0:
        return None
    return 100.0 * snap["productive_s"] / both
