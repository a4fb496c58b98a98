"""One of the persistent compile cache's counts (``hits`` or ``misses``)
as the program's start-up record holds them: the process's counts at the
moment the program logged its start-up line (a trainer's first step has
returned, a serving loop first reports SERVING), so what the harness or
the reference compiles afterwards is not in them.  Nothing where the
program keeps no such record, has not logged the line, or installed no
listeners (it then counted nothing, which is not zero)."""


def read(ctx, key):
    try:
        from rocket_tpu.observe.trace import get_startup
    except ImportError:
        return None
    cache = getattr(get_startup(), "cache", None)
    if not cache or key not in cache:
        return None
    return float(cache[key])
