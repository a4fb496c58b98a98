"""Device seconds of one compiled program's runs in the traced stretch over
the stretch's busy seconds, in percent: how much of the device's work the
program is (the admissions' share of a serving cell).  Nothing where the
program did not run or the device was never busy."""


def read(ctx, program):
    trace = ctx["trace"]
    spent = sum(trace.program_seconds(program))
    if not spent or not trace.busy_s:
        return None
    return 100.0 * spent / trace.busy_s
