"""A quantity of the serving loop's book of requests, as the program's
process-wide record holds it (``rocket_tpu.observe.trace.get_requests``:
for each terminated request the first token's and the terminal's
instants on the loop's clock, its output tokens, the admission turns of
other requests it sat through while decoding, and critpath's segments of
its time, ``admit_stall`` among them).  The population is the one the
harness takes ``tpot_p80_ms`` over: requests completed inside the window
with more than one output token.  ``what`` chooses:

- ``tpot_clean_p80``: p80 of (terminal − first token − ``admit_stall``)
  ÷ (output tokens − 1), ms: the time between tokens with other
  requests' admissions taken out;
- ``admit_stall_share``: Σ ``admit_stall`` ÷ Σ (terminal − first
  token), in percent;
- ``admit_stall_per_turn``: Σ ``admit_stall`` ÷ Σ stalled turns, ms:
  what one admission turn costs a decoding row.

Nothing where the program keeps no such record, or where none of its
requests fall in the window."""

from benchmark.harness import percentile


def quantity(entries, t0, t1, what):
    """``what`` over the record's ``entries`` that complete in
    ``[t0, t1]`` with more than one output token."""
    picked = [e for e in entries
              if e["outcome"] == "complete" and t0 <= e["end_s"] <= t1
              and e["out"] > 1]
    if not picked:
        return None
    stall = [e["segments"]["admit_stall"] for e in picked]
    decoding = [(e["end_s"] - e["first_s"]) * 1e3 for e in picked]
    if what == "tpot_clean_p80":
        return percentile([(d - s) / (e["out"] - 1) for e, d, s
                           in zip(picked, decoding, stall)], 80)
    if what == "admit_stall_share":
        return 100.0 * sum(stall) / sum(decoding) if sum(decoding) \
            else None
    if what == "admit_stall_per_turn":
        turns = sum(e["stalled_turns"] for e in picked)
        return sum(stall) / turns if turns else None
    raise ValueError(f"request_book knows no quantity {what!r}")


def read(ctx, what):
    try:
        from rocket_tpu.observe.trace import get_requests
    except ImportError:
        return None
    run = ctx["run"]
    return quantity(get_requests().snapshot(), run["t0"], run["t1"], what)
