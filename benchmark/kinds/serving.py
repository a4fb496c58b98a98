"""Shared by the ``closed`` and ``open`` traffic kinds: a ``ServingLoop``
driven from one thread through ``submit`` / ``run_round`` /
``drain_results``.

The system under test is the serving loop as a user builds it: a
``ContinuousBatcher`` factory (target, draft, bf16 weights from the seed,
greedy), ``ServingLoop(max_batch=rows)``.  The benchmark hands it a tracer
of its own and reads the loop's instants from it (``serve/admit`` with row
and prompt length, ``serve/first_token``, ``serve/complete``, the
``serve/round`` spans), all on ``perf_counter``; due times, submit times and
every raw sample are the benchmark's own.

Set-up: weights, one warm admission per prompt length the mix can produce
(the program compiles ``_spec_admit`` per prompt shape), the rows a steady
server already holds.  Then the window: requests are offered when they are
due (open) or as rows free (closed); nothing else happens in it.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import harness, traffic, weights
from benchmark.kinds import train as train_kind

SAMPLE = 6          # finished requests compared with the reference a run


def program_models(cell: harness.Cell):
    """Target and draft modules and their abstract bf16 parameter trees."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    family, serving = cell.family, cell.config["serving"]
    max_seq = int(serving["total_len"]) + int(serving["n_draft"])
    model = family.program(cell.arch, max_seq=max_seq, attention="auto")
    draft = family.program(family.draft(cell.arch, serving),
                           max_seq=max_seq, attention="auto")
    dtype = jnp.dtype(serving["weights_dtype"])

    def abstract(m):
        sample = {"tokens": jnp.zeros((1, 8), jnp.int32)}
        tree = jax.eval_shape(m.init, jax.random.PRNGKey(0), sample)["params"]
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
            nn.meta.unbox(tree))

    return model, draft, abstract(model), abstract(draft)


def make_params(cell: harness.Cell, seed: int, abstract_t, abstract_d):
    """Both parameter trees on the device from the seed's key, one jitted
    call a group (a layer), in the type they are served in."""
    key, family = weights.base_key(seed), cell.family
    d_arch = family.draft(cell.arch, cell.config["serving"])
    return (train_kind.fill_tree(abstract_t, family, cell.arch, key),
            train_kind.fill_tree(abstract_d, family, d_arch, key,
                                 prefix="draft."))


class Session:
    """One serving loop with its tracer and the benchmark's bookkeeping."""

    def __init__(self, cell: harness.Cell, seed: int) -> None:
        from rocket_tpu.models.generate import ContinuousBatcher
        from rocket_tpu.observe.trace import Tracer
        from rocket_tpu.serve import ServingLoop
        from rocket_tpu.serve.policy import (DegradationLevel,
                                             DegradationPolicy)

        self.cell = cell
        serving = cell.config["serving"]
        self.rows = int(serving["rows"])
        model, draft, abs_t, abs_d = program_models(cell)
        params, draft_params = make_params(cell, seed, abs_t, abs_d)
        harness.log("weights made")
        weights.release()
        harness.log("makers released")
        self.batchers: List[Any] = []

        def factory():
            bat = ContinuousBatcher(
                model, draft, params, draft_params,
                total_len=int(serving["total_len"]),
                n_draft=int(serving["n_draft"]))
            self.batchers.append(bat)
            return bat

        self.tracer = Tracer(capacity=1 << 19, enabled=True)
        # Full quality at any queue depth: the degradation ladder would
        # shorten the draft chain (a new program, compiled inside the
        # window) and cap outputs under a backlog.
        policy = DegradationPolicy(ladder=(DegradationLevel("full"),),
                                   engage_depth=())
        self.loop = ServingLoop(
            factory, max_batch=self.rows, queue_capacity=4096,
            policy=policy, clock=time.perf_counter, tracer=self.tracer)
        self.requests: Dict[int, traffic.Req] = {}
        self.submitted_s: Dict[int, float] = {}
        self.results: Dict[int, Any] = {}

    # -- driving --------------------------------------------------------

    def submit(self, req: traffic.Req) -> None:
        from rocket_tpu.serve import Request

        self.requests[req.rid] = req
        self.submitted_s[req.rid] = time.perf_counter()
        rejected = self.loop.submit(Request(
            rid=req.rid, prompt=req.prompt, max_new_tokens=req.max_new))
        if rejected is not None:
            self.results[req.rid] = rejected

    def round(self) -> bool:
        ran = self.loop.run_round()
        for res in self.loop.drain_results():
            self.results[res.rid] = res
        return ran

    def warm(self, lengths: List[int]) -> None:
        """One admission of every prompt length the mix can produce, and
        rounds until it has finished: every program the window uses.  One
        at a time, the longest first, the device drained in between: a
        program's scratch space is reserved when it is loaded, and the
        longest prompt's (3.2 GiB at the cell's sizes) fits only while no
        second copy of the round state is in flight."""
        for i, n in enumerate(sorted(lengths, reverse=True)):
            rid = -1 - i
            self.submit(traffic.Req(rid=rid, due_s=None, warm=True,
                                    prompt=np.ones(n, np.int32), max_new=2))
            while rid not in self.results:
                self.round()
            np.asarray(self.batchers[-1].state[1])      # drain the device
        self.requests.clear()
        self.submitted_s.clear()
        self.results.clear()

    def close(self) -> None:
        self.loop.close()

    # -- reading --------------------------------------------------------

    def emitted(self) -> Dict[str, float]:
        """Output tokens emitted so far by every request, finished or in a
        row, and the context the live rows hold (one host read of the
        batcher's per-row token counts)."""
        live: Dict[int, tuple] = {}
        done = set()
        for kind, name, _ts, _dur, _tid, fields in self.tracer.events():
            if name == "serve/admit":
                live[fields["row"]] = (fields["rid"], fields["prompt_len"])
            elif name in ("serve/complete", "serve/evict", "serve/failed"):
                done.add(fields["rid"])
        n_tok = np.asarray(self.batchers[-1].state[1])
        total = sum(res.n_tok - len(self.requests[rid].prompt)
                    for rid, res in self.results.items()
                    if rid in self.requests and hasattr(res, "n_tok"))
        context = 0
        for row, (rid, prompt_len) in live.items():
            if rid in done or rid not in self.requests:
                continue
            total += int(n_tok[row]) - prompt_len
            context += int(n_tok[row])
        return {"tokens": float(total), "live_context": float(context)}

    def instants(self) -> Dict[str, Dict[int, float]]:
        """Seconds on ``perf_counter`` of each request's first token and
        completion, the admissions, and the round spans."""
        first, complete, admit_at, admits, rounds = {}, {}, {}, [], []
        for kind, name, ts, dur, _tid, fields in self.tracer.events():
            t = ts / 1e9
            if name == "serve/first_token":
                first[fields["rid"]] = t
            elif name == "serve/complete":
                complete[fields["rid"]] = t
            elif name == "serve/admit":
                admit_at[fields["rid"]] = t
                admits.append((t, dur / 1e9, fields["prompt_len"],
                               fields["rid"]))
            elif name == "serve/round":
                rounds.append((t, dur / 1e9, fields.get("live", 0)))
        return {"first": first, "complete": complete, "admit_at": admit_at,
                "admits": admits, "rounds": rounds}

    def host_spans(self) -> List[tuple]:
        return [(name, ts, ts + dur)
                for kind, name, ts, dur, _tid, _f in self.tracer.events()
                if kind == "X"]


def drive(cell: harness.Cell, *, seed: int, seconds: float, compiles,
          trace, process_start: float) -> Dict:
    mix = cell.traffic
    harness.arm_compile_cache()
    harness.log("building the server")
    session = Session(cell, seed)
    harness.log("weights made, loop warm-started")
    ladder = [n for n, c in zip(
        mix["prompt_ladder"], traffic.ladder_counts(
            mix["prompt_ladder"], mix["prompt_lognormal"]["median"],
            mix["prompt_lognormal"]["sigma"], int(mix["cycle"]))) if c]
    session.warm(ladder)
    harness.log(f"warmed prompt lengths {ladder}")
    n_warm = int(mix.get("initial_in_service", 0))
    expected = n_warm + int(mix.get("expected_per_s", 3.0) * seconds) + 64
    stream = traffic.serving_requests(mix, cell.arch["vocab"], seed, expected)
    for req in stream[:n_warm]:
        session.submit(req)
    session.round()                 # admits them all, one round: set-up
    harness.log(f"{n_warm} rows in service; window opens")
    pending = stream[n_warm:]
    open_loop = mix["kind"] == "open"
    backlog = int(mix.get("backlog", 2))
    trace_seconds = float(mix.get("trace_seconds", 6.0))

    compiles.open()
    before = session.emitted()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    t1 = after = None
    nxt = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            if t1 is None:
                # the window closes here; a traced run then keeps the same
                # load going for a further stretch and traces that, so the
                # profiler's start and stop stall nothing that is timed
                t1, after = now, session.emitted()
                compiles.close()
                if trace is None:
                    break
                trace.start()
                t_end = time.perf_counter() + trace_seconds
                continue
            break
        if open_loop:
            while nxt < len(pending) and t0 + pending[nxt].due_s <= now:
                session.submit(pending[nxt])
                nxt += 1
        else:
            while nxt < len(pending) and len(session.loop.queue) < backlog:
                session.submit(pending[nxt])
                nxt += 1
        if not session.round():
            wait = 0.002
            if open_loop and nxt < len(pending):
                wait = min(wait, max(0.0, t0 + pending[nxt].due_s - now))
            time.sleep(wait)
    if trace is not None:
        trace.stop()
    if nxt >= len(pending):
        raise harness.BenchmarkError(
            "the request stream ran out inside the window; raise "
            "'expected_per_s' in the traffic file")

    run = summarize(cell, session, stream, t0, t1, before, after,
                    process_start)
    run["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
    run["host_spans"] = session.host_spans()
    # free the program's state before the reference takes the chip
    session.close()
    session.batchers.clear()
    del session
    gc.collect()
    return run


def summarize(cell, session: Session, stream, t0, t1, before, after,
              process_start) -> Dict:
    """Everything the metrics read, from raw samples on one clock."""
    inst = session.instants()
    window_s = t1 - t0
    in_window = lambda t: t is not None and t0 <= t <= t1  # noqa: E731
    done = [rid for rid, t in inst["complete"].items()
            if rid in session.requests and in_window(t)]
    tpot, ttft, queue_wait, late = [], [], [], []
    finished = []
    for rid in done:
        req, res = session.requests[rid], session.results.get(rid)
        if res is None or not hasattr(res, "n_tok"):
            continue
        out = res.n_tok - len(req.prompt)
        finished.append({"rid": rid, "prompt": req.prompt,
                         "tokens": np.asarray(res.tokens[:res.n_tok]),
                         "out": out})
        first = inst["first"].get(rid)
        if first is not None and out > 1:
            tpot.append((inst["complete"][rid] - first) * 1e3 / (out - 1))
    for rid, req in session.requests.items():
        if req.due_s is None:
            continue
        due = t0 + req.due_s
        if not in_window(due):
            continue
        late += traffic.lateness_ms([session.submitted_s[rid]], [due])
        if rid in inst["first"]:
            ttft.append((inst["first"][rid] - due) * 1e3)
        if rid in inst["admit_at"]:
            queue_wait.append((inst["admit_at"][rid] - due) * 1e3)
    arrived = [rid for rid, req in session.requests.items()
               if not req.warm and t0 <= session.submitted_s[rid] <= t1]
    failed = [rid for rid in session.requests
              if rid in session.results
              and not hasattr(session.results[rid], "n_tok")]
    rounds = [(t, d, live) for t, d, live in inst["rounds"]
              if t0 <= t <= t1]
    admits = [(t, d, p, rid) for t, d, p, rid in inst["admits"]
              if t0 <= t <= t1]
    tokens = after["tokens"] - before["tokens"]
    row_rounds = sum(live for _t, _d, live in rounds)
    prompt_tokens = sum(p for _t, _d, p, _r in admits)
    # context each token attended to: a prompt attends causally to itself
    # (p*p/2); an output token to the context its row held (the window's
    # mean live context per row)
    live_rows = max(1.0, row_rounds / max(1, len(rounds)))
    mean_context = (before["live_context"] + after["live_context"]) / 2
    products = sum(p * p / 2 for _t, _d, p, _r in admits) \
        + tokens * mean_context / live_rows
    end_to_end = {"setup_s": t0 - process_start}
    if tokens > 0:
        end_to_end["serve_tokens_per_s"] = tokens / window_s
    p80 = harness.percentile(tpot, 80)
    if p80 is not None:
        end_to_end["tpot_p80_ms"] = p80
    return {
        "attempted": len(arrived) + sum(
            1 for r in session.requests.values() if r.warm),
        "failed": len(failed),
        "window_s": window_s, "t0": t0, "t1": t1,
        "setup_s": t0 - process_start,
        "end_to_end": end_to_end,
        "tokens": tokens, "prompt_tokens": prompt_tokens,
        "context_products": products, "mean_live_context": mean_context,
        "rounds": len(rounds), "row_rounds": row_rounds,
        "round_host_ms": [d * 1e3 for _t, d, _l in rounds],
        "admissions": len(admits), "completed": len(done),
        "tpot_ms": tpot, "ttft_ms": ttft, "queue_wait_ms": queue_wait,
        "late_ms": late, "finished": finished,
        "queue_depth_end": len(session.loop.queue),
    }


def sample_finished(finished: List[Dict], seed: int) -> List[Dict]:
    """The longest finished request and a few more drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda f: -len(f["tokens"]))
    rng = np.random.default_rng(int(seed) + 1)
    rest = order[1:]
    picks = rng.permutation(len(rest))[: SAMPLE - 1]
    return [order[0]] + [rest[i] for i in sorted(picks)]


def served_gaps(cell: harness.Cell, seed: int, sample: List[Dict],
                prec: str = "f32", altered: Optional[str] = None) -> Dict:
    """The widest gap, over every served token of the sample, by which the
    served token's reference logit lies below the reference's best, in
    units of the spread (standard deviation) of that position's logits.

    ``altered`` names a lower precision: then the token judged at each
    position is the one that precision puts first (the control), not the
    served one."""
    import jax
    import jax.numpy as jnp

    arch, reference = cell.arch, cell.family.reference
    dtype = jnp.dtype(cell.config["serving"]["weights_dtype"])
    key = weights.base_key(seed)
    shapes = weights.groups(cell.family.leaf_shapes(arch))

    def get(group):
        # the weights as served: rounded to the serving type
        return {k: v.astype(jnp.float32) for k, v in weights.make_group(
            key, group, shapes[group], str(dtype)).items()}

    # one shape for every row: the longest row and the longest output
    shape = dict(pad_to=int(cell.config["serving"]["total_len"]),
                 count_pad=int(cell.traffic["output_lognormal"]["max"]))
    widest, n_tokens = 0.0, 0
    for item in sample:
        tokens, first = item["tokens"], len(item["prompt"])
        count = len(tokens) - first
        if count < 1:
            continue
        ref = reference.served_logits(arch, prec, get, tokens, first, count,
                                      **shape)
        judged = jnp.asarray(tokens[first:])
        if altered is not None:
            low = reference.served_logits(arch, altered, get, tokens,
                                          first, count, **shape)
            judged = jnp.argmax(low, axis=-1)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
        gap = (best - got) / jnp.std(ref, axis=-1)
        widest = max(widest, float(jnp.max(gap)))
        n_tokens += count
    return {"served_gap": widest, "tokens_compared": n_tokens}


def check(cell: harness.Cell, *, seed: int, run: Dict) -> Dict:
    sample = sample_finished(run["finished"], seed)
    if not sample:
        return {"correct": False, "compared": {
            "served_gap": {"value": float("inf"), "limit": 0.0}}}
    numbers = served_gaps(cell, seed, sample)
    run["tokens_compared"] = numbers.pop("tokens_compared")
    return harness.judge(cell, numbers)
