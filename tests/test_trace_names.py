"""Trace-name lint (ISSUE 9 satellite): every literal trace event name
in the library follows the lowercase ``cat/name`` slash convention.

The merged cross-host timeline, the flight-recorder tail, the Prometheus
export, and the goodput/ledger counters all key off these names; a
dot-separated or CamelCase stray silently forks the namespace (this lint
caught ``quant.int8_matmul.fallback`` and ``tune.probe.dead``, renamed to
slash form when it landed).  The scan is AST-based so multi-line calls
are seen and docstring examples are not.
"""

import ast
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "rocket_tpu")

# The emitting calls whose first positional argument is an event name.
# ``_instant`` is FleetRouter's tracer-guarded wrapper — same first-arg
# contract, so its fleet/* names lint too; ``_span`` is the Dispatcher's
# lazy handle to ``trace.span``; ``phase`` / ``mark`` write the start-up
# record.
_EMITTERS = {"span", "counter", "instant", "health", "flow", "_instant",
             "_span", "phase", "mark"}

# lowercase slug segments joined by '/' — at least one slash (a bare
# word has no category and collides with everything).  Dots are allowed
# INSIDE a segment (e.g. a dotted metric suffix), never as the separator.
_NAME_RE = re.compile(r"^[a-z0-9_]+(/[a-z0-9_.]+)+$")

# The one other form: a capsule's lifecycle event, ``<Capsule>.<event>``
# (``Optimizer.launch``), whose class name fills an f-string hole.
_CAPSULE_RE = re.compile(r"^x\.x$")


def _called_name(func):
    """The trailing identifier of the call target: ``span`` for both the
    module-level convenience and ``tracer.span``."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _literal_name(node):
    """First-arg string literal, with f-string ``{...}`` holes filled by
    a placeholder segment (``f"{prefix}/depth"`` lints as ``x/depth``)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts = []
        for piece in node.values:
            if isinstance(piece, ast.Constant):
                parts.append(str(piece.value))
            else:
                parts.append("x")
        return "".join(parts)
    return None


def _scan_file(path):
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError:  # pragma: no cover - the suite would be broken
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        if _called_name(node.func) not in _EMITTERS:
            continue
        name = _literal_name(node.args[0])
        if name is None:
            continue  # computed names are the caller's responsibility
        out.append((path, node.lineno, name))
    return out


def _all_sites():
    sites = []
    for dirpath, _dirnames, filenames in os.walk(PKG):
        for fname in filenames:
            if fname.endswith(".py"):
                sites.extend(_scan_file(os.path.join(dirpath, fname)))
    return sites


@pytest.mark.goodput
def test_library_emits_trace_events():
    # the lint is only meaningful if the scan actually sees the emitters
    names = {name for _p, _l, name in _all_sites()}
    assert {"serve/submit", "ledger/compile",
            "quant/int8_matmul/fallback", "attention/flash/fallback",
            # ISSUE 31: which attention a compiled decode round holds
            "attention/decode/kernel", "attention/decode/fallback",
            # ISSUE 33: attention that chooses its keys, chunked admission
            "attention/select/decode", "attention/select/prefill",
            "generate/spec_admit",
            # which state update a compiled round holds, and the chunked
            # scan of a prompt through a state-space layer
            "ssm/decode/kernel", "ssm/decode/fallback", "ssm/prefill",
            # multi-tenant serving: preemption lifecycle markers
            "serve/preempt", "serve/resume",
            # distributed request tracing: the stitched-timeline and
            # critical-path event vocabulary (docs/observability.md)
            "serve/request", "serve/pool_fetch", "serve/first_token",
            "serve/new_weights", "fleet/delivered", "fleet/requeued",
            "pool/fetch",
            # ZeRO host-offload round trip (engine/offload.py)
            "offload/d2h", "offload/h2d",
            # ISSUE 24: the two hot loops' spans and the start-up record
            # (stable names: the benchmark's readers and
            # docs/observability.md's table key off them)
            "looper/x/iter", "looper/host_fetch", "x.x",
            "module/build_steps", "serve/round", "serve/dispatch",
            "serve/fetch", "serve/harvest", "serve/admit", "serve/shed",
            "serve/policy", "startup/import", "startup/runtime",
            "startup/build", "startup/first_dispatch",
            "startup/serve_warm_start"} <= names
    # the train step's span takes its name from a constant
    from rocket_tpu.engine.step import STEP_SPAN

    assert STEP_SPAN == "train/step_dispatch" and _NAME_RE.match(STEP_SPAN)


# -- jax.jit chokepoint lint (ISSUE 15 satellite) ----------------------------
#
# Every ``jax.jit`` call site in the library must either dispatch through
# ``observe.ledger.ledger_call`` (the retrace sentinel + warm-start
# chokepoint) or appear below with the reason it legitimately doesn't.
# The assertion is STRICT set equality: a new jit edge fails until it is
# consciously classified here, and a removed one fails until its stale
# entry is dropped — sites can't silently dodge the sentinel or the
# WarmupPlan.  Keys are ``(path-under-rocket_tpu, enclosing def/assign)``.

KNOWN_JIT_SITES = {
    # ledgered: dispatch routes through ledger_call
    ("engine/step.py", "steps"): "ledgered via _AnnotatedStep (sync)",
    ("engine/step.py", "build_train_step"):
        "ledgered via _AnnotatedStep (micro)",
    ("engine/step.py", "build_window_step"):
        "ledgered via _AnnotatedStep (window)",
    ("engine/step.py", "build_eval_step"):
        "ledgered via _AnnotatedStep (eval)",
    ("models/generate.py", "_spec_prefill"):
        "ledgered: ContinuousBatcher.start",
    ("models/generate.py", "_spec_round"):
        "ledgered: ContinuousBatcher.step",
    ("models/generate.py", "_spec_admit"):
        "ledgered: ContinuousBatcher.admit",
    ("models/generate.py", "_mtp_prefill"):
        "ledgered: ContinuousBatcher.start with a hidden-state draft",
    ("models/generate.py", "_mtp_round"):
        "ledgered: ContinuousBatcher.step with a hidden-state draft",
    ("models/generate.py", "_mtp_admit"):
        "ledgered: ContinuousBatcher.admit with a hidden-state draft",
    ("models/generate.py", "_spec_import_row"):
        "ledgered: admit_prefilled / kvstore import",
    ("models/generate.py", "_spec_suffix_prefill"):
        "ledgered: cached-prefix suffix prefill",
    # exempt: one-shot or deliberately unledgered edges, with reasons
    ("models/generate.py", "_prefill_cache"):
        "exempt: chunked-prefill helper, inner edge of ledgered entries",
    ("models/generate.py", "_chunk_step"):
        "exempt: chunked-prefill helper, inner edge of ledgered entries",
    ("models/generate.py", "_spec_batched_run"):
        "exempt: one-dispatch offline path, not the serving loop",
    ("models/generate.py", "_chunk_probs"):
        "exempt: offline eval utility (perplexity chunks)",
    ("ops/quant.py", "_int8_matmul_kernel_call"):
        "exempt: kernel micro-dispatch, traced via quant/* instants",
    ("ops/decode_attention.py", "decode_attention"):
        "exempt: inner edge of the ledgered round, so that its layers "
        "share one trace and one lowered kernel",
    ("ops/latent_attention.py", "latent_decode_attention"):
        "exempt: inner edge of the ledgered round, so that its layers and "
        "the MTP module share one trace and one lowered kernel",
    ("ops/select_attention.py", "masked_attention"):
        "exempt: inner edge of the ledgered admission, so that its layers "
        "and chunks share one trace and one lowered kernel",
    ("ops/ssm.py", "ssm_decode"):
        "exempt: inner edge of the ledgered round, so that its state-space "
        "layers share one trace and one lowered kernel",
    ("observe/meter.py", "_launch_in_step"):
        "exempt: MFU meter's own probe, must not perturb the ledger",
    ("parallel/mpmd.py", "__init__"):
        "exempt: per-stage MPMD programs, single compile at stage build",
    ("parallel/multihost.py", "_replicate_fn"):
        "exempt: one-shot replication helper at setup",
    ("core/module.py", "materialize"):
        "exempt: one-shot sharded state init, before any step exists",
}


def _enclosing_context(tree, target):
    """Name of the nearest enclosing def (or assignment target) holding
    ``target`` — the stable, line-number-free identity of a jit site."""
    class _Finder(ast.NodeVisitor):
        def __init__(self):
            self.stack = []
            self.found = None

        def generic_visit(self, node):
            if node is target:
                self.found = self.stack[-1] if self.stack else "<module>"
            if self.found is None:
                super().generic_visit(node)

        def visit_FunctionDef(self, node):
            self.stack.append(node.name)
            self.generic_visit(node)
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Assign(self, node):
            name = node.targets[0].id \
                if isinstance(node.targets[0], ast.Name) else None
            if name:
                self.stack.append(name)
            self.generic_visit(node)
            if name:
                self.stack.pop()

    finder = _Finder()
    finder.visit(tree)
    return finder.found or "<module>"


def _jit_sites():
    """Every ``jax.jit`` attribute reference in the library — direct
    calls, decorators, and ``functools.partial(jax.jit, ...)`` all
    contain the ``jax.jit`` Attribute node."""
    sites = set()
    for dirpath, _dirnames, filenames in os.walk(PKG):
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError:  # pragma: no cover
                    continue
            rel = os.path.relpath(path, PKG)
            for node in ast.walk(tree):
                if (isinstance(node, ast.Attribute) and node.attr == "jit"
                        and isinstance(node.value, ast.Name)
                        and node.value.id == "jax"):
                    sites.add((rel, _enclosing_context(tree, node)))
    return sites


@pytest.mark.goodput
def test_every_jit_site_is_ledgered_or_exempt():
    found = _jit_sites()
    known = set(KNOWN_JIT_SITES)
    new = sorted(found - known)
    stale = sorted(known - found)
    assert not new and not stale, (
        "jax.jit site inventory drifted.\n"
        "NEW sites (route them through ledger_call, or classify them in "
        "KNOWN_JIT_SITES with a reason):\n  "
        + "\n  ".join(f"{p}::{ctx}" for p, ctx in new)
        + "\nSTALE entries (the site is gone — drop them):\n  "
        + "\n  ".join(f"{p}::{ctx}" for p, ctx in stale)
    )


@pytest.mark.goodput
def test_trace_names_follow_slash_convention():
    bad = [
        f"{os.path.relpath(path, REPO)}:{line}: {name!r}"
        for path, line, name in _all_sites()
        if not (_NAME_RE.match(name) or _CAPSULE_RE.match(name))
    ]
    assert not bad, (
        "trace event names must be lowercase 'cat/name' slugs "
        "(see docs/observability.md):\n  " + "\n  ".join(bad)
    )
