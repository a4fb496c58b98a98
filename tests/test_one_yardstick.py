"""One yardstick: the chip's numbers come from ``benchmark/run.py``; a CPU
run yields counts.  These tests keep that rule where it was once broken:
no record beside the checkout decides what the train step or the warm
start does, no test asserts on a wall-clock reading, and the documents
cite files that exist and metrics the benchmark has."""

import ast
import json
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "tests")

DOCS = (
    "README.md",
    "examples/README.md",
    "docs/architecture.md",
    "docs/getting_started.md",
    "docs/migration.md",
    "docs/observability.md",
    "docs/performance.md",
    "docs/reliability.md",
)


# -- no record beside the checkout reaches the programs ----------------------


def test_train_step_and_warm_start_read_no_tune_record(
        devices, tmp_path, monkeypatch):
    """A tune record that says ``donate: false, n_draft: 7`` — in the
    directory ``ROCKET_TPU_TUNE_DIR`` names and under ``experiments/tunes/``
    of the working directory — changes neither what ``Module`` resolves
    for donation nor the depths ``plan_for_batcher`` warms."""
    import jax.numpy as jnp

    import rocket_tpu as rt
    from rocket_tpu.models.objectives import cross_entropy
    from rocket_tpu.tune.warmup import plan_for_batcher

    from test_async_loop import MLP, synthetic_classification

    import jax

    # the record the deleted store (``rocket_tpu/tune/store.py``) would
    # have matched to this host: its device kind, its backend
    record = {"schema": 1, "model": "gpt2", "batch": 8,
              "device": jax.devices()[0].device_kind,
              "backend": jax.default_backend(),
              "created": "2026-10-03T00:00:00Z", "value": 1.0,
              "tune": {"donate": False, "n_draft": 7, "batch": 8}}
    for tunes in (tmp_path / "named", tmp_path / "experiments" / "tunes"):
        tunes.mkdir(parents=True)
        (tunes / "gpt2-cpu-b8-cpu.json").write_text(json.dumps(record))
    monkeypatch.setenv("ROCKET_TPU_TUNE_DIR", str(tmp_path / "named"))
    monkeypatch.chdir(tmp_path)

    data = synthetic_classification(n=64)
    model = rt.Module(
        MLP(),
        capsules=[
            rt.Loss(cross_entropy(labels_key="label"), name="ce"),
            rt.Optimizer(learning_rate=2e-2),
        ],
    )
    model.bind(rt.Runtime())
    model.setup()
    model.launch(rt.Attributes(
        batch={"x": jnp.asarray(data["x"]),
               "label": jnp.asarray(data["label"])},
        looper=rt.Attributes(grad_enabled=True, state=rt.Attributes()),
    ))
    assert model._donate is True

    class Bat:
        n_draft = 3

    assert plan_for_batcher(Bat(), 4).n_drafts == (3,)
    assert plan_for_batcher(Bat(), 4, extra_drafts=(2, 3)).n_drafts == (3, 2)


# -- no test asserts on a clock ----------------------------------------------

_CLOCKS = ("perf_counter", "monotonic", "time", "perf_counter_ns",
           "monotonic_ns", "time_ns")


def _clock_sites(source: str):
    """``(line, text)`` of every ``assert`` whose condition depends on a
    wall-clock reading: on a call of ``time.perf_counter``, ``time.monotonic``
    or ``time.time``, or on a name that was bound — through plain
    assignments, arithmetic, calls that take it, ``.append`` / ``+=`` into a
    container, or the return value of a local function — from such a call.
    A deadline that a loop compares the clock with is never asserted on,
    so it passes."""
    tree = ast.parse(source)
    modules, direct = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules |= {a.asname or a.name for a in node.names
                        if a.name == "time"}
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            direct |= {a.asname or a.name for a in node.names
                       if a.name in _CLOCKS}

    def is_clock(node):
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _CLOCKS:
            return isinstance(f.value, ast.Name) and f.value.id in modules
        return isinstance(f, ast.Name) and f.id in direct

    def ref(node):
        if isinstance(node, (ast.Name, ast.Attribute)):
            return ast.unparse(node)
        return None

    scopes = [n for n in ast.walk(tree)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    # functions (by bare name) that return a reading: True, or the places
    # of the returned tuple that hold one
    timed_calls = {}

    def called(node):
        f = node.func
        return f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None)

    def walk(node):
        """The sub-expressions a reading can reach an assert through: not a
        ``lambda`` (a clock handed on is not a reading) and not a
        ``deadline=`` / ``timeout=`` argument (it bounds a wait)."""
        yield node
        for field, value in ast.iter_fields(node):
            for child in value if isinstance(value, list) else [value]:
                if not isinstance(child, ast.AST) \
                        or isinstance(child, ast.Lambda):
                    continue
                if isinstance(child, ast.keyword) and child.arg in (
                        "deadline", "timeout", "clock"):
                    continue
                yield from walk(child)

    def tainted(node, names):
        for sub in walk(node):
            if is_clock(sub) or ref(sub) in names:
                return True
            if isinstance(sub, ast.Call) \
                    and timed_calls.get(called(sub)) is True:
                return True
        return False

    def targets(node):
        if isinstance(node, (ast.Tuple, ast.List)):
            for elt in node.elts:
                yield from targets(elt)
        elif isinstance(node, ast.Starred):
            yield from targets(node.value)
        elif ref(node):
            yield ref(node)
        elif isinstance(node, ast.Subscript) and ref(node.value):
            yield ref(node.value)

    def bind(target, value, names):
        """Names ``target = value`` gives a reading to."""
        if isinstance(target, (ast.Tuple, ast.List)):
            if isinstance(value, (ast.Tuple, ast.List)) \
                    and len(value.elts) == len(target.elts):
                for t, v in zip(target.elts, value.elts):
                    bind(t, v, names)
                return
            places = timed_calls.get(called(value)) \
                if isinstance(value, ast.Call) else None
            if isinstance(places, set) and not any(
                    tainted(a, names) for a in value.args):
                for i, t in enumerate(target.elts):
                    if i in places:
                        names.update(targets(t))
                return
        if tainted(value, names) or (
                isinstance(value, ast.Call)
                and isinstance(timed_calls.get(called(value)), set)
                and not isinstance(target, (ast.Tuple, ast.List))):
            names.update(targets(target))

    names_of = {id(s): set() for s in scopes}
    changed = True
    while changed:
        changed = False
        for scope in scopes:
            names = names_of[id(scope)]
            before = len(names), repr(sorted(timed_calls.items(), key=str))
            for node in ast.walk(scope):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        bind(t, node.value, names)
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign,
                                       ast.NamedExpr)) \
                        and node.value is not None:
                    bind(node.target, node.value, names)
                elif isinstance(node, ast.For) and tainted(node.iter, names):
                    names.update(targets(node.target))
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("append", "extend", "add",
                                               "insert", "update",
                                               "setdefault") \
                        and any(tainted(a, names) for a in node.args) \
                        and ref(node.func.value):
                    names.add(ref(node.func.value))
                elif isinstance(node, (ast.Return, ast.Yield)) \
                        and node.value is not None:
                    if isinstance(node.value, ast.Tuple):
                        places = {i for i, e in enumerate(node.value.elts)
                                  if tainted(e, names)}
                        old = timed_calls.get(scope.name)
                        if places and old is not True:
                            timed_calls[scope.name] = places | (old or set())
                    elif tainted(node.value, names):
                        timed_calls[scope.name] = True
            # a closure sees what its enclosing function bound
            for inner in ast.walk(scope):
                if inner is not scope and id(inner) in names_of:
                    names_of[id(inner)] |= names
            after = len(names), repr(sorted(timed_calls.items(), key=str))
            if after != before:
                changed = True

    sites = {}
    for scope in scopes:
        for node in ast.walk(scope):
            if isinstance(node, ast.Assert) \
                    and tainted(node.test, names_of[id(scope)]):
                sites[node.lineno] = ast.unparse(node.test)[:100]
    return sorted(sites.items())


def test_no_test_asserts_on_a_clock():
    """``ROADMAP.md``'s first aim: a CPU run proves correctness and counts;
    it never yields a time.  A test may read a clock to bound how long it
    waits; it may not ``assert`` on a duration or on a ratio of two."""
    found = []
    for name in sorted(os.listdir(TESTS)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(TESTS, name)) as fh:
            for line, text in _clock_sites(fh.read()):
                found.append(f"tests/{name}:{line}: assert {text}")
    assert not found, "\n".join(found)


_WALK_CASES = {
    # name: (source of one test, whether its assert is a clock's)
    "a_median_of_a_closures_list": ("""
import time
def test():
    def times():
        out = []
        for _ in range(3):
            t0 = time.perf_counter()
            out.append(time.perf_counter() - t0)
        return out
    bare = float(median(times())) / 3
    assert bare < 1.0
""", True),
    "an_elapsed_time_beside_a_count": ("""
from time import monotonic
def test(loop):
    t0 = monotonic()
    elapsed = monotonic() - t0
    assert elapsed < 2 and loop.rounds == 4
""", True),
    "the_clock_in_the_assert_itself": ("""
import time as _t
def test(t_end):
    assert _t.time() < t_end
""", True),
    "one_place_of_a_returned_tuple": ("""
import time
def drive():
    t0 = time.perf_counter()
    return [1, 2], time.perf_counter() - t0
def test():
    results, wall = drive()
    assert wall < 5.0
""", True),
    "the_other_place_of_a_returned_tuple": ("""
import time
def drive():
    t0 = time.perf_counter()
    return [1, 2], time.perf_counter() - t0
def test():
    results, wall = drive()
    assert len(results) == 2
""", False),
    "a_deadline_that_bounds_a_loop": ("""
import time
def test():
    deadline = time.monotonic() + 5.0
    n = 0
    while time.monotonic() < deadline and n < 3:
        n += 1
    assert n == 3
""", False),
    "a_deadline_handed_to_a_call": ("""
import time
def test():
    assert retry_call(work, deadline=time.monotonic() + 60.0) == "done"
""", False),
    "a_clock_handed_on_as_a_lambda": ("""
import time
def test():
    t0 = time.perf_counter()
    loop = ServingLoop(clock=lambda: time.perf_counter() - t0)
    assert loop.submit(1) is None
""", False),
    "an_injected_clock": ("""
def test(fake):
    fake.t += 5.0
    assert fake.t == 5.0
""", False),
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_the_clock_walk_sees_what_it_should(case):
    """The walk itself, one behaviour a case: durations are found however
    they reach the assert; deadlines, clocks handed on and counts are
    not."""
    source, found = _WALK_CASES[case]
    assert bool(_clock_sites(source)) is found


# -- the documents cite what exists ------------------------------------------


def _tracked():
    out = subprocess.run(["git", "ls-files", "--cached", "--others",
                          "--exclude-standard"], cwd=ROOT, check=True,
                         capture_output=True, text=True).stdout.split("\n")
    return {p for p in out if p and os.path.exists(os.path.join(ROOT, p))}


@pytest.mark.parametrize("doc", DOCS)
def test_docs_cite_only_files_that_exist(doc):
    """Every back-quoted path with a ``/`` whose first part is a tracked
    top-level directory, and every back-quoted bare ``*.py`` (read from the
    root or from the document's own directory), exists.  A ``:line``, a
    ``::test`` or a trailing ``/`` is left off; a path with ``<``, ``*`` or
    ``{`` is a pattern, not a citation; one introduced as "the reference's"
    is the reference project's file; one ``.gitignore`` lists is made at
    run time."""
    tracked = _tracked()
    tops = {p.split("/", 1)[0] for p in tracked if "/" in p}
    known = set(tracked)
    for p in tracked:
        while "/" in p:
            p = p.rsplit("/", 1)[0]
            known.add(p)
    with open(os.path.join(ROOT, doc)) as fh:
        text = fh.read()
    here = os.path.dirname(doc)
    missing = []
    for match in re.finditer(r"`([^`\n]+)`", text):
        quoted = match.group(1)
        if re.search(r"reference's\s+$", text[:match.start()]):
            continue
        cite = quoted.split("::", 1)[0].strip()
        cite = re.sub(r":\d+(-\d+)?$", "", cite)
        # a directory asks git with its slash: a ``dir/`` pattern matches
        # a directory that does not exist only when the path says it is one
        asked = cite
        cite = cite.rstrip("/")
        if not re.fullmatch(r"[\w.\-/]+", cite) or cite.startswith("/"):
            continue
        if "/" in cite:
            if cite.split("/", 1)[0] not in tops:
                continue
        elif not cite.endswith(".py"):
            continue
        elif os.path.join(here, cite).lstrip("/") in known:
            continue
        if cite in known:
            continue
        ignored = subprocess.run(["git", "check-ignore", "-q", asked],
                                 cwd=ROOT).returncode == 0
        if not ignored:
            missing.append(quoted)
    assert not missing, f"{doc} cites what does not exist: {missing}"


def test_docs_performance_names_only_the_benchmarks_metrics():
    """Every back-quoted name in ``docs/performance.md``'s metric table is
    an ``end_to_end`` or ``per_layer`` name of ``BENCHMARK.json``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    metrics = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    with open(os.path.join(ROOT, "docs", "performance.md")) as fh:
        text = fh.read()
    head = "| metric |"
    assert head in text, "docs/performance.md has no metric table"
    rows = []
    for line in text[text.index(head):].split("\n"):
        if not line.startswith("|"):
            break
        rows.append(line)
    named = [n for row in rows[2:]
             for n in re.findall(r"`([^`]+)`", row.split("|")[1])]
    assert len(named) >= 4
    unknown = [n for n in named if n not in metrics]
    assert not unknown, unknown
