"""``BENCHMARK.json`` against the contract it is checked by, and against
the files it names: every cell, configuration, traffic mix, metric file,
reader and limits file resolves by name."""

import importlib
import json
import os
import re

import pytest

from benchmark import harness

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden_size|intermediate_size|n_embd|n_inner|head_dim|"
                   r"_dim$|_rank$|expand|per_tok)")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cells(manifest):
    return [w["name"] for w in manifest["workloads"]]


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert manifest["paths"] == ["benchmark", "tests/test_benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_every_name_and_unit_keeps_to_the_allowed_characters(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(set(names)) == len(names)
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in manifest["configs"]:
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key


def test_entries_have_just_the_contracts_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_setup_s_is_reported_everywhere_and_each_cell_has_another(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "workloads" not in e2e["setup_s"] and e2e["setup_s"]["bound"] <= 0.1
    for name in cells(manifest):
        cell = harness.resolve_cell(name, manifest)
        reported = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in reported and len(reported) >= 2, name
        assert cell.per_layer(), name


def test_every_cell_resolves_its_files_by_name(manifest):
    for name in cells(manifest):
        cell = harness.resolve_cell(name, manifest)
        assert cell.config["name"] == cell.config_name
        assert cell.kind in ("train", "closed", "open")
        assert hasattr(harness.load_kind(cell), "drive")
        assert hasattr(harness.load_kind(cell), "check")
        limits = os.path.join(ROOT, "benchmark", "limits", name + ".json")
        with open(limits) as fh:
            assert all(v > 0 for v in json.load(fh).values())
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(ROOT, c["file"])) as fh:
            data = json.load(fh)
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


def test_every_configuration_names_an_architecture_with_the_seven_names(
        manifest):
    """The contract has one definition, ``harness.FAMILY_NAMES``, which
    ``load_family`` holds every module to.  What ``reference`` and ``counts``
    hold is for the cell's kind and its listed readers to find."""
    assert len(harness.FAMILY_NAMES) == 7
    for c in manifest["configs"]:
        config = harness.load_json(os.path.join(ROOT, c["file"]))
        family = harness.load_family(config)
        assert family.__name__ == "benchmark.archs." + config["reference"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "archs", config["reference"] + ".py"))
        arch = family.normalise(config)
        assert arch["vocab"] <= arch["vocab_padded"] and arch["layers"] >= 1


@pytest.mark.parametrize("reference,match", [
    (None, "needs a 'reference' key"),
    ("", "needs a 'reference' key"),
    ("no-such-architecture", "no archs/no-such-architecture.py"),
])
def test_a_configuration_without_an_architecture_is_an_error(
        manifest, tmp_path, reference, match):
    entry = manifest["configs"][0]
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    config.pop("reference")
    if reference is not None:
        config["reference"] = reference
    with pytest.raises(harness.BenchmarkError, match=match):
        harness.load_family(config)
    # and through the cell: the file as the manifest names it
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    broken = dict(manifest, configs=[dict(entry, file=str(path))])
    cell = next(w["name"] for w in manifest["workloads"]
                if w["config"] == entry["name"])
    with pytest.raises(harness.BenchmarkError, match=match):
        harness.resolve_cell(cell, broken)


def test_an_architecture_lacking_a_name_is_an_error(tmp_path):
    (tmp_path / "archs").mkdir()
    (tmp_path / "archs" / "half.py").write_text(
        "def normalise(config):\n    return {}\n")
    with pytest.raises(harness.BenchmarkError, match="lacks .*'counts'"):
        harness.load_family({"reference": "half"}, bench_dir=str(tmp_path))


def test_a_path_loaded_module_is_one_a_file_not_one_a_name(tmp_path):
    """Two benchmark directories that each bring an ``archs/x.py`` keep a
    module each, a second load of either is the same object (a flax module
    of it is a static jit argument), and a file rewritten in place is
    loaded anew."""
    body = "\n".join(f"{n} = {i}" for i, n in
                     enumerate(harness.FAMILY_NAMES)) + "\nWHO = {!r}\n"
    dirs = []
    for who in ("one", "two"):
        d = tmp_path / who
        (d / "archs").mkdir(parents=True)
        (d / "archs" / "x.py").write_text(body.format(who))
        dirs.append(str(d))
    load = lambda d: harness.load_family({"reference": "x"}, bench_dir=d)  # noqa: E731
    one, two = load(dirs[0]), load(dirs[1])
    assert (one.WHO, two.WHO) == ("one", "two")
    assert load(dirs[0]) is one and load(dirs[1]) is two
    (tmp_path / "one" / "archs" / "x.py").write_text(body.format("again"))
    assert load(dirs[0]).WHO == "again" and load(dirs[1]) is two


def test_every_per_layer_metric_has_its_file_and_reader(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    known = set(cells(manifest))
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        path = os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".json")
        with open(path) as fh:
            spec = json.load(fh)
        assert spec["name"] == m["name"] and spec["layer"] == m["layer"]
        assert spec["unit"] == m["unit"] and spec["moves"] == m["moves"]
        assert spec.get("workloads") == m.get("workloads")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        assert callable(reader.read)
        for w in m.get("workloads", []):
            assert w in known
            cell = harness.resolve_cell(w, manifest)
            assert m["moves"] in {x["name"] for x in cell.end_to_end()}
    on_disk = {f[:-5] for f in os.listdir(
        os.path.join(ROOT, "benchmark", "metrics"))}
    assert on_disk == {m["name"] for m in manifest["per_layer"]}


def test_shares_of_a_peak_are_named_and_united_as_the_contract_says(manifest):
    names = {m["name"]: m for m in manifest["per_layer"]}
    rooflines = [n for n in names if "_roofline" in n]
    mfus = [n for n in names if "mfu" in n]
    assert rooflines and mfus
    for n in rooflines + mfus:
        assert names[n]["unit"] == "%" and names[n]["better"] == "higher"
    # beside every roofline, the whole step's share, moving the same metric
    for n in rooflines:
        assert any(names[m]["moves"] == names[n]["moves"]
                   and set(names[m]["workloads"]) >= set(names[n]["workloads"])
                   for m in mfus), n


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_unknown_names_are_errors_not_defaults(manifest):
    with pytest.raises(harness.BenchmarkError):
        harness.resolve_cell("no-such-cell", manifest)
    broken = dict(manifest, workloads=[dict(
        manifest["workloads"][0], traffic="no-such-mix")])
    with pytest.raises(harness.BenchmarkError):
        harness.resolve_cell(broken["workloads"][0]["name"], broken)


def test_files_under_paths_are_named_from_the_allowed_characters(manifest):
    for path in manifest["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (dirpath, f)
