"""BENCHMARK.json against the files under benchmarks/, and against the
limits of the benchmark's contract that a file can be checked for."""

import json
import os
import re
import shutil

import pytest

from benchmarks import run

from . import tiny  # noqa: F401  (conftest has put the repo on sys.path)

ROOT = run.ROOT
HERE = run.HERE
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# a width, which `reduced` may never name; `num_hidden_layers` is a depth
WIDTHS = re.compile(r"(_dim|_rank)$|hidden_size|intermediate|latent|state"
                    r"|proj|head_size|n_embd|n_inner|d_model|d_ff|width")


@pytest.fixture(scope="module")
def manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert manifest["paths"] == ["benchmarks"]
    assert manifest["command"][1].startswith("benchmarks/")
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_names_units_and_lines(manifest):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry and section != "end_to_end" \
                        and not (section == "per_layer" and key == "source"):
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text, (entry["name"], key)
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_name_has_its_file_and_every_file_its_entry(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert {w["config"] for w in cells.values()} == set(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)
    for c in configs.values():
        data = run.load_json(ROOT, c["file"])
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert data["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if WIDTHS.search(k)]
        assert os.path.exists(os.path.join(
            HERE, "references", f"{data['reference']}.py"))
    on_disk = {f[:-5] for f in os.listdir(os.path.join(HERE, "workloads"))}
    assert on_disk == set(cells)
    assert {f[:-5] for f in os.listdir(os.path.join(HERE, "configs"))} \
        == set(configs)
    pairs = set()
    for name, w in cells.items():
        cell = run.load_json(HERE, "workloads", f"{name}.json")
        for key in ("config", "traffic", "chips"):
            assert cell[key] == w[key]
        assert os.path.exists(os.path.join(
            HERE, "drivers", f"{cell['driver']}.py"))
        assert os.path.exists(os.path.join(
            HERE, "traffic", f"{w['traffic']}.json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(cells)
    readers = {f[:-3] for f in os.listdir(os.path.join(HERE, "layer_metrics"))
               if f.endswith(".py") and not f.startswith("_")}
    assert readers == {m["name"] for m in manifest["per_layer"]}
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(1, len(cells) // 4)


@pytest.mark.parametrize("key,refused", [
    ("hidden_size", True), ("intermediate_size", True), ("hidden_dim", True),
    ("head_dim", True), ("q_lora_rank", True), ("ssm_state_size", True),
    ("num_hidden_layers", False), ("mixer_types", False),
    ("num_experts", False)])
def test_reduced_may_name_a_depth_and_never_a_width(key, refused):
    assert bool(WIDTHS.search(key)) is refused


def test_each_cell_reports_setup_another_metric_and_a_layer(manifest):
    end = {m["name"]: m for m in manifest["end_to_end"]}
    layers = set()
    for m in manifest["per_layer"]:
        assert m["moves"] in end
        layers.add(m["layer"])
        for cell in m.get("workloads", []):
            mover = end[m["moves"]]
            assert cell in mover.get("workloads", [cell]), (m["name"], cell)
    for w in manifest["workloads"]:
        mine = [m["name"] for m in run.metrics_of(
            manifest, "end_to_end", w["name"], {})]
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        assert run.metrics_of(manifest, "per_layer", w["name"], set(mine))
    text = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in text, f"PERF.md's list of layers lacks {layer!r}"


def test_run_has_no_branch_on_a_cell_or_a_metric(manifest):
    source = open(os.path.join(HERE, "run.py")).read()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            if entry["name"] != "setup_s":
                assert entry["name"] not in source, entry["name"]


def test_a_later_pr_adds_a_cell_and_a_metric_with_files_alone(tmp_path,
                                                              monkeypatch,
                                                              capsys):
    """A copy of the benchmark gains a configuration, a traffic mix, a cell
    with a driver of its own and a per-layer metric, by new files and new
    entries; no file that was there is edited, and the cell runs."""
    tree = tmp_path / "checkout"
    shutil.copytree(HERE, tree / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tree / "mmlspark_tpu").mkdir()
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    manifest["configs"].append(dict(
        name="dummy", source="https://example.org/dummy",
        file="benchmarks/configs/dummy.json", reduced=[], why="a test"))
    manifest["workloads"].append(dict(
        name="dummy_cell", config="dummy", traffic="dummy_mix", chips=1,
        why="a test"))
    manifest["end_to_end"].append(dict(
        name="dummy_per_s", unit="things/s", better="higher", bound=0.05,
        source="host_clock", workloads=["dummy_cell"]))
    manifest["per_layer"].append(dict(
        name="dummy_count", unit="things", better="higher",
        source="program_counter", layer="dummy layer", moves="dummy_per_s",
        workloads=["dummy_cell"]))
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest))
    b = tree / "benchmarks"
    (b / "configs" / "dummy.json").write_text(json.dumps(
        dict(source="x", reference="dummy", reduced=[])))
    (b / "references" / "dummy.py").write_text("ANSWER = 42\n")
    (b / "traffic" / "dummy_mix.json").write_text(json.dumps(dict(n=5)))
    (b / "workloads" / "dummy_cell.json").write_text(json.dumps(dict(
        config="dummy", driver="dummy", traffic="dummy_mix", chips=1)))
    (b / "drivers" / "dummy.py").write_text(
        "import json, os, time\n"
        "class Driver:\n"
        "    def __init__(self, cell, config, seed, reference):\n"
        "        here = os.path.dirname(os.path.dirname(__file__))\n"
        "        self.n = json.load(open(os.path.join(\n"
        "            here, 'traffic', cell['traffic'] + '.json')))['n']\n"
        "        self.want = reference.ANSWER\n"
        "    def warm(self): pass\n"
        "    def window(self, seconds):\n"
        "        time.sleep(seconds)\n"
        "        return dict(metrics={'dummy_per_s': self.n / seconds},\n"
        "                    attempted=self.n, failed=0, elapsed_s=seconds,\n"
        "                    counters={'things': self.n})\n"
        "    def check(self):\n"
        "        return [dict(name='answer_off', value=abs(self.want - 42),\n"
        "                     limit=0)]\n"
        "    def close(self): pass\n")
    (b / "layer_metrics" / "dummy_count.py").write_text(
        "def read(trace, counters, cell, config, peak):\n"
        "    return counters['things']\n")
    monkeypatch.setattr(run, "ROOT", str(tree))
    monkeypatch.setattr(run, "HERE", str(b))
    monkeypatch.setenv(run.CACHE_DIR_ENV, str(tree / ".jax_cache"))
    for trace, want in ((0, {"dummy_per_s", "setup_s"}),
                        (1, {"dummy_count", "warm_cache_misses"})):
        run.main(["--workload", "dummy_cell", "--seed", "3", "--seconds",
                  "0.2", "--trace", str(trace)], require_chip=False)
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["correct"] is True and line["attempted"] == 5
        assert set(line["metrics"]) == want
        assert set(line) >= {"correct", "attempted", "failed", "metrics",
                             "device"}


def test_a_bare_checkout_prints_no_result(tmp_path, monkeypatch, capsys):
    """BENCHMARK.json and benchmarks/ alone: a non-zero exit, no result."""
    tree = tmp_path / "bare"
    shutil.copytree(HERE, tree / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    monkeypatch.setattr(run, "ROOT", str(tree))
    monkeypatch.setattr(run, "HERE", str(tree / "benchmarks"))
    cell = run.load_json(ROOT, "BENCHMARK.json")["workloads"][0]["name"]
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"], require_chip=False)
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


def test_no_accelerator_is_refused(capsys):
    """The harness's own look for a chip: on this CPU it refuses."""
    cell = run.load_json(ROOT, "BENCHMARK.json")["workloads"][0]["name"]
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""
