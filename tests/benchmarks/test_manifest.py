"""``BENCHMARK.json`` against the contract, and against the files it
names. Nothing here needs a device."""

import json
import re
from pathlib import Path

import pytest

from benchmarks.harness import costs, manifest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def one_line(text: str, limit: int = 200) -> bool:
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    assert BENCH["trace_in_run"] is True  # a traced run is --trace 2
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p and not p.startswith("/")
               for p in BENCH["paths"])
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(one_line(word) for word in BENCH["command"])
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert (ROOT / script).is_file()


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert one_line(config["source"]) and one_line(config["why"])
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    # no width may be cut
    for key in config["reduced"]:
        assert not re.search(r"(_dim|_rank|_size)$|head|experts_per_tok", key)
    for key in ("preset", "model_class", "reference", "mode", "chips",
                "assumed", "deployment", "tiny"):
        assert key in body, key
    reference = ROOT / "benchmarks" / "references" / f"{body['reference']}.py"
    assert reference.is_file()
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


def test_config_files_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_catalog_models_hold_every_number_of_their_entry():
    catalog = {}
    for line in CATALOG.read_text().splitlines():
        row = json.loads(line)
        catalog[row["source_url"]] = row["config"]
    checked = 0
    for config in BENCH["configs"]:
        entry = catalog.get(config["source"])
        if entry is None:
            continue  # Qwen3-30B-A3B is not in the catalog
        body = json.loads((ROOT / config["file"]).read_text())
        for key, value in entry.items():
            if key in config["reduced"]:
                continue
            assert body.get(key, "absent") == value, (config["name"], key)
        checked += 1
    assert checked >= 1  # DeepSeek-V2-Lite is in it


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4)
    assert one_line(cell["why"])
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    loaded = manifest.cell(cell["name"])
    assert loaded.config["chips"] == cell["chips"]
    assert loaded.traffic["kind"] in ("train_steps", "closed_loop")
    reported = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert len(loaded.per_layer) >= 1
    # every per-layer metric of the cell moves a metric the cell reports
    for metric in loaded.per_layer:
        assert metric["moves"] in reported, metric["name"]


def with_a_new_cell(tmp_path, kind: str):
    """A copy of the manifest and its data files with one more cell: the
    decode configuration under a traffic mix of a kind no cell has yet,
    added the way a later PR adds one (a traffic file, a ``workloads``
    entry, the cell's name on the serving metrics' lists)."""
    import shutil

    name = "qwen3-30b-a3b-decode.serve-new-mix"
    for sub in ("configs", "traffic"):
        shutil.copytree(ROOT / "benchmarks" / sub, tmp_path / "benchmarks" / sub)
    closed = json.loads(
        (ROOT / "benchmarks/traffic/serve-rollout-closed.json").read_text())
    (tmp_path / "benchmarks/traffic/serve-new-mix.json").write_text(
        json.dumps(dict(closed, kind=kind, rate_per_s=5.0)))
    bench = json.loads(json.dumps(BENCH))
    old = "qwen3-30b-a3b-decode.serve-rollout-closed"
    bench["workloads"].append({
        "name": name, "config": "qwen3-30b-a3b-decode",
        "traffic": "serve-new-mix", "chips": 1, "why": "a test",
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if old in metric.get("workloads", ()):
            metric["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def test_a_cell_of_a_new_traffic_kind_is_files_and_entries_only(tmp_path):
    """The serving metrics apply to a cell of another kind once the
    manifest lists it: no metric file is edited (REVIEW, PR 24)."""
    name = with_a_new_cell(tmp_path, kind="open_loop")
    new = manifest.cell(name, root=tmp_path)
    old = manifest.cell("qwen3-30b-a3b-decode.serve-rollout-closed")
    assert new.traffic["kind"] == "open_loop" and new.traffic["rate_per_s"] == 5.0
    names = lambda metrics: [m["name"] for m in metrics]  # noqa: E731
    assert names(new.end_to_end) == names(old.end_to_end)
    assert names(new.per_layer) == names(old.per_layer)
    assert {"serve_tokens_per_s", "serve_ttft_p95_ms", "serve_tpot_p95_ms",
            "setup_s"} == set(names(new.end_to_end))
    # and the cells that were there report what they reported
    same = manifest.cell(old.name, root=tmp_path)
    assert names(same.per_layer) == names(old.per_layer)


def test_a_metric_with_no_list_is_every_cells_and_a_list_is_exact():
    for cell in CELLS:
        assert manifest.applies({"name": "x"}, cell)
        assert manifest.applies({"name": "x", "workloads": [cell]}, cell)
        assert not manifest.applies({"name": "x", "workloads": ["other"]}, cell)
    ep4 = manifest.cell("qwen3-30b-a3b-ep4.train-16k")
    l1 = manifest.cell("qwen3-30b-a3b-l1.train-16k")
    exposed = "shard.collective_exposed_pct"
    assert exposed in {m["name"] for m in ep4.per_layer}
    assert exposed not in {m["name"] for m in l1.per_layer}


def test_cells_are_distinct_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) and 1 <= len(pairs) <= 24
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(pairs) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_its_own_file(metric):
    end_to_end = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if end_to_end else {"layer", "moves"}
    assert set(metric) <= allowed
    assert set(metric) >= allowed - {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert one_line(metric["layer"])
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    own = manifest.metric_file(metric["name"])
    for key in ("unit", "better", "source", "layer", "moves"):
        assert own.get(key) == metric.get(key), key
    # which cells report a metric is the manifest's to say, and only its
    assert not {"kinds", "min_chips", "workloads"} & set(own)
    reader = own["reader"]
    if reader.get("file"):
        assert (ROOT / "benchmarks/metrics" / f"{metric['name']}.py").is_file()
    else:
        from benchmarks.harness import readers

        assert callable(getattr(readers, reader["use"]))
    if re.search(r"roofline", metric["name"]):
        assert metric["name"].endswith("_roofline") and metric["unit"] == "%"


def test_metric_names_are_distinct_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.1


def test_files_under_paths_have_legal_names():
    for top in BENCH["paths"]:
        for path in (ROOT / top).rglob("*"):
            if "__pycache__" in path.parts or path.suffix == ".pyc":
                continue
            assert PATH.match(str(path.relative_to(ROOT))), path


def test_presets_agree_with_their_files():
    """The sizes the program's preset has and the file states are equal,
    so the reference (which reads the file) sees the model that runs."""
    from benchmarks.harness import build

    for config in BENCH["configs"]:
        body = json.loads((ROOT / config["file"]).read_text())
        build.check_against_file(build.model_config(body, tiny=False), body)
        wrong = dict(body, hidden_size=body["hidden_size"] + 1)
        with pytest.raises(ValueError):
            build.check_against_file(
                build.model_config(body, tiny=False), wrong
            )


def test_costs_from_the_published_sizes():
    qwen = json.loads(
        (ROOT / "benchmarks/configs/qwen3-30b-a3b-l1.json").read_text()
    )
    # ISSUE 24: "of the 368 M parameters a token multiplies, 311 M are the
    # output head"
    assert round(costs.active_matmul_params(qwen) / 1e6) == 368
    assert qwen["hidden_size"] * qwen["vocab_size"] == 311_164_928
    # 6 FLOPs a weight and 6*H*D*T of causal attention a token
    assert costs.train_flops_per_token(qwen, 4096) == pytest.approx(
        6 * costs.active_matmul_params(qwen) + 6 * 32 * 128 * 4096
    )
    deepseek = json.loads(
        (ROOT / "benchmarks/configs/deepseek-v2-lite-l2.json").read_text()
    )
    assert costs.is_mla(deepseek) and costs.n_dense_layers(deepseek) == 1
    # one decode step over 64 slots touches nearly every expert
    assert 120 < costs.expected_experts_touched(qwen, 64) < 128
    work = costs.expert_mm_decode(qwen, 64, 128)
    assert work["bytes"] > 128 * 3 * 2048 * 768 * 2  # the weights alone
