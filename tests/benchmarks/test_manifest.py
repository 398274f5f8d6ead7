"""``BENCHMARK.json`` against the contract, and against the files it
names. Nothing here needs a device."""

import contextlib
import functools
import inspect
import json
import re
import subprocess
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

# what ``reduced`` may never name: a width. The vocabulary's rows end in
# ``_size`` and are a count: a chip may hold a slice of them
WIDTH = re.compile(r"(_dim|_rank|_size)$|head|experts_per_tok")
COUNTS = ("vocab_size",)
# the counts a chip holds a share of, where a layer is divided over chips:
# the routed experts, under whichever of the four keys the source uses
EXPERT_COUNTS = costs.EXPERT_COUNTS
SHARED = (*COUNTS, *EXPERT_COUNTS)


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


def check_reduced(body: dict, published: dict | None = None) -> None:
    """The rule for a configuration file's ``reduced``: no width may be
    cut, and a chip's share of a stated deployment (the ``model-configs``
    guide, section 4) is stated and kept to the guide's floors. A file
    that lists the vocabulary or the routed experts (under the source's
    own key, one of ``EXPERT_COUNTS``) carries

        "share": {"published": {key: count}}

    with the source's count of each such key (``published``, where the
    catalog has the model, pins it to the source): the held count
    divides it, the experts held are at least 8, the vocabulary held at
    least an eighth. How many chips share a layer is the published
    experts over those held, and is written nowhere. A file that lists
    neither carries no such block: the cost functions read it from any
    file that has one."""
    for key in body["reduced"]:
        assert key in COUNTS or not WIDTH.search(key), key
    shared = [key for key in body["reduced"] if key in SHARED]
    if not shared:
        assert "share" not in body
        return
    assert set(body.get("share", ())) == {"published"}, shared
    whole_of = body["share"]["published"]
    assert set(whole_of) == set(shared)
    for key in shared:
        held, whole = body[key], whole_of[key]
        assert 0 < held < whole and whole % held == 0, key
        if key in EXPERT_COUNTS:
            assert held >= 8, key
        else:
            assert 8 * held >= whole, key
        if published is not None:
            assert whole == published[key], key


def check_config(config: dict, bench: dict, root: Path) -> None:
    """A ``configs`` entry of ``bench`` and its file under ``root``."""
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert one_line(config["source"]) and one_line(config["why"])
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    assert any(config["file"].startswith(p + "/") for p in bench["paths"])
    body = json.loads((root / config["file"]).read_text())
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    check_reduced(body)
    for key in ("preset", "model_class", "reference", "mode", "chips",
                "assumed", "deployment", "tiny"):
        assert key in body, key
    reference = ROOT / "benchmarks" / "references" / f"{body['reference']}.py"
    assert reference.is_file()
    assert any(w["config"] == config["name"] for w in bench["workloads"])


def check_against_catalog(config: dict, body: dict, entry: dict) -> None:
    """Every number of the catalog's entry is in the file under the same
    key, but for the keys in ``reduced``; of those, a count this chip
    holds a share of is still pinned to the source by ``share``."""
    for key, value in entry.items():
        if key not in config["reduced"]:
            assert body.get(key, "absent") == value, (config["name"], key)
    check_reduced(body, published=entry)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    check_config(config, BENCH, ROOT)


def test_config_files_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)


def catalog() -> dict:
    rows = map(json.loads, CATALOG.read_text().splitlines())
    return {row["source_url"]: row["config"] for row in rows}


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog here")
def test_catalog_models_hold_every_number_of_their_entry():
    checked, entries = 0, catalog()
    for config in BENCH["configs"]:
        entry = entries.get(config["source"])
        if entry is None:
            continue  # Qwen3-30B-A3B is not in the catalog
        body = json.loads((ROOT / config["file"]).read_text())
        check_against_catalog(config, body, entry)
        checked += 1
    assert checked >= 1  # DeepSeek-V2-Lite is in it


# -- a chip's share of a stated deployment -------------------------------------

DEEPSEEK = "deepseek-v2-lite-l2"


def a_share_cut(tmp_path, **changes):
    """A manifest and a configuration file as a later PR would add them:
    one chip's share of a DeepSeek-V2-Lite training job whose layers are
    each divided over eight chips. Depth, the routed experts held (64 ->
    8) and the vocabulary's slice (102,400 -> 12,800 rows) are reduced,
    all else is the catalog's; ``changes`` then alter the file
    (``share=None`` takes the block out)."""
    entry = next(c for c in BENCH["configs"] if c["name"] == DEEPSEEK)
    body = json.loads((ROOT / entry["file"]).read_text())
    body.update(n_routed_experts=8, vocab_size=12_800,
                reduced=with_reduced(), share=share_of())
    body.update(changes)
    if body["share"] is None:
        del body["share"]
    config = dict(entry, name=DEEPSEEK + "-share8", reduced=body["reduced"],
                  file="benchmarks/configs/" + DEEPSEEK + "-share8.json")
    (tmp_path / "benchmarks/configs").mkdir(parents=True)
    (tmp_path / config["file"]).write_text(json.dumps(body))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(config)
    bench["workloads"].append({
        "name": config["name"] + ".train-16k", "config": config["name"],
        "traffic": "train-16k", "chips": 1, "why": "a test",
    })
    return config, bench, body


def with_reduced(*more):
    return ["num_hidden_layers", "n_routed_experts", "vocab_size", *more]


def share_of(**published):
    return {"published": {"n_routed_experts": 64, "vocab_size": 102_400,
                          **published}}


SHARE_CUTS = {
    "at_the_floors": ({}, True),
    "four_experts": ({"n_routed_experts": 4}, False),
    "a_sixteenth_of_the_vocabulary": ({"vocab_size": 6_400}, False),
    "no_share_block": ({"share": None}, False),
    "a_cut_expert_width": (
        {"moe_intermediate_size": 704,
         "reduced": with_reduced("moe_intermediate_size")}, False),
    "a_cut_hidden_width": (
        {"hidden_size": 1024, "reduced": with_reduced("hidden_size")}, False),
    "experts_per_token_cut": (
        {"num_experts_per_tok": 2,
         "reduced": with_reduced("num_experts_per_tok")}, False),
    "a_slice_that_does_not_divide": ({"vocab_size": 13_000}, False),
    "a_key_beside_the_published_counts": (
        {"share": dict(share_of(), chips_sharing_a_layer=8)}, False),
    "a_share_block_where_nothing_is_shared": (
        {"n_routed_experts": 64, "vocab_size": 102_400,
         "reduced": ["num_hidden_layers"]}, False),
    "nothing_shared_and_no_block": (
        {"n_routed_experts": 64, "vocab_size": 102_400,
         "reduced": ["num_hidden_layers"], "share": None}, True),
    "a_published_count_that_is_not_the_sources": (
        {"n_routed_experts": 16, "share": share_of(n_routed_experts=128)},
        False),
    "a_share_of_a_key_that_is_not_reduced": (
        {"reduced": ["num_hidden_layers", "vocab_size"]}, False),
}


@pytest.mark.parametrize("case", SHARE_CUTS)
def test_a_share_cut_configuration_is_held_to_the_guides_floors(
        tmp_path, case):
    """``vocab_size`` and the experts held may be listed in ``reduced``
    beside a ``share`` block at the guide's floors; below a floor,
    without the block or with a width cut the file is refused, by the
    same checks the committed configurations pass."""
    changes, passes = SHARE_CUTS[case]
    config, bench, body = a_share_cut(tmp_path, **changes)
    entry = catalog()[config["source"]] if CATALOG.exists() else None

    def check():
        check_config(config, bench, tmp_path)
        if entry is not None:
            check_against_catalog(config, body, entry)

    if case == "a_published_count_that_is_not_the_sources" and entry is None:
        pytest.skip("only the catalog can say that 128 is not the source's")
    if passes:
        check()
    else:
        with pytest.raises(AssertionError):
            check()


def test_the_share_cut_is_what_the_cost_functions_read(tmp_path):
    """The file's ``share`` block is the one thing the harness reads to
    know a chip holds a share: an eighth of the routed rows, the router
    at its published width, the head over the slice."""
    _, _, cut = a_share_cut(tmp_path)
    whole = json.loads(
        (ROOT / "benchmarks/configs" / f"{DEEPSEEK}.json").read_text())
    assert costs.published_experts(cut) == 64 == costs.published_experts(whole)
    assert costs.routed_per_token(cut) == 6 * 8 / 64
    assert costs.routed_per_token(whole) == 6
    rows = lambda cfg: costs.expert_mm_train(cfg, 16_384)["flops"]  # noqa: E731
    assert rows(cut) * 8 == rows(whole)
    d, per_expert = 2048, 3 * 2048 * 1408
    assert costs.active_matmul_params(whole) - costs.active_matmul_params(cut) \
        == d * (102_400 - 12_800) + (6 - 0.75) * per_expert


# a share stated under another of the keys a source may count its experts
# by: 18 of 72 experts and a quarter of a 100,352-row vocabulary, the
# numbers ISSUE 43 reckons for a source that says ``num_local_experts``
LOCAL_EXPERTS = {
    "num_hidden_layers": 10, "num_local_experts": 18, "vocab_size": 25_088,
    "reduced": ["num_hidden_layers", "num_local_experts", "vocab_size"],
    "share": {"published": {"num_local_experts": 72, "vocab_size": 100_352}},
}
THE_SOURCE = {"num_hidden_layers": 40, "num_local_experts": 72,
              "vocab_size": 100_352}


@pytest.mark.parametrize("held,published,passes", [
    (18, None, True), (18, THE_SOURCE, True),
    (6, THE_SOURCE, False),  # under the floor of 8
    (20, THE_SOURCE, False),  # 72 experts do not divide into twenties
    (18, dict(THE_SOURCE, num_local_experts=144), False),
], ids=["alone", "against_its_source", "six_held", "twenty_held",
        "not_the_sources_count"])
def test_a_share_is_stated_under_the_sources_own_key(held, published, passes):
    """``check_against_catalog`` reads ``published[key]`` for every shared
    key of ``reduced``, so the key has to be the source's own: an alias
    would be a ``KeyError`` on the catalog's row."""
    body = dict(LOCAL_EXPERTS, num_local_experts=held)
    if passes:
        check_reduced(body, published=published)
    else:
        with pytest.raises(AssertionError):
            check_reduced(body, published=published)


@pytest.mark.parametrize("key", EXPERT_COUNTS)
def test_every_expert_count_key_is_held_to_the_share_rule(key):
    body = {key: 8, "reduced": [key], "share": {"published": {key: 64}}}
    check_reduced(body, published={key: 64})
    with pytest.raises(AssertionError):
        check_reduced(dict(body, **{key: 4}))
    with pytest.raises(AssertionError):  # listed, and no block beside it
        check_reduced({key: 8, "reduced": [key]})


def check_cell(cell: dict, bench: dict, root: Path) -> None:
    """A ``workloads`` entry of ``bench`` and what the manifest under
    ``root`` gives the cell."""
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4)
    assert one_line(cell["why"])
    assert cell["config"] in {c["name"] for c in bench["configs"]}
    loaded = manifest.cell(cell["name"], root=root)
    assert loaded.config["chips"] == cell["chips"]
    assert loaded.traffic["kind"] in ("train_steps", "closed_loop")
    reported = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert len(loaded.per_layer) >= 1
    # every per-layer metric of the cell moves a metric the cell reports
    for metric in loaded.per_layer:
        assert metric["moves"] in reported, metric["name"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    check_cell(cell, BENCH, ROOT)


def copy_the_data_files(tmp_path) -> None:
    import shutil

    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "benchmarks" / sub, tmp_path / "benchmarks" / sub)


def with_a_new_cell(tmp_path, kind: str):
    """A copy of the manifest and its data files with one more cell: the
    decode configuration under a traffic mix of a kind no cell has yet,
    added the way a later PR adds one (a traffic file, a ``workloads``
    entry, the cell's name on the serving metrics' lists)."""
    name = "qwen3-30b-a3b-decode.serve-new-mix"
    copy_the_data_files(tmp_path)
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


def check_metric(metric: dict, bench: dict, root: Path) -> None:
    """An ``end_to_end`` or ``per_layer`` entry of ``bench`` and the
    metric's own file under ``root``."""
    end_to_end = metric in bench["end_to_end"]
    cells = [w["name"] for w in bench["workloads"]]
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
        assert metric["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert set(metric.get("workloads", cells)) <= set(cells)
    own = manifest.metric_file(metric["name"], root=root)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert own.get(key) == metric.get(key), key
    # which cells report a metric is the manifest's to say, and only its
    assert not {"kinds", "min_chips", "workloads"} & set(own)
    reader = own["reader"]
    if reader.get("file"):
        assert (root / "benchmarks/metrics" / f"{metric['name']}.py").is_file()
    else:
        from benchmarks.harness import readers

        assert callable(getattr(readers, reader["use"]))
    if re.search(r"roofline", metric["name"]):
        assert metric["name"].endswith("_roofline") and metric["unit"] == "%"


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_its_own_file(metric):
    check_metric(metric, BENCH, ROOT)


def check_files_and_entries(bench: dict, root: Path) -> None:
    """No file under ``benchmarks/metrics/`` waits for an entry (55 of 55
    since PR 43), so a reading that a run makes reaches the ledger."""
    files = {p.stem for p in (root / "benchmarks/metrics").glob("*.json")}
    listed = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert files == set(listed) and len(set(listed)) == len(listed)


def test_every_metric_file_is_listed_and_every_entry_has_its_file():
    check_files_and_entries(BENCH, ROOT)


def test_metric_names_are_distinct_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.1


# -- what a later PR may add with files and entries alone ----------------------

def with_an_appended_entry(tmp_path, cell: str):
    """A copy of the manifest and its data files with one more per-layer
    metric, added the way a later PR adds one: the metric's two files, and
    an entry at the end of ``per_layer`` that lists ``cell`` alone."""
    name = "layer.appended_by_a_later_pr"
    copy_the_data_files(tmp_path)
    bench = json.loads(json.dumps(BENCH))
    moves = next(m["name"] for m in bench["end_to_end"]
                 if cell in m.get("workloads", ()))
    entry = {"name": name, "unit": "count", "better": "lower",
             "source": "program_counter", "layer": "model", "moves": moves}
    metrics = tmp_path / "benchmarks/metrics"
    (metrics / f"{name}.json").write_text(json.dumps(dict(
        entry, what="a test", reader={"file": True})))
    (metrics / f"{name}.py").write_text("def read(run):\n    return None\n")
    bench["per_layer"].append(dict(entry, workloads=[cell]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


@functools.cache
def the_other_files_checks() -> dict:
    """The test files that check the manifest's or a cell's list of
    metrics, loaded once: the cost functions', the tiny runs' and the
    single readers' files, and the dispatch split's, which has its check
    as a function of the root."""
    from tests.conftest import load_repo_module

    here = Path(__file__).parent
    stems = sorted({
        p.stem for pattern in ("test_*cost*.py", "test_run_tiny_*.py",
                               "test_*reader.py")
        for p in here.glob(pattern)
    } | {"test_dispatch_split_readers"})
    return {
        stem: load_repo_module(f"bench_{stem}", f"tests/benchmarks/{stem}.py")
        for stem in stems
    }


class NeedsASubprocess(Exception):
    """A test asked for a run of ``benchmarks/run.py``."""


def needs_a_subprocess(*args, **kwargs):
    raise NeedsASubprocess


@contextlib.contextmanager
def the_manifest_under(root: Path):
    """While it holds, ``manifest.manifest``, ``cell`` and
    ``metric_file`` read the manifest under ``root`` where a caller names
    none (each has ``root`` as its one default), and nothing starts a
    process."""
    readers_of = (manifest.manifest, manifest.metric_file, manifest.cell)
    plain, run = [f.__defaults__ for f in readers_of], subprocess.run
    try:
        for f in readers_of:
            f.__defaults__ = (root,)
        subprocess.run = needs_a_subprocess
        yield
    finally:
        subprocess.run = run
        for f, defaults in zip(readers_of, plain):
            f.__defaults__ = defaults


def the_cases_of(test) -> list[dict]:
    """The calls pytest makes of a test that takes no fixture: one, or
    one a case of its ``parametrize`` marks; none of a test that takes a
    fixture."""
    cases = [{}]
    for mark in getattr(test, "pytestmark", ()):
        if mark.name != "parametrize":
            continue
        names, values = mark.args[:2]
        if isinstance(names, str):
            names = [n.strip() for n in names.split(",")]
        rows = [getattr(v, "values", v) for v in values]
        if len(names) == 1:
            rows = [r if hasattr(v, "values") else (r,)
                    for r, v in zip(rows, values)]
        cases = [dict(c, **dict(zip(names, r))) for c in cases for r in rows]
    asked = set(inspect.signature(test).parameters)
    return cases if asked <= set(cases[0]) else []


def check_the_other_files_tests(root: Path) -> int:
    """Every test of the files ``the_other_files_checks`` loads that
    needs no fixture and no run of ``benchmarks/run.py``, against the
    manifest under ``root``: the tests of a cell's list among them, by
    whatever name (PR 48's two pins of the list's last place stood in
    such tests and stopped every appended entry until PR 53). Returns how
    many calls it made."""
    made = 0
    with the_manifest_under(root):
        for module in the_other_files_checks().values():
            for name, test in vars(module).items():
                if not name.startswith("test_") or not inspect.isfunction(test):
                    continue
                for case in the_cases_of(test):
                    try:
                        test(**case)
                    except NeedsASubprocess:
                        continue
                    made += 1
    return made


def check_the_whole_manifest(root: Path) -> None:
    """Every check of this file that reads the manifest, and every check
    that another file of ``tests/benchmarks/`` makes of the manifest's or
    a cell's list of metrics, against the manifest under ``root``."""
    bench = manifest.manifest(root)
    entries = catalog() if CATALOG.exists() else {}
    for config in bench["configs"]:
        check_config(config, bench, root)
        if config["source"] in entries:
            body = json.loads((root / config["file"]).read_text())
            check_against_catalog(config, body, entries[config["source"]])
    for cell in bench["workloads"]:
        check_cell(cell, bench, root)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        check_metric(metric, bench, root)
    check_files_and_entries(bench, root)
    assert len(bench["per_layer"]) <= 128
    others = the_other_files_checks()
    for stem in ("test_run_tiny_glm", "test_run_tiny_jamba",
                 "test_run_tiny_mimo", "test_mhc_train_cost"):
        others[stem].check_the_manifest_gives_the_cell_its_metrics(root)
    mimo = others["test_run_tiny_mimo"]
    split = others["test_dispatch_split_readers"]
    for own in mimo.OWN_FILES:
        mimo.check_an_own_metrics_file_is_listed_for_its_cell(*own, root=root)
    for name in split.SPLIT:
        split.check_a_dispatch_metric_is_listed_for_the_serving_cells(
            name, root)
    split.check_the_manifest_lists_the_exchanges_counters_fill_before_fallback(
        root)
    assert check_the_other_files_tests(root) >= 80


@pytest.mark.parametrize("cell", CELLS)
def test_an_appended_per_layer_entry_is_files_and_an_entry_only(
        tmp_path, cell):
    """What a ``model_config`` PR owes and PR 37 and PR 41 could not
    deliver: a per-layer entry at the end of the list, for any cell,
    passes every check of the manifest and of the cells; the cell's line
    gains the metric after all it had, and no other cell's changes."""
    name = with_an_appended_entry(tmp_path, cell)
    check_the_whole_manifest(tmp_path)
    listed = lambda c, root: [  # noqa: E731
        m["name"] for m in manifest.cell(c, root=root).per_layer]
    for other in CELLS:
        grown = [name] if other == cell else []
        assert listed(other, tmp_path) == listed(other, ROOT) + grown


def test_a_new_serving_cell_on_the_lists_passes_every_check(tmp_path):
    """The other addition a later PR makes: a cell's name appended to the
    lists of the metrics it reports."""
    name = with_a_new_cell(tmp_path, kind="closed_loop")
    check_the_whole_manifest(tmp_path)
    assert len(manifest.cell(name, root=tmp_path).per_layer) == len(
        manifest.cell("qwen3-30b-a3b-decode.serve-rollout-closed").per_layer)


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
