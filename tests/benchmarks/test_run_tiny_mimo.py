"""The MiMo-V2-Flash serving cell end to end at tiny widths on the CPU
rig, a new process per run as the driver starts it: the contract's last
line, ``correct`` true against the family's reference (which reads the
layer kinds from the tree there), counters only; the manifest's entry
for the cell, and the cell's own four metrics as files ready to be
listed."""

import pytest

from tests.conftest import load_repo_module

# the helpers of the first tiny-run tests: one run per module and case
_tiny = load_repo_module("bench_run_tiny", "tests/benchmarks/test_run_tiny.py")
COUNTERS, tiny_line = _tiny.COUNTERS, _tiny.tiny_line
CELL = "mimo-v2-flash-share16-decode.serve-reason-closed"
JAMBA = "jamba2-3b-decode.serve-reason-closed"

OWN = [
    "serve.window_cache_gb", "model.decode_window_attention_device_pct",
    "kernel.gqa_decode_roofline", "moe.decode_held_rows_pct",
]


@pytest.mark.parametrize("trace", [0, 2])
def test_tiny_run_prints_the_contracts_last_line(trace):
    line = tiny_line(CELL, trace, 1)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["device"]["memory_peak_bytes"] > 0
    # a CPU run gives counts only: no time, rate, share of a peak or trace
    assert set(line["metrics"]) <= COUNTERS
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_the_cells_counters_are_read_from_the_program():
    line = tiny_line(CELL, 2, 1)
    metrics = line["metrics"]
    assert metrics["entry.serve_compiles_in_window"]["value"] == 0.0
    # the tiny table: prompts 3 and 6, outputs 10 and 20
    context = metrics["serve.mean_context_tokens"]
    assert 6.5 <= context["value"] <= 13.0
    # the cell's own four metrics are files only (below): never printed
    assert not set(OWN) & set(metrics)


def test_the_manifest_gives_the_cell_its_metrics():
    """A later PR that drops the cell from a list fails here and not in
    the driver's check (a listed metric missing from the last line is
    ``output_malformed``, one never listed is never read)."""
    from benchmarks.harness import manifest

    cell = manifest.cell(CELL)
    jamba = manifest.cell(JAMBA)
    glm = manifest.cell("glm-4.7-flash-decode.serve-reason-closed")
    names = [m["name"] for m in cell.per_layer]
    # what every serving cell reports and the expert metrics of the MoE
    # serving cells
    shared = [m["name"] for m in jamba.per_layer][:-3]
    experts = {"kernel.expert_mm_decode_roofline",
               "model.decode_experts_device_pct"}
    assert set(names) == set(shared) | experts
    assert experts <= {m["name"] for m in glm.per_layer}
    # what the other serving cells report and this one has nothing to read for
    absent = {"kernel.mla_decode_roofline", "serve.latent_pool_used_pct",
              "model.decode_ssm_device_pct", "kernel.ssm_decode_roofline",
              "serve.recurrent_state_gb"}
    assert not absent & set(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "serve_ttft_p95_ms", "serve_tpot_p95_ms",
        "setup_s",
    ]
    assert cell.chips == 1
    assert cell.config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cell.config["share"] == {
        "published": {"n_routed_experts": 256, "vocab_size": 152_576}}
    assert cell.config["serving"] == {
        "slots": 256, "page_size": 64, "decode_max_length": 1152,
    }
    # the ring wraps at the tiny size: window 6, pages of 4, contexts to 26
    assert cell.config["tiny"]["serving"] == {
        "slots": 4, "page_size": 4, "decode_max_length": 32,
    }
    assert cell.traffic_name == "serve-reason-closed"
    # the same table of requests as the other two reasoning cells
    assert cell.traffic == jamba.traffic == glm.traffic


@pytest.mark.parametrize("name,unit,source,layer,moves,better", [
    ("serve.window_cache_gb", "GB", "program_counter", "serving loop",
     "serve_tokens_per_s", "lower"),
    ("model.decode_window_attention_device_pct", "%", "device_trace",
     "model", "serve_tpot_p95_ms", "lower"),
    ("kernel.gqa_decode_roofline", "%", "device_trace", "kernels",
     "serve_tokens_per_s", "higher"),
    ("moe.decode_held_rows_pct", "%", "program_counter", "model",
     "serve_tokens_per_s", "higher"),
])
def test_an_own_metrics_file_is_ready_to_be_listed(
        name, unit, source, layer, moves, better):
    """The cell's own four metrics are files only, as PR 37's five are:
    the driver takes new entries at the end of ``per_layer`` alone, and
    ``test_dispatch_split_readers.py`` pins the list's last two to the
    exchange's counters, a file of the benchmark that only a
    ``benchmark`` PR may edit. That PR lists them with entries alone,
    ``"workloads": [CELL]`` each: a file holds what its entry has to
    repeat, and a reader (``test_gqa_decode_cost.py`` has each over a
    hand-made run)."""
    from benchmarks.harness import manifest

    assert name in OWN
    assert name not in {m["name"] for m in manifest.manifest()["per_layer"]}
    own = manifest.metric_file(name)
    assert own["name"] == name and own["reader"] == {"file": True}
    assert (manifest.BENCH_DIR / "metrics" / f"{name}.py").is_file()
    assert (own["unit"], own["source"], own["layer"], own["moves"],
            own["better"]) == (unit, source, layer, moves, better)
    assert not {"kinds", "min_chips", "workloads"} & set(own)
