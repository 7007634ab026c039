"""The traced run of the train kind on the CPU, from the throw-away checkout:
the per-layer metrics a CPU can give are read, the added one is found by its
name, and what could not be read is said aloud."""

import pytest

import benchmark_tiny_tree as tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench_traced")))


@pytest.mark.parametrize("workload,devices,unread", [
    # Listed for the one-chip cell and read from the device trace, which a CPU does not give.
    ("tiny-train", 1, {"train_step.device_ms", "assign_fused.kernel_ms", "assign_fused_roofline"}),
    ("tiny-dp4", 4, set()),
])
def test_traced_run_reports_layer_metrics_and_names_what_it_could_not_read(tree, workload, devices, unread):
    rc, line, out = tiny.run_cell(tree, workload, trace=1, devices=devices)
    assert rc == 0 and line is not None, out[-3000:]
    assert set(line) == tiny.KEYS | {"breakdown"}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # The throw-away metric was found by its name alone.
    assert line["metrics"]["tiny.work_units"]["value"] == line["attempted"]
    assert line["metrics"]["setup.compiles_in_window"]["value"] == 0
    assert {"train_loop.data_wait_ms", "train_step.mfu_pct"} <= set(line["metrics"])
    assert not unread & set(line["metrics"])
    # A listed metric whose reader found nothing is left out of the line AND
    # makes the run incorrect by name; so does a trace without a device in it.
    problems = tiny.said(out, "benchmark: NOT CORRECT:")
    named = {p.split()[2] for p in problems if p.startswith("per-layer metric")}
    assert named == unread, problems
    assert [p for p in problems if not p.startswith("per-layer metric")] == [
        "the trace holds no device operation inside the window"], problems
    assert line["correct"] is False and "busy_s" not in line["device"]
