"""Two asserts of ``test_program_trace.py`` (PR 23) cannot hold beside any
training cell added after ``r50-train-b8-portrait``.

They pin that cell as the LAST name of ``train_img_per_s_chip``'s
``workloads`` and of every per-layer ``workloads`` list.  ISSUE 26 appends
``granite-h-train-pack8k`` to four of those lists and adds four metrics listed
for it alone, the benchmark check refuses a ``model_config`` PR without its
cell, and only a ``benchmark`` PR may edit a file the benchmark has.  So the
two tests are expected to fail, STRICTLY: once a ``benchmark`` issue (ROADMAP
S0c) re-anchors them they pass, this file turns that into a failure, and it
goes.  Every other assert of their bodies is kept alive in
``test_program_trace_after_pr26.py``."""

import pytest

PINNED_TO_THE_LAST_CELL = {
    "test_the_portrait_cell_is_one_chip_of_the_flagship",
    "test_the_portrait_cell_lists_what_the_flagship_cell_lists_and_nothing_new",
}
WHY = ("asserts r50-train-b8-portrait is the last cell of every workloads list; PR 26 appended "
       "granite-h-train-pack8k after it (ROADMAP S0c: a benchmark issue re-anchors the assert and removes "
       "tests/benchmark/conftest.py)")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name in PINNED_TO_THE_LAST_CELL and item.path.name == "test_program_trace.py":
            item.add_marker(pytest.mark.xfail(reason=WHY, strict=True, raises=AssertionError))
