"""One test of the first benchmark (PR 22) cannot hold once the
benchmark has a second family of model:
``test_benchmark_yardstick.py::test_configurations_keep_the_published_widths``
holds EVERY configuration of ``BENCHMARK.json`` to Mistral-7B's widths.
A PR that adds to the benchmark edits no file the benchmark already
has, so the test is marked here as expected to fail instead (PR 25),
and ``test_moe_metrics.py::test_every_configuration_keeps_its_own_source_widths``
asserts the same of each configuration against its own source. A
``benchmark`` PR should generalise the old test and delete this file."""

import pytest

SUPERSEDED = {
    "test_configurations_keep_the_published_widths":
        "holds every configuration to Mistral-7B's widths; superseded by "
        "test_every_configuration_keeps_its_own_source_widths (PR 25)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        reason = SUPERSEDED.get(item.name)
        if reason and item.fspath.basename == "test_benchmark_yardstick.py":
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
