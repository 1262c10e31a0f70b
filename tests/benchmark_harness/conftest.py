"""Fixtures of the benchmark's own tests.

`doc` is the manifest a test of a cell's entries reads: once the real
`BENCHMARK.json`, once the copy the next `model_config` PR would leave
(`appending.appended_copy`). So no such test can hold a list to its end, or
the manifest to the cells it has now, without failing on the copy;
`test_manifest_append_only.py` refuses a test file that reads the manifest's
lists any other way."""

import pytest

from appending import Manifest, appended_copy


@pytest.fixture(scope="session")
def next_pr(tmp_path_factory):
    """The copy's manifest, in a root of its own."""
    own, _ = appended_copy(tmp_path_factory.mktemp("next_pr"))
    return own


@pytest.fixture(params=["real", "next_pr"])
def doc(request):
    if request.param == "real":
        return Manifest().doc
    return request.getfixturevalue("next_pr").doc
