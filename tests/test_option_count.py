"""The count of independently settable values, pinned (ROADMAP.md D4).

Each option doubles the configurations that tests and cells must cover,
so adding one is a decision and not a side effect: this test fails, and
the PR that raises a number says which two callers need different
values. Deleting one lowers the number here in the same change.
"""

import pytest

from zipkin_tpu.main.example import build_parser
from zipkin_tpu.store.device import StoreConfig

DAEMON_FLAGS = [a.option_strings[0] for a in build_parser()._actions
                if a.option_strings and a.dest != "help"]


@pytest.mark.parametrize("names, pinned", [
    pytest.param(StoreConfig._fields, 32, id="StoreConfig-fields"),
    pytest.param(DAEMON_FLAGS, 37, id="daemon-flags"),
])
def test_option_count(names, pinned):
    assert len(names) == pinned, (
        f"{len(names)} options, pinned at {pinned}. An option was "
        f"added: ROADMAP.md D4 asks what two callers need different "
        f"values (one removed: lower the pin). {list(names)}")
