"""`test_manifest.py`'s `four_chips` case sets the first three cells to four
chips and expects the manifest refused — true of the three and the five
cells the benchmark had, but with a sixth cell three are half, which the
contract allows (at most 50%, rounded down). The case means "more than half
the cells on four chips is refused", so here it is given a breaker that says
so for any number of cells. A PR that adds a cell may add files under the
benchmark's paths and edit none; the `benchmark` PR that next edits
`test_manifest.py` should move this into `_break_four_chips` and delete the
file."""

STALE_CASE = "test_a_broken_manifest_is_refused[four_chips]"


def _break_four_chips(d):
    """One cell more than half asks for four chips."""
    for w in d["workloads"][:len(d["workloads"]) // 2 + 1]:
        w["chips"] = 4


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == STALE_CASE and \
                item.nodeid.endswith("test_manifest.py::" + STALE_CASE):
            item.callspec.params["breaker"] = _break_four_chips
