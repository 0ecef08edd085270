"""Suite-size ratchet: the test count may only grow.

Motivation (round 3): a bad patch once corrupted a test module in a way
that silently DELETED two tests while everything still imported — the
suite stayed green because the assertions were simply gone.  This
ratchet makes that class of loss loud: if `def test_` count drops below
the committed floor, someone deleted coverage without saying so.
Raise the floor when adding tests (never lower it silently).
"""
import pathlib
import re

FLOOR = 1011  # committed minimum number of test FUNCTIONS under
# tests/ (parametrize expansion makes the collected count higher)


def test_suite_size_only_grows():
    here = pathlib.Path(__file__).parent
    count = 0
    for p in here.glob("*.py"):
        count += len(re.findall(r"^def test_", p.read_text(), re.M))
        count += len(re.findall(r"^    def test_", p.read_text(), re.M))
    assert count >= FLOOR, (
        f"test function count {count} fell below the committed floor "
        f"{FLOOR}: tests were deleted (or a module was corrupted) — "
        "restore them or consciously lower the floor with a rationale")
