"""Every test starts with an empty store of round programs.

A fit reuses the round program an earlier fit of the same signature built
(``repro.core.engine.round_program``). A test that plants a fault where
the program is traced, by monkeypatching a function its body calls, would
otherwise run a program built before the fault. The store is emptied only
where the engine is already imported; nothing is imported here.
"""
import sys

import pytest


@pytest.fixture(autouse=True)
def _empty_round_programs():
    engine = sys.modules.get("repro.core.engine")
    if engine is not None:
        engine.clear_round_programs()
