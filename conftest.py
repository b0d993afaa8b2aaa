"""Test isolation shared by every test directory of the repo.

The search engines keep their jitted programs in a process-wide cache
(:mod:`repro.core.programs`).  Each test starts with it empty, as a new
process does, so a test that plants a fault in code an engine traces gets
that fault in the program its searches run.
"""
import sys

import pytest


@pytest.fixture(autouse=True)
def _fresh_engine_programs():
    programs = sys.modules.get("repro.core.programs")
    if programs is not None:
        programs.clear()
    yield
